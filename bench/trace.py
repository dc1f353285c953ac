"""Profiler traces: record one, and reduce it to what the metrics read.

``Tracer`` writes the JAX profiler's ``.xplane.pb`` for the measured window
into ``.bench_out/trace`` inside the checkout.  :func:`summarize` reads it
with ``jax.profiler.ProfileData`` and returns, over the window that the
host span ``bench.window`` marks:

* ``window_s`` and ``busy_s``: the window's length and the union of the
  intervals in which an operation ran on the device (averaged over the
  devices traced);
* ``programs``: per jitted program (the device's "XLA Modules" line),
  executions and device seconds;
* ``ops``: per operation (the "XLA Ops" line; a Pallas kernel appears
  under the name of its kernel function), calls and device seconds;
* ``idle``: seconds of device idle time by the innermost ``bench.*`` host
  span open in the middle of each gap (``host: no span`` where none is);
* ``breakdown``: the ten operations that took most time and the ten
  longest idle gaps, each with the host span open during it.
"""
from __future__ import annotations

import glob
import os
import shutil
from pathlib import Path

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "host: no span"


class Tracer:
    """Starts and stops the JAX profiler; does nothing without a
    directory."""

    def __init__(self, directory):
        self.dir = Path(directory) if directory else None
        self.active = False
        self.path = None

    def start(self):
        if self.dir is None:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self.active = True

    def stop(self):
        if not self.active:
            return
        import jax
        jax.profiler.stop_trace()
        self.active = False
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        self.path = found[-1] if found else None

    def summary(self):
        return summarize(self.path) if self.path else None


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_events(device_lines: list, host_events: list) -> dict:
    """The reduction itself, on plain data.

    ``device_lines``: one dict per device, ``{line name: [(name, start_ns,
    dur_ns), ...]}``; ``host_events``: ``[(name, start_ns, dur_ns), ...]``
    of the host's spans, all on one clock."""
    spans = [(n, s, s + d) for n, s, d in host_events
             if n.startswith("bench.")]
    win = [(s, e) for n, s, e in spans if n == WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    programs, ops, idle, gaps = {}, {}, {}, []
    busy_total = 0.0
    for lines in device_lines:
        busy = []
        for line, table in ((MODULES_LINE, programs), (OPS_LINE, ops)):
            for name, s, d in lines.get(line, ()):
                a, b = _clip(s, s + d, lo, hi)
                if b <= a:
                    continue
                t = table.setdefault(name, {"count": 0, "seconds": 0.0})
                t["count"] += 1
                t["seconds"] += (b - a) / 1e9
                if line == OPS_LINE:
                    busy.append((a, b))
        merged = _union(busy)
        busy_total += sum(b - a for a, b in merged) / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            open_ = [(e - s, n) for n, s, e in inner if s <= mid < e]
            who = min(open_)[1] if open_ else NO_SPAN
            idle[who] = idle.get(who, 0.0) + (b - a) / 1e9
            gaps.append(((b - a) / 1e9, who))
    ndev = max(len(device_lines), 1)
    for table in (programs, ops):
        for t in table.values():
            t["seconds"] /= ndev
            t["count"] = t["count"] / ndev
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1]["seconds"])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / ndev,
        "programs": programs, "ops": ops,
        "idle": {k: v / ndev for k, v in idle.items()},
        "breakdown": {
            "device_ops": [[n, t["seconds"]] for n, t in top_ops],
            "idle_gaps": [[n, s] for s, n in sorted(gaps, reverse=True)[:10]],
        },
    }


def load(path: str) -> tuple[list, list]:
    """(device lines, host spans) of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
            devices.append(lines)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench."))
    return devices, host


def summarize(path: str) -> dict:
    return reduce_events(*load(path))
