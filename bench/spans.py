"""The program's own spans, counters and scopes, read from the run's trace.

The program opens ``batcher.*`` host spans in its serving loop
(``launch/batcher.py``; counters ride as span arguments) and names its
device work with ``jax.named_scope`` (``attn``, ``mlp``, ``embed``,
``lm_head``, ``mlm_head``, ``sample``, ``sentinel``, ``optimizer``, and a
scope around each kernel).  :func:`summary` loads the run's own
``.xplane.pb`` (the newest under ``.bench_out/trace``, where
``bench/serve.py`` and ``bench/train.py`` trace), and returns over the
window that ``bench.window`` marks:

* ``idle``: device idle seconds by the innermost ``batcher.*`` span open
  at each gap's midpoint, ``unspanned`` where none is (the rule of
  ``bench/trace.py:reduce_events``);
* ``scopes``: device seconds per scope, the union of the intervals of the
  ops whose ``op_name`` holds the scope (``while``, ``call`` and
  ``conditional`` ops, which only hold other ops, left out);
* ``segment``: the decode segment program's executions (``jit__segment``)
  and their device time, split into the union of the ops under a model
  scope and the ``residual`` under none (the layer scan's slicing and
  stacking of the caches, XLA's copies of the carry, the loops' own
  control); ``residual_ops`` are the five costliest ops of the residual
  and ``outside_ops_s`` its part that no op but a loop or call covers;
* ``spans``: per ``batcher.*`` span started in the window, its count and
  the sum of each numeric argument.

A program without these spans or scopes gives empty tables, and a reader
then returns nothing.
"""
from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from pathlib import Path

from bench import trace

TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"
PREFIX = "batcher."
UNSPANNED = "unspanned"
#: The program's model scopes: an op of the segment program under none of
#: them is the residual.
MODEL_SCOPES = ("embed", "attn", "mlp", "lm_head", "mlm_head", "sample",
                "sentinel", "optimizer")
#: The scopes around the kernels, each inside ``attn``.
KERNEL_SCOPES = ("lln_prefill", "lln_decode", "lln_bidir", "lln_bidir_bwd",
                 "block_diag", "diag_tail")
SCOPES = MODEL_SCOPES + KERNEL_SCOPES
_MODEL = frozenset(MODEL_SCOPES)
SEGMENT = "_segment"
#: Ops whose interval only holds other ops.
CONTAINERS = ("while", "call", "conditional")
#: The statistic of an op's event metadata that carries its ``op_name``.
OP_NAME_STAT = "tf_op"
#: A transformation around a scope in an ``op_name``: ``transpose(jvp(x))``.
_WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")


def newest(directory: Path = TRACE_DIR):
    """The newest ``.xplane.pb`` under ``directory``, or None."""
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> tuple[list, list, dict]:
    """``(device lines, host spans, op names)`` of an ``.xplane.pb``:
    per device ``{line: [(name, start_ns, dur_ns), ...]}`` for the ops and
    modules lines; ``bench.window`` and ``batcher.*`` spans as
    ``(name, start_ns, dur_ns, args)``; each op's ``op_name``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append({line.name: [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
                            for line in plane.lines
                            if line.name in (trace.OPS_LINE,
                                             trace.MODULES_LINE)})
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace.WINDOW or e.name.startswith(PREFIX):
                        host.append((e.name, e.start_ns, e.duration_ns,
                                     {k: v for k, v in e.stats
                                      if isinstance(v, (int, float))}))
    return devices, host, op_names(path)


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a serialized protobuf message: an int
    for a varint, a memoryview for a length-delimited or fixed field."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def op_names(path: str) -> dict:
    """``{op event name: op_name}`` of the device planes of an
    ``.xplane.pb``.  A TPU op's event names the op by its HLO text only; its
    ``op_name`` is the ``tf_op`` statistic of the event's metadata, which
    ``ProfileData`` does not expose, so the ``XSpace`` message (``planes``
    1; per ``XPlane``: ``name`` 2, ``event_metadata`` 4, ``stat_metadata``
    5; per metadata: ``name`` 2, ``stats`` 5; per ``XStat``:
    ``metadata_id`` 1, ``str_value`` 5) is read here directly."""
    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for f, v in parts if f == 2), "")
        if not name.startswith("/device:TPU:"):
            continue
        stat_ids = {}
        for f, v in parts:
            if f == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_ids[bytes(meta.get(2, b"")).decode()] = meta.get(1, 0)
        want = stat_ids.get(OP_NAME_STAT)
        for f, v in parts:
            if f != 4 or want is None:
                continue
            event, op = None, None
            for g, w in _fields(dict(_fields(v)).get(2, b"")):
                if g == 2:
                    event = bytes(w).decode(errors="replace")
                elif g == 5:
                    stat = dict(_fields(w))
                    if stat.get(1) == want and 5 in stat:
                        op = bytes(stat[5]).decode().rstrip(":")
            if event is not None and op:
                out.setdefault(event, op)
    return out


def scopes_of(op_name) -> frozenset:
    """The scopes an ``op_name`` holds: its ``/``- and ``;``-separated
    parts, each stripped of transformations (``jvp(attn)`` -> ``attn``)."""
    out = set()
    for part in re.split(r"[/;]", op_name or ""):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            out.add(part)
    return frozenset(out)


def is_container(name: str) -> bool:
    """Whether the op only holds other ops: its HLO opcode, in the event's
    HLO text, is a loop, call or branch.  (Not its ``op_name``: the copies
    XLA makes of a loop's carry inherit the loop's ``.../while``.)"""
    return re.search(r"\s(?:%s)\(" % "|".join(CONTAINERS), name) is not None


def _length(intervals) -> float:
    return sum(b - a for a, b in trace._union(intervals))


def _attribute(gaps, spans, idle: dict) -> None:
    """Add each gap to the innermost span open at its midpoint."""
    j, open_ = 0, []
    for a, b in sorted(gaps):
        mid = (a + b) / 2
        while j < len(spans) and spans[j][0] <= mid:
            open_.append(spans[j])
            j += 1
        open_ = [sp for sp in open_ if sp[1] > mid]
        who = min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ \
            else UNSPANNED
        idle[who] = idle.get(who, 0.0) + (b - a) / 1e9


def reduce(devices: list, host: list, op_names: dict) -> dict:
    """The reduction itself, on plain data (see the module docstring)."""
    win = [(s, s + d) for n, s, d, _ in host if n == trace.WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    inner = sorted((s, s + d, n) for n, s, d, _ in host
                   if n.startswith(PREFIX))
    spans = {}
    for n, s, d, args in host:
        if n.startswith(PREFIX) and lo <= s < hi:
            t = spans.setdefault(n, {"count": 0, "args": {}})
            t["count"] += 1
            for k, v in args.items():
                t["args"][k] = t["args"].get(k, 0) + v
    scope_cache = {}

    def scoped(name):
        if name not in scope_cache:
            scope_cache[name] = (scopes_of(op_names.get(name)),
                                 is_container(name))
        return scope_cache[name]

    idle, scope_s = {}, {}
    seg = {"executions": 0, "device_s": 0.0, "scoped_s": 0.0,
           "residual_s": 0.0, "outside_ops_s": 0.0}
    residual_ops = {}
    for lines in devices:
        ops = []
        for name, s, d in lines.get(trace.OPS_LINE, ()):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                ops.append((a, b, name))
        busy = trace._union([(a, b) for a, b, _ in ops])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        _attribute([(a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a], inner, idle)
        leaves = [(a, b, name) for a, b, name in ops
                  if not scoped(name)[1]]
        for sc in SCOPES:
            got = _length([(a, b) for a, b, name in leaves
                           if sc in scoped(name)[0]])
            if got:
                scope_s[sc] = scope_s.get(sc, 0.0) + got / 1e9
        mods = sorted((max(s, lo), min(s + d, hi))
                      for name, s, d in lines.get(trace.MODULES_LINE, ())
                      if SEGMENT in name and min(s + d, hi) > max(s, lo))
        device = sum(b - a for a, b in mods)
        seg["executions"] += len(mods)
        seg["device_s"] += device / 1e9
        starts = [a for a, _ in mods]
        in_seg, in_scope = [], []
        for a, b, name in leaves:
            i = bisect.bisect_right(starts, a) - 1
            for ma, mb in mods[max(i, 0):i + 2]:
                ca, cb = max(a, ma), min(b, mb)
                if cb <= ca:
                    continue
                in_seg.append((ca, cb))
                if scoped(name)[0] & _MODEL:
                    in_scope.append((ca, cb))
                else:
                    residual_ops[name] = residual_ops.get(name, 0.0) \
                        + (cb - ca) / 1e9
        scoped_seg = _length(in_scope)
        seg["scoped_s"] += scoped_seg / 1e9
        seg["residual_s"] += (device - scoped_seg) / 1e9
        seg["outside_ops_s"] += (device - _length(in_seg)) / 1e9
    ndev = max(len(devices), 1)
    for k in ("executions", "device_s", "scoped_s", "residual_s",
              "outside_ops_s"):
        seg[k] /= ndev
    top = sorted(residual_ops.items(), key=lambda kv: -kv[1])[:5]
    seg["residual_ops"] = [[n, s / ndev] for n, s in top]
    return {"window_s": (hi - lo) / 1e9,
            "idle": {k: v / ndev for k, v in idle.items()},
            "scopes": {k: v / ndev for k, v in scope_s.items()},
            "segment": seg, "spans": spans}


@functools.lru_cache(maxsize=4)
def _reduced(path: str) -> dict:
    return reduce(*load(path))


def summary(rec: dict):
    """The reduction of the run's own trace, or None for a run without a
    trace.  A trace whose window differs from the record's by more than a
    microsecond is another run's, and is refused."""
    tr = rec.get("trace")
    path = newest() if tr else None
    if path is None:
        return None
    got = _reduced(path)
    if abs(got["window_s"] - tr["window_s"]) > 1e-6:
        raise ValueError(f"{path} holds a window of {got['window_s']} s, "
                         f"the run's was {tr['window_s']} s")
    return got


def idle_ms_per(rec: dict, under: tuple, per: str, arg: str = None):
    """Device idle milliseconds under the spans ``under``, per span
    ``per`` started in the window (or per the sum of its argument
    ``arg``); None where the window holds none."""
    s = summary(rec)
    t = s and s["spans"].get(per)
    if not t:
        return None
    n = t["args"].get(arg, 0) if arg else t["count"]
    if not n:
        return None
    return 1e3 * sum(s["idle"].get(k, 0.0) for k in under) / n


def scope_ms_per_step(rec: dict, scope: str):
    """Device milliseconds per train step under ``scope``."""
    if rec["kind"] != "train":
        return None
    s = summary(rec)
    if not s or not s["scopes"].get(scope):
        return None
    return 1e3 * s["scopes"][scope] / rec["train"]["steps"]
