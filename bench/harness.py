"""Run one cell of ``BENCHMARK.json`` and print its result line.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
names a configuration (``bench/configs/<config>.json``, via its ``file``)
and a traffic mix (``bench/traffic/<traffic>.json``, which names its
driver module ``bench/<driver>.py``); the cell's correctness limits are
``bench/limits/<cell>.json``; each metric is read from the run's record by
``bench/metrics/<metric>.py``.  Adding a cell, a configuration or a metric
adds files and entries and edits none.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(bench: dict, name: str, root: Path = ROOT) -> dict:
    """The cell's entry with its configuration, mix and limits loaded."""
    entry = _named(bench["workloads"], name, "workload")
    conf = _named(bench["configs"], entry["config"], "configuration")
    base = root / "bench"
    return {"name": name, "chips": int(entry["chips"]), "entry": entry,
            "config": load_json(root / conf["file"]),
            "mix": load_json(base / "traffic" / f"{entry['traffic']}.json"),
            "limits": load_json(base / "limits" / f"{name}.json")}


def metrics_of(bench: dict, name: str, trace: bool) -> list[dict]:
    """The metrics a run of cell ``name`` reports: its end-to-end metrics
    with ``trace`` off, the per-layer metrics that move them with it on."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric: str, root: Path = ROOT):
    """``read(record) -> float | None`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(chips: int):
    """The TPU devices, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (at
    ``$JAX_COMPILATION_CACHE_DIR`` or inside the checkout), keeping every
    program, however quickly it compiled."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_of(devs, rec: dict, trace: bool) -> dict:
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs),
           "memory_peak_bytes": rec["memory_peak_bytes"]}
    if trace:
        out["busy_s"] = rec["trace"]["busy_s"]
        out["window_s"] = rec["trace"]["window_s"]
    return out


def result_line(bench: dict, c: dict, rec: dict, devs, trace: bool,
                root: Path = ROOT) -> dict:
    metrics = {}
    for m in metrics_of(bench, c["name"], trace):
        value = reader(m["name"], root)(rec)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": bool(rec["correct"]),
            "attempted": int(rec["attempted"]),
            "failed": int(rec["failed"]),
            "metrics": metrics,
            "device": device_of(devs, rec, trace)}
    if trace and rec["trace"].get("breakdown"):
        line["breakdown"] = rec["trace"]["breakdown"]
    line["checks"] = rec["checks"]
    return line


def save_record(rec: dict, stem: str, root: Path = ROOT) -> Path:
    """Keep the run's whole record (stamps, counters, trace summary) in
    ``.bench_out/records`` for a reader who wants more than the line."""
    out = root / ".bench_out" / "records" / f"{stem}.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    keep = {k: v for k, v in rec.items() if k not in ("config", "mix")}
    with open(out, "w") as f:
        json.dump(keep, f, default=str)
    return out


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    bench = load_benchmark()
    c = cell(bench, args.workload)
    try:
        devs = require_chips(c["chips"])
    except NoChip as e:
        print(f"bench: {e}; this benchmark runs only on the chip",
              file=sys.stderr)
        return 3
    enable_compile_cache()
    driver = importlib.import_module(f"bench.{c['mix']['driver']}")
    rec = driver.run(c, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=t_start)
    save_record(rec, f"{args.workload}-{args.seed}-{args.trace}")
    line = result_line(bench, c, rec, devs, bool(args.trace))
    for name, chk in rec["checks"].items():
        print(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
