"""Serving driver over a mesh: ``bench/serve.py``'s pool and window with the
model split over the cell's chips.

Set-up builds a ``(data=1, model=chips)`` mesh and hands it to the
program's ``make_pool_setup``; the weights are ``bench/weights.py``'s draw
from the seed (the same numbers bit for bit), made in one jitted call
straight into the shardings the program's parameters take, so no chip
ever holds the whole model.  The window, the stamps, the spans and the
sample are ``bench/serve.py``'s.  The sample is scored by the float32 GQA
reference (``bench/reference_gqa.py``), computed over the same mesh from
the same sharded weights.  A traced run adds the decode segment's
collective operations (``bench/collectives.py``) to the record.

The same sample is also scored with the precision control (the float8
reference, ``bench/control.py``'s) through the same checks, so that every
run records the pair of readings its cell's ``max_logit_gap`` limit is set
from, and the control's run is not correct.  The readings of several
seeds, in one process (``bench/control.py``'s job for one-chip cells):

    PYTHONPATH=src python3 -m bench.serve_mesh \
        --workload yi9b-tp4-decode-backlog --seeds 1,2,3,4,5,6 \
        --seconds 20 --trace-seed 6 --out readings.json

The pool's programs must take fixed shardings under a mesh (the program's
``PoolSetup.rows_fn``): without them every new mix of admissions and
evictions compiles inside the window, and the run stops at set-up.
"""
from __future__ import annotations

import functools
import gc
import json
import sys
import time

import numpy as np

from bench import collectives, reference_gqa, serve, traffic, weights
from bench.program import (CompileCounter, check_layout, peak_bytes,
                           program_config)


def mesh_of(chips: int):
    from repro.launch.mesh import make_mesh
    return make_mesh((1, chips), ("data", "model"))


def make_sharded(layout, seed: int, mesh):
    """``weights.make(layout, seed)`` bit for bit, each array made on the
    devices where the program's ``param_shardings`` put it.  The key is
    the program's argument, so another seed compiles nothing new."""
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import param_shardings

    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def build(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = getattr(path[-1], "key", "")
            if name == "scale":
                out.append(jnp.ones(s.shape, s.dtype))
            elif name == "bias":
                out.append(jnp.zeros(s.shape, s.dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                      jnp.float32)
                out.append((x * weights._std(path, s.shape)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=param_shardings(layout, mesh))(
        weights.key_of(seed))


class Pool(serve.Pool):
    """``bench/serve.py``'s pool, built over the cell's chips."""

    def __init__(self, c: dict, seed: int, seconds: float, fault=None):
        from repro.launch.steps import make_pool_setup

        self.config, self.mix = c["config"], c["mix"]
        pool = self.mix["pool"]
        self.sz = reference_gqa.sizes_of(self.config)
        self.layout = reference_gqa.layout(self.sz)
        self.mesh = mesh_of(c["chips"])
        setup = make_pool_setup(program_config(self.config), self.mesh,
                                slots=pool["slots"], max_len=pool["max_len"],
                                segment=pool["segment"])
        if getattr(setup, "rows_fn", None) is None:
            raise RuntimeError(
                "the program's pool takes no fixed shardings under a mesh "
                "(no PoolSetup.rows_fn): each new mix of admissions and "
                "evictions would compile inside the window")
        check_layout(setup.model, self.layout)
        setup = serve.spanned_setup(setup)
        if fault is not None:
            setup = fault(setup)
        self.eng = serve.make_batcher_class()(
            setup, make_sharded(self.layout, seed, self.mesh),
            queue_cap=traffic.request_count(self.mix, seconds) + 1)
        self.eng.warmup(traffic.prompt_lengths(self.mix, seconds))
        self.compiles = CompileCounter()

    def reseed(self, seed: int) -> None:
        """New weights from ``seed``, for another run in this process."""
        self.eng.params = None
        gc.collect()
        self.eng.params = make_sharded(self.layout, seed, self.mesh)


@functools.lru_cache(maxsize=None)
def _reference_logits(sz_json: str, fp8: bool):
    import jax
    sz = json.loads(sz_json)
    return jax.jit(lambda prm, tok, pl, at: reference_gqa.decoder_logits(
        prm, tok, pl, at, sz, fp8=fp8))


def score(config: dict, params, sample, outputs, n: int, *, fp8=False,
          against=None) -> list:
    """``bench/serve.py:score`` with the GQA reference, every sequence padded
    to ``n`` positions: per sampled request, the gaps (reference best logit
    minus the reference logit of each served token), float64; ``fp8`` and
    ``against`` as there."""
    import jax.numpy as jnp

    sz = reference_gqa.sizes_of(config)
    fn = _reference_logits(json.dumps(sz, sort_keys=True), fp8)
    gaps = []
    for a in sample:
        out = np.asarray(outputs[a.rid], np.int32)
        p, t = a.prompt.shape[0], out.shape[0]
        seq = np.concatenate([a.prompt, out[:-1]])
        # One padded size for every request (the pool's max_len; answers
        # in 512s), so that the reference compiles once per precision.
        tokens = np.zeros((serve._round_up(seq.shape[0], n),), np.int32)
        tokens[:seq.shape[0]] = seq
        at = np.full((serve._round_up(t, 512),), p - 1, np.int32)
        at[:t] = np.arange(p - 1, p - 1 + t)
        logits = np.asarray(fn(params, jnp.asarray(tokens), p,
                               jnp.asarray(at)), np.float64)[:t]
        if fp8:
            ref = against[a.rid]
            gaps.append(ref.max(-1) - ref[np.arange(t), logits.argmax(-1)])
        else:
            if against is not None:
                against[a.rid] = logits
            gaps.append(logits.max(-1) - logits[np.arange(t), out])
    return gaps


def checks_of(c: dict, got: dict, gaps: list) -> tuple[dict, bool]:
    """A run's checks and whether it is correct, for the gaps read on its
    sample (the program's, or the control's)."""
    # No finished request to score reads as a gap far past any limit.
    max_gap = float(max((g.max() for g in gaps), default=1e30))
    limit = float(c["limits"]["max_logit_gap"])
    checks = {"max_logit_gap": {"value": max_gap, "limit": limit},
              "failed_requests": {"value": got["failed"], "limit": 0},
              "compiles_in_window": {"value": got["compiles"], "limit": 0}}
    correct = bool(got["sample"]) and all(
        chk["value"] <= chk["limit"] for chk in checks.values())
    return checks, correct


def record(c: dict, got: dict, params, *, trace: bool, setup_s: float,
           peak: int, kind: str) -> dict:
    """The record of one served window: ``bench/serve.py``'s, with
    ``chips``, ``control`` (the same checks with the float8 reference
    picking each token, read in the float32 logits: ``correct`` false is
    what the limit has to give it) and, traced, ``collectives``."""
    config, n = c["config"], c["mix"]["pool"]["max_len"]
    ref = {}
    gaps = score(config, params, got["sample"], got["outputs"], n,
                 against=ref)
    control = score(config, params, got["sample"], got["outputs"], n,
                    fp8=True, against=ref)
    checks, correct = checks_of(c, got, gaps)
    control_checks, control_correct = checks_of(c, got, control)
    rec = {
        "kind": "serve", "correct": correct, "attempted": got["attempted"],
        "failed": got["failed"], "checks": checks, "chips": c["chips"],
        "control": {"correct": control_correct, "checks": control_checks},
        "setup_s": setup_s, "window_s": got["window_s"],
        "memory_peak_bytes": peak, "device_kind": kind,
        "sample": {"requests": len(got["sample"]),
                   "tokens": int(sum(len(o) for o in
                                     got["outputs"].values()))},
        "serve": {"window": got["window"], "requests": got["requests"]},
        "config": config, "mix": c["mix"], "trace": got["trace"],
    }
    if trace:
        rec["collectives"] = collectives.summary(rec)
    return rec


def run(c: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, fault=None) -> dict:
    """One run of a serving cell over its chips; returns its record
    (:func:`record`)."""
    import jax

    pool = Pool(c, seed, seconds, fault)
    got = pool.serve(seed, seconds, trace)
    peak = peak_bytes()
    params = pool.eng.params
    del pool
    gc.collect()
    return record(c, got, params, trace=trace,
                  setup_s=got["t_open"] - t_start, peak=peak,
                  kind=jax.devices()[0].device_kind)


def readings(c: dict, seeds, seconds: float, *, t_start: float,
             trace_seed=None) -> list:
    """The readings the cell's limit is set from, in one process: set-up
    once, then for every seed new weights and a served window, with the
    result line ``bench/run.py`` would print for it and the control's
    checks.  ``trace_seed``'s window is traced.  The first seed's
    ``setup_s`` is a cold set-up; a later seed's, the time its new weights
    took."""
    import jax
    from bench import harness

    bench = harness.load_benchmark()
    devs = jax.devices()[:c["chips"]]
    pool = Pool(c, seeds[0], seconds)
    rows = []
    t0 = t_start
    for i, seed in enumerate(seeds):
        if i:
            t0 = time.perf_counter()
            pool.reseed(seed)
        trace = seed == trace_seed
        got = pool.serve(seed, seconds, trace)
        rec = record(c, got, pool.eng.params, trace=trace,
                     setup_s=got["t_open"] - t0, peak=peak_bytes(),
                     kind=devs[0].device_kind)
        harness.save_record(rec, f"{c['name']}-{seed}-{int(trace)}")
        row = {"seed": seed, "line": harness.result_line(bench, c, rec,
                                                         devs, trace),
               "control": rec["control"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    import argparse
    from bench import harness

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace-seed", type=int)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    t_start = time.perf_counter()
    c = harness.cell(harness.load_benchmark(), args.workload)
    try:
        harness.require_chips(c["chips"])
    except harness.NoChip as e:
        print(f"serve_mesh: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    rows = readings(c, [int(s) for s in args.seeds.split(",")],
                    args.seconds, t_start=t_start,
                    trace_seed=args.trace_seed)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": rows,
                   "seconds": time.perf_counter() - t_start}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
