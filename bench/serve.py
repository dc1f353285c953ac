"""Serving driver: the program's continuous-batching pool under a traffic mix.

Set-up calls the program's own entry points: ``make_pool_setup``, weights
from the seed (``bench/weights.py``), ``ContinuousBatcher.warmup`` over the
mix's prompt lengths, then ``ContinuousBatcher.run`` over the mix's
requests.  :class:`WindowedBatcher` adds what the program lacks: it stamps
the host time of each request's prefill start, first token and last token,
closes the measured window at the first segment boundary past
``--seconds``, and opens host spans (``bench.*``) around admission,
dispatch and harvest so that the trace can attribute the device's idle
gaps.

After the run has drained, a sample of the finished requests (drawn from
the seed, the longest always in it) is scored by the float32 reference
(``bench/reference.py``): the widest gap by which a served token's
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

from bench import reference, traffic, weights
from bench.program import (CompileCounter, check_layout, peak_bytes,
                           program_config)
from bench.trace import Tracer

TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _spanned(fn, name):
    def call(*a, **k):
        with _span(name):
            return fn(*a, **k)
    return call


def spanned_setup(setup):
    """The pool's programs, each dispatched inside a host span."""
    pf = setup.prefill_fn
    return dataclasses.replace(
        setup,
        prefill_fn=lambda plen, k=1: _spanned(pf(plen, k), "bench.prefill"),
        admit_fn=_spanned(setup.admit_fn, "bench.scatter"),
        segment_fn=_spanned(setup.segment_fn, "bench.segment"),
        evict_fn=_spanned(setup.evict_fn, "bench.evict"))


def make_batcher_class():
    """:class:`WindowedBatcher`, built on the program's batcher at call
    time so that importing this module loads no JAX."""
    from repro.launch.batcher import ContinuousBatcher

    class WindowedBatcher(ContinuousBatcher):
        """A ``ContinuousBatcher`` that records the measured window over a
        backlog of requests.  Inert until :meth:`arm`, so the program's own
        ``warmup`` runs unchanged."""

        armed = False

        def arm(self, rids, seconds: float, on_open=None, on_close=None):
            self.armed = True
            self.seconds = seconds
            self.on_open, self.on_close = on_open, on_close
            self.t_open = None
            self.closed = None
            self.stamps = {rid: {} for rid in rids}
            self.seg_rows = 0        # active row-steps in the window
            self.seg_slots = 0       # slots x steps dispatched in the window
            self.seg_count = 0
            self.prefills = []       # (prompt length, rows) in the window
            self.window_span = None

        def _now(self) -> float:
            return time.perf_counter()

        def _admit_all(self, st) -> None:
            if not self.armed:
                return super()._admit_all(st)
            if self.t_open is None:
                self.t_open = self._now()
                self.window_span = _span("bench.window")
                self.window_span.__enter__()
                if self.on_open:
                    self.on_open()
            with _span("bench.admit"):
                super()._admit_all(st)

        def _admit_group(self, st, group, free) -> None:
            if not self.armed:
                return super()._admit_group(st, group, free)
            start = self._now()
            for tr in group:
                self.stamps[tr.req.rid]["prefill"] = start
            super()._admit_group(st, group, free)
            first = self._now()
            for tr in group:
                s = self.stamps[tr.req.rid]
                s["first"] = first
                if st.statuses.get(tr.req.rid) == "done":
                    s["last"] = first
            if self.closed is None:
                self.prefills.append((int(group[0].req.prompt.shape[0]),
                                      len(group)))

        def _harvest(self, st, toks_h, emitted_h, active_h,
                     unhealthy_h) -> None:
            if not self.armed:
                return super()._harvest(st, toks_h, emitted_h, active_h,
                                        unhealthy_h)
            rids = [int(r) for r in st.slot_rid if r >= 0]
            with _span("bench.harvest"):
                super()._harvest(st, toks_h, emitted_h, active_h,
                                 unhealthy_h)
            now = self._now()
            for rid in rids:
                if rid in st.statuses:
                    self.stamps[rid]["last"] = now
            if self.closed is not None:
                return
            self.seg_rows += int(emitted_h.sum())
            self.seg_slots += int(emitted_h.shape[0] * emitted_h.shape[1])
            self.seg_count += 1
            if now - self.t_open >= self.seconds:
                self._close(st, now)

        def _close(self, st, now) -> None:
            queued = [tr for tr in st.queue if not st.outputs[tr.req.rid]]
            if not queued:
                raise RuntimeError("the backlog ran out inside the window; "
                                   "raise the mix's per_second")
            self.closed = {
                "t": now,
                "tokens": sum(len(o) for o in st.outputs.values()),
                "started": {rid for rid, s in self.stamps.items()
                            if "prefill" in s}}
            self.window_span.__exit__(None, None, None)
            if self.on_close:
                self.on_close()
            # Requests never started are not attempted: drop them so that
            # the run drains what it started and ends.
            for tr in queued:
                st.queue.remove(tr)
                del st.tracked[tr.req.rid]
                del st.outputs[tr.req.rid]

    return WindowedBatcher


def warm_host_ops(slots: int, vocab_rows: int) -> None:
    """Run once, for every group size, the small eager operations the
    batcher issues around admission and eviction, so that none compiles
    inside the window."""
    import jax.numpy as jnp
    base = jnp.zeros((slots,), jnp.int32)
    flags = jnp.zeros((slots,), jnp.bool_)
    for k in range(1, slots + 1):
        logits = jnp.zeros((k, 1, vocab_rows), jnp.float32)
        last = logits[:, -1]
        np.asarray(jnp.argmax(last, -1).astype(jnp.int32))
        idx = jnp.asarray(list(range(k)), jnp.int32)
        base.at[idx].set(jnp.asarray(np.zeros(k, np.int32)))   # tok, rem
        base.at[idx].set(jnp.full((k,), 1, jnp.int32))         # pos
        flags.at[idx].set(True)                                # admit
        flags.at[idx].set(False)                               # evict
        base.at[idx].set(0)


def _sample(arrivals, outputs, statuses, started, seed, want_tokens,
            max_requests):
    """Finished requests to score: the longest, then others in an order
    drawn from the seed, until ``want_tokens`` served tokens are in."""
    done = [a for a in arrivals
            if a.rid in started and statuses.get(a.rid) == "done"]
    if not done:
        return []
    longest = max(done, key=lambda a: (a.prompt.shape[0]
                                       + len(outputs[a.rid]), -a.rid))
    rng = np.random.default_rng([int(seed), 4])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i].rid != longest.rid]
    pick, tokens = [], 0
    for a in [longest] + rest:
        if tokens >= want_tokens or len(pick) >= max_requests:
            break
        pick.append(a)
        tokens += len(outputs[a.rid])
    return pick


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.lru_cache(maxsize=None)
def _reference_logits(sz_json: str, fp8: bool):
    import jax
    sz = json.loads(sz_json)
    return jax.jit(lambda prm, tok, pl, at: reference.decoder_logits(
        prm, tok, pl, at, sz, fp8=fp8))


def score(config: dict, params, sample, outputs, *, fp8=False,
          against=None) -> list:
    """Per sampled request, the gaps (reference best logit minus the
    reference logit of each served token), float64.

    With ``fp8`` the float8 reference picks the token at each position of
    the same sequences instead (the control), and the gaps are read in the
    float32 logits that an earlier call left in ``against``."""
    import jax.numpy as jnp

    sz = reference.sizes_of(config)
    fn = _reference_logits(json.dumps(sz, sort_keys=True), fp8)
    gaps = []
    for a in sample:
        out = np.asarray(outputs[a.rid], np.int32)
        p, t = a.prompt.shape[0], out.shape[0]
        seq = np.concatenate([a.prompt, out[:-1]])
        # Sequences and positions padded to a few sizes, so that the
        # reference compiles a handful of programs, not one per request.
        tokens = np.zeros((_round_up(seq.shape[0], 512),), np.int32)
        tokens[:seq.shape[0]] = seq
        at = np.full((_round_up(t, 128),), p - 1, np.int32)
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


class Pool:
    """The program's pool for one cell, built and warmed once: set-up."""

    def __init__(self, c: dict, seed: int, seconds: float, fault=None):
        from repro.launch.steps import make_pool_setup

        self.config, self.mix = c["config"], c["mix"]
        pool = self.mix["pool"]
        self.sz = reference.sizes_of(self.config)
        self.layout = reference.layout(self.sz)
        # One chip, no mesh: under a (1, 1) mesh the pool's segment program
        # is traced again for every mix of mesh-placed and unplaced tok/pos/
        # remaining/active arrays that admission and eviction leave behind,
        # which compiles inside the window (PERF.md, Open questions).
        setup = make_pool_setup(program_config(self.config), None,
                                slots=pool["slots"], max_len=pool["max_len"],
                                segment=pool["segment"])
        check_layout(setup.model, self.layout)
        setup = spanned_setup(setup)
        if fault is not None:
            setup = fault(setup)
        self.eng = make_batcher_class()(
            setup, weights.make(self.layout, seed),
            queue_cap=traffic.request_count(self.mix, seconds) + 1)
        self.eng.warmup(traffic.prompt_lengths(self.mix, seconds))
        warm_host_ops(pool["slots"], self.sz["vocab_rows"])
        self.compiles = CompileCounter()

    def reseed(self, seed: int) -> None:
        """New weights from ``seed``, for another run in this process."""
        self.eng.params = None
        gc.collect()
        self.eng.params = weights.make(self.layout, seed)

    def serve(self, seed: int, seconds: float, trace: bool) -> dict:
        """Serve the seed's traffic through the window and drain it."""
        from repro.launch.batcher import Request

        eng, mix = self.eng, self.mix
        arrivals = traffic.arrivals(mix, seed, seconds, self.sz["vocab"])
        tracer = Tracer(TRACE_DIR if trace else None)
        compiles = self.compiles
        compiles.in_window, compiles.names = 0, []

        def close():
            compiles.stop()
            tracer.stop()

        eng.arm([a.rid for a in arrivals], seconds, on_open=compiles.start,
                on_close=close)
        tracer.start()
        stats = eng.run([Request(rid=a.rid, prompt=a.prompt,
                                 gen_len=a.gen_len) for a in arrivals],
                        key=weights.key_of(seed, 3))
        t_end = time.perf_counter()
        closed = eng.closed
        # The attempts are the requests started in the window.
        started = closed["started"]
        by_rid = {a.rid: a for a in arrivals}
        requests = []
        for rid in sorted(started):
            s, a = eng.stamps[rid], by_rid[rid]
            requests.append({
                "prefill": s.get("prefill"),
                "first": s.get("first"), "last": s.get("last"),
                "prompt_len": int(a.prompt.shape[0]),
                "tokens": len(stats.outputs.get(rid, ())),
                "done": stats.statuses.get(rid) == "done"})
        sample = _sample(arrivals, stats.outputs, stats.statuses, started,
                         seed, mix["sample"]["tokens"],
                         mix["sample"]["max_requests"])
        if compiles.names:
            print("bench: programs built in the window:",
                  sorted(set(compiles.names)), file=sys.stderr)
        out = {
            "t_open": eng.t_open, "window_s": closed["t"] - eng.t_open,
            "waiting_at_close": len(arrivals) - len(closed["started"]),
            "drain_s": max(0.0, t_end - closed["t"]),
            "attempted": len(started),
            "failed": sum(1 for r in requests if not r["done"]),
            "compiles": compiles.in_window, "requests": requests,
            "sample": sample,
            "outputs": {a.rid: list(stats.outputs[a.rid]) for a in sample},
            "window": {"tokens": closed["tokens"],
                       "segments": eng.seg_count,
                       "row_steps": eng.seg_rows,
                       "slot_steps": eng.seg_slots,
                       "prefills": eng.prefills},
            "trace": tracer.summary(),
        }
        eng.armed = False          # inert until the next serve()
        return out


def run(c: dict, *, seed: int, seconds: float, trace: bool,
        t_start: float, fault=None) -> dict:
    """One run of a serving cell; returns its record.  ``fault`` (tests)
    wraps the pool's programs to plant a fault in the timed path."""
    import jax

    pool = Pool(c, seed, seconds, fault)
    got = pool.serve(seed, seconds, trace)
    peak = peak_bytes()
    kind = jax.devices()[0].device_kind
    del pool
    gc.collect()
    config = c["config"]
    params = weights.make(reference.layout(reference.sizes_of(config)), seed)
    gaps = score(config, params, got["sample"], got["outputs"])
    del params
    # No finished request to score reads as a gap far past any limit.
    max_gap = float(max((g.max() for g in gaps), default=1e30))
    limit = float(c["limits"]["max_logit_gap"])
    checks = {"max_logit_gap": {"value": max_gap, "limit": limit},
              "failed_requests": {"value": got["failed"], "limit": 0},
              "compiles_in_window": {"value": got["compiles"], "limit": 0}}
    correct = bool(got["sample"]) and all(
        chk["value"] <= chk["limit"] for chk in checks.values())
    return {
        "kind": "serve", "correct": correct, "attempted": got["attempted"],
        "failed": got["failed"], "checks": checks,
        "setup_s": got["t_open"] - t_start, "window_s": got["window_s"],
        "memory_peak_bytes": peak, "device_kind": kind,
        "sample": {"requests": len(got["sample"]),
                   "tokens": int(sum(len(o) for o in
                                     got["outputs"].values()))},
        "serve": {"window": got["window"], "requests": got["requests"]},
        "config": config, "mix": c["mix"], "trace": got["trace"],
    }
