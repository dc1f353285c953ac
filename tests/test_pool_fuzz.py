"""Property-based serving fuzz suite for the continuous-batching pool.

Random traffic schedules — prompt/generation lengths, admit/evict order
(driven by mixed budgets over few slots), deadlines, scripted fault
events, speculative on/off — run through :class:`ContinuousBatcher`, and
every harvested request is checked token-for-token against its solo
oracle (``make_serve_setup.make_generate`` for plain pools, the solo
``SpecSetup`` loop for speculative pools):

* status ``done``/``retried``  -> output EXACTLY equals the oracle, at
  exactly the request's budget;
* status ``timeout``/``failed`` -> the partial output is a PREFIX of the
  oracle (a harvested token is never wrong, only missing);
* every output is hard-capped at the budget (a speculative row may emit
  up to ``spec_k + 1`` tokens in its budget-expiry iteration — the
  overshoot must never surface).

A failing schedule prints a replayable FaultPlan-style JSON seed; feed it
back through :func:`run_schedule` to reproduce.  The tier-1 sweep is
small; the ``slow``-marked sweep runs 200+ schedules (``-m slow``).

The sweeps are deterministic: the hypothesis shim draws from a fixed
seed, prompts/budgets derive from the drawn schedule seed, and fault
plans are seeded scripts.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # container has no
    from _hypothesis_shim import given, settings       # hypothesis; use the
    from _hypothesis_shim import strategies as st      # deterministic shim

from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.batcher import ContinuousBatcher, Request
from repro.launch.faults import FaultPlan
from repro.launch.mesh import make_mesh
from repro.launch.steps import (flatten_spec_tokens, make_pool_setup,
                                make_serve_setup, make_spec_setup)
from repro.models import build_model

SLOTS, SEGMENT, MAX_LEN = 2, 3, 48
SPEC_K, DRAFT_LAYERS = 2, 1
PROMPT_MENU = (6, 9)          # small menus bound the compile count
GEN_MENU = (1, 2, 4, 7)
#: The fuzzed impl axis: each schedule draws the attention state family —
#: lln_diag (O(d^2) state + diag tails) or log_linear (Fenwick bucket
#: pyramid).  Oracle parity over random admit/evict/quarantine+replay
#: schedules is exactly the "lifecycle preserves the bucket pyramid
#: bitwise" property: any merge/occupancy corruption changes tokens.
IMPL_MENU = ("lln_diag", "log_linear")


def _cfg(impl: str = "lln_diag"):
    h = 4
    return ArchConfig(
        name=f"pool-fuzz-{impl}", family="dense", n_layers=2, d_model=64,
        n_heads=h, n_kv_heads=h // 2, d_ff=128, vocab=128, head_dim=16,
        attn_impl=impl, diag_block=8, lln_chunk=8, softmax_chunk=16,
        lln_fixed_ab=2.1, lln_num_scales=3, compute_dtype="float32",
        param_dtype="float32", remat="none", tie_embeddings=True)


_STATE: dict = {}


def _pool(spec: bool, impl: str = "lln_diag"):
    """Module-cached pool (cfg, model, params, mesh, setup): every
    schedule reuses the same jitted executables."""
    key = ("pool", spec, impl)
    if key not in _STATE:
        cfg = _cfg(impl)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(
                cfg, mesh, slots=SLOTS, max_len=MAX_LEN, segment=SEGMENT,
                spec_k=SPEC_K if spec else 0,
                draft_layers=DRAFT_LAYERS if spec else 0)
        _STATE[key] = (cfg, model, params, mesh, setup)
    return _STATE[key]


def _oracle(spec: bool, impl: str, prompt: tuple,
            gen_len: int) -> np.ndarray:
    """Solo greedy reference for one request, cached per (prompt, len)."""
    key = ("oracle", spec, impl, prompt, gen_len)
    if key in _STATE:
        return _STATE[key]
    cfg, model, params, mesh, _ = _pool(spec, impl)
    plen = len(prompt)
    with mesh:
        if not spec:
            skey = ("serve", spec, impl, plen)
            if skey not in _STATE:
                shape = ShapeSpec("fuzz-solo", MAX_LEN, 1, "decode")
                _STATE[skey] = make_serve_setup(cfg, shape, mesh,
                                                multi_pod=False)
            ss = _STATE[skey]
            batch = {"inputs": jnp.asarray(prompt, jnp.int32)[None, :],
                     "targets": jnp.asarray(prompt, jnp.int32)[None, :],
                     "mask": jnp.ones((1, plen), jnp.float32)}
            logits, caches = ss.prefill_fn(params, batch)
            last = logits[:, -1] if logits.ndim == 3 else logits
            tok0 = jnp.argmax(last, -1).astype(jnp.int32)
            toks = [int(tok0[0])]
            if gen_len > 1:
                gkey = ("gen", spec, impl, plen, gen_len)
                if gkey not in _STATE:
                    _STATE[gkey] = ss.make_generate(gen_len - 1, 0.0)
                out, _ = _STATE[gkey](params, caches, tok0,
                                      jnp.asarray(plen, jnp.int32),
                                      jax.random.PRNGKey(0))
                toks.extend(int(t) for t in np.asarray(out)[0])
        else:
            skey = ("spec-solo", impl, plen)
            if skey not in _STATE:
                shape = ShapeSpec("fuzz-spec", MAX_LEN, 1, "decode")
                _STATE[skey] = make_spec_setup(cfg, shape, mesh,
                                               spec_k=SPEC_K,
                                               draft_layers=DRAFT_LAYERS)
            ss = _STATE[skey]
            logits, tgt, dr = ss.prefill_fn(
                params, {"inputs": jnp.asarray(prompt, jnp.int32)[None, :]})
            last = logits[:, -1] if logits.ndim == 3 else logits
            tok0 = jnp.argmax(last, -1).astype(jnp.int32)
            toks = [int(tok0[0])]
            steps = gen_len - 1
            if steps > 0:
                gkey = ("gen", spec, impl, plen, steps)
                if gkey not in _STATE:
                    _STATE[gkey] = ss.make_generate(steps, 0.0)
                t, n_emit, *_ = _STATE[gkey](
                    params, tgt, dr, tok0, jnp.asarray([plen], jnp.int32),
                    jax.random.PRNGKey(0))
                flat = flatten_spec_tokens(np.asarray(t),
                                           np.asarray(n_emit), steps)
                toks.extend(int(x) for x in flat[0])
    _STATE[key] = np.asarray(toks, np.int32)
    return _STATE[key]


def make_schedule(seed: int, spec: bool, n_req: int,
                  fault_mode: int, deadline_mode: int,
                  impl_idx: int = 0) -> dict:
    """Expand drawn knobs into a fully explicit, replayable schedule."""
    rng = np.random.RandomState(seed)
    vocab = 128
    reqs = []
    for rid in range(n_req):
        plen = int(PROMPT_MENU[rng.randint(len(PROMPT_MENU))])
        glen = int(GEN_MENU[rng.randint(len(GEN_MENU))])
        req = {"rid": rid, "gen_len": glen,
               "prompt": rng.randint(0, vocab, size=(plen,)).tolist()}
        if deadline_mode == 1 and rid == 0:
            req["deadline_s"] = 1e-6       # expires at the first boundary
        elif deadline_mode == 2:
            req["deadline_s"] = 300.0      # never fires
        if rng.rand() < 0.25:
            req["max_tokens"] = max(1, glen - 1)
        reqs.append(req)
    faults = []
    if fault_mode == 1:
        faults = [{"kind": "nan", "segment": 1}]
    elif fault_mode == 2:
        faults = [{"kind": "drop", "segment": 1, "rid": 0}]
    elif fault_mode == 3:
        faults = [{"kind": "delay", "segment": 1, "seconds": 0.002},
                  {"kind": "nan", "segment": 2}]
    return {"seed": seed, "spec": bool(spec),
            "impl": IMPL_MENU[impl_idx % len(IMPL_MENU)], "requests": reqs,
            "faults": {"seed": seed, "events": faults}}


def run_schedule(schedule: dict) -> None:
    """Run one schedule and assert the oracle-parity properties.  Feed a
    printed failure seed straight back in to reproduce."""
    spec = schedule["spec"]
    impl = schedule.get("impl", "lln_diag")
    cfg, model, params, mesh, setup = _pool(spec, impl)
    reqs = [Request(rid=r["rid"],
                    prompt=np.asarray(r["prompt"], np.int32),
                    gen_len=r["gen_len"],
                    deadline_s=r.get("deadline_s"),
                    max_tokens=r.get("max_tokens"))
            for r in schedule["requests"]]
    plan = (FaultPlan(**schedule["faults"])
            if schedule["faults"]["events"] else None)
    with mesh:
        eng = ContinuousBatcher(setup, params)
        stats = eng.run(reqs, key=jax.random.PRNGKey(schedule["seed"]),
                        fault_plan=plan)
    for req in reqs:
        status = stats.statuses.get(req.rid)
        assert status is not None, f"rid {req.rid} has no terminal status"
        got = np.asarray(stats.outputs[req.rid], np.int32)
        assert len(got) <= req.budget, \
            f"rid {req.rid}: harvested {len(got)} > budget {req.budget}"
        ref = _oracle(spec, impl, tuple(int(t) for t in req.prompt),
                      req.budget)
        if status in ("done", "retried"):
            assert len(got) == req.budget, \
                f"rid {req.rid}: {status} with {len(got)}/{req.budget}"
            np.testing.assert_array_equal(got, ref,
                                          err_msg=f"rid {req.rid}")
        elif status in ("timeout", "failed"):
            np.testing.assert_array_equal(
                got, ref[:len(got)],
                err_msg=f"rid {req.rid} (prefix, status={status})")


def _fuzz_one(seed, spec, n_req, fault_mode, deadline_mode, impl_idx=0):
    schedule = make_schedule(seed, spec, n_req, fault_mode, deadline_mode,
                             impl_idx)
    try:
        run_schedule(schedule)
    except AssertionError:
        print("\nreplayable schedule seed:\n"
              + json.dumps(schedule, indent=None))
        raise


class TestPoolFuzz:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 10**6), spec=st.booleans(),
           n_req=st.integers(1, 5), fault_mode=st.integers(0, 3),
           deadline_mode=st.integers(0, 2),
           impl_idx=st.integers(0, len(IMPL_MENU) - 1))
    def test_fuzz_quick(self, seed, spec, n_req, fault_mode,
                        deadline_mode, impl_idx):
        """Tier-1 smoke sweep (12 random schedules)."""
        _fuzz_one(seed, spec, n_req, fault_mode, deadline_mode, impl_idx)

    @pytest.mark.slow
    @settings(max_examples=200, deadline=None)
    @given(seed=st.integers(0, 10**6), spec=st.booleans(),
           n_req=st.integers(1, 5), fault_mode=st.integers(0, 3),
           deadline_mode=st.integers(0, 2),
           impl_idx=st.integers(0, len(IMPL_MENU) - 1))
    def test_fuzz_deep(self, seed, spec, n_req, fault_mode,
                       deadline_mode, impl_idx):
        """The deep sweep: 200 schedules, zero parity violations
        (``pytest -m slow tests/test_pool_fuzz.py``)."""
        _fuzz_one(seed, spec, n_req, fault_mode, deadline_mode, impl_idx)

    def test_replay_seed_roundtrip(self):
        """A printed failure seed replays: make_schedule -> JSON ->
        run_schedule is the documented reproduction loop."""
        schedule = make_schedule(1234, True, 3, 1, 0, impl_idx=1)
        run_schedule(json.loads(json.dumps(schedule)))
