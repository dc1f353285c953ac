"""Fault-tolerant serving: every recovery path proven end to end.

* state-health sentinel (``core/health.py`` + ``AttentionEngine.
  check_health``) flags exactly the poisoned rows;
* under an injected per-row NaN, healthy pool rows are token-for-token
  identical to the fault-free run, and the quarantined row recovers —
  re-prefill + partial-commit replay — to the SAME final tokens (which
  equal its fresh solo run, by the pool-parity suite) with status
  ``retried``;
* poisoned FREE slots reset silently without touching live rows;
* typed admission rejection (bad rid/prompt/vocab/budget, duplicate,
  queue cap) never crashes the loop and always yields status
  ``rejected`` with a reason;
* deadlines fire at segment boundaries (status ``timeout``, partial
  output kept), and an injected ``delay`` trips the straggler watchdog;
* retry exhaustion under repeated poison yields status ``failed``;
* a ``kill`` fault mid-run + ``run(resume=True)`` restores the pool from
  the latest snapshot and finishes every in-flight request with the same
  final tokens as the crash-free run.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core.engine import AttentionEngine
from repro.core.health import HealthConfig, row_health, unhealthy_rows
from repro.checkpoint.manager import CheckpointManager
from repro.kernels.registry import AttnSpec
from repro.launch.batcher import (AdmissionError, ContinuousBatcher,
                                  QueueFullError, Request, synthetic_traffic)
from repro.launch.faults import (FaultEvent, FaultPlan, SimulatedCrash,
                                 poison_rows)
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_pool_setup
from repro.models import build_model


def _tiny_cfg(impl="lln_diag", r=2, fixed_ab=False):
    h = 4
    return ArchConfig(
        name=f"robust-test-{impl}-r{r}", family="dense", n_layers=2,
        d_model=64, n_heads=h, n_kv_heads=h // r, d_ff=128, vocab=128,
        head_dim=16, attn_impl=impl, diag_block=8, lln_chunk=8,
        softmax_chunk=16,
        lln_fixed_ab=2.1 if fixed_ab and impl != "softmax" else 0.0,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True)


@dataclasses.dataclass
class _Pool:
    cfg: object
    model: object
    params: object
    mesh: object
    setup: object


@pytest.fixture(scope="module")
def pool():
    """One shared 2-slot pool (dynamic per-row calibration — the hardest
    recovery mode: alpha/beta must survive re-prefill bitwise)."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh:
        setup = make_pool_setup(cfg, mesh, slots=2, max_len=48, segment=3)
        yield _Pool(cfg=cfg, model=model, params=params, mesh=mesh,
                    setup=setup)


def _run(pool, reqs, **kw):
    eng = ContinuousBatcher(pool.setup, pool.params)
    with pool.mesh:
        return eng.run(reqs, key=jax.random.PRNGKey(42), **kw)


# ---------------------------------------------------------------------------
# Sentinel unit level.
# ---------------------------------------------------------------------------

class TestSentinel:
    def test_row_health_flags_each_failure_mode(self):
        s = np.zeros((4, 2, 3), np.float32)
        s[1, 0, 2] = np.nan
        s[2, 1, 1] = 1e9                      # magnitude explosion
        alpha = np.ones((4, 2), np.float32)
        alpha[3, 0] = -0.5                    # calibration drift
        tree = {"s": jnp.asarray(s), "alpha": jnp.asarray(alpha),
                "len": jnp.zeros((4,), jnp.int32)}   # int leaf skipped
        flags = row_health(tree, row_axis=0)
        np.testing.assert_array_equal(
            np.asarray(flags["nonfinite"]), [False, True, False, False])
        np.testing.assert_array_equal(
            np.asarray(flags["magnitude"]), [False, False, True, False])
        np.testing.assert_array_equal(
            np.asarray(flags["calib"]), [False, False, False, True])
        np.testing.assert_array_equal(
            np.asarray(flags["unhealthy"]), [False, True, True, True])

    def test_config_disables_checks(self):
        s = np.zeros((2, 3), np.float32)
        s[1] = 1e9
        cfg = HealthConfig(check_magnitude=False)
        got = unhealthy_rows({"s": jnp.asarray(s)}, config=cfg)
        assert not np.asarray(got).any()

    def test_no_float_leaves_raises(self):
        with pytest.raises(ValueError):
            row_health({"len": jnp.zeros((2,), jnp.int32)})

    def test_engine_check_health_hook(self):
        g, r, d = 2, 2, 8
        spec = AttnSpec(impl="lln_diag", causal=True, r=r, lln_chunk=8,
                        diag_block=8, fixed_ab=2.1)
        eng = AttentionEngine(spec=spec, heads=g * r, kv_heads=g,
                              head_dim=d, v_dim=d,
                              cache_dtype=jnp.float32)
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(kq, (2, 16, g * r, d))
        k = jax.random.normal(kk, (2, 16, g, d))
        v = jax.random.normal(kv, (2, 16, g, d))
        _, state = eng.prefill(q, k, v, max_len=24)
        healthy = eng.check_health(state)
        assert not np.asarray(healthy["unhealthy"]).any()
        bad = jax.tree_util.tree_map(
            lambda a: a.at[0].set(jnp.nan)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, state)
        flags = eng.check_health(bad)
        np.testing.assert_array_equal(np.asarray(flags["unhealthy"]),
                                      [True, False])

    def test_free_pool_slot_is_healthy_by_construction(self, pool):
        caches = pool.setup.cache_init()
        got = unhealthy_rows(caches, row_axis=1)
        assert not np.asarray(got).any()


# ---------------------------------------------------------------------------
# Quarantine -> re-prefill recovery (the tentpole parity test).
# ---------------------------------------------------------------------------

class TestQuarantineRecovery:
    def test_nan_row_recovers_and_healthy_rows_unaffected(self, pool):
        """Poison slot 0 mid-run.  Healthy rows must be token-for-token
        identical to the fault-free run; the quarantined request must
        recover (re-prefill + replay) to the SAME final tokens with
        status ``retried``."""
        reqs = synthetic_traffic(3, pool.cfg.vocab, prompt_lens=[8, 11],
                                 gen_lens=[14, 9], seed=3)
        clean = _run(pool, reqs)
        assert all(v == "done" for v in clean.statuses.values())

        plan = FaultPlan(events=[FaultEvent(kind="nan", segment=2, row=0)])
        faulty = _run(pool, reqs, fault_plan=plan)

        assert faulty.recoveries == 1
        assert len(faulty.health_events) == 1
        hurt_rid = faulty.health_events[0]["rid"]
        assert hurt_rid >= 0
        for req in reqs:
            np.testing.assert_array_equal(
                faulty.outputs[req.rid], clean.outputs[req.rid],
                err_msg=f"rid {req.rid}")
            want = "retried" if req.rid == hurt_rid else "done"
            assert faulty.statuses[req.rid] == want
        assert faulty.completed_tokens == clean.completed_tokens

    def test_poisoned_free_slot_resets_silently(self, pool):
        """NaN in a FREE slot (rid -1) must reset the row without touching
        the live request — and must not count as a recovery."""
        reqs = synthetic_traffic(1, pool.cfg.vocab, prompt_lens=[8],
                                 gen_lens=[10], seed=5)
        clean = _run(pool, reqs)
        plan = FaultPlan(events=[FaultEvent(kind="nan", segment=1, row=1)])
        faulty = _run(pool, reqs, fault_plan=plan)
        np.testing.assert_array_equal(faulty.outputs[0], clean.outputs[0])
        assert faulty.statuses[0] == "done"
        assert faulty.recoveries == 0
        assert faulty.health_events and faulty.health_events[0]["rid"] == -1

    def test_retry_exhaustion_fails_request(self, pool):
        """Repeated poison on the same request: retries back off, then
        exhaust -> status ``failed`` with a typed reason."""
        reqs = synthetic_traffic(1, pool.cfg.vocab, prompt_lens=[8],
                                 gen_lens=[30], seed=9)
        plan = FaultPlan(events=[
            FaultEvent(kind="nan", segment=1, row=0),
            FaultEvent(kind="nan", segment=4, row=0),
            FaultEvent(kind="nan", segment=8, row=0)])
        eng = ContinuousBatcher(pool.setup, pool.params, max_retries=2)
        with pool.mesh:
            stats = eng.run(reqs, key=jax.random.PRNGKey(42),
                            fault_plan=plan)
        assert stats.statuses[0] == "failed"
        assert "retries exhausted" in stats.reject_reasons[0]
        assert stats.failed == 1

    def test_drop_fault_cancels_request(self, pool):
        reqs = synthetic_traffic(2, pool.cfg.vocab, prompt_lens=[8],
                                 gen_lens=[12], seed=11)
        clean = _run(pool, reqs)
        plan = FaultPlan(events=[FaultEvent(kind="drop", segment=1,
                                            rid=0)])
        faulty = _run(pool, reqs, fault_plan=plan)
        assert faulty.statuses[0] == "failed"
        assert "dropped" in faulty.reject_reasons[0]
        assert faulty.statuses[1] == "done"
        np.testing.assert_array_equal(faulty.outputs[1], clean.outputs[1])


# ---------------------------------------------------------------------------
# Admission validation + queue bounds (typed rejection, no crashes).
# ---------------------------------------------------------------------------

class TestAdmissionGuards:
    def test_typed_validation_errors(self, pool):
        eng = ContinuousBatcher(pool.setup, pool.params)
        ok = np.zeros((8,), np.int32)
        cases = [
            Request(rid=-2, prompt=ok, gen_len=4),
            Request(rid=1, prompt=np.zeros((0,), np.int32), gen_len=4),
            Request(rid=2, prompt=np.zeros((8,), np.float32), gen_len=4),
            Request(rid=3, prompt=ok + pool.cfg.vocab, gen_len=4),
            Request(rid=4, prompt=ok, gen_len=0),
            Request(rid=5, prompt=ok, gen_len=1000),   # exceeds max_len
            Request(rid=6, prompt=ok, gen_len=4, deadline_s=-1.0),
            Request(rid=7, prompt=ok, gen_len=4, max_tokens=0),
        ]
        for req in cases:
            with pytest.raises(AdmissionError):
                eng.check_request(req)

    def test_rejected_requests_get_status_and_survivors_complete(self, pool):
        good = synthetic_traffic(2, pool.cfg.vocab, prompt_lens=[8],
                                 gen_lens=[6], seed=13)
        bad = [Request(rid=10, prompt=np.zeros((8,), np.int32),
                       gen_len=1000),
               Request(rid=11,
                       prompt=np.full((8,), pool.cfg.vocab, np.int32),
                       gen_len=4)]
        clean = _run(pool, good)
        stats = _run(pool, good + bad)
        assert stats.statuses[10] == "rejected"
        assert "max_len" in stats.reject_reasons[10]
        assert stats.statuses[11] == "rejected"
        assert stats.rejected == 2
        for req in good:
            assert stats.statuses[req.rid] == "done"
            np.testing.assert_array_equal(stats.outputs[req.rid],
                                          clean.outputs[req.rid])

    def test_duplicate_rid_rejected(self, pool):
        reqs = synthetic_traffic(1, pool.cfg.vocab, prompt_lens=[8],
                                 gen_lens=[4], seed=15)
        dup = Request(rid=0, prompt=reqs[0].prompt, gen_len=4)
        stats = _run(pool, reqs + [dup])
        assert stats.statuses[0] == "done"
        assert stats.rejected == 1

    def test_queue_cap_rejects_overflow(self, pool):
        reqs = synthetic_traffic(4, pool.cfg.vocab, prompt_lens=[8],
                                 gen_lens=[4], seed=17)
        eng = ContinuousBatcher(pool.setup, pool.params, queue_cap=2)
        with pool.mesh:
            stats = eng.run(reqs, key=jax.random.PRNGKey(42))
        served = [r for r, v in stats.statuses.items() if v == "done"]
        capped = [r for r, v in stats.statuses.items() if v == "rejected"]
        assert len(served) == 2 and len(capped) == 2
        for rid in capped:
            assert "queue" in stats.reject_reasons[rid]

    def test_max_tokens_bounds_output_buffer(self, pool):
        req = Request(rid=0,
                      prompt=np.zeros((8,), np.int32), gen_len=20,
                      max_tokens=5)
        stats = _run(pool, [req])
        assert stats.statuses[0] == "done"
        assert len(stats.outputs[0]) == 5


# ---------------------------------------------------------------------------
# Deadlines + straggler watchdog.
# ---------------------------------------------------------------------------

class TestDeadlines:
    def test_deadline_times_out_with_partial_output(self, pool):
        reqs = [Request(rid=0, prompt=np.zeros((8,), np.int32),
                        gen_len=30, deadline_s=1e-4),
                Request(rid=1, prompt=np.ones((8,), np.int32),
                        gen_len=6)]
        stats = _run(pool, reqs)
        assert stats.statuses[0] == "timeout"
        assert stats.timeouts == 1
        assert 1 <= len(stats.outputs[0]) < 30   # partial kept
        assert stats.statuses[1] == "done"
        assert len(stats.outputs[1]) == 6

    def test_delay_fault_trips_watchdog(self, pool):
        reqs = synthetic_traffic(1, pool.cfg.vocab, prompt_lens=[8],
                                 gen_lens=[36], seed=21)
        plan = FaultPlan(events=[FaultEvent(kind="delay", segment=8,
                                            seconds=1.0)])
        stats = _run(pool, reqs, fault_plan=plan)
        assert stats.segment_ewma_s > 0
        # The EWMA threshold is ~1ms here, so an OS scheduling blip on a
        # loaded host can also register — require the injected delay to
        # be AMONG the stragglers, not necessarily the first.
        delayed = [r for r in stats.stragglers if r.duration >= 1.0]
        assert delayed, "1s delay must register as a straggler"


# ---------------------------------------------------------------------------
# Snapshot / kill / restore.
# ---------------------------------------------------------------------------

class TestKillRestore:
    def test_kill_and_restore_resumes_identically(self, pool, tmp_path):
        """Crash (kill fault) after segment 3 with per-segment snapshots;
        ``run(resume=True)`` must finish every in-flight request with the
        same final tokens as the crash-free run."""
        reqs = synthetic_traffic(3, pool.cfg.vocab, prompt_lens=[8, 11],
                                 gen_lens=[16, 9], seed=23)
        clean = _run(pool, reqs)

        mgr = CheckpointManager(str(tmp_path), keep_n=2, interval=1)
        eng = ContinuousBatcher(pool.setup, pool.params, snapshot_mgr=mgr,
                                snapshot_every=1)
        plan = FaultPlan(events=[FaultEvent(kind="kill", segment=3)])
        with pool.mesh:
            with pytest.raises(SimulatedCrash):
                eng.run(reqs, key=jax.random.PRNGKey(42), fault_plan=plan)
            assert mgr.latest_step() == 3
            stats = eng.run([], resume=True)
        assert stats.restored_step == 3
        assert stats.snapshots > 0
        for req in reqs:
            np.testing.assert_array_equal(
                stats.outputs[req.rid], clean.outputs[req.rid],
                err_msg=f"rid {req.rid}")
            assert stats.statuses[req.rid] == "done"

    def test_resume_without_snapshot_raises(self, pool, tmp_path):
        mgr = CheckpointManager(str(tmp_path), interval=1)
        eng = ContinuousBatcher(pool.setup, pool.params, snapshot_mgr=mgr,
                                snapshot_every=1)
        with pytest.raises(RuntimeError):
            eng.run([], resume=True)


# ---------------------------------------------------------------------------
# Fault-plan plumbing.
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_json_roundtrip_and_inline_load(self):
        plan = FaultPlan(events=[
            FaultEvent(kind="nan", segment=2, row=1),
            FaultEvent(kind="kill", segment=4)], seed=7)
        back = FaultPlan.load(plan.to_json())
        assert back.seed == 7
        assert [e.kind for e in back.events] == ["nan", "kill"]
        assert back.at(4)[0].kind == "kill"

    def test_bad_kind_rejected(self):
        with pytest.raises(ValueError):
            FaultEvent(kind="meteor", segment=0)

    def test_seeded_row_pick_is_deterministic(self):
        ev = FaultEvent(kind="nan", segment=0, row=-1)
        rows1 = [FaultPlan(events=[ev], seed=3).pick_row(ev, 8)
                 for _ in range(3)]
        rows2 = [FaultPlan(events=[ev], seed=3).pick_row(ev, 8)
                 for _ in range(3)]
        assert rows1 == rows2

    def test_poison_rows_hits_only_target_rows(self, pool):
        caches = pool.setup.cache_init()
        bad = poison_rows(caches, [1])
        flags = np.asarray(unhealthy_rows(bad, row_axis=1))
        np.testing.assert_array_equal(flags, [False, True])
