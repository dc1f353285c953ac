"""Length-robustness contracts (PR 7).

Four promises, each with its own class:

* **renorm is semantics-preserving and backend-uniform** — the drift
  renormalization (``core/lln.py:decode_chunk(renorm=...)``) changes no
  output on any backend, never touches masked / ``commit_len=0`` rows
  (bitwise), and a continuation from a renormalized state matches one
  from the raw state;
* **beta(n) reduces to the fixed calibration** at ``n <= calib_len`` —
  the length schedule is exactly inert where the shipped constants were
  fit, and the length-aware constant table returns the legacy entries
  there;
* **serving parity survives the robustness layer** — a mixed-depth pool
  with renorm + beta(n) on matches solo runs token-for-token, drifting
  rows quarantine through the sentinel path, and the fused telemetry is
  produced inside ``segment_fn``'s jit;
* **estimators** — the power-iteration spectral gap matches the dense
  eigendecomposition, the seeded fit reproduces the shipped constants,
  and masked ``update_stats`` ignores padding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import lln
from repro.core import moment_matching as mm
from repro.core.health import HealthConfig
from repro.core.metrics import (spectral_gap, spectral_gap_power,
                                streaming_concentration)
from repro.kernels import ops as kops
from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_pool_setup
from repro.models import build_model

B, H, D, DV, T = 2, 4, 8, 8, 12


def _qkv(key, t=T, g=H):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (B, t, H, D), jnp.float32),
            jax.random.normal(kk, (B, t, g, D), jnp.float32),
            jax.random.normal(kv, (B, t, g, DV), jnp.float32))


def _warm_state(key, steps=3):
    """A state that has folded a few chunks (c_k bound, z populated)."""
    st = lln.LLNState.init(B, H, D, DV)
    for i in range(steps):
        q, k, v = _qkv(jax.random.fold_in(key, i))
        _, st = lln.decode_chunk(st, q, k, v, 0.6, 0.6)
    return st


class TestRenormSemantics:
    def test_outputs_invariant_and_continuation_matches(self):
        """Force renorm with a tiny threshold: outputs match the
        renorm-off run, z is pinned under the threshold, and decoding ON
        from the renormalized state matches decoding on from the raw
        state."""
        key = jax.random.PRNGKey(0)
        st = _warm_state(key)
        thresh = float(jnp.max(st.z)) * 0.5     # guaranteed to fire
        q, k, v = _qkv(jax.random.fold_in(key, 100))
        out_off, st_off = lln.decode_chunk(st, q, k, v, 0.6, 0.6)
        out_on, st_on = lln.decode_chunk(st, q, k, v, 0.6, 0.6,
                                         renorm=thresh)
        np.testing.assert_allclose(np.asarray(out_on), np.asarray(out_off),
                                   rtol=2e-5, atol=2e-5)
        assert float(jnp.max(st_on.z)) <= thresh * (1 + 1e-5)
        assert float(jnp.max(st_on.log_scale)) > 0.0
        q2, k2, v2 = _qkv(jax.random.fold_in(key, 101))
        cont_off, _ = lln.decode_chunk(st_off, q2, k2, v2, 0.6, 0.6)
        cont_on, _ = lln.decode_chunk(st_on, q2, k2, v2, 0.6, 0.6)
        np.testing.assert_allclose(np.asarray(cont_on),
                                   np.asarray(cont_off),
                                   rtol=2e-5, atol=2e-5)

    def test_backend_uniform(self, backend_gqa_cell):
        """Every backend (Pallas kernel incl. GQA grouping, scan/ref
        twins) applies the renormalization with the same semantics."""
        backend, r = backend_gqa_cell
        g = H // r
        key = jax.random.PRNGKey(1)
        st = _warm_state(key)
        thresh = float(jnp.max(st.z)) * 0.5
        q, k, v = _qkv(jax.random.fold_in(key, 200), g=g)
        kf = k if g == H else jnp.repeat(k, H // g, axis=2)
        vf = v if g == H else jnp.repeat(v, H // g, axis=2)
        ref_out, ref_st = lln.decode_chunk(st, q, kf, vf, 0.6, 0.6,
                                           renorm=thresh)
        got_out, got_st = kops.lln_decode_chunk(st, q, k, v, 0.6, 0.6,
                                                backend=backend,
                                                renorm=thresh)
        np.testing.assert_allclose(np.asarray(got_out),
                                   np.asarray(ref_out),
                                   rtol=2e-4, atol=2e-4)
        assert float(jnp.max(got_st.z)) <= thresh * (1 + 1e-4)
        # z / c_k / log_scale are gauge: the Pallas GQA path carries a
        # group-level reference constant where the twin keeps per-head
        # ones.  The invariant is the c-corrected log mass.
        def mass(st):
            return streaming_concentration(
                st.z, c=jnp.squeeze(st.c_k, axis=(-1, -3)))["log_mass"]
        np.testing.assert_allclose(np.asarray(mass(got_st)),
                                   np.asarray(mass(ref_st)),
                                   rtol=2e-4, atol=2e-4)

    def test_bitwise_inert_for_masked_and_uncommitted_rows(self):
        """A renorm threshold NEVER touches rows that folded nothing this
        chunk: row_mask=False and commit_len=0 rows keep every leaf —
        including ``log_scale`` — bitwise."""
        key = jax.random.PRNGKey(2)
        st = _warm_state(key)
        thresh = float(jnp.max(st.z)) * 0.5
        q, k, v = _qkv(jax.random.fold_in(key, 300))
        for kwargs in ({"row_mask": jnp.asarray([True, False])},
                       {"commit_len": jnp.asarray([T, 0], jnp.int32)}):
            _, st2 = lln.decode_chunk(st, q, k, v, 0.6, 0.6,
                                      renorm=thresh, **kwargs)
            for name in ("s", "z", "c_k", "log_scale"):
                old = np.asarray(getattr(st, name))
                new = np.asarray(getattr(st2, name))
                np.testing.assert_array_equal(
                    old[1] if name != "c_k" else old[1:2],
                    new[1] if name != "c_k" else new[1:2],
                    err_msg=f"{name} {kwargs.keys()}")
            # ... and the folding row DID renormalize.
            assert float(np.max(np.asarray(st2.z)[0])) <= thresh * (1 + 1e-5)


class TestLengthSchedule:
    def test_gain_exactly_one_at_or_below_calib(self):
        n = jnp.asarray([1.0, 100.0, float(mm.CALIB_LEN)])
        np.testing.assert_array_equal(
            np.asarray(mm.length_gain(n, beta_n=0.7)), np.ones(3))
        assert float(mm.length_gain(jnp.asarray(4.0 * mm.CALIB_LEN),
                                    beta_n=0.7)) > 1.0

    def test_constants_reduce_to_legacy_at_short_n(self):
        for d in mm.FITTED_CONSTANTS:
            assert mm.constants_for_dim(d, n=None) == mm.FITTED_CONSTANTS[d]
            assert mm.constants_for_dim(d, n=512) == mm.FITTED_CONSTANTS[d]
            assert (mm.constants_for_dim(d, n=mm.CALIB_LEN)
                    == mm.FITTED_CONSTANTS[d])
            long = mm.constants_for_dim(d, n=4096)
            assert long == mm.FITTED_CONSTANTS_N[d][4096]

    def test_beta_n_inert_below_calib_token_parity(self):
        """With every depth in the run <= calib_len, a beta_n > 0 model
        decodes bitwise like beta_n = 0 — the schedule reduces to the
        fixed calibration."""
        h = 4
        base = dict(family="dense", n_layers=2, d_model=64, n_heads=h,
                    n_kv_heads=h, d_ff=128, vocab=128, head_dim=16,
                    attn_impl="lln_diag", diag_block=8, lln_chunk=8,
                    softmax_chunk=16, lln_fixed_ab=0.0,
                    compute_dtype="float32", param_dtype="float32",
                    remat="none", tie_embeddings=True)
        cfg0 = ArchConfig(name="sched-off", lln_beta_n=0.0, **base)
        cfg1 = ArchConfig(name="sched-on", lln_beta_n=0.7,
                          lln_calib_len=1024, **base)
        toks = {}
        for cfg in (cfg0, cfg1):
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            mesh = make_mesh((1, 1), ("data", "model"))
            with mesh:
                setup = make_pool_setup(cfg, mesh, slots=2, max_len=32,
                                        segment=3)
                stats = ContinuousBatcher(setup, params).run(
                    synthetic_traffic(2, cfg.vocab, [8], [6], seed=0))
            toks[cfg.name] = [stats.outputs[r] for r in sorted(stats.outputs)]
        for a, b in zip(toks["sched-off"], toks["sched-on"]):
            np.testing.assert_array_equal(a, b)


def _robust_cfg(name, **over):
    h = 4
    return ArchConfig(
        name=name, family="dense", n_layers=2, d_model=64, n_heads=h,
        n_kv_heads=h, d_ff=128, vocab=128, head_dim=16,
        attn_impl="lln_diag", diag_block=8, lln_chunk=8, softmax_chunk=16,
        lln_fixed_ab=0.0, lln_beta_n=0.5, lln_calib_len=4,
        lln_renorm=4.0, compute_dtype="float32", param_dtype="float32",
        remat="none", tie_embeddings=True, **over)


class TestPoolRobustness:
    def test_mixed_depth_pool_matches_solo(self):
        """Renorm + beta(n) BOTH engaged (calib_len=4 < every depth,
        renorm threshold low enough to fire): mixed-depth pooled rows
        still decode token-for-token like solo runs — per-row gain off
        ``state.pos`` and per-row renorm do not couple slots."""
        cfg = _robust_cfg("robust-pool")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 40
        reqs = synthetic_traffic(4, cfg.vocab, prompt_lens=[8, 8, 14],
                                 gen_lens=[3, 9, 5], seed=11)
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=max_len,
                                    segment=3)
            stats = ContinuousBatcher(setup, params).run(reqs)
            # Solo reference via the pool machinery at 1 slot: same
            # engine, no slot interleaving, no mixed depths.
            solo_setup = make_pool_setup(cfg, mesh, slots=1,
                                         max_len=max_len, segment=3)
            for req in reqs:
                solo = ContinuousBatcher(solo_setup, params).run([req])
                np.testing.assert_array_equal(
                    stats.outputs[req.rid], solo.outputs[req.rid],
                    err_msg=f"rid {req.rid}")

    def test_drift_quarantine_reuses_recovery_path(self):
        """check_drift with an absurd threshold quarantines every live
        row: health events are recorded and retries exhaust into failed
        statuses — the same path corruption takes."""
        cfg = _robust_cfg("robust-drift")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(
                cfg, mesh, slots=2, max_len=32, segment=3,
                health=HealthConfig(check_drift=True, max_conc_drift=1e-6))
            eng = ContinuousBatcher(setup, params, max_retries=1)
            stats = eng.run(synthetic_traffic(2, cfg.vocab, [8], [6],
                                              seed=0))
        assert stats.health_events
        assert all(s == "failed" for s in stats.statuses.values())

    def test_telemetry_fused_in_segment_and_surfaced(self):
        """segment_fn returns the metrics dict from inside its jit; the
        run summary surfaces finite instruments; softmax pools and
        telemetry=False report empty."""
        cfg = _robust_cfg("robust-tele")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=32,
                                    segment=3)
            stats = ContinuousBatcher(setup, params).run(
                synthetic_traffic(2, cfg.vocab, [8], [6], seed=0))
            assert set(stats.telemetry) == {"conc_drift_max",
                                            "log_mass_mean",
                                            "log_mass_var_mean",
                                            "tau_hat_mean"}
            assert all(np.isfinite(v) for v in stats.telemetry.values())

            off = make_pool_setup(cfg, mesh, slots=2, max_len=32,
                                  segment=3, telemetry=False)
            stats_off = ContinuousBatcher(off, params).run(
                synthetic_traffic(2, cfg.vocab, [8], [6], seed=0))
            assert stats_off.telemetry == {}

            sm = cfg.replace(name="tele-sm", attn_impl="softmax",
                             lln_beta_n=0.0, lln_renorm=0.0)
            sm_model = build_model(sm)
            sm_params = sm_model.init(jax.random.PRNGKey(0))
            sm_setup = make_pool_setup(sm, mesh, slots=2, max_len=32,
                                       segment=3)
            sm_stats = ContinuousBatcher(sm_setup, sm_params).run(
                synthetic_traffic(2, sm.vocab, [8], [6], seed=0))
            assert sm_stats.telemetry == {}


class TestStreamingInstruments:
    def test_log_mass_renorm_invariant(self):
        """Same stream, renorm on vs off: the c_k-corrected log mass
        agrees to rounding (the renorm shift folds into c_k)."""
        key = jax.random.PRNGKey(5)
        st_off = lln.LLNState.init(B, H, D, DV)
        st_on = lln.LLNState.init(B, H, D, DV)
        for i in range(6):
            q, k, v = _qkv(jax.random.fold_in(key, i))
            _, st_off = lln.decode_chunk(st_off, q, k, v, 0.6, 0.6)
            _, st_on = lln.decode_chunk(st_on, q, k, v, 0.6, 0.6,
                                        renorm=2.0)

        def mass(st):
            return streaming_concentration(
                st.z, c=jnp.squeeze(st.c_k, axis=(-1, -3)),
                log_scale=st.log_scale)["log_mass"]

        assert float(jnp.max(st_on.log_scale)) > 0.0    # renorm fired
        np.testing.assert_allclose(np.asarray(mass(st_on)),
                                   np.asarray(mass(st_off)),
                                   rtol=1e-5, atol=1e-5)

    def test_spectral_gap_power_matches_dense(self):
        rng = np.random.default_rng(0)
        for n, conc in ((24, 0.5), (48, 2.0), (48, 8.0)):
            logits = conc * rng.standard_normal((n, n))
            p = np.exp(logits - logits.max(axis=-1, keepdims=True))
            p /= p.sum(axis=-1, keepdims=True)
            dense = spectral_gap(p)
            power = spectral_gap_power(p, iters=400)
            assert abs(power - dense) < 0.02, (n, conc, dense, power)

    def test_fit_pins_shipped_constants(self):
        """The seeded fit reproduces the shipped tables: exactly the grid
        entry it generated (same seed, same env), and the legacy defaults
        within a drift tolerance (they were fit under an older stack)."""
        a, b = mm.fit_lln_constants(d=64, n=1024, num_seeds=4, seed=0)
        ga, gb = mm.FITTED_CONSTANTS_N[64][1024]
        assert abs(a - ga) < 5e-3 and abs(b - gb) < 5e-2, (a, b, ga, gb)
        la, lb = mm.FITTED_CONSTANTS[64]
        assert abs(a - la) < 2e-2 and abs(b - lb) < 1.5e-1, (a, b, la, lb)

    def test_update_stats_mask_ignores_padding(self):
        """Masked update on a padded batch == unmasked update on the
        dense batch; the unmasked padded update is polluted toward 0."""
        key = jax.random.PRNGKey(9)
        kq, kk = jax.random.split(key)
        q = jax.random.normal(kq, (2, 6, H, D), jnp.float32)
        k = 2.0 * jax.random.normal(kk, (2, 6, H, D), jnp.float32)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0], [1, 1, 0, 0, 0, 0]],
                           jnp.float32)
        qp = q * mask[:, :, None, None]
        kp = k * mask[:, :, None, None]
        st0 = mm.QKStats.init(H)
        got = mm.update_stats(st0, qp, kp, decay=0.5, mask=mask)
        # Dense reference: only the real tokens, flattened into one row.
        keep = np.asarray(mask).astype(bool)
        qd = jnp.asarray(np.asarray(q)[keep])[None]
        kd = jnp.asarray(np.asarray(k)[keep])[None]
        want = mm.update_stats(st0, qd, kd, decay=0.5)
        np.testing.assert_allclose(np.asarray(got.sigma_q),
                                   np.asarray(want.sigma_q), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(got.sigma_k),
                                   np.asarray(want.sigma_k), rtol=1e-6)
        polluted = mm.update_stats(st0, qp, kp, decay=0.5)
        assert float(jnp.max(polluted.sigma_k)) < float(jnp.max(got.sigma_k))
