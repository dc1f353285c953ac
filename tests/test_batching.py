"""Continuous-batching engine: parity with solo serving + masked-row
state-isolation.

* the pool (staggered admits/evicts, per-row positions, row masks) emits
  token-for-token the SAME sequence per request as running that request
  alone through ``ServeSetup.make_generate`` — softmax/lln/lln_diag ×
  GQA r ∈ {1, 4};
* masked rows provably do not mutate state: every cache leaf of a
  masked-off row is bitwise unchanged through ``model.decode``, at both
  the model level and the ``lln_decode_chunk``/``decode_lln_chunk`` level;
* per-row positions degenerate to the scalar path when all rows agree;
* ``admit_fn`` writes exactly one pool row.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core import attention as ca
from repro.core import lln as core_lln
from repro.kernels import ops as kops
from repro.launch.batcher import (ContinuousBatcher, Request,
                                  synthetic_traffic)
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_pool_setup, make_serve_setup
from repro.models import build_model
from repro.models import transformer as tr
from repro.models.layers import apply_norm, embed_lookup, logits_from_hidden


def _tiny_cfg(impl, r, fixed_ab=True):
    h = 4
    return ArchConfig(
        name=f"pool-test-{impl}-r{r}", family="dense", n_layers=2,
        d_model=64, n_heads=h, n_kv_heads=h // r, d_ff=128, vocab=128,
        head_dim=16, attn_impl=impl, diag_block=8, lln_chunk=8,
        softmax_chunk=16,
        lln_fixed_ab=2.1 if fixed_ab and impl != "softmax" else 0.0,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True)


def _solo_tokens(cfg, model, params, mesh, req, max_len, gen_cache):
    """The request served alone: B=1 prefill + ``make_generate``."""
    plen = len(req.prompt)
    if ("setup", plen) not in gen_cache:
        shape = ShapeSpec("solo", max_len, 1, "decode")
        gen_cache[("setup", plen)] = make_serve_setup(cfg, shape, mesh,
                                                      multi_pod=False)
    setup = gen_cache[("setup", plen)]
    batch = {"inputs": jnp.asarray(req.prompt)[None, :],
             "targets": jnp.asarray(req.prompt)[None, :],
             "mask": jnp.ones((1, plen), jnp.float32)}
    logits, caches = setup.prefill_fn(params, batch)
    last = logits[:, -1] if logits.ndim == 3 else logits
    tok0 = jnp.argmax(last, -1).astype(jnp.int32)
    toks = [int(tok0[0])]
    if req.gen_len > 1:
        key = ("gen", plen, req.gen_len)
        if key not in gen_cache:
            gen_cache[key] = setup.make_generate(req.gen_len - 1, 0.0)
        out, _ = gen_cache[key](params, caches, tok0,
                                jnp.asarray(plen, jnp.int32),
                                jax.random.PRNGKey(0))
        toks.extend(int(t) for t in np.asarray(out)[0])
    return np.asarray(toks, np.int32)


class TestPoolParity:
    @pytest.mark.parametrize("r", [1, 4])
    @pytest.mark.parametrize("impl", ["softmax", "lln", "lln_diag",
                                      "log_linear"])
    def test_pool_matches_solo_generate(self, impl, r):
        """2 slots, 4 mixed-length requests: admits/evicts stagger (short
        requests retire and refill their slot while a long one is still
        mid-flight), yet every request's tokens equal its solo run."""
        cfg = _tiny_cfg(impl, r)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 32
        # Two leading same-length prompts exercise grouped admission (one
        # batched prefill admitting both slots); the 11-prompt exercises
        # the per-length compile path.
        reqs = synthetic_traffic(4, cfg.vocab, prompt_lens=[8, 8, 11],
                                 gen_lens=[2, 7, 4], seed=r)
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=max_len,
                                    segment=3)
            stats = ContinuousBatcher(setup, params).run(reqs)
            assert stats.admitted == len(reqs)
            gen_cache: dict = {}
            for req in reqs:
                ref = _solo_tokens(cfg, model, params, mesh, req, max_len,
                                   gen_cache)
                got = stats.outputs[req.rid]
                assert len(got) == req.gen_len
                np.testing.assert_array_equal(got, ref,
                                              err_msg=f"rid {req.rid}")

    def test_pool_matches_solo_dynamic_calibration(self):
        """Dynamic moment matching (no fixed alpha/beta): every slot
        carries genuinely different per-row (B, H) alpha/beta from its own
        prompt statistics.  Per-row calibration (``lln_per_row_calib``,
        the pool default) makes a batched slot prefill exact per request,
        so admission is GROUPED even here — and pooled rows still decode
        token-for-token like solo runs."""
        cfg = _tiny_cfg("lln_diag", 2, fixed_ab=False)
        assert cfg.lln_fixed_ab == 0
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(3))
        max_len = 32
        reqs = synthetic_traffic(3, cfg.vocab, prompt_lens=[8],
                                 gen_lens=[3, 6], seed=7)
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=max_len,
                                    segment=3)
            eng = ContinuousBatcher(setup, params)
            assert eng.group_admits     # batched-prefill admission
            stats = eng.run(reqs)
            gen_cache: dict = {}
            for req in reqs:
                ref = _solo_tokens(cfg, model, params, mesh, req, max_len,
                                   gen_cache)
                np.testing.assert_array_equal(stats.outputs[req.rid], ref,
                                              err_msg=f"rid {req.rid}")


class TestMaskedRows:
    @pytest.mark.parametrize("impl", ["softmax", "lln_diag"])
    def test_masked_rows_do_not_mutate_model_caches(self, impl):
        """model.decode with a row mask leaves every cache leaf of the
        masked rows bitwise unchanged (and matches the unmasked decode on
        active rows)."""
        cfg = _tiny_cfg(impl, 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        b, plen, max_len = 3, 8, 24
        toks = jax.random.randint(jax.random.PRNGKey(2), (b, plen), 0,
                                  cfg.vocab, jnp.int32)
        # Per-row pooled caches at a common depth (prefill each row solo
        # would also work; a shared prefill keeps the test fast).
        _, caches = model.prefill(params, {"inputs": toks}, max_len)

        def per_rowify(leaf):
            if leaf.ndim == 1 and leaf.shape[0] == cfg.n_layers:  # len/pos
                return jnp.broadcast_to(leaf[:, None],
                                        (cfg.n_layers, b)).astype(leaf.dtype)
            if leaf.ndim == 2 and leaf.shape == (cfg.n_layers, cfg.n_heads):
                return jnp.broadcast_to(leaf[:, None, :],
                                        (cfg.n_layers, b, cfg.n_heads))
            return leaf
        caches = jax.tree_util.tree_map(per_rowify, caches)

        mask = jnp.asarray([True, False, True])
        tok = jnp.asarray([3, 5, 7], jnp.int32)
        pos = jnp.full((b,), plen, jnp.int32)
        _, c_masked = model.decode(params, caches, tok, pos, row_mask=mask)
        _, c_all = model.decode(params, caches, tok, pos,
                                row_mask=jnp.ones((b,), jnp.bool_))

        def rows(leaf, i):
            # Every cache leaf carries the batch axis at position 1
            # (stacked layers first); counters/calibration are (L, B[, H]).
            return np.asarray(leaf)[:, i]
        for kp, before in jax.tree_util.tree_leaves_with_path(caches):
            after = c_masked
            for k in kp:
                after = after[k.key] if hasattr(k, "key") else after[k.idx]
            path = jax.tree_util.keystr(kp)
            np.testing.assert_array_equal(
                rows(after, 1), rows(before, 1),
                err_msg=f"masked row mutated: {path}")
            got = c_all
            for k in kp:
                got = got[k.key] if hasattr(k, "key") else got[k.idx]
            np.testing.assert_array_equal(
                rows(after, 0), rows(got, 0),
                err_msg=f"active row diverged under masking: {path}")

    @pytest.mark.parametrize("backend", ["auto", "ref"])
    def test_masked_rows_lln_decode_chunk(self, backend):
        """decode_lln_chunk row mask: masked rows keep (s, z, c_k), tails
        and pos exactly."""
        b, t, g, r, d, block = 3, 2, 2, 2, 8, 8
        h = g * r
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
        q0 = jax.random.normal(kq, (b, 24, h, d))
        k0 = jax.random.normal(kk, (b, 24, g, d))
        v0 = jax.random.normal(kv, (b, 24, g, d))
        alpha = jnp.full((h,), 1.2)
        beta = jnp.full((g,), 1.0)
        _, s, z, c_k = kops.lln_prefill(q0, k0, v0, alpha, beta, chunk=8)
        st = ca.LLNDecodeState(
            lln=core_lln.LLNState(s=s, z=z, c_k=c_k),
            tail_k=k0[:, -block:], tail_v=v0[:, -block:],
            pos=jnp.full((b,), 24, jnp.int32))
        qn, kn, vn = (jax.random.normal(k_, (b, t, hh, d)) for k_, hh in
                      zip(jax.random.split(jax.random.PRNGKey(4), 3),
                          (h, g, g)))
        mask = jnp.asarray([False, True, False])
        _, st2 = ca.decode_lln_chunk(st, qn, kn, vn, alpha,
                                     jnp.repeat(beta, r),
                                     backend=backend, row_mask=mask)
        for name in ("tail_k", "tail_v", "pos"):
            a, bfr = getattr(st2, name), getattr(st, name)
            for i in (0, 2):
                np.testing.assert_array_equal(np.asarray(a)[i],
                                              np.asarray(bfr)[i],
                                              err_msg=name)
        for name in ("s", "z", "c_k"):
            a, bfr = getattr(st2.lln, name), getattr(st.lln, name)
            for i in (0, 2):
                np.testing.assert_array_equal(np.asarray(a)[i],
                                              np.asarray(bfr)[i],
                                              err_msg=name)
        # The active row advanced.
        assert int(np.asarray(st2.pos)[1]) == 24 + t
        assert not np.array_equal(np.asarray(st2.lln.s)[1],
                                  np.asarray(st.lln.s)[1])


class TestMaskedLogits:
    @pytest.mark.parametrize("impl", ["softmax", "lln_diag"])
    def test_masked_row_logits_never_reach_sampling(self, impl):
        """The masked-row contract says an inactive slot's logits are
        garbage — segment_fn must neutralize them before sample_token.
        Regression: poison a free slot's cache state with NaN (the worst
        legal garbage) and assert the active rows' harvested tokens are
        bitwise identical to a clean-pool run, with no NaN anywhere in
        the emitted stream."""
        cfg = _tiny_cfg(impl, 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(4))
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=32,
                                    segment=4, temperature=0.7)
            prompt = jax.random.randint(jax.random.PRNGKey(5), (1, 8), 0,
                                        cfg.vocab, jnp.int32)
            _, slot_caches = setup.prefill_fn(8)(params, prompt)

            def run_segment(pool):
                tok = jnp.zeros((2,), jnp.int32).at[0].set(7)
                pos = jnp.zeros((2,), jnp.int32).at[0].set(8)
                remaining = jnp.zeros((2,), jnp.int32).at[0].set(4)
                active = jnp.asarray([True, False])
                out = setup.segment_fn(params, pool, tok, pos, remaining,
                                       active, jax.random.PRNGKey(6))
                _, tok2, _, _, _, toks, emitted, _, _ = out
                return np.asarray(toks), np.asarray(emitted), \
                    np.asarray(tok2)

            clean = setup.admit_fn(setup.cache_init(), slot_caches,
                                   jnp.asarray([0], jnp.int32))
            toks_clean, em_clean, tok_clean = run_segment(clean)

            poisoned = setup.admit_fn(setup.cache_init(), slot_caches,
                                      jnp.asarray([0], jnp.int32))
            poisoned = jax.tree_util.tree_map(
                lambda a: a.at[:, 1].set(jnp.nan)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, poisoned)
            toks_poi, em_poi, tok_poi = run_segment(poisoned)

        np.testing.assert_array_equal(em_clean, em_poi)
        np.testing.assert_array_equal(toks_clean[:, 0], toks_poi[:, 0])
        assert tok_clean[0] == tok_poi[0]
        # Nothing NaN-shaped leaked into the emitted token stream.
        assert (toks_poi[em_poi] >= 0).all()


class TestEvictCalibration:
    def test_evict_resets_alpha_beta_to_init(self):
        """evict_fn resets a freed slot to its init_state values: zeros
        everywhere EXCEPT alpha/beta, which reset to ONES — a previous
        request's moment-matching constants must not survive in the
        pool."""
        cfg = _tiny_cfg("lln_diag", 2, fixed_ab=False)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(7))
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=32,
                                    segment=2)
            pooled = setup.cache_init()
            prompt = jax.random.randint(jax.random.PRNGKey(8), (1, 8), 0,
                                        cfg.vocab, jnp.int32)
            _, sc = setup.prefill_fn(8)(params, prompt)
            pooled = setup.admit_fn(pooled, sc,
                                    jnp.asarray([1], jnp.int32))
            # The admitted row carries genuine prompt calibration != 1.
            a1 = np.asarray(pooled["layers"]["alpha"])[:, 1]
            assert not np.allclose(a1, 1.0)
            mask = np.zeros((2,), np.bool_)
            mask[1] = True
            pooled = setup.evict_fn(pooled, jnp.asarray(mask))
        for kp, leaf in jax.tree_util.tree_leaves_with_path(pooled):
            name = jax.tree_util.keystr(kp)
            row = np.asarray(leaf)[:, 1]
            want = 1.0 if ("alpha" in name or "beta" in name) else 0.0
            np.testing.assert_array_equal(
                row, np.full_like(row, want),
                err_msg=f"evict left {name} at non-init values")

    def test_readmit_into_evicted_slot_matches_solo(self):
        """Re-admission regression: serve request A in a slot, evict it,
        then admit request B — whose prompt statistics (and therefore
        per-row dynamic alpha/beta) genuinely differ — into the SAME
        slot.  B must decode token-for-token like a solo run; any stale
        calibration or state surviving eviction would break this."""
        cfg = _tiny_cfg("lln_diag", 2, fixed_ab=False)
        assert cfg.lln_fixed_ab == 0     # dynamic moment matching
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(9))
        max_len = 32
        # Different prompt lengths => different lengths AND statistics.
        reqs = synthetic_traffic(2, cfg.vocab, prompt_lens=[8, 11],
                                 gen_lens=[3, 5], seed=11)
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=1, max_len=max_len,
                                    segment=2)
            eng = ContinuousBatcher(setup, params)
            # ONE slot: request B can only run through the evicted slot A
            # used, so stale-state leakage would be on the critical path.
            stats = eng.run(reqs)
            gen_cache: dict = {}
            for req in reqs:
                ref = _solo_tokens(cfg, model, params, mesh, req, max_len,
                                   gen_cache)
                np.testing.assert_array_equal(
                    stats.outputs[req.rid], ref,
                    err_msg=f"rid {req.rid} diverged after re-admission")


class TestPerRowPositions:
    def test_vector_pos_matches_scalar_pos(self):
        """All rows at the same depth: the per-row (B,) position path and
        the scalar path produce identical outputs and states."""
        b, t, g, r, d, block, n0 = 2, 3, 2, 2, 8, 8, 21
        h = g * r
        keys = jax.random.split(jax.random.PRNGKey(5), 6)
        q0 = jax.random.normal(keys[0], (b, n0, h, d))
        k0 = jax.random.normal(keys[1], (b, n0, g, d))
        v0 = jax.random.normal(keys[2], (b, n0, g, d))
        alpha = jnp.full((h,), 1.3)
        beta_h = jnp.full((h,), 1.1)
        _, s, z, c_k = kops.lln_prefill(q0, k0, v0, alpha,
                                        jnp.full((g,), 1.1), chunk=7)
        nb = -(-n0 // block)
        pad = nb * block - n0
        tail_k = jnp.pad(k0, ((0, 0), (0, pad), (0, 0), (0, 0)))[:,
                                                                 -block:]
        tail_v = jnp.pad(v0, ((0, 0), (0, pad), (0, 0), (0, 0)))[:,
                                                                 -block:]
        qn = jax.random.normal(keys[3], (b, t, h, d))
        kn = jax.random.normal(keys[4], (b, t, g, d))
        vn = jax.random.normal(keys[5], (b, t, g, d))
        lln = core_lln.LLNState(s=s, z=z, c_k=c_k)
        st_scalar = ca.LLNDecodeState(lln=lln, tail_k=tail_k, tail_v=tail_v,
                                      pos=jnp.asarray(n0, jnp.int32))
        st_vec = ca.LLNDecodeState(lln=lln, tail_k=tail_k, tail_v=tail_v,
                                   pos=jnp.full((b,), n0, jnp.int32))
        o1, s1 = ca.decode_lln_chunk(st_scalar, qn, kn, vn, alpha, beta_h,
                                     backend="auto")
        o2, s2 = ca.decode_lln_chunk(st_vec, qn, kn, vn, alpha, beta_h,
                                     backend="auto")
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        np.testing.assert_array_equal(np.asarray(s1.tail_k),
                                      np.asarray(s2.tail_k))
        assert np.asarray(s2.pos).shape == (b,)


def _chunk_path_decode(params, caches, tok, cfg, pos, row_mask=None):
    """The layer loop as ``lm_decode`` ran it before the in-place carry:
    the stacked caches go in as scan inputs, each layer's new cache comes
    out stacked, and each token runs as a (B, 1) chunk, so every layer's
    tails are rebuilt by ``decode_lln_chunk``'s chunk update."""
    x = embed_lookup(params["embed"], tok[:, None], cfg.cdtype,
                     cfg.embed_scale)

    def layer(x, xs):
        lp, cache = xs
        return tr.block_decode(lp, x, cache, cfg, pos, use_moe=False,
                               row_mask=row_mask)

    x, stack = jax.lax.scan(layer, x, (params["layers"], caches["layers"]))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = logits_from_hidden(tr.lm_head_of(params), x, cfg.cdtype,
                                cfg.logit_softcap)
    return logits[:, 0], {"layers": stack}


def _assert_trees_equal(got, want):
    for kp, a in jax.tree_util.tree_leaves_with_path(want):
        b = got
        for k in kp:
            b = b[k.key]
        np.testing.assert_array_equal(
            np.asarray(b), np.asarray(a),
            err_msg=f"differs: {jax.tree_util.keystr(kp)}")


class TestInPlaceDecodeCache:
    """``lm_decode`` carries the stacked caches through its layer loop and
    writes single-token diag tails one row per step after it: bitwise the
    chunk path's update, step for step."""

    @staticmethod
    def _pool(impl, r):
        cfg = _tiny_cfg(impl, r, fixed_ab=False)
        setup = make_pool_setup(cfg, None, slots=4, max_len=40, segment=6,
                                health=None, telemetry=False)
        params = setup.model.init(jax.random.PRNGKey(3))
        caches = setup.cache_init()
        # diag_block 8: rows at 5 and 14 cross a block boundary inside the
        # segment, the row at 8 starts a block, the row at 11 does not.
        plens = (5, 8, 11, 14)
        for slot, plen in enumerate(plens):
            prompt = jax.random.randint(jax.random.PRNGKey(10 + slot),
                                        (1, plen), 0, cfg.vocab, jnp.int32)
            _, c = setup.prefill_fn(plen)(params, prompt)
            caches = setup.admit_fn(caches, c,
                                    jnp.asarray([slot], jnp.int32))
        return setup, params, caches, jnp.asarray(plens, jnp.int32)

    @pytest.mark.parametrize("impl,r", [("lln_diag", 1), ("lln_diag", 4),
                                        ("lln", 1)])
    def test_segment_matches_chunk_path(self, impl, r):
        """A pool segment with an active row, a masked row, and rows whose
        budget runs out mid-segment: tokens, ``emitted`` and every cache
        leaf equal the same steps run through the chunk path, bitwise."""
        setup, params, caches, pos = self._pool(impl, r)
        cfg = setup.cfg
        tok = jnp.asarray([3, 5, 7, 9], jnp.int32)
        remaining = jnp.asarray([6, 4, 2, 6], jnp.int32)
        active = jnp.asarray([True, False, True, True])

        @jax.jit
        def reference(caches, tok, pos, remaining, active):
            def body(carry, i):
                caches, tok, pos, remaining, active = carry
                logits, caches = _chunk_path_decode(params, caches, tok,
                                                    cfg, pos, active)
                logits = jnp.where(active[:, None], logits, 0.0)
                tok = jnp.where(active, jnp.argmax(logits, -1)
                                .astype(jnp.int32), tok)
                adv = active.astype(jnp.int32)
                carry = (caches, tok, pos + adv, remaining - adv,
                         active & (remaining - adv > 0))
                return carry, (tok, active)
            return jax.lax.scan(body, (caches, tok, pos, remaining, active),
                                jnp.arange(setup.segment))

        before = jax.tree_util.tree_map(np.asarray, caches)
        (want_c, *want_state), (want_toks, want_emitted) = reference(
            caches, tok, pos, remaining, active)
        got = setup.segment_fn(params, caches, tok, pos, remaining, active,
                               jax.random.PRNGKey(0))
        got_c, got_state = got[0], got[1:5]
        np.testing.assert_array_equal(np.asarray(got[5]),
                                      np.asarray(want_toks))
        np.testing.assert_array_equal(np.asarray(got[6]),
                                      np.asarray(want_emitted))
        for a, b in zip(got_state, want_state):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        _assert_trees_equal(got_c, want_c)
        # The masked row's cache, its tails included, is bitwise unchanged.
        for kp, leaf in jax.tree_util.tree_leaves_with_path(got_c):
            old = before
            for k in kp:
                old = old[k.key]
            np.testing.assert_array_equal(
                np.asarray(leaf)[:, 1], old[:, 1],
                err_msg=f"masked row mutated: {jax.tree_util.keystr(kp)}")

    def test_single_token_tails_written_one_row(self):
        """A single-token decode changes exactly one tail slot per active
        row (``pos % diag_block``) and no slot of a masked row."""
        setup, params, caches, pos = self._pool("lln_diag", 2)
        mask = jnp.asarray([True, False, True, False])
        before = jax.tree_util.tree_map(np.asarray, caches)
        _, after = setup.model.decode(params, caches,
                                      jnp.asarray([1, 2, 3, 4], jnp.int32),
                                      pos, row_mask=mask)
        block = setup.cfg.diag_block
        for name in ("tail_k", "tail_v"):
            old = before["layers"][name]
            new = np.asarray(after["layers"][name])
            changed = (old != new).any(axis=(0, 3, 4))      # (B, BLK)
            for row in range(4):
                want = np.zeros(block, bool)
                if mask[row]:
                    want[int(pos[row]) % block] = True
                np.testing.assert_array_equal(changed[row], want,
                                              err_msg=f"{name} row {row}")

    @pytest.mark.parametrize("impl", ["lln_diag", "softmax"])
    def test_static_generate_matches_chunk_path(self, impl):
        """``make_generate`` (static batch, scalar position) gives the
        tokens of the chunk path run one token at a time."""
        cfg = _tiny_cfg(impl, 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(4))
        b, plen, steps, max_len = 2, 6, 9, 24
        mesh = make_mesh((1, 1), ("data", "model"))
        setup = make_serve_setup(cfg, ShapeSpec("gen", max_len, b, "decode"),
                                 mesh, multi_pod=False)
        prompt = jax.random.randint(jax.random.PRNGKey(5), (b, plen), 0,
                                    cfg.vocab, jnp.int32)
        logits, caches = model.prefill(params, {"inputs": prompt}, max_len)
        last = logits[:, -1] if logits.ndim == 3 else logits
        tok0 = jnp.argmax(last, -1).astype(jnp.int32)
        want, c, tok = [], caches, tok0
        for i in range(steps):
            lg, c = _chunk_path_decode(params, c, tok, cfg,
                                       jnp.asarray(plen + i, jnp.int32))
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            want.append(np.asarray(tok))
        got, _ = setup.make_generate(steps)(params, caches, tok0,
                                            jnp.asarray(plen, jnp.int32),
                                            jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.stack(want, axis=1))


class TestAdmit:
    def test_admit_writes_exactly_one_row(self):
        cfg = _tiny_cfg("lln_diag", 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(6))
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=3, max_len=32,
                                    segment=2)
            pooled = setup.cache_init()
            ref = jax.tree_util.tree_map(jnp.copy, pooled)
            prompt = jnp.ones((1, 8), jnp.int32)
            _, slot_caches = setup.prefill_fn(8)(params, prompt)
            new = setup.admit_fn(pooled, slot_caches,
                                 jnp.asarray([1], jnp.int32))
        for kp, leaf in jax.tree_util.tree_leaves_with_path(new):
            before = ref
            for k in kp:
                before = before[k.key] if hasattr(k, "key") else \
                    before[k.idx]
            path = jax.tree_util.keystr(kp)
            for row in (0, 2):
                np.testing.assert_array_equal(
                    np.asarray(leaf)[:, row], np.asarray(before)[:, row],
                    err_msg=f"admit leaked into row {row}: {path}")
        # And the admitted row is the slot prefill's state.
        tgt = np.asarray(new["layers"]["pos"])[:, 1]
        np.testing.assert_array_equal(tgt, np.full((cfg.n_layers,), 8))


# ---------------------------------------------------------------------------
# Speculative continuous batching (PoolSetup.spec_k >= 1).
# ---------------------------------------------------------------------------

def _solo_spec_tokens(cfg, params, mesh, req, max_len, spec_k,
                      draft_layers, cache):
    """The request served alone through the solo ``SpecSetup`` loop —
    the speculative oracle pooled rows must reproduce token-for-token."""
    from repro.launch.steps import flatten_spec_tokens, make_spec_setup
    plen = len(req.prompt)
    if ("setup", plen) not in cache:
        shape = ShapeSpec("solo-spec", max_len, 1, "decode")
        cache[("setup", plen)] = make_spec_setup(
            cfg, shape, mesh, spec_k=spec_k, draft_layers=draft_layers)
    ss = cache[("setup", plen)]
    logits, tgt, dr = ss.prefill_fn(
        params, {"inputs": jnp.asarray(req.prompt)[None, :]})
    last = logits[:, -1] if logits.ndim == 3 else logits
    tok0 = jnp.argmax(last, -1).astype(jnp.int32)
    toks = [int(tok0[0])]
    steps = req.budget - 1
    if steps > 0:
        gkey = ("gen", plen, steps)
        if gkey not in cache:
            cache[gkey] = ss.make_generate(steps, 0.0)
        t, n_emit, *_ = cache[gkey](params, tgt, dr, tok0,
                                    jnp.asarray([plen], jnp.int32),
                                    jax.random.PRNGKey(0))
        flat = flatten_spec_tokens(np.asarray(t), np.asarray(n_emit),
                                   steps)
        toks.extend(int(x) for x in flat[0])
    return np.asarray(toks, np.int32)


class TestSpeculativePool:
    SPEC_K, DRAFT_LAYERS = 2, 1

    def test_pool_matches_solo_spec(self, impl_gqa_cell):
        """Pooled speculative greedy decode (staggered admits/evicts over
        2 slots, per-row commit_len) is token-for-token the solo
        ``SpecSetup`` run per request — softmax/lln/lln_diag × r."""
        impl, r = impl_gqa_cell
        cfg = _tiny_cfg(impl, r)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 48
        reqs = synthetic_traffic(4, cfg.vocab, prompt_lens=[8, 8, 11],
                                 gen_lens=[2, 7, 4], seed=r)
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=max_len,
                                    segment=3, spec_k=self.SPEC_K,
                                    draft_layers=self.DRAFT_LAYERS)
            stats = ContinuousBatcher(setup, params).run(reqs)
            assert stats.admitted == len(reqs)
            assert stats.spec_k == self.SPEC_K
            assert stats.verify_iters > 0
            assert 1.0 <= stats.goodput_tokens_per_iter <= self.SPEC_K + 1
            cache: dict = {}
            for req in reqs:
                ref = _solo_spec_tokens(cfg, params, mesh, req, max_len,
                                        self.SPEC_K, self.DRAFT_LAYERS,
                                        cache)
                got = stats.outputs[req.rid]
                assert len(got) == req.gen_len
                np.testing.assert_array_equal(got, ref,
                                              err_msg=f"rid {req.rid}")

    def test_quarantine_recovery_replays_both_states(self):
        """NaN-poisoning a speculative row mid-stream quarantines it; the
        re-prefill + paired replay rebuilds BOTH states and the request
        still finishes with its exact solo-spec tokens."""
        from repro.launch.faults import FaultPlan
        cfg = _tiny_cfg("lln_diag", 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 48
        reqs = synthetic_traffic(2, cfg.vocab, prompt_lens=[8],
                                 gen_lens=[9], seed=5)
        plan = FaultPlan(events=[{"kind": "nan", "segment": 1, "row": 0}])
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=max_len,
                                    segment=2, spec_k=self.SPEC_K,
                                    draft_layers=self.DRAFT_LAYERS)
            stats = ContinuousBatcher(setup, params).run(
                reqs, key=jax.random.PRNGKey(1), fault_plan=plan)
            assert stats.recoveries >= 1
            cache: dict = {}
            for req in reqs:
                ref = _solo_spec_tokens(cfg, params, mesh, req, max_len,
                                        self.SPEC_K, self.DRAFT_LAYERS,
                                        cache)
                np.testing.assert_array_equal(stats.outputs[req.rid], ref,
                                              err_msg=f"rid {req.rid}")

    def test_budget_expiry_caps_multi_token_harvest(self):
        """Regression (multi-token emission bugfix): a speculative row's
        final verify iteration may emit up to spec_k + 1 tokens past its
        budget — the harvest must cap the stored output at EXACTLY
        ``Request.budget`` (including the ``max_tokens`` form), and the
        kept prefix must still match the oracle."""
        cfg = _tiny_cfg("lln", 4)    # r=4 tends to accept multi-token runs
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        max_len = 48
        mesh = make_mesh((1, 1), ("data", "model"))
        # gen_len chosen NOT ≡ 1 (mod spec_k+1) so expiry can land
        # mid-iteration; max_tokens on rid 1 exercises the min() budget.
        reqs = [Request(rid=0, prompt=np.arange(2, 10, dtype=np.int32),
                        gen_len=6),
                Request(rid=1, prompt=np.arange(3, 11, dtype=np.int32),
                        gen_len=7, max_tokens=5)]
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=max_len,
                                    segment=3, spec_k=self.SPEC_K,
                                    draft_layers=cfg.n_layers)  # accept=1
            stats = ContinuousBatcher(setup, params).run(reqs)
            cache: dict = {}
            for req in reqs:
                got = stats.outputs[req.rid]
                assert len(got) == req.budget, \
                    f"rid {req.rid}: {len(got)} != budget {req.budget}"
                ref = _solo_spec_tokens(cfg, params, mesh, req, max_len,
                                        self.SPEC_K, cfg.n_layers, cache)
                np.testing.assert_array_equal(got, ref,
                                              err_msg=f"rid {req.rid}")

    def test_check_request_reserves_spec_slack(self):
        """Admission rejects a request whose prompt + budget would fit a
        plain pool but not the speculative overshoot slack."""
        cfg = _tiny_cfg("lln_diag", 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            setup = make_pool_setup(cfg, mesh, slots=2, max_len=24,
                                    segment=2, spec_k=self.SPEC_K,
                                    draft_layers=self.DRAFT_LAYERS)
            eng = ContinuousBatcher(setup, params)
            fits = Request(rid=0, prompt=np.zeros((8,), np.int32),
                           gen_len=24 - 8 - self.SPEC_K)
            eng.check_request(fits)
            from repro.launch.batcher import AdmissionError
            with pytest.raises(AdmissionError, match="spec slack"):
                eng.check_request(
                    Request(rid=1, prompt=np.zeros((8,), np.int32),
                            gen_len=24 - 8 - self.SPEC_K + 1))
