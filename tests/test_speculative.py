"""Speculative decoding: the partial-commit contract + the accept/reject
loop.

* ``commit_len`` partial commit: scoring covers all T positions while the
  state (LLN ``(s, z, c_k)``, diag tails, softmax KV rows, ``pos``/``len``)
  folds exactly the accepted prefix — pinned against prefix-only decodes
  across the pallas/scan/ref backends, with ``commit_len=0`` bitwise equal
  to a masked row;
* acceptance rules (``core/speculative.py``): greedy longest-prefix match
  and residual resampling;
* the headline gate: greedy speculative decode
  (``launch/steps.py:make_spec_setup``) is token-for-token identical to
  the non-speculative scanned loop for softmax / lln / lln_diag ×
  GQA r ∈ {1, 4}, including runs where rows of one batch accept
  different numbers of draft tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core import attention as ca
from repro.core import lln as core_lln
from repro.core import speculative as spec
from repro.core.engine import AttentionEngine
from repro.kernels import ops as kops
from repro.kernels.registry import AttnSpec
from repro.launch.mesh import make_mesh
from repro.launch.steps import (flatten_spec_tokens, make_serve_setup,
                                make_spec_setup)
from repro.models import build_model, draft_config, draft_params, \
    synthetic_batch


def _qkv(seed, b, n, h, g, d):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, n, h, d)),
            jax.random.normal(kk, (b, n, g, d)),
            jax.random.normal(kv, (b, n, g, d)))


def _tiny_cfg(impl, r, **kw):
    h = 4
    base = dict(
        name=f"spec-test-{impl}-r{r}", family="dense", n_layers=2,
        d_model=64, n_heads=h, n_kv_heads=h // r, d_ff=128, vocab=128,
        head_dim=16, attn_impl=impl, diag_block=8, lln_chunk=8,
        softmax_chunk=16,
        lln_fixed_ab=2.1 if impl != "softmax" else 0.0,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True)
    base.update(kw)
    return ArchConfig(**base)


# ---------------------------------------------------------------------------
# Acceptance rules.
# ---------------------------------------------------------------------------

class TestAcceptRules:
    def test_greedy_verify_prefix_and_correction(self):
        v = 8
        # Row 0: target argmax agrees with drafts [3, 5] then extends 6.
        # Row 1: first draft rejected -> correction is argmax at pos 0.
        logits = np.full((2, 3, v), -5.0, np.float32)
        logits[0, 0, 3] = 5.0
        logits[0, 1, 5] = 5.0
        logits[0, 2, 6] = 5.0
        logits[1, 0, 7] = 5.0
        logits[1, 1, 1] = 5.0
        logits[1, 2, 2] = 5.0
        drafts = jnp.asarray([[3, 5], [4, 1]], jnp.int32)
        n_acc, nxt, commit = spec.greedy_verify(drafts,
                                                jnp.asarray(logits))
        assert np.asarray(n_acc).tolist() == [2, 0]
        assert np.asarray(nxt).tolist() == [6, 7]
        assert np.asarray(commit).tolist() == [3, 1]

    def test_greedy_no_acceptance_after_first_mismatch(self):
        """A later match behind a mismatch must NOT count."""
        v = 8
        logits = np.full((1, 4, v), -5.0, np.float32)
        for i, tok in enumerate([2, 9 % v, 4, 5]):
            logits[0, i, tok] = 5.0
        drafts = jnp.asarray([[2, 3, 4]], jnp.int32)   # pos 1 mismatches
        n_acc, nxt, commit = spec.greedy_verify(drafts,
                                                jnp.asarray(logits))
        assert int(n_acc[0]) == 1
        assert int(nxt[0]) == 9 % v
        assert int(commit[0]) == 2

    def test_emit_tokens_packing(self):
        drafts = jnp.asarray([[10, 11, 12], [20, 21, 22]], jnp.int32)
        n_acc = jnp.asarray([2, 0], jnp.int32)
        nxt = jnp.asarray([77, 88], jnp.int32)
        out = np.asarray(spec.emit_tokens(drafts, n_acc, nxt))
        assert out[0, :3].tolist() == [10, 11, 77]
        assert out[1, 0] == 88

    def test_residual_verify_identical_dists_accept_all(self):
        """draft dist == target dist => accept probability 1 everywhere,
        next token is the bonus sample."""
        b, k, v = 2, 3, 16
        logits = jax.random.normal(jax.random.PRNGKey(0), (b, k + 1, v))
        drafts = jnp.argmax(logits[:, :k], -1).astype(jnp.int32)
        n_acc, nxt, commit = spec.residual_verify(
            drafts, logits[:, :k], logits, jax.random.PRNGKey(1), 1.0)
        assert np.asarray(n_acc).tolist() == [k, k]
        assert np.asarray(commit).tolist() == [k + 1, k + 1]

    def test_residual_verify_rejects_zero_prob_draft(self):
        """A draft token the target gives ~zero probability is rejected,
        and the resample never returns it (zero residual mass there)."""
        b, k, v = 1, 1, 8
        tgt = np.full((b, 2, v), 0.0, np.float32)
        tgt[0, 0, 3] = 50.0            # target: all mass on 3
        tgt[0, 1, 4] = 50.0
        dr = np.full((b, 1, v), 0.0, np.float32)
        dr[0, 0, 6] = 50.0             # draft: all mass on 6
        drafts = jnp.asarray([[6]], jnp.int32)
        for seed in range(5):
            n_acc, nxt, _ = spec.residual_verify(
                drafts, jnp.asarray(dr), jnp.asarray(tgt),
                jax.random.PRNGKey(seed), 1.0)
            assert int(n_acc[0]) == 0
            assert int(nxt[0]) == 3

    def test_verify_tokens_dispatch(self):
        drafts = jnp.zeros((1, 2), jnp.int32)
        logits = jnp.zeros((1, 3, 8))
        n_acc, _, _ = spec.verify_tokens(drafts, logits, 0.0)
        assert n_acc.shape == (1,)
        with pytest.raises(ValueError, match="requires draft_logits"):
            spec.verify_tokens(drafts, logits, 1.0)
        with pytest.raises(ValueError, match="temperature > 0"):
            spec.residual_verify(drafts, logits[:, :2], logits,
                                 jax.random.PRNGKey(0), 0.0)


# ---------------------------------------------------------------------------
# The partial-commit contract.
# ---------------------------------------------------------------------------

class TestPartialCommit:
    def _lln_state(self, b, h, g, d, n0, seed=0):
        q, k, v = _qkv(seed, b, n0, h, g, d)
        alpha = jnp.full((h,), 1.3)
        beta = jnp.full((g,), 1.1)
        _, s, z, c_k = kops.lln_prefill(q, k, v, alpha, beta, chunk=8)
        return core_lln.LLNState(s=s, z=z, c_k=c_k), alpha, beta

    @pytest.mark.parametrize("backend", ["pallas", "scan", "ref"])
    @pytest.mark.parametrize("t", [3, 5])
    def test_commit_equals_prefix_decode(self, backend, t):
        """lln_decode_chunk(commit_len=c): outputs == full-chunk scoring,
        state == plain decode of the first c tokens — per row, on every
        backend, at odd T (the verify pass calls T = k+1)."""
        b, g, r, d = 3, 2, 2, 8
        h = g * r
        st, alpha, beta = self._lln_state(b, h, g, d, 24)
        qn, kn, vn = _qkv(7, b, t, h, g, d)
        cl = jnp.asarray([0, t // 2 + 1, t], jnp.int32)
        o_c, st_c = kops.lln_decode_chunk(st, qn, kn, vn, alpha, beta,
                                          backend=backend, commit_len=cl)
        o_f, st_f = kops.lln_decode_chunk(st, qn, kn, vn, alpha, beta,
                                          backend=backend)
        np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_f),
                                   rtol=2e-5, atol=2e-5)
        # Row 0 (commit 0): state bitwise preserved.
        for name in ("s", "z", "c_k"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st_c, name))[0],
                np.asarray(getattr(st, name))[0], err_msg=name)
        # Row 2 (commit T): the plain full decode.
        for name in ("s", "z", "c_k"):
            np.testing.assert_allclose(
                np.asarray(getattr(st_c, name))[2],
                np.asarray(getattr(st_f, name))[2],
                rtol=2e-5, atol=2e-5, err_msg=name)
        # Row 1 (partial): decode of only the accepted prefix.
        c = t // 2 + 1
        _, st_p = kops.lln_decode_chunk(st, qn[:, :c], kn[:, :c],
                                        vn[:, :c], alpha, beta,
                                        backend=backend)
        for name in ("s", "z", "c_k"):
            np.testing.assert_allclose(
                np.asarray(getattr(st_c, name))[1],
                np.asarray(getattr(st_p, name))[1],
                rtol=2e-5, atol=2e-5, err_msg=name)

    @pytest.mark.parametrize("impl", ["softmax", "lln_diag"])
    def test_engine_verify_commit_zero_is_masked_row(self, impl):
        """engine.verify(commit_len=0) == decode(row_mask=False) on every
        state leaf, bitwise — and verify raises without commit_len."""
        b, t, g, r, d = 2, 3, 2, 2, 8
        h = g * r
        espec = AttnSpec(impl=impl, causal=True, r=r, lln_chunk=8,
                         diag_block=8, fixed_ab=2.1)
        eng = AttentionEngine(spec=espec, heads=h, kv_heads=g, head_dim=d,
                              v_dim=d, cache_dtype=jnp.float32)
        q0, k0, v0 = _qkv(0, b, 16, h, g, d)
        _, state = eng.prefill(q0, k0, v0, max_len=32)
        qn, kn, vn = _qkv(1, b, t, h, g, d)
        mask = jnp.zeros((b,), jnp.bool_)
        _, st_mask = eng.decode(state, qn, kn, vn, row_mask=mask)
        out, st_zero = eng.verify(state, qn, kn, vn,
                                  commit_len=jnp.zeros((b,), jnp.int32))
        for (kp, a), (_, bb) in zip(
                jax.tree_util.tree_leaves_with_path(st_zero),
                jax.tree_util.tree_leaves_with_path(st_mask)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(bb),
                err_msg=f"{impl} {jax.tree_util.keystr(kp)}")
        # verify still scored every position (outputs are NOT garbage).
        out_ref, _ = eng.decode(state, qn, kn, vn)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                                   rtol=2e-5, atol=2e-5)
        with pytest.raises(ValueError, match="commit_len"):
            eng.verify(state, qn, kn, vn, commit_len=None)

    @pytest.mark.parametrize("impl", ["softmax", "lln", "lln_diag"])
    def test_model_score_pass_touches_nothing(self, impl):
        """lm_decode(commit_len=0 everywhere) returns the chunk's logits
        AND leaves every cache leaf bitwise untouched — the verify score
        pass."""
        cfg = _tiny_cfg(impl, 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        b, plen, t = 2, 10, 4
        batch = synthetic_batch(cfg, batch=b, seq=plen + t)
        chunk = batch["inputs"][:, plen:plen + t]
        _, caches = model.prefill(
            params, {"inputs": batch["inputs"][:, :plen]}, plen + t + 4)
        pos = jnp.full((b,), plen, jnp.int32)
        lg_score, c_after = model.decode(
            params, caches, chunk, pos,
            commit_len=jnp.zeros((b,), jnp.int32))
        lg_plain, _ = model.decode(params, caches, chunk, pos)
        np.testing.assert_allclose(np.asarray(lg_score),
                                   np.asarray(lg_plain),
                                   rtol=2e-5, atol=2e-5)
        for (kp, a), (_, bb) in zip(
                jax.tree_util.tree_leaves_with_path(c_after),
                jax.tree_util.tree_leaves_with_path(caches)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(bb),
                err_msg=f"{impl} {jax.tree_util.keystr(kp)}")

    def test_softmax_commit_rolls_back_length_not_scoring(self):
        """Softmax verify: all T draft keys are visible to scoring, but
        ``len`` advances only by the accepted prefix and a commit_len=0
        row's buffer is bitwise restored."""
        b, t, g, h, d, mx = 3, 4, 2, 4, 8, 32
        keys = jax.random.split(jax.random.PRNGKey(3), 5)
        k0 = jax.random.normal(keys[0], (b, 6, g, d))
        v0 = jax.random.normal(keys[1], (b, 6, g, d))
        cache = ca.KVCache(
            k=jnp.zeros((b, mx, g, d)).at[:, :6].set(k0),
            v=jnp.zeros((b, mx, g, d)).at[:, :6].set(v0),
            length=jnp.full((b,), 6, jnp.int32))
        q = jax.random.normal(keys[2], (b, t, h, d))
        kn = jax.random.normal(keys[3], (b, t, g, d))
        vn = jax.random.normal(keys[4], (b, t, g, d))
        cl = jnp.asarray([0, 2, 4], jnp.int32)
        out_c, cc = ca.decode_softmax(cache, q, kn, vn, commit_len=cl)
        out_f, _ = ca.decode_softmax(cache, q, kn, vn)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_f),
                                   rtol=1e-5, atol=1e-5)
        assert np.asarray(cc.length).tolist() == [6, 8, 10]
        np.testing.assert_array_equal(np.asarray(cc.k)[0],
                                      np.asarray(cache.k)[0])
        with pytest.raises(ValueError, match="per-row"):
            ca.decode_softmax(
                ca.KVCache(k=cache.k, v=cache.v,
                           length=jnp.asarray(6, jnp.int32)),
                q, kn, vn, commit_len=cl)


# ---------------------------------------------------------------------------
# The tied first-k-layers draft.
# ---------------------------------------------------------------------------

class TestDraftModel:
    def test_draft_config_validates(self):
        cfg = _tiny_cfg("lln_diag", 2)
        assert draft_config(cfg, 1).n_layers == 1
        with pytest.raises(ValueError, match="draft_layers"):
            draft_config(cfg, 3)
        with pytest.raises(ValueError, match="draft_layers"):
            draft_config(cfg, 0)       # cfg.draft_layers defaults to 0

    def test_full_depth_draft_is_the_target(self):
        """draft_layers == n_layers: the sliced params ARE the target's
        (stacked leaves equal), so the draft's logits match the target's."""
        cfg = _tiny_cfg("lln_diag", 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        dp = draft_params(params, cfg, cfg.n_layers)
        for a, b in zip(jax.tree_util.tree_leaves(dp["layers"]),
                        jax.tree_util.tree_leaves(params["layers"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert dp["embed"] is params["embed"]

    def test_first_k_draft_params_slice(self):
        cfg = _tiny_cfg("lln_diag", 1)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(2))
        dp = draft_params(params, cfg, 1)
        lead = jax.tree_util.tree_leaves(dp["layers"])[0]
        full = jax.tree_util.tree_leaves(params["layers"])[0]
        assert lead.shape[0] == 1 and full.shape[0] == cfg.n_layers
        np.testing.assert_array_equal(np.asarray(lead),
                                      np.asarray(full[:1]))


# ---------------------------------------------------------------------------
# The headline gate: spec greedy == non-spec greedy, token for token.
# ---------------------------------------------------------------------------

def _run_pair(cfg, draft_layers, spec_k, steps, bsz=2, plen=12, seed=0):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    max_len = plen + steps + spec_k + 2
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("spec", max_len, bsz, "decode")
    batch = synthetic_batch(cfg, bsz, max_len, text_seq=plen)
    with mesh:
        serve = make_serve_setup(cfg, shape, mesh, multi_pod=False)
        logits, caches = serve.prefill_fn(params, batch)
        tok0 = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits,
                          -1).astype(jnp.int32)
        gen = serve.make_generate(steps, 0.0)
        ref, _ = gen(params, caches, tok0, jnp.asarray(plen, jnp.int32),
                     jax.random.PRNGKey(0))

        sp = make_spec_setup(cfg, shape, mesh, spec_k=spec_k,
                             draft_layers=draft_layers)
        lg, tc, dc = sp.prefill_fn(params, batch)
        tok0s = jnp.argmax(lg[:, -1] if lg.ndim == 3 else lg,
                           -1).astype(jnp.int32)
        sgen = sp.make_generate(steps, 0.0)
        toks, n_emit, n_acc, live, *_ = sgen(
            params, tc, dc, tok0s, jnp.asarray(plen, jnp.int32),
            jax.random.PRNGKey(0))
    got = flatten_spec_tokens(toks, n_emit, steps)
    return got, np.asarray(ref), np.asarray(n_acc), np.asarray(live)


class TestSpecParity:
    @pytest.mark.parametrize("r", [1, 4])
    @pytest.mark.parametrize("impl", ["softmax", "lln", "lln_diag"])
    def test_spec_greedy_matches_scanned_loop(self, impl, r):
        """Greedy draft-then-verify (imperfect first-1-layer draft, so
        accept/reject genuinely fires) emits token-for-token the
        non-speculative scanned loop's sequence."""
        cfg = _tiny_cfg(impl, r)
        got, ref, n_acc, live = _run_pair(cfg, draft_layers=1, spec_k=3,
                                          steps=9, seed=r)
        np.testing.assert_array_equal(got, ref)
        # The draft is imperfect: BOTH branches of accept/reject must have
        # fired — some drafts accepted, some rejected (the chosen seeds
        # guarantee it; all-accept or all-reject would leave half the
        # partial-commit machinery unexercised).
        drafted = live.sum() * 3
        assert 0 < n_acc.sum() < drafted, (
            f"acceptance degenerate: {n_acc.sum()}/{drafted}")

    def test_rows_accept_different_counts(self):
        """Rows of one batch accept different numbers of draft tokens in
        the same verify step — positions, commits and emits diverge per
        row — and parity still holds."""
        cfg = _tiny_cfg("lln_diag", 2)
        got, ref, n_acc, live = _run_pair(cfg, draft_layers=1, spec_k=3,
                                          steps=9, seed=0)
        np.testing.assert_array_equal(got, ref)
        both_live = live.all(axis=0)
        diff = (n_acc[0] != n_acc[1]) & both_live
        assert diff.any(), (
            "expected at least one verify step where the two rows accept "
            f"different draft counts; got n_acc={n_acc.tolist()}")

    def test_tied_full_draft_accepts_everything(self):
        """draft_layers == n_layers: the draft IS the target, so greedy
        acceptance is ~total and tokens/step approaches k+1."""
        cfg = _tiny_cfg("lln_diag", 2)
        k, steps = 3, 8
        got, ref, n_acc, live = _run_pair(cfg, draft_layers=cfg.n_layers,
                                          spec_k=k, steps=steps)
        np.testing.assert_array_equal(got, ref)
        acc = n_acc.sum() / max(live.sum() * k, 1)
        assert acc > 0.9, f"tied draft acceptance {acc:.2f}"

    def test_spec_temperature_sampling_runs(self):
        """Residual-resampling path: the loop runs, emits the requested
        token budget, and positions stay consistent (distribution-level
        correctness is pinned at the rule level)."""
        cfg = _tiny_cfg("lln_diag", 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        bsz, plen, steps, k = 2, 12, 6, 2
        max_len = plen + steps + k + 2
        mesh = make_mesh((1, 1), ("data", "model"))
        shape = ShapeSpec("spec", max_len, bsz, "decode")
        batch = synthetic_batch(cfg, bsz, max_len, text_seq=plen)
        with mesh:
            sp = make_spec_setup(cfg, shape, mesh, spec_k=k,
                                 draft_layers=1)
            lg, tc, dc = sp.prefill_fn(params, batch)
            tok0 = jnp.argmax(lg[:, -1] if lg.ndim == 3 else lg,
                              -1).astype(jnp.int32)
            sgen = sp.make_generate(steps, temperature=0.8)
            toks, n_emit, n_acc, live, *_ = sgen(
                params, tc, dc, tok0, jnp.asarray(plen, jnp.int32),
                jax.random.PRNGKey(3))
        flat = flatten_spec_tokens(toks, n_emit, steps)
        assert flat.shape == (bsz, steps)
        # sample_token draws over the padded head (as everywhere else).
        assert (flat >= 0).all() and (flat < cfg.padded_vocab).all()
