"""Core LLN attention: the paper's math (Props 3.1/4.1, Thms 3.2-3.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AttentionEngine, LLNDecodeState, block_diag_attn,
                        decode_lln_chunk, lln_bidir, lln_causal,
                        naive_softmax)
from repro.core import metrics as M
from repro.core import moment_matching as mm
from repro.core.lln import prefill as lln_prefill
from repro.kernels.registry import AttnSpec


def _jnp_attention(q, k, v, impl, alpha, beta, **kw):
    """The engine's full-sequence forward on its model-layout jnp route."""
    spec = AttnSpec(impl=impl, causal=True, r=q.shape[2] // k.shape[2],
                    train_twin="scan", **kw)
    eng = AttentionEngine(spec=spec, heads=q.shape[2], kv_heads=k.shape[2],
                          head_dim=q.shape[3], v_dim=v.shape[3])
    return eng.attention(q, k, v, alpha=alpha, beta=beta)


def _qkv(key, b=2, n=64, h=4, d=16, g=None):
    g = h if g is None else g
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (b, n, h, d)),
            jax.random.normal(kk, (b, n, g, d)),
            jax.random.normal(kv, (b, n, g, d)))


def _direct_lln(q, k, v, alpha, beta, causal):
    """Quadratic-form oracle straight from eq. 9."""
    fq = jnp.exp(alpha * q - jnp.max(alpha * q, axis=(1, 3), keepdims=True))
    fk = jnp.exp(beta * k - jnp.max(beta * k, axis=(1, 3), keepdims=True))
    s = jnp.einsum("bihd,bjhd->bhij", fq, fk)
    if causal:
        s = s * jnp.tril(jnp.ones(s.shape[-2:]))
    return jnp.einsum("bhij,bjhv->bihv",
                      s / (s.sum(-1, keepdims=True) + 1e-6), v)


class TestLLNForms:
    def test_causal_chunked_equals_quadratic(self):
        q, k, v = _qkv(jax.random.PRNGKey(0))
        out = lln_causal(q, k, v, 1.4, 1.1, chunk=16)
        ref = _direct_lln(q, k, v, 1.4, 1.1, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4)

    def test_bidir_equals_quadratic(self):
        q, k, v = _qkv(jax.random.PRNGKey(1))
        out = lln_bidir(q, k, v, 1.4, 1.1)
        ref = _direct_lln(q, k, v, 1.4, 1.1, False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4)

    @pytest.mark.parametrize("chunk", [8, 16, 64])
    def test_chunk_invariance(self, chunk):
        q, k, v = _qkv(jax.random.PRNGKey(2))
        a = lln_causal(q, k, v, 1.0, 1.0, chunk=chunk)
        b = lln_causal(q, k, v, 1.0, 1.0, chunk=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)

    def test_stabilization_exactness(self):
        """Subtracting global constants must not change the output — the
        exact invariance used for bf16 safety (core/lln.py docstring)."""
        q, k, v = _qkv(jax.random.PRNGKey(3))
        big = lln_causal(q + 10.0, k + 10.0, v, 1.0, 1.0, chunk=16)
        # reference without shift applied to inputs shifted the same way
        ref = _direct_lln(q + 10.0, k + 10.0, v, 1.0, 1.0, True)
        np.testing.assert_allclose(np.asarray(big), np.asarray(ref),
                                   atol=2e-3)
        assert np.all(np.isfinite(np.asarray(big)))

    def test_decode_matches_full_forward(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), n=48)
        alpha = jnp.full((4,), 1.3)
        beta = jnp.full((4,), 0.9)
        full = lln_causal(q, k, v, alpha, beta, chunk=16)
        out_pre, st = lln_prefill(q[:, :40], k[:, :40], v[:, :40], alpha,
                                  beta, chunk=16)
        np.testing.assert_allclose(np.asarray(out_pre),
                                   np.asarray(full[:, :40]), atol=2e-4)
        from repro.core.lln import decode_step
        for t in range(40, 48):
            out, st = decode_step(st, q[:, t:t + 1], k[:, t:t + 1],
                                  v[:, t:t + 1], alpha, beta)
            np.testing.assert_allclose(np.asarray(out[:, 0]),
                                       np.asarray(full[:, t]), atol=3e-4)

    def test_lln_diag_decode_matches_full(self):
        q, k, v = _qkv(jax.random.PRNGKey(5), g=2)
        alpha = jnp.full((4,), 1.2)
        beta = jnp.full((2,), 1.2)
        full = _jnp_attention(q, k, v, "lln_diag", alpha, beta,
                              diag_block=16, lln_chunk=16)
        st = LLNDecodeState.init(2, 4, 16, 16, 16, jnp.float32)
        beta_h = jnp.repeat(beta, 2)
        outs = []
        for t in range(q.shape[1]):
            o, st = decode_lln_chunk(st, q[:, t:t + 1], k[:, t:t + 1],
                                     v[:, t:t + 1], alpha, beta_h,
                                     backend="ref")
            outs.append(o)
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                                   np.asarray(full), atol=3e-4)


class TestPaperTheory:
    """Empirical checks of the paper's propositions and theorems."""

    def test_prop31_lognormality_of_softmax_attention(self):
        """Prop 3.1: P^(SM) is approximately log-normal."""
        key = jax.random.PRNGKey(0)
        kq, kk = jax.random.split(key)
        q = 1.2 * jax.random.normal(kq, (512, 64))
        k = 1.2 * jax.random.normal(kk, (512, 64))
        p = mm.softmax_attn_matrix(q, k)
        assert M.lognormality_score(p) > 0.99

    def test_prop31_variance_prediction(self):
        """Var[ln P^(SM)] ~= sigma_q^2 sigma_k^2 (d-scaled inputs)."""
        key = jax.random.PRNGKey(1)
        for sig in (1.0, 1.3):
            kq, kk = jax.random.split(jax.random.fold_in(key, int(sig * 10)))
            d = 64
            # a_ij = q.k/sqrt(d) has std sig^2 when q,k entries ~ N(0, sig^2)
            q = sig * jax.random.normal(kq, (1024, d))
            k = sig * jax.random.normal(kk, (1024, d))
            p = mm.softmax_attn_matrix(q, k)
            _, var = M.attention_log_moments(p)
            assert abs(float(var) - sig ** 4) / sig ** 4 < 0.15

    def test_prop41_lognormality_of_lln_attention(self):
        key = jax.random.PRNGKey(2)
        kq, kk = jax.random.split(key)
        q = jax.random.normal(kq, (512, 64))
        k = jax.random.normal(kk, (512, 64))
        p = mm.lln_attn_matrix(q, k, 2.1, 2.1)
        assert M.lognormality_score(p) > 0.98

    def test_moment_matching_matches_variance(self):
        """After eq. 10, Var[ln P^(LLN)] ~= Var[ln P^(SM)] (Fig. 5b)."""
        key = jax.random.PRNGKey(3)
        kq, kk = jax.random.split(key)
        d, sig = 64, 1.2
        q = sig * jax.random.normal(kq, (1024, d))
        k = sig * jax.random.normal(kk, (1024, d))
        a, b = mm.constants_for_dim(d)
        alpha, beta = mm.solve_alpha_beta(sig, sig, a, b)
        p_lln = mm.lln_attn_matrix(q, k, float(alpha), float(beta))
        p_sm = mm.softmax_attn_matrix(q, k)
        v_lln = float(M.attention_log_moments(p_lln)[1])
        v_sm = float(M.attention_log_moments(p_sm)[1])
        assert abs(v_lln - v_sm) / v_sm < 0.3
        # without matching (alpha=beta=1) the variance is far too small
        p_raw = mm.lln_attn_matrix(q, k, 1.0, 1.0)
        assert float(M.attention_log_moments(p_raw)[1]) < 0.3 * v_sm

    def test_alpha_beta_in_paper_range(self):
        """Fig. 9: moment matching lands alpha, beta in (2, 2.2) for unit-
        variance inputs (we allow a small tolerance around it)."""
        alpha, beta = mm.solve_alpha_beta(1.0, 1.0)
        assert 1.8 < float(alpha) < 2.6
        assert 1.8 < float(beta) < 2.6

    def test_thm32_entropy_monotone_in_temperature(self):
        key = jax.random.PRNGKey(4)
        scores = jax.random.normal(key, (64, 64))
        ents = []
        for tau in (0.25, 0.5, 1.0, 2.0, 4.0):
            p = jax.nn.softmax(scores / tau, axis=-1)
            ents.append(float(M.row_entropy(p)))
        assert all(a < b for a, b in zip(ents, ents[1:]))

    def test_thm34_variance_decreasing_in_temperature(self):
        key = jax.random.PRNGKey(5)
        scores = jax.random.normal(key, (64, 64))
        vs = []
        for tau in (0.25, 0.5, 1.0, 2.0, 4.0):
            p = jax.nn.softmax(scores / tau, axis=-1)
            vs.append(float(jnp.var(p)))
        assert all(a > b for a, b in zip(vs, vs[1:]))

    def test_thm33_spectral_identity(self):
        """Thm 3.3 building blocks:
        (a) Wielandt deflation: eigs(P - 1 mu^T) = {0} + {lambda_2..};
        (b) variance along the deflated matrix's top eigenvector direction
            equals lambda_2^2.
        NOTE (recorded in DESIGN.md): the paper's stronger phrasing — that
        lambda_2^2 equals the variance along the *major principal
        component* — holds exactly only for normal matrices; for a general
        stochastic matrix the major-PC variance upper-bounds lambda_2^2.
        We verify the provable identities and the symmetric-case equality.
        """
        rng = np.random.default_rng(0)
        logits = rng.normal(size=(48, 48))
        p = np.exp(logits)
        p /= p.sum(axis=1, keepdims=True)
        mu = p.mean(axis=0)
        pbar = p - np.ones((48, 1)) @ mu[None, :]
        ev_p = np.sort(np.abs(np.linalg.eigvals(p)))[::-1]
        ev_bar = np.sort(np.abs(np.linalg.eigvals(pbar)))[::-1]
        # (a) deflation removed lambda_1 = 1, kept the rest
        np.testing.assert_allclose(ev_bar[:5], ev_p[1:6], atol=1e-8)
        # (b) ||Pbar v2||^2 / ||v2||^2 == |lambda_2|^2
        w, vecs = np.linalg.eig(pbar)
        i2 = int(np.argmax(np.abs(w)))
        v2 = vecs[:, i2]
        var_dir = np.linalg.norm(pbar @ v2) ** 2 / np.linalg.norm(v2) ** 2
        np.testing.assert_allclose(var_dir, np.abs(w[i2]) ** 2, rtol=1e-8)
        # general case: major-PC variance >= lambda_2^2
        assert M.variance_along_pc(p) >= ev_p[1] ** 2 - 1e-9
        # symmetric (doubly-stochastic, via Sinkhorn) case: equality
        a = np.exp(0.3 * (logits + logits.T))
        for _ in range(200):
            d = 1.0 / np.sqrt(a.sum(axis=1))
            a = d[:, None] * a * d[None, :]
        ev_s = np.sort(np.abs(np.linalg.eigvalsh(a)))[::-1]
        np.testing.assert_allclose(M.variance_along_pc(a), ev_s[1] ** 2,
                                   rtol=1e-3)

    def test_spectral_gap_increases_with_temperature(self):
        rng = np.random.default_rng(1)
        logits = rng.normal(size=(48, 48))
        gaps = []
        for tau in (0.5, 1.0, 2.0, 4.0):
            p = np.exp(logits / tau)
            p /= p.sum(axis=1, keepdims=True)
            gaps.append(M.spectral_gap(p))
        assert gaps[-1] > gaps[0]

    def test_temperature_formulas(self):
        assert M.temperature_sm(1.0, 1.0) == 1.0
        assert M.temperature_sm(2.0, 1.0) == 0.5
        t = M.temperature_lln(2.0, 2.0, 1.0, 1.0, a=0.2, b=-0.7)
        assert t == pytest.approx(1.0 / np.sqrt(0.2 * 8 - 0.7))


class TestHybridLayer:
    def test_lln_diag_is_average(self):
        q, k, v = _qkv(jax.random.PRNGKey(6))
        cfg = dict(diag_block=16, lln_chunk=16)
        alpha = beta = jnp.full((4,), 1.3)
        h = _jnp_attention(q, k, v, "lln_diag", alpha, beta, **cfg)
        l = _jnp_attention(q, k, v, "lln", alpha, beta, **cfg)
        d = block_diag_attn(q, k, v, block=16, causal=True)
        np.testing.assert_allclose(np.asarray(h),
                                   np.asarray(0.5 * (l + d)), atol=1e-5)

    def test_block_diag_matches_naive_within_block(self):
        q, k, v = _qkv(jax.random.PRNGKey(7), n=32)
        out = block_diag_attn(q, k, v, block=16, causal=True)
        for blk in range(2):
            sl = slice(16 * blk, 16 * (blk + 1))
            ref = naive_softmax(q[:, sl], k[:, sl], v[:, sl], causal=True)
            np.testing.assert_allclose(np.asarray(out[:, sl]),
                                       np.asarray(ref), atol=2e-5)
