"""Pallas kernels vs pure-jnp oracles (interpret mode), incl. hypothesis
shape/dtype sweeps and gradient checks through the custom_vjp wrappers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # container has no
    from _hypothesis_shim import given, settings       # hypothesis; use the
    from _hypothesis_shim import strategies as st      # deterministic shim

from repro.kernels import (block_diag_attention, lln_attention,
                           lln_diag_attention)
from repro.kernels import ref as kref
from repro.kernels.block_diag import block_diag_bwd_pallas, block_diag_pallas
from repro.kernels.lln_attention import (lln_bidir_pallas, lln_causal_pallas,
                                         lln_diag_fused_pallas)
from repro.kernels.lln_backward import (lln_bidir_bwd_pallas,
                                        lln_bidir_bwd_scan,
                                        lln_causal_bwd_pallas,
                                        lln_causal_bwd_scan,
                                        lln_diag_fused_bwd_pallas,
                                        lln_diag_fused_bwd_scan,
                                        block_diag_bwd_scan)


def _inputs(key, bh, bg, n, d, dv, dtype=jnp.float32, shift=-0.5):
    kq, kk, kv = jax.random.split(key, 3)
    qs = (jax.random.normal(kq, (bh, n, d)) + shift).astype(dtype)
    ks = (jax.random.normal(kk, (bg, n, d)) + shift).astype(dtype)
    v = jax.random.normal(kv, (bg, n, dv)).astype(dtype)
    return qs, ks, v


@settings(max_examples=12, deadline=None)
@given(r=st.sampled_from([1, 2, 4]),
       nblk=st.integers(1, 4),
       blk=st.sampled_from([8, 16]),
       d=st.sampled_from([8, 16]),
       seed=st.integers(0, 2**16))
def test_lln_causal_kernel_sweep(r, nblk, blk, d, seed):
    bg, n = 2, nblk * blk
    qs, ks, v = _inputs(jax.random.PRNGKey(seed), bg * r, bg, n, d, d)
    out = lln_causal_pallas(qs, ks, v, r=r, blk=blk, interpret=True)
    ref = kref.lln_causal_ref(qs, ks, v, r=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@settings(max_examples=12, deadline=None)
@given(r=st.sampled_from([1, 2]),
       nblk=st.integers(1, 4),
       blk=st.sampled_from([8, 16]),
       dv=st.sampled_from([8, 16]),
       seed=st.integers(0, 2**16))
def test_lln_bidir_kernel_sweep(r, nblk, blk, dv, seed):
    bg, n, d = 2, nblk * blk, 16
    qs, ks, v = _inputs(jax.random.PRNGKey(seed), bg * r, bg, n, d, dv)
    out = lln_bidir_pallas(qs, ks, v, r=r, blk=blk, interpret=True)
    ref = kref.lln_bidir_ref(qs, ks, v, r=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


@settings(max_examples=12, deadline=None)
@given(r=st.sampled_from([1, 4]),
       causal=st.booleans(),
       blk=st.sampled_from([8, 16]),
       seed=st.integers(0, 2**16))
def test_block_diag_kernel_sweep(r, causal, blk, seed):
    bg, n, d = 2, 3 * blk, 16
    q, k, v = _inputs(jax.random.PRNGKey(seed), bg * r, bg, n, d, d, shift=0)
    out = block_diag_pallas(q, k, v, r=r, blk=blk, causal=causal,
                            interpret=True)
    ref = kref.block_diag_ref(q, k, v, block=blk, causal=causal, r=r)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_fused_lln_diag_kernel():
    key = jax.random.PRNGKey(0)
    qs, ks, v = _inputs(key, 4, 2, 48, 16, 16)
    q, k, _ = _inputs(jax.random.PRNGKey(1), 4, 2, 48, 16, 16, shift=0)
    out = lln_diag_fused_pallas(qs, ks, q, k, v, r=2, blk=16, causal=True,
                                interpret=True)
    ref = kref.lln_diag_fused_ref(qs, ks, q, k, v, block=16, causal=True, r=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_fused_kernel_rejects_bidir():
    with pytest.raises(ValueError):
        lln_diag_fused_pallas(jnp.zeros((1, 16, 8)), jnp.zeros((1, 16, 8)),
                              jnp.zeros((1, 16, 8)), jnp.zeros((1, 16, 8)),
                              jnp.zeros((1, 16, 8)), causal=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernels_dtype(dtype):
    qs, ks, v = _inputs(jax.random.PRNGKey(2), 4, 2, 32, 16, 16, dtype=dtype)
    out = lln_causal_pallas(qs, ks, v, r=2, blk=16, interpret=True)
    ref = kref.lln_causal_ref(qs, ks, v, r=2)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2 if dtype == jnp.bfloat16 else 2e-4)


class TestPublicOps:
    def _model_inputs(self, key, b=2, n=32, h=4, g=2, d=16):
        kq, kk, kv = jax.random.split(key, 3)
        return (jax.random.normal(kq, (b, n, h, d)),
                jax.random.normal(kk, (b, n, g, d)),
                jax.random.normal(kv, (b, n, g, d)))

    def test_lln_attention_grads_match_ref(self):
        q, k, v = self._model_inputs(jax.random.PRNGKey(0))
        alpha = jnp.full((4,), 1.5)
        beta = jnp.full((2,), 1.2)
        from repro.core import lln_causal

        def loss_kernel(q, k, v):
            return jnp.sum(lln_attention(q, k, v, alpha, beta, True, 16) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(lln_causal(q, jnp.repeat(k, 2, 2),
                                      jnp.repeat(v, 2, 2), alpha,
                                      jnp.repeat(beta, 2), chunk=16) ** 2)

        gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-3)

    def test_block_diag_attention_grad_finite(self):
        q, k, v = self._model_inputs(jax.random.PRNGKey(1))
        g = jax.grad(lambda q: jnp.sum(
            block_diag_attention(q, k, v, 16, True) ** 2))(q)
        assert np.all(np.isfinite(np.asarray(g)))

    def test_lln_diag_attention_matches_unfused(self):
        q, k, v = self._model_inputs(jax.random.PRNGKey(2))
        alpha = jnp.full((4,), 1.5)
        beta = jnp.full((2,), 1.2)
        fused = lln_diag_attention(q, k, v, alpha, beta, True, 16)
        lln = lln_attention(q, k, v, alpha, beta, True, 16)
        diag = block_diag_attention(q, k, v, 16, True)
        np.testing.assert_allclose(np.asarray(fused),
                                   np.asarray(0.5 * (lln + diag)), atol=1e-4)

    def test_unaligned_seq_falls_back(self):
        q, k, v = self._model_inputs(jax.random.PRNGKey(3), n=30)
        out = lln_attention(q, k, v, 1.0, 1.0, True, 16)
        assert out.shape == q.shape
        assert np.all(np.isfinite(np.asarray(out, np.float32)))


class TestPallasBackwardKernels:
    """Interpret-mode parity of the backward kernels vs the ref.py oracles
    (kernel layout, small blocks — fast unit coverage of the kernel math)."""

    def _inputs(self, seed, r, bg=2, nblk=3, blk=16, d=8, dv=8):
        n = nblk * blk
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        qs = jax.random.normal(ks[0], (bg * r, n, d)) - 0.5
        kk = jax.random.normal(ks[1], (bg, n, d)) - 0.5
        v = jax.random.normal(ks[2], (bg, n, dv))
        g = jax.random.normal(ks[3], (bg * r, n, dv))
        return qs, kk, v, g, blk

    @pytest.mark.parametrize("r", [1, 2, 4])
    def test_causal_bwd_kernel(self, r):
        qs, ks, v, g, blk = self._inputs(0, r)
        o, den = lln_causal_pallas(qs, ks, v, r=r, blk=blk, interpret=True,
                                   return_res=True)
        outs = lln_causal_bwd_pallas(qs, ks, v, g, o, den, r=r, blk=blk,
                                     interpret=True)
        refs = kref.lln_bwd_ref(qs, ks, v, g, o, den, causal=True, r=r)
        for a, b_ in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4)

    @pytest.mark.parametrize("r", [1, 2])
    def test_bidir_bwd_kernel(self, r):
        qs, ks, v, g, blk = self._inputs(1, r)
        o, s, z, den = lln_bidir_pallas(qs, ks, v, r=r, blk=blk,
                                        interpret=True, return_res=True)
        outs = lln_bidir_bwd_pallas(qs, ks, v, g, o, den, s, z, r=r, blk=blk,
                                    interpret=True)
        refs = kref.lln_bwd_ref(qs, ks, v, g, o, den, causal=False, r=r)
        for a, b_ in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4)

    @pytest.mark.parametrize("r", [1, 2])
    def test_fused_bwd_kernel(self, r):
        qs, ks, v, g, blk = self._inputs(2, r)
        q, k, _, _, _ = self._inputs(3, r)
        o, den = lln_diag_fused_pallas(qs, ks, q, k, v, r=r, blk=blk,
                                       causal=True, interpret=True,
                                       return_res=True)
        outs = lln_diag_fused_bwd_pallas(qs, ks, q, k, v, g, o, den, r=r,
                                         blk=blk, interpret=True)
        refs = kref.lln_diag_fused_bwd_ref(qs, ks, q, k, v, g, o, den,
                                           block=blk, r=r)
        for a, b_ in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_block_diag_bwd_kernel(self, causal):
        q, k, v, g, blk = self._inputs(4, 2)
        outs = block_diag_bwd_pallas(q, k, v, g, r=2, blk=blk, causal=causal,
                                     interpret=True)
        refs = kref.block_diag_bwd_ref(q, k, v, g, block=blk, causal=causal,
                                       r=2)
        for a, b_ in zip(outs, refs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-4)

    @pytest.mark.parametrize("r", [1, 2])
    def test_scan_twins_match_kernels(self, r):
        """The lax.scan twins (interpret-mode dispatch) produce the same
        gradients as the Pallas kernels for all four entry points."""
        qs, ks, v, g, blk = self._inputs(5, r)
        q, k, _, _, _ = self._inputs(6, r)
        o, den = lln_causal_pallas(qs, ks, v, r=r, blk=blk, interpret=True,
                                   return_res=True)
        for a, b_ in zip(
                lln_causal_bwd_scan(qs, ks, v, g, o, den, r=r, blk=blk),
                lln_causal_bwd_pallas(qs, ks, v, g, o, den, r=r, blk=blk,
                                      interpret=True)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-5)
        o, s, z, den = lln_bidir_pallas(qs, ks, v, r=r, blk=blk,
                                        interpret=True, return_res=True)
        for a, b_ in zip(
                lln_bidir_bwd_scan(qs, ks, v, g, o, den, s, z, r=r, blk=blk),
                lln_bidir_bwd_pallas(qs, ks, v, g, o, den, s, z, r=r,
                                     blk=blk, interpret=True)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-5)
        o, den = lln_diag_fused_pallas(qs, ks, q, k, v, r=r, blk=blk,
                                       causal=True, interpret=True,
                                       return_res=True)
        for a, b_ in zip(
                lln_diag_fused_bwd_scan(qs, ks, q, k, v, g, o, den, r=r,
                                        blk=blk),
                lln_diag_fused_bwd_pallas(qs, ks, q, k, v, g, o, den, r=r,
                                          blk=blk, interpret=True)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-5)
        for a, b_ in zip(
                block_diag_bwd_scan(q, k, v, g, r=r, blk=blk, causal=True),
                block_diag_bwd_pallas(q, k, v, g, r=r, blk=blk, causal=True,
                                      interpret=True)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-5)


class TestPallasVJPGradParity:
    """End-to-end gradients through the custom_vjp wrappers vs jax.vjp of
    the core/lln.py reference: causal/bidir/fused x GQA r in {1, 4} x
    N in {256, 512}, interpret mode, per-dtype tolerances."""

    CHUNK = 128

    def _model_inputs(self, seed, n, r, dtype=jnp.float32, b=1, g=1, d=16):
        h = g * r
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        return (jax.random.normal(ks[0], (b, n, h, d)).astype(dtype),
                jax.random.normal(ks[1], (b, n, g, d)).astype(dtype),
                jax.random.normal(ks[2], (b, n, g, d)).astype(dtype))

    def _ref_loss(self, mode, q, k, v, alpha, beta):
        from repro.core import lln_bidir, lln_causal
        from repro.core.diag import block_diag_attn
        h, g = q.shape[2], k.shape[2]
        kf = jnp.repeat(k, h // g, 2) if g != h else k
        vf = jnp.repeat(v, h // g, 2) if g != h else v
        beta_h = jnp.repeat(beta, h // g) if g != h else beta
        causal = mode in ("causal", "fused")
        if causal:
            out = lln_causal(q, kf, vf, alpha, beta_h, chunk=self.CHUNK)
        else:
            out = lln_bidir(q, kf, vf, alpha, beta_h)
        if mode in ("fused", "fused_bidir"):
            diag = block_diag_attn(q, kf, vf, block=self.CHUNK,
                                   causal=causal)
            out = 0.5 * (out.astype(jnp.float32) + diag.astype(jnp.float32))
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def _kernel_loss(self, mode, q, k, v, alpha, beta):
        if mode in ("fused", "fused_bidir"):
            out = lln_diag_attention(q, k, v, alpha, beta, mode == "fused",
                                     self.CHUNK)
        else:
            out = lln_attention(q, k, v, alpha, beta, mode == "causal",
                                self.CHUNK)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    @pytest.mark.parametrize("r", [1, 4])
    def test_noncausal_hybrid_grads_match_core_vjp(self, r):
        """The non-causal lln_diag backward branch (bidir LLN bwd + diag
        bwd on the halved cotangent, dv summed) in both dispatch variants."""
        from repro.kernels import ops as kops
        q, k, v = self._model_inputs(17, 256, r)
        alpha = jnp.full((q.shape[2],), 1.4)
        beta = jnp.full((k.shape[2],), 1.1)
        gr = jax.grad(lambda *a: self._ref_loss("fused_bidir", *a, alpha,
                                                beta),
                      argnums=(0, 1, 2))(q, k, v)
        for force in (False, True):
            kops.FORCE_KERNEL_BWD = force
            try:
                gk = jax.grad(lambda *a: self._kernel_loss(
                    "fused_bidir", *a, alpha, beta),
                    argnums=(0, 1, 2))(q, k, v)
            finally:
                kops.FORCE_KERNEL_BWD = False
            for a, b_, nm in zip(gk, gr, "qkv"):
                scale = max(1.0, float(jnp.max(jnp.abs(b_))))
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b_), atol=2e-3 * scale,
                    err_msg=f"d{nm} force_kernel={force}")

    @pytest.mark.parametrize("causal", [False, True])
    def test_block_diag_grads_match_ref_vjp(self, causal):
        """End-to-end dq/dk/dv value parity of block_diag_attention's
        Pallas backward wiring vs jax.vjp of the reference path."""
        q, k, v = self._model_inputs(19, 256, 2)
        gk = jax.grad(lambda q_, k_, v_: jnp.sum(block_diag_attention(
            q_, k_, v_, self.CHUNK, causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q_, k_, v_: jnp.sum(block_diag_attention(
            q_, k_, v_, self.CHUNK, causal, False) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_, nm in zip(gk, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-3, err_msg=f"d{nm}")

    @pytest.mark.parametrize("n", [256, 512])
    @pytest.mark.parametrize("r", [1, 4])
    @pytest.mark.parametrize("mode", ["causal", "bidir", "fused"])
    def test_grads_match_core_vjp_fp32(self, mode, r, n):
        q, k, v = self._model_inputs(7, n, r)
        alpha = jnp.full((q.shape[2],), 1.4)
        beta = jnp.full((k.shape[2],), 1.1)
        gk = jax.grad(lambda *a: self._kernel_loss(mode, *a, alpha, beta),
                      argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: self._ref_loss(mode, *a, alpha, beta),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b_, nm in zip(gk, gr, "qkv"):
            scale = max(1.0, float(jnp.max(jnp.abs(b_))))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-3 * scale, err_msg=f"d{nm}")

    @pytest.mark.parametrize("mode", ["causal", "bidir", "fused"])
    def test_grads_match_core_vjp_bf16(self, mode):
        q, k, v = self._model_inputs(9, 256, 4, dtype=jnp.bfloat16)
        alpha = jnp.full((q.shape[2],), 1.4)
        beta = jnp.full((k.shape[2],), 1.1)
        gk = jax.grad(lambda *a: self._kernel_loss(mode, *a, alpha, beta),
                      argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: self._ref_loss(mode, *a, alpha, beta),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b_, nm in zip(gk, gr, "qkv"):
            assert a.dtype == jnp.bfloat16
            af = np.asarray(a, np.float32)
            bf = np.asarray(b_, np.float32)
            scale = max(1.0, float(np.abs(bf).max()))
            np.testing.assert_allclose(af, bf, atol=8e-2 * scale,
                                       err_msg=f"d{nm}")

    @pytest.mark.parametrize("mode", ["causal", "bidir", "fused"])
    def test_kernel_bwd_path_matches_core_vjp(self, mode, monkeypatch):
        """Force the Pallas kernel backward (instead of the scan twins the
        CPU container dispatches to) through the full custom_vjp chain."""
        from repro.kernels import ops as kops
        monkeypatch.setattr(kops, "FORCE_KERNEL_BWD", True)
        q, k, v = self._model_inputs(15, 256, 4)
        alpha = jnp.full((q.shape[2],), 1.4)
        beta = jnp.full((k.shape[2],), 1.1)
        gk = jax.grad(lambda *a: self._kernel_loss(mode, *a, alpha, beta),
                      argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: self._ref_loss(mode, *a, alpha, beta),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b_, nm in zip(gk, gr, "qkv"):
            scale = max(1.0, float(jnp.max(jnp.abs(b_))))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-3 * scale, err_msg=f"d{nm}")

    def test_grads_match_analytic_oracle(self):
        from repro.core.lln import lln_grads
        q, k, v = self._model_inputs(11, 256, 1, g=2)
        alpha = jnp.full((2,), 1.4)
        beta = jnp.full((2,), 1.1)
        out, vjp = jax.vjp(
            lambda q_, k_, v_: lln_attention(q_, k_, v_, alpha, beta, True,
                                             self.CHUNK), q, k, v)
        g = jnp.ones_like(out)
        dq, dk, dv = vjp(g)
        aq, ak, av = lln_grads(q, k, v, alpha, beta, g, causal=True)
        for a, b_ in ((dq, aq), (dk, ak), (dv, av)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-3)

    def test_ragged_fallback_keeps_v_dtype(self):
        # Regression: the n % chunk fallback used to return fp32 while the
        # Pallas path returned v.dtype, recompiling jit'd callers per length.
        q, k, v = self._model_inputs(13, 48, 2, dtype=jnp.bfloat16)
        alpha, beta = 1.0, 1.0
        for n in (48, 30):   # aligned (pallas) and ragged (jnp fallback)
            out = lln_attention(q[:, :n], k[:, :n], v[:, :n], alpha, beta,
                                True, 16)
            assert out.dtype == jnp.bfloat16, n
            fused = lln_diag_attention(q[:, :n], k[:, :n], v[:, :n], alpha,
                                       beta, True, 16)
            assert fused.dtype == jnp.bfloat16, n
            diag = block_diag_attention(q[:, :n], k[:, :n], v[:, :n], 16,
                                        True)
            assert diag.dtype == jnp.bfloat16, n


class TestSSDKernel:
    def _inputs(self, key, b=2, l=48, h=4, g=2, p=8, s=4):
        ks = jax.random.split(key, 4)
        xbar = jax.random.normal(ks[0], (b, l, h, p))
        b_in = jax.random.normal(ks[1], (b, l, g, s))
        c_in = jax.random.normal(ks[2], (b, l, g, s))
        log_a = -jax.nn.softplus(jax.random.normal(ks[3], (b, l, h)))
        return xbar, b_in, c_in, log_a

    @settings(max_examples=8, deadline=None)
    @given(g=st.sampled_from([1, 2, 4]), nblk=st.integers(1, 3),
           seed=st.integers(0, 2**16))
    def test_ssd_kernel_sweep(self, g, nblk, seed):
        from repro.kernels import ssd_scan
        from repro.models.ssm import ssd_chunked
        xbar, b_in, c_in, log_a = self._inputs(
            jax.random.PRNGKey(seed), l=nblk * 16, g=g)
        y = ssd_scan(xbar, b_in, c_in, log_a, 16)
        rep = 4 // g
        bf = jnp.repeat(b_in, rep, 2)
        cf = jnp.repeat(c_in, rep, 2)
        y_ref, _ = ssd_chunked(xbar, bf, cf, log_a, chunk=16)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=3e-4)

    def test_ssd_kernel_grads(self):
        from repro.kernels import ssd_scan
        from repro.models.ssm import ssd_chunked
        xbar, b_in, c_in, log_a = self._inputs(jax.random.PRNGKey(0))
        bf = jnp.repeat(b_in, 2, 2)
        cf = jnp.repeat(c_in, 2, 2)
        gk = jax.grad(lambda x, a: jnp.sum(
            ssd_scan(x, b_in, c_in, a, 16) ** 2), argnums=(0, 1))(
                xbar, log_a)
        gr = jax.grad(lambda x, a: jnp.sum(
            ssd_chunked(x, bf, cf, a, chunk=16)[0] ** 2), argnums=(0, 1))(
                xbar, log_a)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-3)

    def test_mamba_block_with_kernel_matches_jnp(self):
        from repro.configs import get_config
        from repro.models.ssm import ssm_apply, ssm_init
        cfg = get_config("mamba2-130m", smoke=True).replace(
            compute_dtype="float32", ssm_chunk=16)
        p = ssm_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
        y_jnp = ssm_apply(p, x, cfg)
        y_k = ssm_apply(p, x, cfg.replace(use_kernel=True))
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_jnp),
                                   atol=1e-4)
