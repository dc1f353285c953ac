"""Property tests for the flash softmax path (flat-head rewrite) and the
unified attention dispatcher — hypothesis sweeps over shapes, GQA ratios,
chunk sizes, masks and dtypes against the quadratic reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # container has no
    from _hypothesis_shim import given, settings       # hypothesis; use the
    from _hypothesis_shim import strategies as st      # deterministic shim

from repro.core import AttentionEngine, flash_softmax, naive_softmax
from repro.kernels.registry import AttnSpec


def _qkv(seed, b, n, h, g, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, n, h, d), dtype),
            jax.random.normal(kk, (b, n, g, d), dtype),
            jax.random.normal(kv, (b, n, g, d), dtype))


@settings(max_examples=20, deadline=None)
@given(n=st.integers(3, 90),
       hg=st.sampled_from([(1, 1), (4, 4), (4, 2), (8, 1), (6, 3)]),
       chunk=st.sampled_from([8, 16, 64]),
       causal=st.booleans(),
       seed=st.integers(0, 2**16))
def test_flash_matches_naive(n, hg, chunk, causal, seed):
    h, g = hg
    q, k, v = _qkv(seed, 2, n, h, g, 16)
    out = flash_softmax(q, k, v, causal=causal, chunk=chunk)
    ref = naive_softmax(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(16, 64), prefix=st.integers(1, 15),
       seed=st.integers(0, 2**16))
def test_flash_prefix_lm(n, prefix, seed):
    q, k, v = _qkv(seed, 1, n, 4, 2, 8)
    out = flash_softmax(q, k, v, causal=True, chunk=16, prefix_len=prefix)
    ref = naive_softmax(q, k, v, causal=True, prefix_len=prefix)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(8, 64), valid=st.integers(1, 8),
       seed=st.integers(0, 2**16))
def test_flash_key_mask(n, valid, seed):
    q, k, v = _qkv(seed, 2, n, 4, 4, 8)
    m = (jnp.arange(n)[None] < min(valid, n)).repeat(2, 0)
    out = flash_softmax(q, k, v, causal=False, chunk=16, mask=m)
    ref = naive_softmax(q, k, v, causal=False, mask=m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@settings(max_examples=8, deadline=None)
@given(nq=st.integers(1, 8), nk=st.integers(16, 64),
       seed=st.integers(0, 2**16))
def test_flash_decode_shapes(nq, nk, seed):
    """queries are the last nq positions of an nk-long context."""
    q, k, v = _qkv(seed, 2, nk, 4, 2, 8)
    out = flash_softmax(q[:, -nq:], k, v, causal=True, chunk=16)
    ref = naive_softmax(q[:, -nq:], k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_flash_bf16_close_to_f32():
    q, k, v = _qkv(0, 2, 64, 4, 2, 16)
    ref = flash_softmax(q, k, v, causal=True, chunk=16)
    out = flash_softmax(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                        v.astype(jnp.bfloat16), causal=True, chunk=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=5e-2)


def test_flash_grads_match_naive():
    q, k, v = _qkv(1, 2, 48, 4, 2, 8)

    def lf(q, k, v):
        return jnp.sum(flash_softmax(q, k, v, causal=True, chunk=16) ** 2)

    def ln(q, k, v):
        return jnp.sum(naive_softmax(q, k, v, causal=True) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(ln, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(impl=st.sampled_from(["softmax", "lln", "lln_diag"]),
       causal=st.booleans(), seed=st.integers(0, 2**16))
def test_dispatcher_finite_and_shaped(impl, causal, seed):
    q, k, v = _qkv(seed, 2, 32, 4, 2, 16)
    spec = AttnSpec(impl=impl, causal=causal, r=2, diag_block=16,
                    lln_chunk=16, softmax_chunk=16, train_twin="scan")
    eng = AttentionEngine(spec=spec, heads=4, kv_heads=2, head_dim=16,
                          v_dim=16)
    out = eng.attention(q, k, v)
    assert out.shape == q.shape
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


def test_dispatcher_rejects_unknown_impl():
    q, k, v = _qkv(0, 1, 16, 2, 2, 8)
    with pytest.raises(ValueError):
        AttentionEngine(spec=AttnSpec(impl="bogus"), heads=2, kv_heads=2,
                        head_dim=8, v_dim=8).attention(q, k, v)
