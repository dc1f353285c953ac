"""Backend registry + AttentionEngine lifecycle.

* explicit ``backend=pallas(interpret)|scan|ref`` parity at small shapes,
  impl × causal × r ∈ {1, 4} — the scan twins and jnp references are
  first-class testable targets, not accidents of the CPU dispatch;
* ``AttnSpec`` validation errors and ``resolve`` policy;
* ``AttentionState`` lifecycle round-trip (init → prefill → decode →
  evict) matching the KVCache / LLNDecodeState decode functions bitwise;
* MLA chunked multi-token decode through the engine;
* the dispatch table: which implementation each model front door runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import attention as ca
from repro.core import lln as core_lln
from repro.core.engine import AttentionEngine, AttentionState
from repro.kernels import ops as kops
from repro.kernels.registry import AttnSpec, Resolution, resolve


def _qkv(seed, b, n, h, g, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, n, h, d)).astype(dtype),
            jax.random.normal(kk, (b, n, g, d)).astype(dtype),
            jax.random.normal(kv, (b, n, g, d)).astype(dtype))


# ---------------------------------------------------------------------------
# AttnSpec validation + resolve policy.
# ---------------------------------------------------------------------------

class TestSpecValidation:
    def test_defaults_valid(self):
        AttnSpec()

    @pytest.mark.parametrize("kw", [
        {"impl": "bogus"},
        {"backend": "cuda"},
        {"impl": "softmax", "backend": "pallas"},
        {"r": 0},
        {"calibration": "global"},
        {"precision": "int8"},
        {"lln_chunk": 0},
        {"diag_block": -1},
        {"fixed_ab": -2.0},
    ])
    def test_invalid_specs_raise(self, kw):
        with pytest.raises(ValueError):
            AttnSpec(**kw)

    def test_resolve_policy(self):
        assert resolve("auto", ragged=True) == Resolution("ref", False)
        assert resolve("ref", ragged=True) == Resolution("ref", False)
        assert resolve("scan") == Resolution("scan", False)
        for backend in ("pallas", "scan"):
            with pytest.raises(ValueError):
                resolve(backend, ragged=True)
        with pytest.raises(ValueError):
            resolve("tpu")


# ---------------------------------------------------------------------------
# Explicit-backend parity at the ops level.
# ---------------------------------------------------------------------------

class TestBackendParity:
    @pytest.mark.parametrize("causal", [True, False])
    def test_attention_backends_agree(self, lln_parity_cell, causal):
        backend, impl, r = lln_parity_cell
        b, n, g, d = 2, 32, 2, 8
        h = g * r
        q, k, v = _qkv(r, b, n, h, g, d)
        alpha = jnp.full((h,), 1.2)
        beta = jnp.full((g,), 1.0)
        if impl == "log_linear" and not causal:
            pytest.skip("log_linear is causal-only")
        fn = {"lln": kops.lln_attention,
              "lln_diag": kops.lln_diag_attention,
              "log_linear": kops.loglin_attention}[impl]
        ref = fn(q, k, v, alpha, beta, causal, 16, backend="auto")
        out = fn(q, k, v, alpha, beta, causal, 16, backend=backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=f"{impl} {backend}")

    def test_prefill_backends_agree(self, backend_gqa_cell):
        backend, r = backend_gqa_cell
        b, n, g, d = 2, 32, 2, 8
        h = g * r
        q, k, v = _qkv(10 + r, b, n, h, g, d)
        alpha = jnp.full((h,), 1.3)
        beta = jnp.full((g,), 1.1)
        ref = kops.lln_prefill(q, k, v, alpha, beta, chunk=16,
                               backend="auto")
        got = kops.lln_prefill(q, k, v, alpha, beta, chunk=16,
                               backend=backend)
        for name, a, b_ in zip(("out", "s", "z", "c_k"), got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=f"{backend}:{name}")

    def test_decode_chunk_backends_agree(self, backend_gqa_cell):
        backend, r = backend_gqa_cell
        b, g, d, t = 2, 2, 8, 5
        h = g * r
        q0, k0, v0 = _qkv(20 + r, b, 24, h, g, d)
        alpha = jnp.full((h,), 1.3)
        beta = jnp.full((g,), 1.1)
        _, s, z, c_k = kops.lln_prefill(q0, k0, v0, alpha, beta, chunk=8)
        st = core_lln.LLNState(s=s, z=z, c_k=c_k)
        qn, kn, vn = _qkv(30 + r, b, t, h, g, d)
        ref = kops.lln_decode_chunk(st, qn, kn, vn, alpha, beta,
                                    backend="auto")
        o, st2 = kops.lln_decode_chunk(st, qn, kn, vn, alpha, beta,
                                       backend=backend)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref[0]),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=backend)
        np.testing.assert_allclose(np.asarray(st2.s),
                                   np.asarray(ref[1].s), rtol=3e-4,
                                   atol=3e-4, err_msg=backend)

    def test_diag_fwd_backends_agree(self):
        b, n, g, r, d = 2, 32, 2, 2, 8
        h = g * r
        q, k, v = _qkv(40, b, n, h, g, d)
        ref = kops.block_diag_fwd(q, k, v, 8, True, backend="auto")
        for backend in ("pallas", "scan", "ref"):
            out = kops.block_diag_fwd(q, k, v, 8, True, backend=backend)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=3e-4, atol=3e-4,
                                       err_msg=backend)

    def test_explicit_pallas_rejects_ragged(self):
        q, k, v = _qkv(50, 1, 30, 2, 2, 8)     # 30 % 16 != 0
        with pytest.raises(ValueError):
            kops.lln_prefill(q, k, v, 1.0, 1.0, chunk=16, backend="pallas")


# ---------------------------------------------------------------------------
# Engine-level backend parity (softmax included) + state lifecycle.
# ---------------------------------------------------------------------------

def _engine(impl, r, backend="auto", calibration="batch"):
    g = 2
    spec = AttnSpec(impl=impl, causal=True, r=r, backend=backend,
                    lln_chunk=8, diag_block=8, softmax_chunk=16,
                    fixed_ab=0.0 if impl == "softmax" else 2.1,
                    calibration=calibration)
    return AttentionEngine(spec=spec, heads=g * r, kv_heads=g, head_dim=8,
                           v_dim=8, cache_dtype=jnp.float32)


class TestEngineLifecycle:
    def test_engine_backends_agree_end_to_end(self, engine_parity_cell):
        """prefill + decode outputs agree across every legal backend
        (each cell checks one backend against the auto resolution)."""
        backend, impl, r = engine_parity_cell
        b, n, g, d, t = 2, 16, 2, 8, 3
        h = g * r
        q, k, v = _qkv(60 + r, b, n, h, g, d)
        qn, kn, vn = _qkv(70 + r, b, t, h, g, d)
        ref_eng = _engine(impl, r, "auto")
        ref_out, ref_st = ref_eng.prefill(q, k, v, max_len=n + t)
        ref_out2, _ = ref_eng.decode(ref_st, qn, kn, vn)
        eng = _engine(impl, r, backend)
        out, st = eng.prefill(q, k, v, max_len=n + t)
        out2, _ = eng.decode(st, qn, kn, vn)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=3e-4, atol=3e-4, err_msg=backend)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(ref_out2),
                                   rtol=3e-4, atol=3e-4, err_msg=backend)

    @pytest.mark.parametrize("impl", ["softmax", "lln_diag"])
    def test_lifecycle_roundtrip_matches_legacy_bitwise(self, impl):
        """init -> prefill -> decode -> evict; every step bitwise-equal to
        the legacy composition (KVCache/LLNDecodeState + decode_softmax /
        decode_lln_chunk — the pre-engine ``attn_decode`` body)."""
        b, n, g, r, d, t = 2, 16, 2, 2, 8, 2
        h = g * r
        eng = _engine(impl, r)
        q, k, v = _qkv(80, b, n, h, g, d)
        qn, kn, vn = _qkv(81, b, t, h, g, d)

        st0 = eng.init_state(b, n + t)
        assert st0.pos is None or st0.pos.shape == (b,)
        out, st = eng.prefill(q, k, v, max_len=n + t)
        out2, st2 = eng.decode(st, qn, kn, vn)

        if impl == "softmax":
            legacy = ca.KVCache(k=st.k, v=st.v, length=st.len)
            ref2, kv2 = ca.decode_softmax(legacy, qn, kn, vn,
                                          chunk=eng.spec.softmax_chunk)
            np.testing.assert_array_equal(np.asarray(out2),
                                          np.asarray(ref2))
            np.testing.assert_array_equal(np.asarray(st2.k),
                                          np.asarray(kv2.k))
            np.testing.assert_array_equal(np.asarray(st2.len),
                                          np.asarray(kv2.length))
        else:
            legacy = ca.LLNDecodeState(
                lln=core_lln.LLNState(s=st.s, z=st.z, c_k=st.c_k),
                tail_k=st.tail_k, tail_v=st.tail_v, pos=st.pos)
            ref2, lst = ca.decode_lln_chunk(legacy, qn, kn, vn, st.alpha,
                                            st.beta, impl=impl,
                                            backend="auto")
            np.testing.assert_array_equal(np.asarray(out2),
                                          np.asarray(ref2))
            np.testing.assert_array_equal(np.asarray(st2.s),
                                          np.asarray(lst.lln.s))
            np.testing.assert_array_equal(np.asarray(st2.tail_k),
                                          np.asarray(lst.tail_k))
            np.testing.assert_array_equal(np.asarray(st2.pos),
                                          np.asarray(lst.pos))

        # evict resets exactly the named rows to their init values (zeros;
        # calibration alpha/beta back to ONES), others intact.
        st3 = eng.evict(st2, jnp.asarray([0], jnp.int32))
        for kp, leaf in jax.tree_util.tree_leaves_with_path(st3):
            path = jax.tree_util.keystr(kp)
            fill = 1.0 if ("alpha" in path or "beta" in path) else 0.0
            np.testing.assert_array_equal(
                np.asarray(leaf)[0],
                np.full_like(np.asarray(leaf)[0], fill),
                err_msg=f"evicted row not reset to init: {path}")
        for kp, leaf in jax.tree_util.tree_leaves_with_path(st2):
            after = st3
            for kk in kp:
                after = after[kk.key]
            np.testing.assert_array_equal(
                np.asarray(after)[1], np.asarray(leaf)[1],
                err_msg=f"evict leaked into live row: {jax.tree_util.keystr(kp)}")

    def test_state_is_a_pytree_with_dict_paths(self):
        st = _engine("lln_diag", 2).init_state(2, 16)
        leaves = jax.tree_util.tree_leaves_with_path(st)
        names = {kp[-1].key for kp, _ in leaves}
        assert {"s", "z", "c_k", "log_scale", "tail_k", "tail_v", "pos",
                "alpha", "beta"} == names
        assert st["pos"].shape == (2,)
        with pytest.raises(KeyError):
            st["nope"]


# ---------------------------------------------------------------------------
# Per-row calibration (batched-prefill admission).
# ---------------------------------------------------------------------------

class TestPerRowCalibration:
    def test_per_row_matches_solo_rows(self):
        """(B, H) per-row alpha/beta == each row calibrated alone."""
        b, n, g, r, d = 3, 16, 2, 2, 8
        h = g * r
        q, k, _ = _qkv(90, b, n, h, g, d)
        spec = AttnSpec(impl="lln", r=r)
        a_rows, b_rows = ca.batch_alpha_beta(q, k, spec, per_row=True)
        assert a_rows.shape == (b, h) and b_rows.shape == (b, g)
        for i in range(b):
            a1, b1 = ca.batch_alpha_beta(q[i:i + 1], k[i:i + 1], spec)
            np.testing.assert_allclose(np.asarray(a_rows[i]),
                                       np.asarray(a1), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(b_rows[i]),
                                       np.asarray(b1), rtol=1e-6)

    @pytest.mark.parametrize("backend", ["pallas", "scan", "ref"])
    def test_per_row_calibration_works_on_every_backend(self, backend):
        """(B, H)/(B, G) calibration must flow through every backend's
        full-sequence forward — including the jnp core path under ``scan``
        (which pools per-q-head beta to groups and repeats it per row)."""
        b, n, g, r, d = 2, 16, 2, 2, 8
        h = g * r
        eng = _engine("lln", r, backend, calibration="per_row")
        q, k, v = _qkv(92, b, n, h, g, d)
        alpha, beta = eng.calibrate(q, k)
        assert alpha.shape == (b, h) and beta.shape == (b, g)
        out = eng.attention(q, k, v, alpha=alpha, beta=beta)
        assert out.shape == (b, n, h, d)
        # And with calibration computed inside attention(): same result —
        # engine.attention must honour spec.calibration, not silently
        # fall back to batch pooling.
        out2 = eng.attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                                   rtol=2e-5, atol=2e-5)

    def test_per_row_calibration_grads(self):
        """jax.grad through lln_attention with per-row (B, H)/(B, G)
        alpha/beta — the custom_vjp chain rule must broadcast per row."""
        b, n, g, r, d = 2, 16, 2, 2, 8
        h = g * r
        q, k, v = _qkv(93, b, n, h, g, d)
        alpha = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (b, h))) + 1
        beta = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (b, g))) + 1
        for fn in (kops.lln_attention, kops.lln_diag_attention):
            grads = jax.grad(
                lambda q_, k_, v_: fn(q_, k_, v_, alpha, beta, True,
                                      8).sum(), argnums=(0, 1, 2))(q, k, v)
            for gr in grads:
                assert bool(jnp.isfinite(gr).all()), fn.__name__

    def test_engine_per_row_prefill_matches_solo(self):
        """A batched per-row-calibrated prefill carries exactly the state
        each row would get prefilled alone."""
        b, n, g, r, d = 2, 16, 2, 2, 8
        h = g * r
        eng = _engine("lln_diag", r, calibration="per_row")
        q, k, v = _qkv(91, b, n, h, g, d)
        out, st = eng.prefill(q, k, v, max_len=24)
        for i in range(b):
            _, sti = eng.prefill(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                 max_len=24)
            np.testing.assert_allclose(np.asarray(st.alpha[i]),
                                       np.asarray(sti.alpha[0]), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(st.s[i]),
                                       np.asarray(sti.s[0]), rtol=2e-5,
                                       atol=2e-5)


# ---------------------------------------------------------------------------
# MLA through the engine: chunked multi-token decode.
# ---------------------------------------------------------------------------

def _mla_cfg(impl):
    # Dense FFN so chunk-vs-sequential isolates the attention path (MoE
    # capacity routing is per-dispatch and would differ legitimately).
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=f"mla-test-{impl}", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab=128, attn_impl=impl,
        diag_block=8, lln_chunk=8, softmax_chunk=16,
        lln_fixed_ab=2.1 if impl != "softmax" else 0.0,
        kv_lora=32, q_lora=24, rope_head_dim=8, nope_head_dim=16,
        v_head_dim=16, compute_dtype="float32", param_dtype="float32",
        remat="none", tie_embeddings=True)


class TestMLAChunkedDecode:
    @pytest.mark.parametrize("impl", ["softmax", "lln_diag"])
    def test_mla_chunked_decode_matches_sequential(self, impl):
        """model.decode over a (B, T) chunk == T single-token calls for
        MLA — chunked decode now reaches the latent-attention family."""
        from repro.models import build_model, synthetic_batch
        cfg = _mla_cfg(impl)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(4))
        n_prompt, t = 16, 4
        batch = synthetic_batch(cfg, batch=2, seq=n_prompt + t)
        prompt_batch = dict(batch)
        prompt_batch["inputs"] = batch["inputs"][:, :n_prompt]
        draft = batch["inputs"][:, n_prompt:n_prompt + t]

        _, caches = model.prefill(params, prompt_batch, n_prompt + t)
        lg_chunk, _ = model.decode(params, caches, draft,
                                   jnp.asarray(n_prompt, jnp.int32))
        _, caches = model.prefill(params, prompt_batch, n_prompt + t)
        for i in range(t):
            lg, caches = model.decode(params, caches, draft[:, i],
                                      jnp.asarray(n_prompt + i, jnp.int32))
            np.testing.assert_allclose(np.asarray(lg_chunk[:, i]),
                                       np.asarray(lg), rtol=3e-4,
                                       atol=3e-4, err_msg=f"token {i}")

    def test_mla_state_has_g_head_tails(self):
        """The MLA LLN state is the same AttentionState pytree, tails at
        the (here G == H) kv heads."""
        from repro.models.mla import mla_state_init
        cfg = _mla_cfg("lln_diag")
        st = mla_state_init(cfg, 2, 32)
        assert isinstance(st, AttentionState)
        assert st.tail_k.shape == (2, cfg.diag_block, 4,
                                   cfg.nope_head_dim + cfg.rope_head_dim)
        assert st.pos.shape == (2,)
        assert st.alpha.shape == (2, 4)


# ---------------------------------------------------------------------------
# Dispatch table: which implementation each model front door runs, per
# platform, ``use_kernel`` and ``attn_backend`` that a caller uses.
# ---------------------------------------------------------------------------

def _table_cfg(front, impl, use_kernel, backend):
    from repro.configs import get_config
    from repro.configs.base import ArchConfig
    if front == "ssm_apply":
        return get_config("mamba2-130m", smoke=True).replace(
            use_kernel=use_kernel, compute_dtype="float32")
    return ArchConfig(
        name="table", family="dense", n_layers=1, d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=64, vocab=64, attn_impl=impl,
        diag_block=8, lln_chunk=8, softmax_chunk=16, use_kernel=use_kernel,
        attn_backend=backend, compute_dtype="float32",
        param_dtype="float32", remat="none")


def _run_front_door(front, cfg):
    """Trace one front door (``jax.eval_shape``: nothing is compiled, so a
    kernel resolved for the chip traces here too)."""
    from repro.models import attention_block as ab
    from repro.models import ssm
    key = jax.random.PRNGKey(0)
    if front == "ssm_apply":
        p = ssm.ssm_init(key, cfg)
        x = jnp.zeros((1, 2 * cfg.ssm_chunk, cfg.d_model))
        return jax.eval_shape(lambda p, x: ssm.ssm_apply(p, x, cfg), p, x)
    p = ab.attn_init(key, cfg)
    x = jnp.zeros((2, 16, cfg.d_model))
    pos = jnp.arange(16)
    if front == "attn_apply":
        return jax.eval_shape(
            lambda p, x: ab.attn_apply(p, x, cfg, pos, causal=False), p, x)
    if front == "serve_prefill":
        return jax.eval_shape(
            lambda p, x: ab.serve_prefill(p, x, cfg, pos, max_len=24), p, x)
    state = ab.serve_state_init(cfg, 2, 24)
    return jax.eval_shape(
        lambda p, x, s: ab.serve_decode(p, x, s, cfg,
                                        jnp.full((2,), 16, jnp.int32)),
        p, x[:, :1], state)


#: (front door, impl, off the CPU, cfg.use_kernel, cfg.attn_backend) ->
#: the implementations that run: ``pallas`` compiled kernels,
#: ``pallas-interpret`` the kernels interpreted, ``scan`` the kernel-layout
#: twins, ``ref`` the kernels' jnp references (kernels/ops.py), ``jnp`` the
#: model-layout jnp math (core/lln.py, models/ssm.py:ssd_chunked),
#: ``flash``/``naive`` the softmax forms.  Each row names a caller.
DISPATCH_TABLE = [
    # roberta-mlm512's training forward (bidirectional lln_diag).
    ("attn_apply", "lln_diag", False, False, "auto", {"jnp"}),       # tests
    ("attn_apply", "lln_diag", False, True, "auto",
     {"pallas-interpret"}),                       # bench/tests/smoke.py
    ("attn_apply", "lln_diag", False, True, "pallas",
     {"pallas-interpret"}),                       # test_distributed.py
    ("attn_apply", "lln_diag", True, False, "auto", {"pallas"}),   # the cell
    ("attn_apply", "lln_diag", True, False, "ref", {"ref"}),   # chip_smoke
    ("attn_apply", "softmax", False, False, "auto", {"flash"}),     # tests
    # The decode cells' prefill and decode (causal lln_diag).
    ("serve_prefill", "lln_diag", False, False, "auto", {"scan"}),  # tests
    ("serve_prefill", "lln_diag", False, True, "pallas",
     {"pallas-interpret"}),                       # test_distributed.py
    ("serve_prefill", "lln_diag", True, False, "auto", {"pallas"}),  # cells
    ("serve_prefill", "lln_diag", True, False, "ref", {"ref"}),  # chip_smoke
    ("serve_prefill", "softmax", False, False, "ref", {"naive"}),  # serve CLI
    ("serve_decode", "lln_diag", False, False, "auto", {"jnp"}),    # tests
    ("serve_decode", "lln_diag", False, True, "pallas",
     {"pallas-interpret"}),                       # test_distributed.py
    ("serve_decode", "lln_diag", True, False, "auto", {"pallas"}),  # cells
    ("serve_decode", "lln_diag", True, False, "ref", {"jnp"}),  # chip_smoke
    # The Mamba2 SSD choice.
    ("ssm_apply", "softmax", False, False, "auto", {"jnp"}),        # tests
    ("ssm_apply", "softmax", False, True, "auto",
     {"pallas-interpret"}),                       # test_distributed.py
    ("ssm_apply", "softmax", True, False, "auto", {"pallas"}),  # chip_smoke
]


@pytest.mark.parametrize(
    "front,impl,off_cpu,use_kernel,backend,want", DISPATCH_TABLE,
    ids=["-".join(map(str, row[:5])) for row in DISPATCH_TABLE])
def test_dispatch_table(monkeypatch, front, impl, off_cpu, use_kernel,
                        backend, want):
    from repro.core import engine as engine_mod
    from repro.kernels import registry
    from repro.models import ssm
    seen = set()

    def spy(mod, name, label):
        real = getattr(mod, name)

        def call(*a, **kw):
            seen.add(label + ("-interpret" if kw.get("interpret") else ""))
            return real(*a, **kw)
        monkeypatch.setattr(mod, name, call)

    for name in ("lln_bidir_pallas", "lln_causal_pallas",
                 "lln_diag_fused_pallas", "block_diag_pallas",
                 "lln_decode_pallas", "ssd_pallas"):
        spy(kops, name, "pallas")
    for name in ("_lln_ref", "_lln_prefill_scan", "_block_diag_twin"):
        spy(kops, name, "scan")
    for name in ("_lln_quad_ref", "_diag_ref", "_lln_prefill_ref"):
        spy(kops, name, "ref")
    spy(core_lln, "decode_chunk", "jnp")
    spy(ssm, "ssd_chunked", "jnp")
    spy(ca, "flash_softmax", "flash")
    spy(ca, "naive_softmax", "naive")
    spy(engine_mod, "lln_bidir", "jnp")
    spy(engine_mod, "lln_causal", "jnp")
    if off_cpu:
        monkeypatch.setattr(registry, "on_cpu", lambda: False)
    _run_front_door(front, _table_cfg(front, impl, use_kernel, backend))
    assert seen == want
