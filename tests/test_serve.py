"""Serving-path parity: the kernelized prefill/decode subsystem against the
sequential decode oracles and the full-sequence forward.

* prefill-kernel state == decode-replay state (fp32 tight, bf16 loose);
* ``lln_decode_chunk(T)`` == T sequential ``decode_step``s (state + outputs),
  including chunks that straddle a diag-block boundary and T > block;
* end-to-end greedy prefill + decode logits == the full-sequence forward for
  softmax / lln / lln_diag × GQA r ∈ {1, 4};
* the scanned generation segment == the per-token dispatch loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core import attention as ca
from repro.core import lln as core_lln
from repro.kernels import ops as kops
from repro.models import build_model, synthetic_batch


def _qkv(seed, b, n, h, g, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, n, h, d)).astype(dtype),
            jax.random.normal(kk, (b, n, g, d)).astype(dtype),
            jax.random.normal(kv, (b, n, g, d)).astype(dtype))


def _replay_state(q, k, v, alpha, beta_h, h):
    """Sequential decode_step replay over the prompt (the state oracle)."""
    b, n, _, d = q.shape
    kf = k if k.shape[2] == h else jnp.repeat(k, h // k.shape[2], axis=2)
    vf = v if v.shape[2] == h else jnp.repeat(v, h // v.shape[2], axis=2)
    st = core_lln.LLNState.init(b, h, d, vf.shape[-1])
    for t in range(n):
        _, st = core_lln.decode_step(st, q[:, t:t + 1], kf[:, t:t + 1],
                                     vf[:, t:t + 1], alpha, beta_h)
    return st


class TestPrefillState:
    @pytest.mark.parametrize("r", [1, 4])
    @pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 2e-4),
                                            (jnp.bfloat16, 5e-2)])
    def test_prefill_state_matches_decode_replay(self, r, dtype, rtol):
        b, n, g, d = 2, 48, 2, 16
        h = g * r
        q, k, v = _qkv(r, b, n, h, g, d, dtype)
        alpha = jnp.full((h,), 1.3)
        beta = jnp.full((g,), 1.1)
        out, s, z, c_k = kops.lln_prefill(q, k, v, alpha, beta, chunk=16)
        st = _replay_state(q, k, v, alpha, jnp.repeat(beta, r), h)
        # The reference constants may differ by a bf16 ulp (fp32 vs bf16
        # beta*k product); the states are equivalent after rescaling both
        # to a common constant.
        np.testing.assert_allclose(np.asarray(c_k), np.asarray(st.c_k),
                                   atol=1e-5 if dtype == jnp.float32
                                   else 2e-2)
        c_ref = jnp.maximum(c_k, st.c_k)
        fa = jnp.exp(c_k - c_ref)[:, 0, :, 0]
        fb = jnp.exp(st.c_k - c_ref)[:, 0, :, 0]
        s_a, s_b = s * fa[..., None, None], st.s * fb[..., None, None]
        z_a, z_b = z * fa[..., None], st.z * fb[..., None]
        scale = float(np.abs(np.asarray(s_b)).max())
        np.testing.assert_allclose(np.asarray(s_a), np.asarray(s_b),
                                   atol=rtol * scale)
        scale = float(np.abs(np.asarray(z_b)).max())
        np.testing.assert_allclose(np.asarray(z_a), np.asarray(z_b),
                                   atol=rtol * scale)
        assert out.dtype == dtype

    @pytest.mark.parametrize("n", [30, 48])
    def test_prefill_out_matches_core(self, n):
        """Aligned (scan twin) and ragged (jnp fallback) dispatch both match
        the core causal reference."""
        b, g, r, d = 1, 2, 2, 8
        h = g * r
        q, k, v = _qkv(3, b, n, h, g, d)
        alpha = jnp.full((h,), 1.2)
        beta = jnp.full((g,), 1.0)
        out, s, z, c_k = kops.lln_prefill(q, k, v, alpha, beta, chunk=16)
        kf, vf = jnp.repeat(k, r, 2), jnp.repeat(v, r, 2)
        ref, st_ref = core_lln.prefill(q, kf, vf, alpha,
                                       jnp.repeat(beta, r), chunk=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(st_ref.s),
                                   rtol=1e-4, atol=1e-4)

    def test_prefill_kernel_path_matches_scan_twin(self, monkeypatch):
        """Interpret-mode Pallas state-emitting kernel == the scan twin the
        CPU container dispatches to."""
        b, n, g, r, d = 1, 32, 2, 2, 8
        h = g * r
        q, k, v = _qkv(5, b, n, h, g, d)
        alpha, beta = jnp.full((h,), 1.2), jnp.full((g,), 1.0)
        twin = kops.lln_prefill(q, k, v, alpha, beta, chunk=16)
        from repro.kernels.lln_attention import lln_causal_pallas
        monkeypatch.setattr(kops, "_interpret", lambda flag: False)
        monkeypatch.setattr(
            kops, "lln_causal_pallas",
            lambda *a, **kw: lln_causal_pallas(*a, **{**kw,
                                                      "interpret": True}))
        pallas = kops.lln_prefill(q, k, v, alpha, beta, chunk=16)
        for a, b_ in zip(pallas, twin):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-5, atol=2e-5)


class TestDecodeChunk:
    def _state(self, b, h, g, d, n0, seed=0):
        q, k, v = _qkv(seed, b, n0, h, g, d)
        alpha = jnp.full((h,), 1.3)
        beta = jnp.full((g,), 1.1)
        _, s, z, c_k = kops.lln_prefill(q, k, v, alpha, beta, chunk=8)
        return core_lln.LLNState(s=s, z=z, c_k=c_k), alpha, \
            jnp.repeat(beta, h // g)

    @pytest.mark.parametrize("r", [1, 2])
    @pytest.mark.parametrize("t", [1, 7])
    def test_chunk_matches_sequential_steps(self, r, t):
        b, g, d, n0 = 2, 2, 8, 24
        h = g * r
        st, alpha, beta_h = self._state(b, h, g, d, n0)
        qn, kn, vn = _qkv(9, b, t, h, g, d)
        knh, vnh = jnp.repeat(kn, r, 2), jnp.repeat(vn, r, 2)
        oc, stc = kops.lln_decode_chunk(st, qn, kn, vn, alpha, beta_h)
        sts, outs = st, []
        for i in range(t):
            o, sts = core_lln.decode_step(sts, qn[:, i:i + 1],
                                          knh[:, i:i + 1], vnh[:, i:i + 1],
                                          alpha, beta_h)
            outs.append(o)
        oseq = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(oc), np.asarray(oseq),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(stc.s), np.asarray(sts.s),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(stc.z), np.asarray(sts.z),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(stc.c_k), np.asarray(sts.c_k),
                                   atol=1e-6)

    def test_chunk_kernel_matches_twin(self, monkeypatch):
        """Interpret-mode decode-chunk Pallas kernel (padded T path) == the
        jnp twin."""
        b, g, r, d, t = 2, 2, 2, 8, 7
        h = g * r
        st, alpha, beta_h = self._state(b, h, g, d, 24, seed=2)
        qn, kn, vn = _qkv(11, b, t, h, g, d)
        o_twin, st_twin = kops.lln_decode_chunk(st, qn, kn, vn, alpha,
                                                beta_h)
        real = kops.lln_decode_pallas
        monkeypatch.setattr(kops, "_interpret", lambda flag: False)
        monkeypatch.setattr(
            kops, "lln_decode_pallas",
            lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
        o_pal, st_pal = kops.lln_decode_chunk(st, qn, kn, vn, alpha, beta_h)
        np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_twin),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(st_pal.s),
                                   np.asarray(st_twin.s), rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(st_pal.c_k),
                                   np.asarray(st_twin.c_k), atol=1e-6)

    @pytest.mark.parametrize("t", [1, 3, 5, 7, 12])
    def test_chunk_backends_agree_non_sublane_t(self, t):
        """lln_decode_chunk parity across explicit pallas/scan/ref backends
        for T that is NOT a sublane multiple (the Pallas path pads T with
        NEG_INF keys => Phi(k) = 0).  The speculative verify pass calls
        T = k+1 with arbitrary k, so odd chunk lengths are routine."""
        b, g, r, d = 2, 2, 2, 8
        h = g * r
        st, alpha, beta_h = self._state(b, h, g, d, 24, seed=t)
        qn, kn, vn = _qkv(17 + t, b, t, h, g, d)
        results = {}
        for backend in ("pallas", "scan", "ref"):
            results[backend] = kops.lln_decode_chunk(
                st, qn, kn, vn, alpha, beta_h, backend=backend)
        o_ref, st_ref = results["ref"]
        for backend in ("pallas", "scan"):
            o, stb = results[backend]
            np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=backend)
            np.testing.assert_allclose(np.asarray(stb.s),
                                       np.asarray(st_ref.s),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=backend)
            np.testing.assert_allclose(np.asarray(stb.z),
                                       np.asarray(st_ref.z),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=backend)
            np.testing.assert_allclose(np.asarray(stb.c_k),
                                       np.asarray(st_ref.c_k),
                                       atol=1e-6, err_msg=backend)

    @pytest.mark.parametrize("t", [7, 19])
    def test_full_decode_chunk_crosses_block_boundary(self, t):
        """decode_lln_chunk (LLN + tail-softmax diag) over a chunk straddling
        a diag-block boundary == T sequential single-token decodes; G-head
        tail == the repeated H-head (seed-layout) tail."""
        b, g, r, d, block, n0 = 2, 2, 2, 8, 8, 21
        h = g * r
        st_lln, alpha, beta_h = self._state(b, h, g, d, n0, seed=3)
        _, k0, v0 = _qkv(3, b, n0, h, g, d)
        nb = -(-n0 // block)
        pad = nb * block - n0
        tg_k = jnp.pad(k0, ((0, 0), (0, pad), (0, 0), (0, 0)))[:, (nb - 1) * block:]
        tg_v = jnp.pad(v0, ((0, 0), (0, pad), (0, 0), (0, 0)))[:, (nb - 1) * block:]
        pos = jnp.asarray(n0, jnp.int32)
        st_g = ca.LLNDecodeState(lln=st_lln, tail_k=tg_k, tail_v=tg_v,
                                 pos=pos)
        st_h = ca.LLNDecodeState(lln=st_lln, tail_k=jnp.repeat(tg_k, r, 2),
                                 tail_v=jnp.repeat(tg_v, r, 2), pos=pos)
        qn, kn, vn = _qkv(13, b, t, h, g, d)
        for impl in ("lln", "lln_diag"):
            oc, stc = ca.decode_lln_chunk(st_g, qn, kn, vn, alpha, beta_h,
                                          impl=impl)
            sts, outs = st_h, []
            for i in range(t):
                o, sts = ca.decode_lln_chunk(
                    sts, qn[:, i:i + 1], kn[:, i:i + 1], vn[:, i:i + 1],
                    alpha, beta_h, impl=impl)
                outs.append(o)
            oseq = jnp.concatenate(outs, axis=1)
            np.testing.assert_allclose(np.asarray(oc), np.asarray(oseq),
                                       rtol=3e-5, atol=3e-5, err_msg=impl)
            np.testing.assert_allclose(
                np.asarray(jnp.repeat(stc.tail_k, r, 2)),
                np.asarray(sts.tail_k), atol=1e-6)
            assert int(stc.pos) == int(sts.pos)


class TestSingleTokenDiag:
    """The T = 1 diag component, read from the tails as stored in two
    parts, == the concatenated form that chunks of T > 1 keep."""

    BLOCK = 256
    # Per row: block start with an empty tail, block start with a full
    # previous block (all masked), mid-block, the last slot of a block
    # after a full one, mid-block, the last slot of the first block.
    POS = (0, 256, 100, 511, 700, 255)
    ROW_MASK = (True, False, True, True, False, True)

    def _inputs(self, r, d, dtype, seed):
        b, g = len(self.POS), 2
        h = g * r
        kq, kk, kv, kt, ku = jax.random.split(jax.random.PRNGKey(seed), 5)
        q = jax.random.normal(kq, (b, 1, h, d)).astype(dtype)
        kn = jax.random.normal(kk, (b, 1, g, d)).astype(dtype)
        vn = jax.random.normal(kv, (b, 1, g, d)).astype(dtype)
        tail_k = (2 * jax.random.normal(kt, (b, self.BLOCK, g, d))
                  ).astype(jnp.bfloat16)
        tail_v = jax.random.normal(ku, (b, self.BLOCK, g, d)
                                   ).astype(jnp.bfloat16)
        return q, kn, vn, tail_k, tail_v, jnp.asarray(self.POS, jnp.int32)

    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("r", [1, 8])
    def test_matches_concatenated_form(self, r, d):
        """Serving dtypes (bf16 q, keys and tails), every row position."""
        args = self._inputs(r, d, jnp.bfloat16, seed=r + d)
        two = ca._diag_single(*args)
        cat = ca._diag_chunk(*args)
        assert two.dtype == cat.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(two), np.asarray(cat),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("defer_tail", [False, True])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("r", [1, 8])
    def test_decode_matches_concatenated_form(self, r, d, defer_tail):
        """decode_lln_chunk at T = 1 with masked rows == its LLN output
        plus the concatenated diag form; the state is the LLN path's."""
        q, kn, vn, tail_k, tail_v, pos = self._inputs(r, d, jnp.float32,
                                                      seed=3 * r + d)
        b, _, h, _ = q.shape
        qp, kp, vp = _qkv(r + d, b, 16, h, kn.shape[2], d)
        alpha, beta = jnp.full((h,), 1.3), jnp.full((kn.shape[2],), 1.1)
        _, s, z, c_k = kops.lln_prefill(qp, kp, vp, alpha, beta, chunk=8)
        st = ca.LLNDecodeState(lln=core_lln.LLNState(s=s, z=z, c_k=c_k),
                               tail_k=tail_k, tail_v=tail_v, pos=pos)
        beta_h = jnp.repeat(beta, r)
        mask = jnp.asarray(self.ROW_MASK)
        kw = dict(row_mask=mask, defer_tail=defer_tail)
        out, st_new = ca.decode_lln_chunk(st, q, kn, vn, alpha, beta_h, **kw)
        lln_out, st_lln = ca.decode_lln_chunk(st, q, kn, vn, alpha, beta_h,
                                              impl="lln", **kw)
        ref = 0.5 * (lln_out.astype(jnp.float32)
                     + ca._diag_chunk(q, kn, vn, tail_k, tail_v, pos))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        for a, e in zip(jax.tree.leaves(st_new), jax.tree.leaves(st_lln)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(e))
        np.testing.assert_array_equal(np.asarray(st_new.pos),
                                      np.asarray(pos + mask))


def _tiny_cfg(impl, r, **kw):
    h = 4
    return ArchConfig(
        name=f"serve-test-r{r}", family="dense", n_layers=2, d_model=64,
        n_heads=h, n_kv_heads=h // r, d_ff=128, vocab=128, head_dim=16,
        attn_impl=impl, diag_block=8, lln_chunk=8, softmax_chunk=16,
        lln_fixed_ab=2.1 if impl != "softmax" else 0.0,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True, **kw)


class TestEndToEnd:
    def test_greedy_decode_matches_full_forward(self, impl_gqa_cell):
        """Greedy prefill + decode logits == teacher-forced full-sequence
        forward logits (fixed alpha/beta so prompt-time stats match)."""
        from repro.models.layers import logits_from_hidden
        impl, r = impl_gqa_cell
        cfg = _tiny_cfg(impl, r)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        n_prompt, n_gen = 16, 5
        total = n_prompt + n_gen
        batch = synthetic_batch(cfg, batch=2, seq=total)
        full_h, _ = model.hidden(params, batch)
        head = params["embed"]["table"].T
        ref_logits = logits_from_hidden(head, full_h, cfg.cdtype, 0.0)

        prompt_batch = dict(batch)
        prompt_batch["inputs"] = batch["inputs"][:, :n_prompt]
        logits, caches = model.prefill(params, prompt_batch, total)
        last = logits[:, -1] if logits.ndim == 3 else logits
        np.testing.assert_allclose(
            np.asarray(last), np.asarray(ref_logits[:, n_prompt - 1]),
            atol=2e-3)
        for t in range(n_gen - 1):
            tok = batch["inputs"][:, n_prompt + t]
            logits, caches = model.decode(params, caches, tok,
                                          jnp.asarray(n_prompt + t,
                                                      jnp.int32))
            np.testing.assert_allclose(
                np.asarray(logits),
                np.asarray(ref_logits[:, n_prompt + t]), atol=2e-3,
                err_msg=f"step {t}")

    @pytest.mark.parametrize("impl", ["softmax", "lln_diag"])
    def test_chunked_model_decode_matches_sequential(self, impl):
        """model.decode over a (B, T) token chunk == T single-token calls."""
        cfg = _tiny_cfg(impl, 2)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        n_prompt, t = 16, 6
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
                                       np.asarray(lg), rtol=2e-4, atol=2e-4,
                                       err_msg=f"token {i}")

    def test_scanned_generate_matches_loop(self):
        """ServeSetup.make_generate (one lax.scan dispatch) produces the
        same greedy tokens as the per-token decode_fn loop."""
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_serve_setup
        cfg = _tiny_cfg("lln_diag", 2)
        model = build_model(cfg)
        n_prompt, steps = 16, 6
        mesh = make_mesh((1, 1), ("data", "model"))
        shape = ShapeSpec("t", n_prompt + steps + 1, 2, "decode")
        with mesh:
            setup = make_serve_setup(cfg, shape, mesh, multi_pod=False)
            params = model.init(jax.random.PRNGKey(2))
            batch = synthetic_batch(cfg, 2, n_prompt + steps + 1,
                                    text_seq=n_prompt)
            pos0 = jnp.asarray(n_prompt, jnp.int32)

            logits, caches = setup.prefill_fn(params, batch)
            tok = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits,
                             -1).astype(jnp.int32)
            tok0 = tok
            loop_toks = []
            for i in range(steps):
                logits, caches = setup.decode_fn(params, caches, tok,
                                                 pos0 + i)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                loop_toks.append(np.asarray(tok))

            _, caches = setup.prefill_fn(params, batch)
            gen_fn = setup.make_generate(steps, 0.0)
            toks, _ = gen_fn(params, caches, tok0, pos0,
                             jax.random.PRNGKey(0))
            np.testing.assert_array_equal(np.asarray(toks),
                                          np.stack(loop_toks, 1))
