"""jit'd public wrappers around the Pallas kernels.

Responsibilities:
* layout:  (B, N, H, D) model convention  <->  (B*H, N, D) kernel convention;
* LLN pre-scaling + stabilization:  qs = alpha*q - c_q, ks = beta*k - c_k
  (global per batch*head constants — exactly invariant, see core/lln.py);
* GQA ratio r = H // G threaded to the kernels' BlockSpec index maps
  (repeated KV is never materialized);
* interpret-mode dispatch (CPU container -> interpret=True; TPU -> compiled);
* custom_vjp: Pallas forward AND a fused analytic backward (lln_backward.py
  / block_diag.py).  The forward saves the pre-scaled (qs, ks), the kernel-
  layout v, the output and the per-row normalizer ``den`` as residuals, so
  the backward never recomputes the stabilization constants or the feature
  maps' normalizers; GQA dK/dV is segment-summed over the ``h // r`` index
  map without materializing repeated KV.  On compiled backends the backward
  runs the Pallas kernels; under interpret mode it runs their lax.scan
  twins (same math/residuals — see lln_backward.py docstring).  The legacy
  jax.vjp-through-the-reference backward remains as (a) the fallback for
  ragged sequence lengths (n % chunk != 0, same static dispatch as the
  forward) and (b) an explicit ``pallas_bwd=False`` escape used by
  ``benchmarks/bench_train_step.py`` to measure the speedup.
* device scopes: every kernel call runs inside a ``jax.named_scope`` that
  names it on every backend (``lln_prefill``, ``lln_decode``, ``lln_bidir``,
  ``lln_bidir_bwd``, ``block_diag``), so a profiler trace or the compiled
  module's ``op_name`` metadata finds it after a refactor.

alpha/beta are calibration constants (moment matching) — non-differentiable
by construction; gradients w.r.t. them are zero.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import lln as core_lln
from repro.core import loglinear as core_loglin
from repro.core.diag import block_diag_attn as core_diag
from . import ref as kref
from . import registry
from .block_diag import block_diag_bwd_pallas, block_diag_pallas
from .lln_attention import (lln_bidir_pallas, lln_causal_pallas,
                            lln_decode_pallas, lln_diag_fused_pallas)
from .loglinear import loglin_causal_pallas
from .lln_backward import (lln_bidir_bwd_pallas, lln_bidir_bwd_scan,
                           lln_causal_bwd_pallas, lln_causal_bwd_scan,
                           lln_diag_fused_bwd_pallas,
                           lln_diag_fused_bwd_scan, block_diag_bwd_scan)
from .ssd import ssd_pallas


def _interpret(flag: Optional[bool]) -> bool:
    if flag is not None:
        return flag
    return jax.default_backend() == "cpu"


def _dispatch(backend: str, interpret: Optional[bool], *, ragged: bool,
              cpu_twin: str, ragged_kind: str = "ref") -> tuple[str, bool]:
    """Resolve (kind, interpret) for one op call.

    ``backend='auto'`` reproduces the historical per-op dispatch (honouring
    the legacy ``interpret=`` override): ragged lengths fall back to
    ``ragged_kind``, interpret mode runs ``cpu_twin``, compiled backends run
    the Pallas kernel.  Explicit backends go through
    :func:`repro.kernels.registry.resolve`.
    """
    if backend == "auto":
        if ragged:
            return ragged_kind, False
        ip = _interpret(interpret)
        return (cpu_twin if ip else "pallas"), ip
    res = registry.resolve(backend, ragged=ragged, cpu_twin=cpu_twin)
    return res.kind, res.interpret


# Interpret-mode Pallas pays a full block copy per grid step, so the fused
# backward dispatches to the lax.scan twins there (same math, same
# residuals); compiled backends run the Pallas kernels.  Tests flip this to
# exercise the kernel path end-to-end on CPU.
FORCE_KERNEL_BWD = False


def _kernel_bwd(interpret: Optional[bool]) -> bool:
    return FORCE_KERNEL_BWD or not _interpret(interpret)


def _to_kernel(t: jnp.ndarray) -> jnp.ndarray:
    """(B, N, H, D) -> (B*H, N, D)."""
    b, n, h, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b * h, n, d)


def _from_kernel(t: jnp.ndarray, b: int) -> jnp.ndarray:
    bh, n, d = t.shape
    return t.reshape(b, bh // b, n, d).transpose(0, 2, 1, 3)


def _bcast_heads(p, heads: int) -> jnp.ndarray:
    """Scalar -> (heads,); (heads,) and per-row (B, heads) pass through."""
    p = jax.lax.stop_gradient(jnp.asarray(p, jnp.float32))
    if p.ndim == 0:
        p = jnp.broadcast_to(p, (heads,))
    return p


def _row_head_bcast(p: jnp.ndarray) -> jnp.ndarray:
    """Broadcast (H,) or per-row (B, H) calibration over (B, N, H, D)."""
    return p[:, None, :, None] if p.ndim == 2 else p[None, None, :, None]


def _scaled_stabilized(q, k, alpha, beta, with_const: bool = False):
    """Return (qs, ks) in kernel layout plus the broadcast (alpha, beta);
    fp32-safe exponents.  alpha/beta may be scalar, per-head (H,)/(G,) or
    per-row (B, H)/(B, G) (continuous-batching calibration).  ``with_const``
    appends the key stabilization constant ``c_k`` (B, 1, G, 1) — the
    decode state's reference constant."""
    alpha = _bcast_heads(alpha, q.shape[2])
    beta = _bcast_heads(beta, k.shape[2])
    aq = q.astype(jnp.float32) * _row_head_bcast(alpha)
    bk = k.astype(jnp.float32) * _row_head_bcast(beta)
    c_q = jax.lax.stop_gradient(jnp.max(aq, axis=(1, 3), keepdims=True))
    c_k = jax.lax.stop_gradient(jnp.max(bk, axis=(1, 3), keepdims=True))
    out = (_to_kernel(aq - c_q), _to_kernel(bk - c_k), alpha, beta)
    return out + (c_k,) if with_const else out


def _dtype_tag(t: jnp.ndarray) -> jnp.ndarray:
    """Zero-size carrier so the backward can recover a primal dtype from
    residuals (residual leaves must be arrays, not dtypes)."""
    return jnp.zeros((0,), t.dtype)


def _zero_ab(alpha, beta):
    zero_a = jnp.zeros_like(jnp.asarray(alpha, jnp.float32))
    zero_b = jnp.zeros_like(jnp.asarray(beta, jnp.float32))
    return zero_a, zero_b


# ---------------------------------------------------------------------------
# LLN attention.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def lln_attention(q, k, v, alpha, beta, causal: bool = True,
                  chunk: int = 256, interpret: Optional[bool] = None,
                  pallas_bwd: bool = True, backend: str = "auto"):
    """LLN attention (paper eq. 8) via Pallas — the training entry point.

    Args:
      q: (B, N, H, D); k/v: (B, N, G, D[v]) with G | H — GQA ratio
        ``r = H // G`` is threaded to the kernels' BlockSpec index maps, so
        repeated KV is never materialized.  Any float dtype; output is
        ``v.dtype``, internal exponents/accumulators fp32.
      alpha/beta: moment-matching calibration, scalar or per-head
        ((H,) / (G,)); non-differentiable by construction (zero gradients).
      chunk: block size of the causal scan; ``N % chunk != 0`` falls back
        to the jnp reference (``core.lln``) — same math, ragged-safe.

    Backend: ``backend='auto'`` (the default) keeps the historical
    dispatch — compiled (TPU) runs the Pallas forward and, under
    ``custom_vjp``, the fused Pallas backward (kernels/lln_backward.py);
    interpret mode (CPU container) runs the forward kernel interpreted and
    the backward's chunked ``lax.scan`` twins.  Explicit
    ``backend='pallas' | 'scan' | 'ref'`` forces the Pallas kernel
    (interpreted on CPU), the core chunked-scan reference, or the quadratic
    oracle (kernels/ref.py) respectively — see kernels/registry.py.
    ``pallas_bwd=False`` forces the chunked-jnp reference backward (the
    pre-fused behaviour) — kept for benchmarking and debugging.
    """
    return _lln_fwd_impl(q, k, v, alpha, beta, causal, chunk, interpret,
                         backend)


def _lln_fwd_impl(q, k, v, alpha, beta, causal, chunk, interpret,
                  backend="auto"):
    b, n, h, _ = q.shape
    g = k.shape[2]
    # The historical ragged fallback IS the core chunked scan ("scan").
    kind, ip = _dispatch(backend, interpret, ragged=bool(n % chunk),
                         cpu_twin="pallas", ragged_kind="scan")
    if kind == "scan":
        return _lln_ref(q, k, v, alpha, beta, causal, chunk)
    if kind == "ref":
        return _lln_quad_ref(q, k, v, alpha, beta, causal)
    qs, ks, _, _ = _scaled_stabilized(q, k, alpha, beta)
    vk = _to_kernel(v)
    if causal:
        out = lln_causal_pallas(qs, ks, vk, r=h // g, blk=chunk,
                                interpret=ip)
    else:
        with jax.named_scope("lln_bidir"):
            out = lln_bidir_pallas(qs, ks, vk, r=h // g, blk=chunk,
                                   interpret=ip)
    return _from_kernel(out, b)


def _lln_ref(q, k, v, alpha, beta, causal, chunk):
    h = q.shape[2]
    g = k.shape[2]
    kf = k if g == h else jnp.repeat(k, h // g, axis=2)
    vf = v if g == h else jnp.repeat(v, h // g, axis=2)
    beta = jnp.asarray(beta, jnp.float32)
    if beta.ndim and beta.shape[-1] == g and g != h:
        beta = jnp.repeat(beta, h // g, axis=-1)
    if causal:
        out = core_lln.lln_causal(q, kf, vf, alpha, beta, chunk=chunk)
    else:
        out = core_lln.lln_bidir(q, kf, vf, alpha, beta)
    # The Pallas path emits v.dtype; pin the fallback to the same so jit'd
    # callers don't recompile (or silently upcast) with the sequence length.
    return out.astype(v.dtype)


def _lln_quad_ref(q, k, v, alpha, beta, causal):
    """Quadratic-form oracle (kernels/ref.py) — the ``backend='ref'``
    target for the training forward: materializes the full (masked) score
    matrix, O(N^2) memory."""
    b, _, h, _ = q.shape
    g = k.shape[2]
    qs, ks, _, _ = _scaled_stabilized(q, k, alpha, beta)
    vk = _to_kernel(v)
    fn = kref.lln_causal_ref if causal else kref.lln_bidir_ref
    return _from_kernel(fn(qs, ks, vk, r=h // g), b).astype(v.dtype)


def _lln_vjp_fwd(q, k, v, alpha, beta, causal, chunk, interpret, pallas_bwd,
                 backend="auto"):
    n, h = q.shape[1], q.shape[2]
    g = k.shape[2]
    if n % chunk or not pallas_bwd or backend in ("scan", "ref"):
        out = _lln_fwd_impl(q, k, v, alpha, beta, causal, chunk, interpret,
                            backend)
        return out, {"ref": (q, k, v, alpha, beta)}
    b = q.shape[0]
    qs, ks, alpha_b, beta_b = _scaled_stabilized(q, k, alpha, beta)
    vk = _to_kernel(v)
    ip = True if backend == "pallas" and registry.on_cpu() \
        else _interpret(interpret)
    if causal:
        out_k, den = lln_causal_pallas(qs, ks, vk, r=h // g, blk=chunk,
                                       interpret=ip, return_res=True)
        s = z = None
    else:
        with jax.named_scope("lln_bidir"):
            out_k, s, z, den = lln_bidir_pallas(qs, ks, vk, r=h // g,
                                                blk=chunk, interpret=ip,
                                                return_res=True)
    res = {"pallas": (qs, ks, vk, out_k, den, s, z, alpha_b, beta_b,
                      _dtype_tag(q), _dtype_tag(k), _dtype_tag(v),
                      jnp.asarray(alpha, jnp.float32),
                      jnp.asarray(beta, jnp.float32))}
    return _from_kernel(out_k, b), res


def _lln_vjp_bwd(causal, chunk, interpret, pallas_bwd, backend, res, g_out):
    if "ref" in res:
        q, k, v, alpha, beta = res["ref"]
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _lln_ref(q_, k_, v_, alpha, beta, causal,
                                        chunk), q, k, v)
        dq, dk, dv = vjp(g_out)
        return (dq, dk, dv) + _zero_ab(alpha, beta)
    (qs, ks, vk, out_k, den, s, z, alpha_b, beta_b,
     tq, tk, tv, alpha0, beta0) = res["pallas"]
    b = g_out.shape[0]
    r = (qs.shape[0] // b) // (ks.shape[0] // b)
    gk = _to_kernel(g_out)
    ip = _interpret(interpret)
    if causal:
        if _kernel_bwd(interpret):
            dqs, dks, dvk = lln_causal_bwd_pallas(qs, ks, vk, gk, out_k,
                                                  den, r=r, blk=chunk,
                                                  interpret=ip)
        else:
            dqs, dks, dvk = lln_causal_bwd_scan(qs, ks, vk, gk, out_k, den,
                                                r=r, blk=chunk)
    else:
        with jax.named_scope("lln_bidir_bwd"):
            if _kernel_bwd(interpret):
                dqs, dks, dvk = lln_bidir_bwd_pallas(
                    qs, ks, vk, gk, out_k, den, s, z, r=r, blk=chunk,
                    interpret=ip)
            else:
                dqs, dks, dvk = lln_bidir_bwd_scan(qs, ks, vk, gk, out_k,
                                                   den, s, z, r=r, blk=chunk)
    # Chain rule through qs = alpha*q - stop_grad(c_q) (and same for k);
    # _row_head_bcast handles per-head (H,) and per-row (B, H) calibration.
    dq = (_from_kernel(dqs, b) * _row_head_bcast(alpha_b)).astype(tq.dtype)
    dk = (_from_kernel(dks, b) * _row_head_bcast(beta_b)).astype(tk.dtype)
    dv = _from_kernel(dvk, b).astype(tv.dtype)
    return dq, dk, dv, jnp.zeros_like(alpha0), jnp.zeros_like(beta0)


lln_attention.defvjp(_lln_vjp_fwd, _lln_vjp_bwd)


# ---------------------------------------------------------------------------
# Serving entry points: state-emitting prefill + chunked multi-token decode.
# Inference-only (no custom_vjp); same three-way dispatch as the training
# forward: Pallas on compiled backends, chunked lax.scan twin under
# interpret mode (CPU container), jnp reference for ragged lengths.
# ---------------------------------------------------------------------------

@jax.named_scope("lln_prefill")
def lln_prefill(q, k, v, alpha, beta, chunk: int = 256,
                interpret: Optional[bool] = None, backend: str = "auto"):
    """Causal LLN prefill emitting outputs AND the decode state in one pass.

    q: (B,N,H,D); k/v: (B,N,G,D[v]) — GQA via the kernels' ``h // r`` index
    maps, repeated KV never materialized.  Returns ``(out, s, z, c_k)``:
    out (B,N,H,Dv); s (B,H,D,Dv) fp32; z (B,H,D) fp32; c_k (B,1,H,1) fp32 —
    exactly the ``core.lln.LLNState`` layout the decode cache stores (state
    per query head: GQA groups share values, matching the H-head cache).

    ``backend``: ``auto`` (historical dispatch — Pallas compiled, scan twin
    on CPU, jnp reference for ragged lengths) | ``pallas`` | ``scan`` |
    ``ref`` (the seed two-pass jnp path, ``core/lln.py:prefill``).
    """
    b, n, h, _ = q.shape
    g = k.shape[2]
    kind, ip = _dispatch(backend, interpret, ragged=bool(n % chunk),
                         cpu_twin="scan")
    if kind == "ref":
        return _lln_prefill_ref(q, k, v, alpha, beta, chunk)
    qs, ks, _, _, c_k = _scaled_stabilized(q, k, alpha, beta, with_const=True)
    vk = _to_kernel(v)
    if kind == "scan":
        out_k, s, z = _lln_prefill_scan(qs, ks, vk, r=h // g, blk=chunk)
    else:
        out_k, s, z = lln_causal_pallas(qs, ks, vk, r=h // g, blk=chunk,
                                        interpret=ip, return_state=True)
    s = s.reshape(b, h, *s.shape[1:])                  # (B, H, D, Dv)
    z = z.reshape(b, h, z.shape[-1])                   # (B, H, D)
    c_kh = jnp.repeat(c_k, h // g, axis=2) if g != h else c_k
    return _from_kernel(out_k, b), s, z, c_kh


def _lln_prefill_ref(q, k, v, alpha, beta, chunk):
    """Ragged-length fallback: the jnp causal scan (whose final carry is the
    state — see core/lln.py:prefill) over repeated KV."""
    h, g = q.shape[2], k.shape[2]
    kf = k if g == h else jnp.repeat(k, h // g, axis=2)
    vf = v if g == h else jnp.repeat(v, h // g, axis=2)
    beta = jnp.asarray(beta, jnp.float32)
    if beta.ndim and beta.shape[-1] == g and g != h:
        beta = jnp.repeat(beta, h // g, axis=-1)
    out, st = core_lln.prefill(q, kf, vf, alpha, beta, chunk=chunk)
    return out.astype(v.dtype), st.s, st.z, st.c_k


def _lln_prefill_scan(qs, ks, vk, *, r: int, blk: int):
    """Chunked lax.scan twin of the state-emitting causal kernel (kernel
    layout, GQA via a (BG, R) head split — no repeated KV)."""
    bh, n, d = qs.shape
    bg, dv = ks.shape[0], vk.shape[-1]
    nc = n // blk
    fq = jnp.exp(qs.astype(jnp.float32)).reshape(bg, r, nc, blk, d) \
        .transpose(2, 0, 1, 3, 4)                      # (nc, BG, R, blk, D)
    fk = jnp.exp(ks.astype(jnp.float32)).reshape(bg, nc, blk, d) \
        .transpose(1, 0, 2, 3)                         # (nc, BG, blk, D)
    vf = vk.astype(jnp.float32).reshape(bg, nc, blk, dv).transpose(1, 0, 2, 3)
    causal = jnp.tril(jnp.ones((blk, blk), jnp.float32))

    def step(carry, xs):
        s, z = carry                                   # (BG,D,Dv), (BG,D)
        cq, ck, cv = xs
        scores = jnp.einsum("grid,gjd->grij", cq, ck) * causal
        intra = jnp.einsum("grij,gjv->griv", scores, cv)
        intra_z = jnp.sum(scores, axis=-1)
        inter = jnp.einsum("grid,gdv->griv", cq, s)
        inter_z = jnp.einsum("grid,gd->gri", cq, z)
        out = (intra + inter) / (intra_z + inter_z + 1e-6)[..., None]
        s = s + jnp.einsum("gjd,gjv->gdv", ck, cv)
        z = z + jnp.sum(ck, axis=1)
        return (s, z), out

    s0 = jnp.zeros((bg, d, dv), jnp.float32)
    z0 = jnp.zeros((bg, d), jnp.float32)
    (s, z), out = jax.lax.scan(step, (s0, z0), (fq, fk, vf))
    out = out.transpose(1, 2, 0, 3, 4).reshape(bh, n, dv).astype(vk.dtype)
    s = jnp.repeat(s, r, axis=0) if r != 1 else s      # group state -> H rows
    z = jnp.repeat(z, r, axis=0) if r != 1 else z
    return out, s, z[:, None, :]


@jax.named_scope("block_diag")
def block_diag_fwd(q, k, v, block: int = 256, causal: bool = True,
                   interpret: Optional[bool] = None, backend: str = "auto"):
    """Inference-only block-diagonal softmax with the serving dispatch:
    Pallas kernel on compiled backends, a GQA-aware grouped-einsum twin
    under interpret mode (no repeated KV either way), jnp reference for
    ragged lengths; explicit ``backend=pallas|scan|ref`` forces one path
    (kernels/registry.py).  Training keeps the ``block_diag_attention``
    custom_vjp entry; this is the prefill path of the §4.2 hybrid."""
    b, n, h, _ = q.shape
    g = k.shape[2]
    kind, ip = _dispatch(backend, interpret, ragged=bool(n % block),
                         cpu_twin="scan")
    if kind == "ref":
        return _diag_ref(q, k, v, block, causal)
    if kind == "scan":
        return _block_diag_twin(q, k, v, block, causal)
    out = block_diag_pallas(_to_kernel(q), _to_kernel(k), _to_kernel(v),
                            r=h // g, blk=block, causal=causal,
                            interpret=ip)
    return _from_kernel(out, b)


def _block_diag_twin(q, k, v, block, causal):
    """Grouped-einsum block-diag softmax: heads split (G, R) so the R query
    heads sharing a kv head contract against it directly."""
    b, n, h, d = q.shape
    g, dv = k.shape[2], v.shape[-1]
    r = h // g
    nb = n // block
    scale = d ** -0.5
    qb = q.reshape(b, nb, block, g, r, d).astype(jnp.float32) * scale
    kb = k.reshape(b, nb, block, g, d).astype(jnp.float32)
    vb = v.reshape(b, nb, block, g, dv).astype(jnp.float32)
    s = jnp.einsum("bnigrd,bnjgd->bngrij", qb, kb)
    if causal:
        tri = jnp.tril(jnp.ones((block, block), jnp.bool_))
        s = jnp.where(tri[None, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngrij,bnjgv->bnigrv", p, vb)
    return out.reshape(b, n, h, dv).astype(v.dtype)


@jax.named_scope("lln_decode")
def lln_decode_chunk(state, q, k, v, alpha, beta,
                     interpret: Optional[bool] = None,
                     row_mask: Optional[jnp.ndarray] = None,
                     backend: str = "auto",
                     commit_len: Optional[jnp.ndarray] = None,
                     renorm: Optional[float] = None):
    """Advance an ``LLNState`` over T new tokens in one dispatch.

    Args:
      state: ``core.lln.LLNState`` — ``s`` (B,H,D,Dv) fp32, ``z`` (B,H,D)
        fp32, ``c_k`` (B,1,H,1) fp32 reference stabilization constant.
      q: (B,T,H,D); k/v: (B,T,G,D[v]) — any dtype (cast to fp32 inside);
        GQA ratio ``r = H // G``: the kernel contracts each query head
        against its group's kv head via the grid index map, repeated KV is
        never materialized (compiled path).
      alpha/beta: calibration constants — scalar, per-head (H,)/(G,), or
        per-row (B, H)/(B, G) for continuous batching.  An (H,)-shaped beta
        that is not a group-uniform repeat is group-mean-pooled to (G,)
        (the ``batch_alpha_beta`` convention) identically on every backend.
      row_mask: optional (B,) bool — rows where it is False keep their old
        ``(s, z, c_k)`` exactly (masked rows must not advance state; their
        outputs are garbage and must be discarded by the caller).
      commit_len: optional per-row (B,) int32 in [0, T] — the speculative
        partial-commit contract: all T positions are scored, but only
        tokens ``j < commit_len[b]`` fold into ``(s, z, c_k)`` (the
        reference constant advances over committed keys only;
        ``commit_len=0`` ≡ ``row_mask=False``, ``commit_len=T`` ≡ a plain
        decode).  On the Pallas path the kernel still scores the full
        chunk; the committed fold is the cheap O(T d^2) jnp einsum below.
      renorm: optional drift-renormalization threshold on the carried
        ``max_d z`` magnitude (``core.lln.decode_chunk``).  Applied with
        identical semantics on every backend: the non-Pallas twins get it
        from the core, the Pallas path applies the same group-level shift
        to its folded state below.  Never fires for masked or
        ``commit_len=0`` rows.

    Returns ``(out (B,T,H,Dv) in v.dtype, new LLNState)``.

    Backend dispatch: one Pallas kernel launch (grid over B*H, T padded to
    a sublane multiple with NEG_INF keys so padded Phi(k) = 0) after a
    single group-level max-rescale of the carried state on compiled
    backends; the jnp twin ``core.lln.decode_chunk`` under interpret mode
    (the CPU container).  Both equal T sequential ``decode_step`` calls.
    ``backend='pallas'`` forces the kernel (interpreted on CPU);
    ``'scan'``/``'ref'`` force the jnp twin (they coincide for decode —
    the twin IS the reference).
    """
    from repro.core.lln import LLNState

    b, t, h, d = q.shape
    g = k.shape[2]
    kind, ip = _dispatch(backend, interpret, ragged=False, cpu_twin="ref")
    # Per-G-head beta shared by BOTH dispatch branches: an (H,)/(B,H) beta
    # that is not a group-uniform repeat is group-mean-pooled (the
    # batch_alpha_beta convention, cf. multi_head_attention) — identically
    # on every backend.
    beta_b = jnp.asarray(beta, jnp.float32)
    if beta_b.ndim and beta_b.shape[-1] == h and g != h:
        beta_b = beta_b.reshape(beta_b.shape[:-1] + (g, h // g)).mean(axis=-1)
    beta_b = _bcast_heads(beta_b, g)
    if kind != "pallas":
        kf = k if g == h else jnp.repeat(k, h // g, axis=2)
        vf = v if g == h else jnp.repeat(v, h // g, axis=2)
        beta_h = jnp.repeat(beta_b, h // g, axis=-1) if g != h else beta_b
        return core_lln.decode_chunk(state, q, kf, vf, alpha, beta_h,
                                     row_mask=row_mask,
                                     commit_len=commit_len,
                                     renorm=renorm)
    alpha_b = _bcast_heads(alpha, h)
    aq = q.astype(jnp.float32) * _row_head_bcast(alpha_b)
    bk = k.astype(jnp.float32) * _row_head_bcast(beta_b)
    c_q = jax.lax.stop_gradient(jnp.max(aq, axis=(1, 3), keepdims=True))
    # Group-level new reference constant: max of the group's carried c_k and
    # the chunk keys; each query head rescales from its own old constant.
    r = h // g
    c_old_g = jnp.max(state.c_k.reshape(b, 1, g, r, 1), axis=3)
    c_bk = jax.lax.stop_gradient(jnp.max(bk, axis=(1, 3), keepdims=True))
    c_new_g = jnp.maximum(c_old_g, c_bk)               # (B,1,G,1)
    c_new_h = jnp.repeat(c_new_g, r, axis=2) if r != 1 else c_new_g
    rescale = jnp.exp(state.c_k - c_new_h)[:, 0, :, 0]  # (B,H)
    s0 = (state.s * rescale[..., None, None]).reshape(b * h, d, -1)
    z0 = (state.z * rescale[..., None]).reshape(b * h, 1, d)

    # Pad T to a sublane multiple; padded keys at NEG_INF => Phi(k) = 0.
    tp = -(-t // 16) * 16
    qs = _to_kernel(aq - c_q)
    ks = _to_kernel(bk - c_new_g)
    vk = _to_kernel(v)
    if tp != t:
        qs = jnp.pad(qs, ((0, 0), (0, tp - t), (0, 0)))
        ks = jnp.pad(ks, ((0, 0), (0, tp - t), (0, 0)),
                     constant_values=-1e30)
        vk = jnp.pad(vk, ((0, 0), (0, tp - t), (0, 0)))
    out_k, s1, z1 = lln_decode_pallas(qs, ks, vk, s0, z0, r=r,
                                      interpret=ip)
    out = _from_kernel(out_k[:, :t], b)
    if commit_len is not None:
        # Partial commit: the kernel scored the full chunk (and its s1/z1
        # folded every key — discarded); refold only the accepted prefix,
        # with the reference constant advanced over committed keys only.
        cl = core_lln.commit_lengths(commit_len, row_mask, t)
        cmask = jnp.arange(t)[None, :] < cl[:, None]             # (B, T)
        bk_c = jnp.where(cmask[:, :, None, None], bk, -jnp.inf)
        c_com_g = jnp.maximum(c_old_g, jax.lax.stop_gradient(
            jnp.max(bk_c, axis=(1, 3), keepdims=True)))          # (B,1,G,1)
        c_com_h = jnp.repeat(c_com_g, r, axis=2) if r != 1 else c_com_g
        resc = jnp.exp(state.c_k - c_com_h)[:, 0, :, 0]          # (B,H)
        fk_c = jnp.exp(bk_c - c_com_g)                # (B,T,G,D), 0 beyond
        add_s = jnp.einsum("bjgd,bjgv->bgdv", fk_c, v.astype(jnp.float32))
        add_z = jnp.sum(fk_c, axis=1)                            # (B,G,D)
        if r != 1:
            add_s = jnp.repeat(add_s, r, axis=1)
            add_z = jnp.repeat(add_z, r, axis=1)
        s_new = state.s * resc[..., None, None] + add_s
        z_new = state.z * resc[..., None] + add_z
        c_new_h = c_com_h
    else:
        s_new = s1.reshape(b, h, d, -1)
        z_new = z1.reshape(b, h, d)
    log_scale = state.log_scale
    if renorm is not None and renorm > 0.0:
        # Same drift renorm as core.lln.decode_chunk: raise the reference
        # constant by delta = ln(max_d z) past the threshold, scale (s, z)
        # by exp(-delta).  Gated on rows that folded at least one token.
        zmax = jax.lax.stop_gradient(jnp.max(z_new, axis=-1))    # (B,H)
        if commit_len is not None:
            folded = (cl > 0)[:, None]
        elif row_mask is not None:
            folded = row_mask[:, None]
        else:
            folded = jnp.ones((b, 1), bool)
        delta = jnp.where(folded & (zmax > renorm),
                          jnp.log(jnp.maximum(zmax, 1e-6)), 0.0)
        scale = jnp.exp(-delta)
        s_new = s_new * scale[..., None, None]
        z_new = z_new * scale[..., None]
        c_new_h = c_new_h + delta[:, None, :, None]
        if log_scale is not None:
            log_scale = log_scale + delta
    if row_mask is not None:
        keep = row_mask
        s_new = jnp.where(keep[:, None, None, None], s_new, state.s)
        z_new = jnp.where(keep[:, None, None], z_new, state.z)
        c_new_h = jnp.where(keep[:, None, None, None], c_new_h, state.c_k)
        if log_scale is not None:
            log_scale = jnp.where(keep[:, None], log_scale, state.log_scale)
    return out, LLNState(s=s_new, z=z_new, c_k=c_new_h,
                         log_scale=log_scale)


def lln_commit_chunk(state, k, v, beta,
                     interpret: Optional[bool] = None,
                     row_mask: Optional[jnp.ndarray] = None,
                     backend: str = "auto",
                     commit_len: Optional[jnp.ndarray] = None,
                     renorm: Optional[float] = None):
    """Fold a chunk's accepted prefix into an ``LLNState`` without scoring.

    The commit half of :func:`lln_decode_chunk` — the single-pass
    speculative-verify primitive.  A ``commit_len=0`` verify pass scores
    the draft chunk and leaves the state untouched; this folds the
    accepted prefix from the (k, v) residuals with the cheap O(T d^2)
    einsum, bit-identical per backend to re-running
    :func:`lln_decode_chunk` with the final ``commit_len`` (the pallas
    kind runs the same group-level jnp fold the kernel path uses; scan/ref
    run the jnp core twin at H heads).  k/v: (B,T,G,D[v]); beta as in
    :func:`lln_decode_chunk`.  Returns the new ``LLNState``.
    """
    from repro.core.lln import LLNState

    b, t, g, _ = k.shape
    h = state.s.shape[1]
    kind, _ = _dispatch(backend, interpret, ragged=False, cpu_twin="ref")
    beta_b = jnp.asarray(beta, jnp.float32)
    if beta_b.ndim and beta_b.shape[-1] == h and g != h:
        beta_b = beta_b.reshape(beta_b.shape[:-1] + (g, h // g)).mean(axis=-1)
    beta_b = _bcast_heads(beta_b, g)
    if kind != "pallas":
        kf = k if g == h else jnp.repeat(k, h // g, axis=2)
        vf = v if g == h else jnp.repeat(v, h // g, axis=2)
        beta_h = jnp.repeat(beta_b, h // g, axis=-1) if g != h else beta_b
        return core_lln.commit_chunk(state, kf, vf, beta_h,
                                     row_mask=row_mask,
                                     commit_len=commit_len, renorm=renorm)
    r = h // g
    bk = k.astype(jnp.float32) * _row_head_bcast(beta_b)
    c_old_g = jnp.max(state.c_k.reshape(b, 1, g, r, 1), axis=3)
    cl = core_lln.commit_lengths(
        commit_len if commit_len is not None
        else jnp.full((b,), t, jnp.int32), row_mask, t)
    cmask = jnp.arange(t)[None, :] < cl[:, None]                 # (B, T)
    bk_c = jnp.where(cmask[:, :, None, None], bk, -jnp.inf)
    c_com_g = jnp.maximum(c_old_g, jax.lax.stop_gradient(
        jnp.max(bk_c, axis=(1, 3), keepdims=True)))              # (B,1,G,1)
    c_com_h = jnp.repeat(c_com_g, r, axis=2) if r != 1 else c_com_g
    resc = jnp.exp(state.c_k - c_com_h)[:, 0, :, 0]              # (B,H)
    fk_c = jnp.exp(bk_c - c_com_g)                    # (B,T,G,D), 0 beyond
    add_s = jnp.einsum("bjgd,bjgv->bgdv", fk_c, v.astype(jnp.float32))
    add_z = jnp.sum(fk_c, axis=1)                                # (B,G,D)
    if r != 1:
        add_s = jnp.repeat(add_s, r, axis=1)
        add_z = jnp.repeat(add_z, r, axis=1)
    s_new = state.s * resc[..., None, None] + add_s
    z_new = state.z * resc[..., None] + add_z
    c_new_h = c_com_h
    log_scale = state.log_scale
    if renorm is not None and renorm > 0.0:
        zmax = jax.lax.stop_gradient(jnp.max(z_new, axis=-1))    # (B,H)
        folded = (cl > 0)[:, None]
        delta = jnp.where(folded & (zmax > renorm),
                          jnp.log(jnp.maximum(zmax, 1e-6)), 0.0)
        scale = jnp.exp(-delta)
        s_new = s_new * scale[..., None, None]
        z_new = z_new * scale[..., None]
        c_new_h = c_new_h + delta[:, None, :, None]
        if log_scale is not None:
            log_scale = log_scale + delta
    if row_mask is not None:
        keep = row_mask
        s_new = jnp.where(keep[:, None, None, None], s_new, state.s)
        z_new = jnp.where(keep[:, None, None], z_new, state.z)
        c_new_h = jnp.where(keep[:, None, None, None], c_new_h, state.c_k)
        if log_scale is not None:
            log_scale = jnp.where(keep[:, None], log_scale, state.log_scale)
    return LLNState(s=s_new, z=z_new, c_k=c_new_h, log_scale=log_scale)


# ---------------------------------------------------------------------------
# Log-linear (Fenwick multi-scale) LLN: full-sequence forward, state-
# emitting prefill and chunked decode/commit.  Inference-only entry points
# (the serving path); the scan/ref kinds are pure jnp and autodiff-able.
# ---------------------------------------------------------------------------

def _loglin_repeat(q, k, v, beta):
    """Model-layout fallback prep: repeated KV + (H,)-shaped beta."""
    h, g = q.shape[2], k.shape[2]
    kf = k if g == h else jnp.repeat(k, h // g, axis=2)
    vf = v if g == h else jnp.repeat(v, h // g, axis=2)
    beta = jnp.asarray(beta, jnp.float32)
    if beta.ndim and beta.shape[-1] == g and g != h:
        beta = jnp.repeat(beta, h // g, axis=-1)
    return kf, vf, beta


def loglin_attention(q, k, v, alpha, beta, causal: bool = True,
                     chunk: int = 256, num_scales: int = 4,
                     scale_decay: float = 0.5,
                     interpret: Optional[bool] = None,
                     backend: str = "auto"):
    """Full-sequence log-linear LLN attention (causal-only).

    Each query mixes a causal intra-granule term (weight 1) with the
    Fenwick bucket pyramid of its prefix: the granule holding key ``j``
    sits at level ``l`` of the pyramid at query time and scores at weight
    ``scale_decay ** l`` (see ``core/loglinear.py``).  ``num_scales=1``
    or ``scale_decay=1`` reduce exactly to plain :func:`lln_attention`.

    Dispatch: Pallas kernel (``kernels/loglinear.py``) on compiled
    backends; the core granule-``lax.scan`` under ``scan`` / interpret
    mode; the quadratic jnp oracle under ``ref`` (and for ragged
    lengths).
    """
    if not causal:
        raise ValueError("log_linear attention is causal-only")
    b, n, h, _ = q.shape
    g = k.shape[2]
    kind, ip = _dispatch(backend, interpret, ragged=bool(n % chunk),
                         cpu_twin="scan")
    if kind in ("ref", "scan"):
        kf, vf, beta_h = _loglin_repeat(q, k, v, beta)
        if kind == "ref":
            out = core_loglin.loglin_attention_ref(
                q, kf, vf, alpha, beta_h, granule=chunk,
                num_scales=num_scales, scale_decay=scale_decay)
        else:
            out, _ = core_loglin.prefill(
                q, kf, vf, alpha, beta_h, granule=chunk,
                num_scales=num_scales, scale_decay=scale_decay)
        return out.astype(v.dtype)
    qs, ks, _, _ = _scaled_stabilized(q, k, alpha, beta)
    out = loglin_causal_pallas(qs, ks, _to_kernel(v),
                               num_scales=num_scales,
                               scale_decay=scale_decay, r=h // g,
                               blk=chunk, interpret=ip)
    return _from_kernel(out, b)


def loglin_prefill(q, k, v, alpha, beta, chunk: int = 256,
                   num_scales: int = 4, scale_decay: float = 0.5,
                   interpret: Optional[bool] = None,
                   backend: str = "auto"):
    """Causal log-linear prefill emitting outputs AND the multi-scale
    decode state in one pass.

    Returns ``(out, s, z, c_k, sl, zl, cl)``: the open-bucket LLN state
    (``s``/``z``/``c_k`` exactly as :func:`lln_prefill` — holding the
    ragged tail past the last closed granule, empty for aligned N) plus
    the Fenwick bucket pyramid ``sl`` (B,L,H,D,Dv), ``zl`` (B,L,H,D),
    ``cl`` (B,L,H) fp32 — the ``core.loglinear.LogLinState`` layout.
    On the kernel/scan paths every bucket shares the global reference
    constant, so ``cl`` is the broadcast ``c_k``.
    """
    b, n, h, d = q.shape
    g, dv = k.shape[2], v.shape[-1]
    ls = num_scales
    kind, ip = _dispatch(backend, interpret, ragged=bool(n % chunk),
                         cpu_twin="scan")
    if kind == "ref":
        kf, vf, beta_h = _loglin_repeat(q, k, v, beta)
        out, st = core_loglin.prefill(q, kf, vf, alpha, beta_h,
                                      granule=chunk, num_scales=ls,
                                      scale_decay=scale_decay)
        return (out.astype(v.dtype), st.s, st.z, st.c_k,
                st.sl, st.zl, st.cl)
    qs, ks, _, _, c_k = _scaled_stabilized(q, k, alpha, beta,
                                           with_const=True)
    vk = _to_kernel(v)
    if kind == "scan":
        out_k, sl, zl = _loglin_prefill_scan(
            qs, ks, vk, r=h // g, blk=chunk, num_scales=ls,
            scale_decay=scale_decay)
    else:
        out_k, sl, zl = loglin_causal_pallas(
            qs, ks, vk, num_scales=ls, scale_decay=scale_decay,
            r=h // g, blk=chunk, interpret=ip, return_state=True)
        zl = zl[:, :, 0, :]                            # (BH, L, D)
    sl = sl.reshape(b, h, ls, d, dv).transpose(0, 2, 1, 3, 4)
    zl = zl.reshape(b, h, ls, d).transpose(0, 2, 1, 3)
    c_kh = jnp.repeat(c_k, h // g, axis=2) if g != h else c_k
    cl = jnp.broadcast_to(c_kh[:, 0, :, 0][:, None, :], (b, ls, h))
    s = jnp.zeros((b, h, d, dv), jnp.float32)
    z = jnp.zeros((b, h, d), jnp.float32)
    return _from_kernel(out_k, b), s, z, c_kh, sl, zl, cl


def _loglin_prefill_scan(qs, ks, vk, *, r: int, blk: int, num_scales: int,
                         scale_decay: float):
    """Chunked lax.scan twin of the state-emitting log-linear kernel
    (kernel layout, GQA via the (BG, R) head split — no repeated KV).
    All buckets share the global pre-stabilized reference, so the
    Fenwick carry-merge is pure adds and merged-out levels are zeroed."""
    bh, n, d = qs.shape
    bg, dv = ks.shape[0], vk.shape[-1]
    nc = n // blk
    ls = num_scales
    wv = jnp.asarray([float(scale_decay) ** l for l in range(ls)],
                     jnp.float32)
    fq = jnp.exp(qs.astype(jnp.float32)).reshape(bg, r, nc, blk, d) \
        .transpose(2, 0, 1, 3, 4)                      # (nc, BG, R, blk, D)
    fk = jnp.exp(ks.astype(jnp.float32)).reshape(bg, nc, blk, d) \
        .transpose(1, 0, 2, 3)                         # (nc, BG, blk, D)
    vf = vk.astype(jnp.float32).reshape(bg, nc, blk, dv).transpose(1, 0, 2, 3)
    causal = jnp.tril(jnp.ones((blk, blk), jnp.float32))

    def step(carry, xs):
        sl, zl = carry                                 # (BG,L,D,Dv),(BG,L,D)
        i, cq, ck, cv = xs
        s_eff = jnp.einsum("l,gldv->gdv", wv, sl)
        z_eff = jnp.einsum("l,gld->gd", wv, zl)
        scores = jnp.einsum("grid,gjd->grij", cq, ck) * causal
        intra = jnp.einsum("grij,gjv->griv", scores, cv)
        intra_z = jnp.sum(scores, axis=-1)
        inter = jnp.einsum("grid,gdv->griv", cq, s_eff)
        inter_z = jnp.einsum("grid,gd->gri", cq, z_eff)
        out = (intra + inter) / (intra_z + inter_z + 1e-6)[..., None]
        c_s = jnp.einsum("gjd,gjv->gdv", ck, cv)
        c_z = jnp.sum(ck, axis=1)
        for l in range(ls - 1):
            reach = (i & ((1 << l) - 1)) == ((1 << l) - 1)
            bit = ((i >> l) & 1) == 1
            mrg = reach & bit
            take = reach & ~bit
            old_s, old_z = sl[:, l], zl[:, l]
            sl = sl.at[:, l].set(jnp.where(
                take, c_s, jnp.where(mrg, jnp.zeros_like(old_s), old_s)))
            zl = zl.at[:, l].set(jnp.where(
                take, c_z, jnp.where(mrg, jnp.zeros_like(old_z), old_z)))
            c_s = jnp.where(mrg, c_s + old_s, c_s)
            c_z = jnp.where(mrg, c_z + old_z, c_z)
        if ls > 1:
            reach_top = (i & ((1 << (ls - 1)) - 1)) == ((1 << (ls - 1)) - 1)
            sl = sl.at[:, ls - 1].add(jnp.where(reach_top, c_s, 0.0))
            zl = zl.at[:, ls - 1].add(jnp.where(reach_top, c_z, 0.0))
        else:
            sl = sl.at[:, 0].add(c_s)
            zl = zl.at[:, 0].add(c_z)
        return (sl, zl), out

    sl0 = jnp.zeros((bg, ls, d, dv), jnp.float32)
    zl0 = jnp.zeros((bg, ls, d), jnp.float32)
    (sl, zl), out = jax.lax.scan(step, (sl0, zl0),
                                 (jnp.arange(nc), fq, fk, vf))
    out = out.transpose(1, 2, 0, 3, 4).reshape(bh, n, dv).astype(vk.dtype)
    sl = jnp.repeat(sl, r, axis=0) if r != 1 else sl   # group state -> H
    zl = jnp.repeat(zl, r, axis=0) if r != 1 else zl
    return out, sl, zl


def loglin_decode_chunk(state, q, k, v, alpha, beta, *,
                        pos, granule: int, num_scales: int,
                        scale_decay: float,
                        interpret: Optional[bool] = None,
                        row_mask: Optional[jnp.ndarray] = None,
                        backend: str = "auto",
                        commit_len: Optional[jnp.ndarray] = None,
                        renorm: Optional[float] = None):
    """Advance a ``core.loglinear.LogLinState`` over T new tokens.

    Same serving contract as :func:`lln_decode_chunk` (``row_mask`` rows
    bitwise inert, ``commit_len`` scores all T but folds the accepted
    prefix, ``renorm`` per-bucket drift guard) plus the multi-scale
    extras: per-row ``pos`` (B,) int32 — tokens already folded, which
    determines each row's bucket layout — and the Fenwick carry-merge
    when the chunk crosses a granule boundary.

    Backend dispatch: ``scan``/``ref``/interpret run the jnp core twin
    (the twin IS the reference, as for lln decode).  The ``pallas`` kind
    runs the committed fold as the same jnp ``core.loglinear._advance``
    (bitwise-identical state on every backend) and scores with TWO
    :func:`kernels.lln_attention.lln_decode_pallas` launches sharing one
    group-level reference: pass A masks keys at/past each row's granule
    boundary and carries the pyramid(n)+open aggregate as its ``s0``;
    pass B masks pre-boundary keys and carries the cascaded pyramid(n+1)
    aggregate; per-position outputs select between the two views.

    ``T > granule`` chunks are processed in granule-sized sub-chunks
    (full commit only — speculative drafts never exceed a granule).
    """
    b, t, h, d = q.shape
    g = k.shape[2]
    kind, ip = _dispatch(backend, interpret, ragged=False, cpu_twin="ref")
    beta_b = jnp.asarray(beta, jnp.float32)
    if beta_b.ndim and beta_b.shape[-1] == h and g != h:
        beta_b = beta_b.reshape(beta_b.shape[:-1] + (g, h // g)).mean(axis=-1)
    beta_b = _bcast_heads(beta_b, g)
    beta_h = jnp.repeat(beta_b, h // g, axis=-1) if g != h else beta_b
    kf = k if g == h else jnp.repeat(k, h // g, axis=2)
    vf = v if g == h else jnp.repeat(v, h // g, axis=2)
    if kind != "pallas":
        return core_loglin.decode_chunk(state, q, kf, vf, alpha, beta_h,
                                        pos=pos, granule=granule,
                                        num_scales=num_scales,
                                        scale_decay=scale_decay,
                                        row_mask=row_mask,
                                        commit_len=commit_len,
                                        renorm=renorm)
    if t > granule:
        if commit_len is not None:
            raise ValueError(
                "log_linear decode_chunk supports commit_len only for "
                f"T <= granule (T={t}, granule={granule})")
        outs = []
        posv = jnp.asarray(pos, jnp.int32)
        done = jnp.zeros((b,), jnp.int32)
        for i0 in range(0, t, granule):
            sl = slice(i0, min(i0 + granule, t))
            o, state = loglin_decode_chunk(
                state, q[:, sl], k[:, sl], v[:, sl], alpha, beta_b,
                pos=posv + done, granule=granule, num_scales=num_scales,
                scale_decay=scale_decay, interpret=interpret,
                row_mask=row_mask, backend=backend, renorm=renorm)
            step = sl.stop - sl.start
            adv = jnp.full((b,), step, jnp.int32)
            done = done + (jnp.where(row_mask, adv, 0)
                           if row_mask is not None else adv)
            outs.append(o)
        return jnp.concatenate(outs, axis=1), state
    # Committed fold: the exact jnp `_advance` the core twin runs, at H
    # heads — the new state is bitwise-identical across backends.
    bk_h = (kf * _row_head_bcast(beta_h)).astype(jnp.float32)
    vf32 = vf.astype(jnp.float32)
    new_state, aux = core_loglin._advance(
        state, bk_h, vf32, pos=pos, granule=granule,
        num_scales=num_scales, row_mask=row_mask,
        commit_len=commit_len, renorm=renorm, t=t)
    (cl_c, split, crossed, occ, occ2, sl2, zl2, cl2,
     closed_s, closed_z, closed_c) = aux
    # Group-level scoring reference covering every bucket and chunk key
    # (the normalized form is exactly invariant to the reference, so the
    # group pooling only changes rounding, not semantics).
    alpha_b = _bcast_heads(alpha, h)
    aq = q.astype(jnp.float32) * _row_head_bcast(alpha_b)
    c_q = jax.lax.stop_gradient(jnp.max(aq, axis=(1, 3), keepdims=True))
    w = core_loglin.level_weights(num_scales, scale_decay)
    cl_occ = jnp.where(occ[..., None] > 0.5, state.cl, -jnp.inf)
    c_state = jnp.max(cl_occ, axis=1)[:, None, :, None]      # (B,1,H,1)
    c_h = jnp.maximum(jnp.maximum(state.c_k, c_state),
                      jax.lax.stop_gradient(
                          jnp.max(bk_h, axis=(1, 3), keepdims=True)))
    r = h // g
    c_g = jnp.max(c_h.reshape(b, 1, g, r, 1), axis=3)        # (B,1,G,1)
    c_out = jnp.repeat(c_g, r, axis=2) if r != 1 else c_g    # (B,1,H,1)
    # Two inter views at the shared reference (jnp aggregates, H heads).
    s_effa, z_effa = core_loglin._aggregate(state.sl, state.zl, state.cl,
                                            occ, w, c_out)
    r_open = jnp.exp(state.c_k - c_out)[:, 0, :, 0]          # (B,H)
    s_effa = s_effa + state.s * r_open[..., None, None]
    z_effa = z_effa + state.z * r_open[..., None]
    s_effb, z_effb = core_loglin._aggregate(sl2, zl2, cl2, occ2, w, c_out)
    # Pass A scores pre-boundary queries (keys at/past the row's split
    # masked to NEG_INF => Phi(k) = 0); pass B scores post-boundary
    # queries (pre-boundary keys masked — they arrive via pyramid(n+1)).
    j = jnp.arange(t)
    bk_g = k.astype(jnp.float32) * _row_head_bcast(beta_b)   # (B,T,G,D)
    ks_full = bk_g - c_g
    pre_key = j[None, :, None, None] < split[:, None, None, None]
    ks_a = jnp.where(pre_key, ks_full, -1e30)
    ks_b = jnp.where(pre_key, -1e30, ks_full)
    qs = _to_kernel(aq - c_q)
    ka = _to_kernel(ks_a)
    kb = _to_kernel(ks_b)
    vk = _to_kernel(v)
    tp = -(-t // 16) * 16
    if tp != t:
        qs = jnp.pad(qs, ((0, 0), (0, tp - t), (0, 0)))
        ka = jnp.pad(ka, ((0, 0), (0, tp - t), (0, 0)),
                     constant_values=-1e30)
        kb = jnp.pad(kb, ((0, 0), (0, tp - t), (0, 0)),
                     constant_values=-1e30)
        vk = jnp.pad(vk, ((0, 0), (0, tp - t), (0, 0)))
    dv = v.shape[-1]
    out_a, _, _ = lln_decode_pallas(qs, ka, vk,
                                    s_effa.reshape(b * h, d, dv),
                                    z_effa.reshape(b * h, 1, d),
                                    r=r, interpret=ip)
    out_b, _, _ = lln_decode_pallas(qs, kb, vk,
                                    s_effb.reshape(b * h, d, dv),
                                    z_effb.reshape(b * h, 1, d),
                                    r=r, interpret=ip)
    pre = j[None, :] < split[:, None]                        # (B,T)
    out = jnp.where(pre[..., None, None],
                    _from_kernel(out_a[:, :t], b),
                    _from_kernel(out_b[:, :t], b))
    return out, new_state


def loglin_commit_chunk(state, k, v, beta, *, pos, granule: int,
                        num_scales: int,
                        interpret: Optional[bool] = None,
                        row_mask: Optional[jnp.ndarray] = None,
                        backend: str = "auto",
                        commit_len: Optional[jnp.ndarray] = None,
                        renorm: Optional[float] = None):
    """Fold a scored chunk's accepted prefix into a ``LogLinState``
    without scoring — the single-pass speculative-verify commit.

    Every backend kind runs the same O(T d^2 L) jnp
    ``core.loglinear._advance`` fold (the Pallas decode path uses it
    too), so commit is bit-identical to re-running
    :func:`loglin_decode_chunk` with the final ``commit_len`` on every
    backend.  k/v: (B,T,G,D[v]); beta as in :func:`lln_decode_chunk`.
    """
    t = k.shape[1]
    g = k.shape[2]
    h = state.s.shape[1]
    _dispatch(backend, interpret, ragged=False, cpu_twin="ref")
    beta_b = jnp.asarray(beta, jnp.float32)
    if beta_b.ndim and beta_b.shape[-1] == h and g != h:
        beta_b = beta_b.reshape(beta_b.shape[:-1] + (g, h // g)).mean(axis=-1)
    beta_b = _bcast_heads(beta_b, g)
    beta_h = jnp.repeat(beta_b, h // g, axis=-1) if g != h else beta_b
    kf = k if g == h else jnp.repeat(k, h // g, axis=2)
    vf = v if g == h else jnp.repeat(v, h // g, axis=2)
    return core_loglin.commit_chunk(state, kf, vf, beta_h, pos=pos,
                                    granule=granule,
                                    num_scales=num_scales,
                                    row_mask=row_mask,
                                    commit_len=commit_len, renorm=renorm)


# ---------------------------------------------------------------------------
# Block-diagonal softmax attention.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def block_diag_attention(q, k, v, block: int = 256, causal: bool = False,
                         interpret: Optional[bool] = None,
                         pallas_bwd: bool = True, backend: str = "auto"):
    """Block-diagonal softmax attention via Pallas (§4.2 diag component).

    q: (B, N, H, D); k/v: (B, N, G, D[v]), GQA via the ``h // r`` index map.
    Each ``block``-sized diagonal block attends only within itself
    (causally masked when ``causal``).  Training entry point (custom_vjp:
    Pallas backward on compiled backends, scan twin under interpret mode,
    jnp reference when ``N % block`` or ``pallas_bwd=False``); returns
    (B, N, H, Dv) in ``v.dtype``.  Inference prefill uses
    :func:`block_diag_fwd` instead.
    """
    return _diag_fwd_impl(q, k, v, block, causal, interpret, backend)


def _diag_fwd_impl(q, k, v, block, causal, interpret, backend="auto"):
    b, n, h, _ = q.shape
    g = k.shape[2]
    kind, ip = _dispatch(backend, interpret, ragged=bool(n % block),
                         cpu_twin="pallas")
    if kind == "ref":
        return _diag_ref(q, k, v, block, causal)
    if kind == "scan":
        return _block_diag_twin(q, k, v, block, causal)
    with jax.named_scope("block_diag"):
        out = block_diag_pallas(_to_kernel(q), _to_kernel(k), _to_kernel(v),
                                r=h // g, blk=block, causal=causal,
                                interpret=ip)
    return _from_kernel(out, b)


def _diag_ref(q, k, v, block, causal):
    h = q.shape[2]
    g = k.shape[2]
    kf = k if g == h else jnp.repeat(k, h // g, axis=2)
    vf = v if g == h else jnp.repeat(v, h // g, axis=2)
    return core_diag(q, kf, vf, block=block, causal=causal).astype(v.dtype)


def _diag_vjp_fwd(q, k, v, block, causal, interpret, pallas_bwd,
                  backend="auto"):
    n = q.shape[1]
    if n % block or not pallas_bwd or backend in ("scan", "ref"):
        return (_diag_fwd_impl(q, k, v, block, causal, interpret, backend),
                {"ref": (q, k, v)})
    qk, kk, vk = _to_kernel(q), _to_kernel(k), _to_kernel(v)
    with jax.named_scope("block_diag"):
        out = block_diag_pallas(qk, kk, vk, r=q.shape[2] // k.shape[2],
                                blk=block, causal=causal,
                                interpret=_interpret(interpret))
    res = {"pallas": (qk, kk, vk, _dtype_tag(q), _dtype_tag(k),
                      _dtype_tag(v))}
    return _from_kernel(out, q.shape[0]), res


def _diag_vjp_bwd(block, causal, interpret, pallas_bwd, backend, res, g_out):
    if "ref" in res:
        q, k, v = res["ref"]
        _, vjp = jax.vjp(lambda q_, k_, v_: _diag_ref(q_, k_, v_, block,
                                                      causal), q, k, v)
        return vjp(g_out)
    qk, kk, vk, tq, tk, tv = res["pallas"]
    b = g_out.shape[0]
    r = (qk.shape[0] // b) // (kk.shape[0] // b)
    with jax.named_scope("block_diag"):
        if _kernel_bwd(interpret):
            dq, dk, dv = block_diag_bwd_pallas(
                qk, kk, vk, _to_kernel(g_out), r=r, blk=block,
                causal=causal, interpret=_interpret(interpret))
        else:
            dq, dk, dv = block_diag_bwd_scan(qk, kk, vk, _to_kernel(g_out),
                                             r=r, blk=block, causal=causal)
    return (_from_kernel(dq, b).astype(tq.dtype),
            _from_kernel(dk, b).astype(tk.dtype),
            _from_kernel(dv, b).astype(tv.dtype))


block_diag_attention.defvjp(_diag_vjp_fwd, _diag_vjp_bwd)


# ---------------------------------------------------------------------------
# Fused LLN + Diag (causal): single-pass hybrid, shared block loads.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def lln_diag_attention(q, k, v, alpha, beta, causal: bool = True,
                       block: int = 256, interpret: Optional[bool] = None,
                       pallas_bwd: bool = True, backend: str = "auto"):
    """The paper's §4.2 hybrid: 0.5 * (LLN + block-diag softmax).

    Shapes/dtypes/GQA semantics as :func:`lln_attention` (``block`` doubles
    as the LLN chunk and the diag block).  When ``causal`` the two
    components run as ONE fused Pallas kernel sharing block loads (fused
    backward likewise); bidirectional runs them as two kernels.  Fallbacks:
    jnp reference when ``N % block`` or ``pallas_bwd=False``; scan twins
    under interpret mode for the backward.  ``backend='scan'`` forces the
    core chunked-scan hybrid, ``'ref'`` the quadratic-oracle hybrid,
    ``'pallas'`` the fused kernel (interpreted on CPU).
    """
    return _lln_diag_fwd_impl(q, k, v, alpha, beta, causal, block, interpret,
                              backend)


def _lln_diag_fwd_impl(q, k, v, alpha, beta, causal, block, interpret,
                       backend="auto"):
    b, n, h, _ = q.shape
    g = k.shape[2]
    kind, ip_forced = _dispatch(backend, interpret, ragged=bool(n % block),
                                cpu_twin="pallas", ragged_kind="scan")
    if kind == "scan":
        return _lln_diag_ref(q, k, v, alpha, beta, causal, block)
    if kind == "ref":
        lln = _lln_quad_ref(q, k, v, alpha, beta, causal)
        diag = _diag_ref(q, k, v, block, causal)
        return (0.5 * (lln.astype(jnp.float32) + diag.astype(jnp.float32))
                ).astype(v.dtype)
    # Kernel-layout conversion hoisted: q/k/v are transposed exactly once
    # per call, and the LLN pre-scaling runs once for both components.
    qs, ks, _, _ = _scaled_stabilized(q, k, alpha, beta)
    vk = _to_kernel(v)
    ip = ip_forced
    if causal:
        out = lln_diag_fused_pallas(qs, ks, _to_kernel(q), _to_kernel(k),
                                    vk, r=h // g, blk=block, causal=True,
                                    interpret=ip)
        return _from_kernel(out, b)
    with jax.named_scope("lln_bidir"):
        lln = lln_bidir_pallas(qs, ks, vk, r=h // g, blk=block,
                               interpret=ip)
    with jax.named_scope("block_diag"):
        diag = block_diag_pallas(_to_kernel(q), _to_kernel(k), vk, r=h // g,
                                 blk=block, causal=False, interpret=ip)
    out = 0.5 * (lln.astype(jnp.float32) + diag.astype(jnp.float32))
    return _from_kernel(out, b).astype(v.dtype)


def _lln_diag_ref(q, k, v, alpha, beta, causal, block):
    lln = _lln_ref(q, k, v, alpha, beta, causal, block)
    diag = _diag_ref(q, k, v, block, causal)
    return (0.5 * (lln.astype(jnp.float32) + diag.astype(jnp.float32))
            ).astype(v.dtype)


def _lln_diag_vjp_fwd(q, k, v, alpha, beta, causal, block, interpret,
                      pallas_bwd, backend="auto"):
    b, n, h, _ = q.shape
    g = k.shape[2]
    if n % block or not pallas_bwd or backend in ("scan", "ref"):
        out = _lln_diag_fwd_impl(q, k, v, alpha, beta, causal, block,
                                 interpret, backend)
        return out, {"ref": (q, k, v, alpha, beta)}
    qs, ks, alpha_b, beta_b = _scaled_stabilized(q, k, alpha, beta)
    qk, kk, vk = _to_kernel(q), _to_kernel(k), _to_kernel(v)
    ip = _interpret(interpret)
    tags = (_dtype_tag(q), _dtype_tag(k), _dtype_tag(v),
            jnp.asarray(alpha, jnp.float32), jnp.asarray(beta, jnp.float32))
    if causal:
        out_k, den = lln_diag_fused_pallas(qs, ks, qk, kk, vk, r=h // g,
                                           blk=block, causal=True,
                                           interpret=ip, return_res=True)
        res = {"pallas_fused": (qs, ks, qk, kk, vk, out_k, den,
                                alpha_b, beta_b) + tags}
        return _from_kernel(out_k, b), res
    with jax.named_scope("lln_bidir"):
        lln_k, s, z, den = lln_bidir_pallas(qs, ks, vk, r=h // g, blk=block,
                                            interpret=ip, return_res=True)
    with jax.named_scope("block_diag"):
        diag_k = block_diag_pallas(qk, kk, vk, r=h // g, blk=block,
                                   causal=False, interpret=ip)
    out = 0.5 * (lln_k.astype(jnp.float32) + diag_k.astype(jnp.float32))
    res = {"pallas_bidir": (qs, ks, qk, kk, vk, lln_k, den, s, z,
                            alpha_b, beta_b) + tags}
    return _from_kernel(out, b).astype(v.dtype), res


def _lln_diag_vjp_bwd(causal, block, interpret, pallas_bwd, backend, res,
                      g_out):
    if "ref" in res:
        q, k, v, alpha, beta = res["ref"]
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _lln_diag_ref(q_, k_, v_, alpha, beta, causal,
                                             block), q, k, v)
        dq, dk, dv = vjp(g_out)
        return (dq, dk, dv) + _zero_ab(alpha, beta)
    b = g_out.shape[0]
    gk = _to_kernel(g_out)
    ip = _interpret(interpret)
    if causal:
        (qs, ks, qk, kk, vk, out_k, den, alpha_b, beta_b,
         tq, tk, tv, alpha0, beta0) = res["pallas_fused"]
        r = (qs.shape[0] // b) // (ks.shape[0] // b)
        if _kernel_bwd(interpret):
            dqs, dqd, dks, dkd, dvk = lln_diag_fused_bwd_pallas(
                qs, ks, qk, kk, vk, gk, out_k, den, r=r, blk=block,
                interpret=ip)
        else:
            dqs, dqd, dks, dkd, dvk = lln_diag_fused_bwd_scan(
                qs, ks, qk, kk, vk, gk, out_k, den, r=r, blk=block)
    else:
        (qs, ks, qk, kk, vk, lln_k, den, s, z, alpha_b, beta_b,
         tq, tk, tv, alpha0, beta0) = res["pallas_bidir"]
        r = (qs.shape[0] // b) // (ks.shape[0] // b)
        gh = 0.5 * gk.astype(jnp.float32)
        kernel = _kernel_bwd(interpret)
        with jax.named_scope("lln_bidir_bwd"):
            if kernel:
                dqs, dks, dvl = lln_bidir_bwd_pallas(
                    qs, ks, vk, gh, lln_k, den, s, z, r=r, blk=block,
                    interpret=ip)
            else:
                dqs, dks, dvl = lln_bidir_bwd_scan(qs, ks, vk, gh, lln_k,
                                                   den, s, z, r=r, blk=block)
        with jax.named_scope("block_diag"):
            if kernel:
                dqd, dkd, dvd = block_diag_bwd_pallas(
                    qk, kk, vk, gh, r=r, blk=block, causal=False,
                    interpret=ip)
            else:
                dqd, dkd, dvd = block_diag_bwd_scan(qk, kk, vk, gh, r=r,
                                                    blk=block, causal=False)
        dvk = dvl + dvd
    dq = (_from_kernel(dqs, b) * _row_head_bcast(alpha_b)
          + _from_kernel(dqd, b)).astype(tq.dtype)
    dk = (_from_kernel(dks, b) * _row_head_bcast(beta_b)
          + _from_kernel(dkd, b)).astype(tk.dtype)
    dv = _from_kernel(dvk, b).astype(tv.dtype)
    return dq, dk, dv, jnp.zeros_like(alpha0), jnp.zeros_like(beta0)


lln_diag_attention.defvjp(_lln_diag_vjp_fwd, _lln_diag_vjp_bwd)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunked scan.
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ssd_scan(xbar, b_in, c_in, log_a, chunk: int = 256,
             interpret: Optional[bool] = None):
    """SSD via Pallas.  xbar: (B,L,H,P); b_in/c_in: (B,L,G,S);
    log_a: (B,L,H).  Returns y: (B,L,H,P) (no final state — training path;
    prefill uses the jnp ssd_chunked which also returns the state)."""
    return _ssd_fwd_impl(xbar, b_in, c_in, log_a, chunk, interpret)


def _ssd_fwd_impl(xbar, b_in, c_in, log_a, chunk, interpret):
    b, l, h, p_dim = xbar.shape
    g = b_in.shape[2]
    if l % chunk:
        return _ssd_ref(xbar, b_in, c_in, log_a, chunk)
    xk = _to_kernel(xbar)
    bk = _to_kernel(b_in)
    ck = _to_kernel(c_in)
    lk = log_a.transpose(0, 2, 1).reshape(b * h, 1, l)
    out = ssd_pallas(lk, xk, bk, ck, r=h // g, blk=chunk,
                     interpret=_interpret(interpret))
    return _from_kernel(out, b)


def _ssd_ref(xbar, b_in, c_in, log_a, chunk):
    from repro.models.ssm import ssd_chunked
    h, g = xbar.shape[2], b_in.shape[2]
    rep = h // g
    bf = jnp.repeat(b_in, rep, axis=2) if rep > 1 else b_in
    cf = jnp.repeat(c_in, rep, axis=2) if rep > 1 else c_in
    y, _ = ssd_chunked(xbar, bf, cf, log_a, chunk=chunk)
    return y.astype(xbar.dtype)


def _ssd_vjp_fwd(xbar, b_in, c_in, log_a, chunk, interpret):
    return _ssd_fwd_impl(xbar, b_in, c_in, log_a, chunk, interpret), \
        (xbar, b_in, c_in, log_a)


def _ssd_vjp_bwd(chunk, interpret, res, g_out):
    xbar, b_in, c_in, log_a = res
    _, vjp = jax.vjp(
        lambda x, b, c, a: _ssd_ref(x, b, c, a, chunk),
        xbar, b_in, c_in, log_a)
    return vjp(g_out.astype(jnp.float32))


ssd_scan.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)
