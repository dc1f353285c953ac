"""The unified AttentionEngine: one spec, one state pytree, one lifecycle.

Every attention path in this repo — training forward, prefill, chunked
decode, continuous batching, MLA — now runs through this module:

* :class:`AttentionState` is the ONE decode-state pytree.  It carries the
  softmax KV cache (``k``/``v``/``len``), the LLN O(d^2) state
  (``s``/``z``/``c_k``), the §4.2 diag tails at the G kv heads
  (``tail_k``/``tail_v``), the MLA latent cache (``ckv``/``kr``) and the
  per-row serving contract (``pos``/``len`` (B,), ``alpha``/``beta``
  (B, H)) — unused fields are ``None`` and vanish from the pytree.
  Scalar-position static batching is just the degenerate case where every
  row agrees; there is no separate scalar cache layout any more.
* :class:`AttentionEngine` binds an :class:`~repro.kernels.registry.AttnSpec`
  to one layer's head geometry and exposes the lifecycle
  ``init_state -> prefill -> decode* -> evict``.  Backend selection
  (pallas / scan twin / jnp ref) is owned by ``kernels/registry.py``.

The legacy entry points (``attn_prefill``/``attn_decode``/
``attn_cache_init``/``mla_decode``/…) survive as thin shims delegating
here — see ``models/attention_block.py`` and ``docs/api.md`` for the
old→new migration table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import attention as ca
from . import health as health_mod
from . import moment_matching as mm
from .attention import KVCache, LLNDecodeState, batch_alpha_beta
from .lln import LLNState, commit_lengths
from .loglinear import LogLinState
from repro.distributed import sharding as shd
from repro.kernels import registry as kreg
from repro.kernels.registry import AttnSpec


# ---------------------------------------------------------------------------
# The one state pytree.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AttentionState:
    """Unified per-layer attention decode state (a registered pytree).

    Exactly one family of fields is populated per impl; ``None`` fields
    contribute no leaves:

    ==========  =======================================================
    softmax     ``k``/``v`` (B, S, G, D[v]) KV cache, ``len`` (B,)
    lln(+diag)  ``s`` (B,H,D,Dv) fp32, ``z`` (B,H,D) fp32, ``c_k``
                (B,1,H,1) fp32, ``tail_k``/``tail_v`` (B,BLK,G,D[v]),
                ``pos`` (B,), ``alpha``/``beta`` (B,H) fp32,
                ``log_scale`` (B,H) fp32 accumulated drift-renorm shift
    log_linear  lln leaves (no tails) plus the Fenwick bucket pyramid:
                ``sl`` (B,L,H,D,Dv), ``zl`` (B,L,H,D), ``cl`` (B,L,H)
                fp32 — level l summarizes a dyadic span of 2^l closed
                granules; occupancy is derived from ``pos``
                (``core/loglinear.py:occupancy``), so no extra counter
    MLA latent  ``ckv`` (B,S,kv_lora), ``kr`` (B,S,rd), ``len`` (B,)
    ==========  =======================================================

    Counters are ALWAYS per-row (B,): a static lockstep batch is simply
    every row holding the same value.  The pytree flattens with dict-style
    key paths (``DictKey``), so path-pattern consumers (the sharding rules
    in ``launch/steps.py:cache_shardings``, tree-walking tests) see the
    same leaf names the legacy dict caches used; ``state["pos"]`` works as
    an alias of ``state.pos`` for the same reason.
    """
    k: Optional[jnp.ndarray] = None
    v: Optional[jnp.ndarray] = None
    len: Optional[jnp.ndarray] = None
    s: Optional[jnp.ndarray] = None
    z: Optional[jnp.ndarray] = None
    c_k: Optional[jnp.ndarray] = None
    tail_k: Optional[jnp.ndarray] = None
    tail_v: Optional[jnp.ndarray] = None
    pos: Optional[jnp.ndarray] = None
    alpha: Optional[jnp.ndarray] = None
    beta: Optional[jnp.ndarray] = None
    log_scale: Optional[jnp.ndarray] = None
    sl: Optional[jnp.ndarray] = None
    zl: Optional[jnp.ndarray] = None
    cl: Optional[jnp.ndarray] = None
    ckv: Optional[jnp.ndarray] = None
    kr: Optional[jnp.ndarray] = None

    def __getitem__(self, name: str):
        """Dict-style read access (legacy cache-dict compatibility)."""
        if name not in _STATE_FIELDS:
            raise KeyError(name)
        return getattr(self, name)

    def replace(self, **kw) -> "AttentionState":
        return dataclasses.replace(self, **kw)


_STATE_FIELDS = tuple(f.name for f in dataclasses.fields(AttentionState))

#: Logical axes of the LLN and log_linear ``AttentionState`` leaves and of
#: the other arrays the engine hands ``distributed/sharding.py:per_device``
#: (q/k/v/out, per-row masks), by name.  A leaf missing here fails loudly
#: there.  Softmax states never go there: XLA partitions that path.
STATE_AXES = {
    **ca.ATTN_AXES,
    "pos": ("act_batch",),
    "row_mask": ("act_batch",), "commit_len": ("act_batch",),
    "s": ("act_batch", "heads", None, None),
    "z": ("act_batch", "heads", None),
    "c_k": ("act_batch", None, "heads", None),
    "tail_k": ("act_batch", None, "heads", None),
    "tail_v": ("act_batch", None, "heads", None),
    "log_scale": ("act_batch", "heads"),
    "sl": ("act_batch", None, "heads", None, None),
    "zl": ("act_batch", None, "heads", None),
    "cl": ("act_batch", None, "heads"),
}


def _state_flatten_with_keys(st: AttentionState):
    return ([(jax.tree_util.DictKey(n), getattr(st, n))
             for n in _STATE_FIELDS], None)


def _state_flatten(st: AttentionState):
    return tuple(getattr(st, n) for n in _STATE_FIELDS), None


def _state_unflatten(_, children) -> AttentionState:
    return AttentionState(**dict(zip(_STATE_FIELDS, children)))


jax.tree_util.register_pytree_with_keys(
    AttentionState, _state_flatten_with_keys, _state_unflatten,
    _state_flatten)


@jax.named_scope("diag_tail")
def _tail_of(t: jnp.ndarray, n: int, blk: int) -> jnp.ndarray:
    """Contents of the (partially filled) last ``blk``-sized block."""
    nb = -(-n // blk)
    last = (nb - 1) * blk
    pad = nb * blk - n
    return jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))[:, last:]


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttentionEngine:
    """One attention configuration bound to one layer's head geometry.

    ``spec`` declares impl/causality/backend/chunking
    (:class:`~repro.kernels.registry.AttnSpec`); ``heads``/``kv_heads``/
    ``head_dim``/``v_dim`` are the layer's projection shapes and
    ``cache_dtype`` the KV/tail storage dtype.  All methods are pure and
    jit-safe; the engine object itself is static (hashable) and cheap to
    construct per call.

    Lifecycle::

        eng = AttentionEngine.from_cfg(cfg)          # or explicit dims
        state = eng.init_state(batch, max_len)       # zeroed, per-row
        out, state = eng.prefill(q, k, v, max_len=max_len)
        out, state = eng.decode(state, q1, k1, v1)   # T >= 1 tokens
        state = eng.evict(state, rows)               # free slots
    """
    spec: AttnSpec
    heads: int
    kv_heads: int
    head_dim: int
    v_dim: int
    # KV/tail storage dtype; None derives it from ``spec.precision`` (the
    # one declared source — pass cache_dtype only to override it).
    cache_dtype: Any = None

    @property
    def state_dtype(self):
        return (jnp.dtype(self.spec.precision) if self.cache_dtype is None
                else jnp.dtype(self.cache_dtype))

    @classmethod
    def from_cfg(cls, cfg, causal: bool = True, *,
                 heads: Optional[int] = None,
                 kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 v_dim: Optional[int] = None) -> "AttentionEngine":
        """Engine for an ``ArchConfig`` layer (dims overridable — MLA binds
        its assembled ``nope+rope`` q/k dim and its own v dim)."""
        h = heads if heads is not None else cfg.n_heads
        g = kv_heads if kv_heads is not None else cfg.n_kv_heads
        d = head_dim if head_dim is not None else cfg.hd
        spec = AttnSpec.from_cfg(cfg, causal=causal, r=h // g)
        return cls(spec=spec, heads=h, kv_heads=g, head_dim=d,
                   v_dim=v_dim if v_dim is not None else d)

    # -- lifecycle ----------------------------------------------------------

    def init_state(self, batch: int, max_len: int) -> AttentionState:
        """Zeroed decode state for ``batch`` rows.  Always per-row: ``len``
        / ``pos`` are (B,) and calibration is (B, H) — the static lockstep
        batch is the degenerate case where all rows stay equal."""
        h, g, d, dv = self.heads, self.kv_heads, self.head_dim, self.v_dim
        if self.spec.impl == "softmax":
            return AttentionState(
                k=jnp.zeros((batch, max_len, g, d), self.state_dtype),
                v=jnp.zeros((batch, max_len, g, dv), self.state_dtype),
                len=jnp.zeros((batch,), jnp.int32))
        if self.spec.impl == "log_linear":
            ls = self.spec.num_scales
            return AttentionState(
                s=jnp.zeros((batch, h, d, dv), jnp.float32),
                z=jnp.zeros((batch, h, d), jnp.float32),
                c_k=jnp.zeros((batch, 1, h, 1), jnp.float32),
                sl=jnp.zeros((batch, ls, h, d, dv), jnp.float32),
                zl=jnp.zeros((batch, ls, h, d), jnp.float32),
                cl=jnp.zeros((batch, ls, h), jnp.float32),
                pos=jnp.zeros((batch,), jnp.int32),
                alpha=jnp.ones((batch, h), jnp.float32),
                beta=jnp.ones((batch, h), jnp.float32),
                log_scale=jnp.zeros((batch, h), jnp.float32))
        blk = self.spec.diag_block
        return AttentionState(
            s=jnp.zeros((batch, h, d, dv), jnp.float32),
            z=jnp.zeros((batch, h, d), jnp.float32),
            c_k=jnp.zeros((batch, 1, h, 1), jnp.float32),
            tail_k=jnp.zeros((batch, blk, g, d), self.state_dtype),
            tail_v=jnp.zeros((batch, blk, g, dv), self.state_dtype),
            pos=jnp.zeros((batch,), jnp.int32),
            alpha=jnp.ones((batch, h), jnp.float32),
            beta=jnp.ones((batch, h), jnp.float32),
            log_scale=jnp.zeros((batch, h), jnp.float32))

    def calibrate(self, q, k, n: Optional[int] = None):
        """Moment-matched (alpha, beta) per ``spec.calibration`` —
        ``batch`` pools statistics (training semantics), ``per_row``
        measures each row alone ((B, H)/(B, G); admission semantics).
        ``n`` (static) selects length-aware (a, b) constants when the
        beta(n) schedule is on; ignored otherwise."""
        return batch_alpha_beta(q, k, self.spec,
                                per_row=self.spec.calibration == "per_row",
                                n=n)

    def _length_gain(self, n):
        """beta(n) schedule gain for a depth ``n`` (static int or traced
        per-row (B,) positions); None when the schedule is off."""
        if self.spec.beta_n <= 0.0 or self.spec.impl == "softmax":
            return None
        return mm.length_gain(n, self.spec.beta_n, self.spec.calib_len)

    def attention(self, q, k, v, *, mask=None, alpha=None, beta=None,
                  prefix_len: int = 0):
        """Stateless full-sequence attention (training / scoring).
        q: (B,N,H,D); k/v: (B,N,G,D[v]).  Softmax ``backend='ref'`` is the
        naive quadratic; other softmax backends run flash."""
        spec = self.spec
        if spec.impl == "softmax":
            if spec.backend == "ref":
                return ca.naive_softmax(q, k, v, causal=spec.causal,
                                        mask=mask, prefix_len=prefix_len)
            return ca.flash_softmax(q, k, v, causal=spec.causal,
                                    chunk=min(spec.softmax_chunk,
                                              k.shape[1]),
                                    mask=mask, prefix_len=prefix_len)
        if alpha is None or beta is None:
            # Calibrate HERE so spec.calibration="per_row" applies to the
            # full-sequence forward too (multi_head_attention's internal
            # batch_alpha_beta only knows the batch-pooled mode).
            alpha, beta = self.calibrate(q, k, n=q.shape[1])
            gain = self._length_gain(q.shape[1])
            if gain is not None:
                alpha = jnp.asarray(alpha, jnp.float32) * gain
                beta = jnp.asarray(beta, jnp.float32) * gain
        acfg = ca.AttnConfig(
            impl=spec.impl, causal=spec.causal, diag_block=spec.diag_block,
            lln_chunk=spec.lln_chunk, softmax_chunk=spec.softmax_chunk,
            use_kernel=spec.backend != "ref",
            backend=None if spec.backend == "auto" else spec.backend,
            fixed_ab=spec.fixed_ab, mm_a=spec.mm_a, mm_b=spec.mm_b,
            num_scales=spec.num_scales, scale_decay=spec.scale_decay)
        return ca.multi_head_attention(q, k, v, acfg, mask=mask,
                                       alpha=alpha, beta=beta,
                                       prefix_len=prefix_len)

    def prefill(self, q, k, v, *, max_len: int, prefix_len: int = 0,
                alpha=None, beta=None):
        """Causal forward over the prompt; returns ``(out, state)``.

        q: (B,N,H,D); k/v: (B,N,G,D[v]).  The softmax KV cache is padded to
        ``max_len`` so decode appends in place; LLN gets outputs AND the
        O(d^2) state from one pass (``kernels/ops.py:lln_prefill`` under
        ``spec.backend``) plus the diag tail at the G kv heads.
        ``alpha``/``beta`` override the moment-matching calibration.
        Under a multi-device mesh each device runs its own rows and heads
        (``distributed/sharding.py:per_device``); the batch-pooled
        calibration is measured before the split, over every row.
        """
        if self.spec.impl == "softmax":     # no kernel: XLA partitions it
            return self._prefill(q, k, v, max_len=max_len,
                                 prefix_len=prefix_len, alpha=alpha,
                                 beta=beta)
        if alpha is None or beta is None:
            alpha, beta = self.calibrate(q, k, n=q.shape[1])

        def run(q, k, v, alpha, beta):
            out, st = self._prefill(q, k, v, max_len=max_len,
                                    prefix_len=prefix_len, alpha=alpha,
                                    beta=beta)
            return {"out": out, "state": st}
        res = shd.per_device(run, STATE_AXES.__getitem__, q=q, k=k, v=v,
                             alpha=alpha, beta=beta)
        return res["out"], res["state"]

    def _prefill(self, q, k, v, *, max_len, prefix_len, alpha, beta):
        b, n, h, _ = q.shape
        g = k.shape[2]
        spec = self.spec
        if spec.impl == "softmax":
            if spec.backend == "ref":     # independent quadratic oracle
                out = ca.naive_softmax(q, k, v, causal=spec.causal,
                                       prefix_len=prefix_len)
            else:
                out = ca.flash_softmax(q, k, v, causal=spec.causal,
                                       chunk=min(spec.softmax_chunk, n),
                                       prefix_len=prefix_len)
            pad = ((0, 0), (0, max_len - n), (0, 0), (0, 0))
            return out, AttentionState(
                k=jnp.pad(k.astype(self.state_dtype), pad),
                v=jnp.pad(v.astype(self.state_dtype), pad),
                len=jnp.full((b,), n, jnp.int32))
        # beta(n) schedule: the prefill forward runs at the prompt-length
        # temperature, but the state stores the BASE calibration — decode
        # re-derives each row's effective temperature from its own pos, so
        # the gain is never baked in twice.
        gain = self._length_gain(n)
        use_alpha, use_beta = alpha, beta
        if gain is not None:
            use_alpha = jnp.asarray(alpha, jnp.float32) * gain
            use_beta = jnp.asarray(beta, jnp.float32) * gain
        if spec.impl == "log_linear":
            out, s, z, c_k, sl, zl, cl = kreg.loglin_prefill(
                spec, q, k, v, use_alpha, use_beta)
            beta_h = jnp.asarray(beta, jnp.float32)
            if beta_h.shape[-1] == g and g != h:
                beta_h = jnp.repeat(beta_h, h // g, axis=-1)
            state = AttentionState(
                s=s, z=z, c_k=c_k, sl=sl, zl=zl, cl=cl,
                pos=jnp.full((b,), n, jnp.int32),
                alpha=jnp.broadcast_to(jnp.asarray(alpha, jnp.float32),
                                       (b, h)).astype(jnp.float32),
                beta=jnp.broadcast_to(beta_h, (b, h)).astype(jnp.float32),
                log_scale=jnp.zeros((b, h), jnp.float32))
            return out, state
        lln_out, s, z, c_k = kreg.prefill(spec, q, k, v, use_alpha,
                                          use_beta)
        if spec.impl == "lln_diag":
            diag_out = kreg.diag_fwd(spec, q, k, v)
            out = (0.5 * (lln_out.astype(jnp.float32)
                          + diag_out.astype(jnp.float32))).astype(v.dtype)
        else:
            out = lln_out
        blk = spec.diag_block
        beta_h = jnp.asarray(beta, jnp.float32)
        if beta_h.shape[-1] == g and g != h:
            beta_h = jnp.repeat(beta_h, h // g, axis=-1)
        state = AttentionState(
            s=s, z=z, c_k=c_k,
            tail_k=_tail_of(k, n, blk).astype(self.state_dtype),
            tail_v=_tail_of(v, n, blk).astype(self.state_dtype),
            pos=jnp.full((b,), n, jnp.int32),
            alpha=jnp.broadcast_to(jnp.asarray(alpha, jnp.float32),
                                   (b, h)).astype(jnp.float32),
            beta=jnp.broadcast_to(beta_h, (b, h)).astype(jnp.float32),
            log_scale=jnp.zeros((b, h), jnp.float32))
        return out, state

    def decode(self, state: AttentionState, q, k, v, *,
               row_mask: Optional[jnp.ndarray] = None,
               commit_len: Optional[jnp.ndarray] = None):
        """Advance ``state`` over T >= 1 new tokens; returns
        ``(out (B,T,H,Dv), new state)``.

        Positions come from the state itself (``len``/``pos`` are per-row
        (B,)).  ``row_mask`` (B,) bool: masked rows advance NOTHING and
        their outputs must be discarded (the continuous-batching
        contract).  ``commit_len`` (B,) int32 in [0, T]: the speculative
        partial-commit contract — all T positions are scored, but only
        the accepted prefix folds into the state (see :meth:`verify`).
        Under a multi-device mesh each device runs its own rows and heads.
        """
        if self.spec.impl == "softmax":
            return self._decode(state, q, k, v, row_mask=row_mask,
                                commit_len=commit_len)

        def run(state, q, k, v, row_mask, commit_len):
            out, st = self._decode(state, q, k, v, row_mask=row_mask,
                                   commit_len=commit_len)
            return {"out": out, "state": st}
        res = shd.per_device(run, STATE_AXES.__getitem__, state=state, q=q,
                             k=k, v=v, row_mask=row_mask,
                             commit_len=commit_len)
        return res["out"], res["state"]

    def _decode(self, state, q, k, v, *, row_mask, commit_len):
        spec = self.spec
        if spec.impl == "softmax":
            out, kv2 = ca.decode_softmax(
                KVCache(k=state.k, v=state.v, length=state.len),
                q, k, v, chunk=spec.softmax_chunk, row_mask=row_mask,
                commit_len=commit_len)
            return out, state.replace(k=kv2.k, v=kv2.v, len=kv2.length)
        # beta(n) schedule: each row's effective calibration keys off its
        # OWN depth (state.pos) — a 400k-context row and a 2k row in the
        # same pool decode at different temperatures.  The stored
        # alpha/beta stay base; the gain is recomputed every chunk.
        alpha_d, beta_d = state.alpha, state.beta
        gain = self._length_gain(state.pos)
        if gain is not None:
            gain = gain[..., None] if gain.ndim else gain    # (B,1) / ()
            alpha_d = state.alpha * gain
            beta_d = state.beta * gain
        if spec.impl == "log_linear":
            st = LogLinState(s=state.s, z=state.z, c_k=state.c_k,
                             sl=state.sl, zl=state.zl, cl=state.cl,
                             log_scale=state.log_scale)
            out, st2 = kreg.decode_chunk(spec, st, q, k, v, alpha_d,
                                         beta_d, row_mask=row_mask,
                                         commit_len=commit_len,
                                         pos=state.pos)
            t = q.shape[1]
            adv = commit_lengths(
                commit_len if commit_len is not None
                else jnp.full((q.shape[0],), t, jnp.int32), row_mask, t)
            return out, state.replace(
                s=st2.s, z=st2.z, c_k=st2.c_k, sl=st2.sl, zl=st2.zl,
                cl=st2.cl, log_scale=st2.log_scale, pos=state.pos + adv)
        st = LLNDecodeState(
            lln=LLNState(s=state.s, z=state.z, c_k=state.c_k,
                         log_scale=state.log_scale),
            tail_k=state.tail_k, tail_v=state.tail_v, pos=state.pos)
        out, st2 = ca.decode_lln_chunk(st, q, k, v, alpha_d, beta_d,
                                       impl=spec.impl, row_mask=row_mask,
                                       backend=spec.backend,
                                       commit_len=commit_len,
                                       renorm=spec.renorm or None)
        return out, state.replace(
            s=st2.lln.s, z=st2.lln.z, c_k=st2.lln.c_k,
            log_scale=st2.lln.log_scale,
            tail_k=st2.tail_k, tail_v=st2.tail_v, pos=st2.pos)

    def verify(self, state: AttentionState, q, k, v, *, commit_len,
               row_mask: Optional[jnp.ndarray] = None,
               return_residuals: bool = False):
        """Speculative verify: score a T-token draft chunk, commit only the
        accepted prefix.

        Identical to :meth:`decode` except ``commit_len`` (B,) int32 is
        required: outputs cover ALL T draft positions (each position
        attends exactly the keys a sequential decode would have seen), but
        the state — LLN ``(s, z, c_k)``, diag tails, softmax KV rows,
        ``pos``/``len`` — folds only tokens ``j < commit_len[b]``.
        ``commit_len=0`` rows behave exactly like ``row_mask=False`` rows;
        ``commit_len=T`` is a plain decode.  A rejected draft token is
        therefore never popped — it simply never enters the running sums.

        ``return_residuals=True`` additionally returns the layer's commit
        residuals ``{"k", "v"}`` — the post-RoPE (B,T,G,D[v]) chunk keys
        and values — as a third element.  A ``commit_len=0`` score pass
        leaves the state bitwise unchanged, so the single-pass verify flow
        is: score once with ``commit_len=0`` + ``return_residuals=True``,
        run the acceptance rule on the logits, then fold the accepted
        prefix with the cheap O(T d^2) :meth:`commit` — no second full
        pass over the model.
        """
        if commit_len is None:
            raise ValueError("verify requires commit_len; use decode for "
                             "an unconditional advance")
        out, st = self.decode(state, q, k, v, row_mask=row_mask,
                              commit_len=commit_len)
        if return_residuals:
            return out, st, {"k": k, "v": v}
        return out, st

    def commit(self, state: AttentionState, residual: dict, *, commit_len,
               row_mask: Optional[jnp.ndarray] = None) -> AttentionState:
        """Fold a scored chunk's accepted prefix into ``state`` — the
        cheap second half of single-pass speculative verify.

        ``residual``: the ``{"k", "v"}`` dict a ``commit_len=0``
        :meth:`verify` returned (post-RoPE, (B,T,G,D[v])).  ``state`` must
        be the state that verify pass ran against (a ``commit_len=0``
        score leaves it bitwise unchanged).  Per backend this is
        bit-identical to re-running :meth:`verify` with the final
        ``commit_len`` — O(T d^2) per layer instead of a full transformer
        pass.  The beta(n) gain is re-derived from ``state.pos`` exactly
        as the score pass derived it (``pos`` did not advance).
        """
        k, v = residual["k"], residual["v"]
        spec = self.spec
        if spec.impl == "softmax":
            kv2 = ca.commit_softmax(
                KVCache(k=state.k, v=state.v, length=state.len), k, v,
                commit_len=commit_len, row_mask=row_mask)
            return state.replace(k=kv2.k, v=kv2.v, len=kv2.length)
        beta_d = state.beta
        gain = self._length_gain(state.pos)
        if gain is not None:
            gain = gain[..., None] if gain.ndim else gain
            beta_d = state.beta * gain
        if spec.impl == "log_linear":
            st = LogLinState(s=state.s, z=state.z, c_k=state.c_k,
                             sl=state.sl, zl=state.zl, cl=state.cl,
                             log_scale=state.log_scale)
            st2 = kreg.commit_chunk(spec, st, k, v, beta_d,
                                    row_mask=row_mask,
                                    commit_len=commit_len, pos=state.pos)
            t = k.shape[1]
            adv = commit_lengths(
                commit_len if commit_len is not None
                else jnp.full((k.shape[0],), t, jnp.int32), row_mask, t)
            return state.replace(
                s=st2.s, z=st2.z, c_k=st2.c_k, sl=st2.sl, zl=st2.zl,
                cl=st2.cl, log_scale=st2.log_scale, pos=state.pos + adv)
        st = LLNDecodeState(
            lln=LLNState(s=state.s, z=state.z, c_k=state.c_k,
                         log_scale=state.log_scale),
            tail_k=state.tail_k, tail_v=state.tail_v, pos=state.pos)
        st2 = ca.commit_lln_chunk(st, k, v, beta_d, impl=spec.impl,
                                  commit_len=commit_len, row_mask=row_mask,
                                  backend=spec.backend,
                                  renorm=spec.renorm or None)
        return state.replace(
            s=st2.lln.s, z=st2.lln.z, c_k=st2.lln.c_k,
            log_scale=st2.lln.log_scale,
            tail_k=st2.tail_k, tail_v=st2.tail_v, pos=st2.pos)

    def check_health(self, state: AttentionState, *,
                     config: Optional["health_mod.HealthConfig"] = None
                     ) -> dict:
        """Per-row state-health flags (the serving sentinel hook).

        Returns ``{"unhealthy", "nonfinite", "magnitude", "calib"}``,
        each a (B,) bool over the state's row axis: non-finite or
        magnitude-exploding ``(s, z, c_k)``/KV/tail leaves, and per-row
        ``alpha``/``beta`` outside the calibration bounds
        (``core/health.py:HealthConfig``).  Pure jnp — callers fold it
        into their own jitted step (``PoolSetup.segment_fn`` runs it on
        the post-segment pool caches in the same dispatch).  A freshly
        evicted row (zeros, alpha/beta = 1) is healthy by construction.
        """
        cfg = config if config is not None else health_mod.HealthConfig()
        return health_mod.row_health(state, row_axis=0, config=cfg)

    def evict(self, state: AttentionState, rows) -> AttentionState:
        """Reset the given rows (freed slots) of every state leaf to their
        ``init_state`` values.

        ``rows``: (k,) int32 slot indices, or a (B,) bool mask of rows to
        clear.  Every leaf resets to zero EXCEPT the per-row calibration
        ``alpha``/``beta``, which reset to ones (their init value) — a
        previous request's moment-matching constants must never leak into
        the next request admitted to that slot.  Semantically eviction is
        belt-and-braces — admission overwrites a slot's rows wholesale —
        but resetting freed slots keeps stale request state from outliving
        its request (and makes the lifecycle testable).
        """
        rows = jnp.asarray(rows)
        if rows.dtype == jnp.bool_:
            def clear(path, leaf):
                name = getattr(path[-1], "key", None)
                fill = (jnp.ones((), leaf.dtype)
                        if name in ("alpha", "beta")
                        else jnp.zeros((), leaf.dtype))
                keep = ~rows.reshape((-1,) + (1,) * (leaf.ndim - 1))
                return jnp.where(keep, leaf, fill)
        else:
            def clear(path, leaf):
                name = getattr(path[-1], "key", None)
                fill = (jnp.ones((), leaf.dtype)
                        if name in ("alpha", "beta")
                        else jnp.zeros((), leaf.dtype))
                return leaf.at[rows].set(fill)
        return jax.tree_util.tree_map_with_path(clear, state)


__all__ = ["AttentionState", "AttentionEngine", "AttnSpec"]
