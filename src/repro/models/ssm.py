"""Mamba2 — State Space Duality (SSD) blocks (arXiv:2405.21060).

The SSD recurrence per head (state N = ssm_state, head dim P):

    h_t = exp(dt_t * A) h_{t-1} + B_t (dt_t x_t)^T      h: (N, P)
    y_t = C_t^T h_t + D x_t

computed in chunks (the dual quadratic form within a chunk + a state pass
between chunks) — the same chunk/state-pass structure as the causal LLN
kernel, which is why the two families share a roofline column in
EXPERIMENTS.md.  All state math in fp32; log-space decay for stability.

Note (DESIGN.md §Arch-applicability): this family is attention-free — the
paper's LLN technique does not apply here; the arch runs without it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.numerics import einsum_f32
from repro.distributed.sharding import constrain, per_device
from repro.kernels.registry import on_cpu
from .layers import apply_norm, dense, dense_init, norm_init, trunc_normal


def _dims(cfg):
    di = cfg.ssm_expand * cfg.d_model
    h = di // cfg.ssm_head_dim
    return di, h, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups


def ssm_init(key, cfg):
    di, h, p_dim, s, g = _dims(cfg)
    d = cfg.d_model
    conv_dim = di + 2 * g * s
    ks = jax.random.split(key, 8)
    return {
        "w_z": dense_init(ks[0], d, di, cfg.pdtype),
        "w_x": dense_init(ks[1], d, di, cfg.pdtype),
        "w_B": dense_init(ks[2], d, g * s, cfg.pdtype),
        "w_C": dense_init(ks[3], d, g * s, cfg.pdtype),
        "w_dt": dense_init(ks[4], d, h, cfg.pdtype),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "conv_w": trunc_normal(ks[5], (cfg.conv_width, conv_dim),
                               conv_dim ** -0.5, cfg.pdtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.pdtype),
        "norm": norm_init(di, "rmsnorm", cfg.pdtype),
        "out_w": dense_init(ks[6], di, d, cfg.pdtype),
    }


def _causal_conv(x, w, b, dtype):
    """Depthwise causal conv, width W: y_t = sum_j x_{t-W+1+j} w_j."""
    wdt = w.shape[0]
    xf = x.astype(dtype)
    out = jnp.zeros_like(xf)
    for j in range(wdt):
        shift = wdt - 1 - j
        shifted = jnp.pad(xf, ((0, 0), (shift, 0), (0, 0)))[:, :xf.shape[1]]
        out = out + shifted * w[j].astype(dtype)[None, None, :]
    return jax.nn.silu(out + b.astype(dtype)[None, None, :])


def ssd_chunked(xbar, b_in, c_in, log_a, *, chunk: int,
                state0: Optional[jnp.ndarray] = None):
    """Chunked SSD scan.

    xbar: (B, L, H, P) dt-scaled inputs; b_in/c_in: (B, L, H, S) (already
    group-broadcast); log_a: (B, L, H) per-step log decay (<= 0).
    Returns (y (B, L, H, P), final_state (B, H, S, P)).
    """
    bsz, l, h, p = xbar.shape
    s = b_in.shape[-1]
    c = min(chunk, l)
    pad = (-l) % c
    if pad:
        xbar = jnp.pad(xbar, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
    nc = xbar.shape[1] // c

    def resh(t, last):
        return t.reshape((bsz, nc, c) + last).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(last))))
    # Stacks keep their input dtype (fp32 accumulation happens in the
    # einsums); constrained like the LLN/flash stacks so the partitioner
    # keeps batch on data and heads on model.
    xc = resh(xbar, (h, p))
    bc = resh(b_in, (h, s))
    cc = resh(c_in, (h, s))
    lc = resh(log_a.astype(jnp.float32), (h,))
    xc = constrain(xc, None, "act_batch", None, "heads", None)
    bc = constrain(bc, None, "act_batch", None, "heads", None)
    cc = constrain(cc, None, "act_batch", None, "heads", None)

    tri = jnp.tril(jnp.ones((c, c), jnp.float32))
    if state0 is None:
        state0 = jnp.zeros((bsz, h, s, p), jnp.float32)

    def step(state, xs):
        xb, bb, cb, la = xs                       # (B,C,H,*)
        lcum = jnp.cumsum(la, axis=1)             # (B,C,H)
        # intra-chunk: score_ij = (C_i . B_j) exp(lcum_i - lcum_j), j <= i
        dot = einsum_f32("bihs,bjhs->bhij", cb, bb)
        dec = jnp.exp(jnp.clip(lcum[:, :, None] - lcum[:, None, :],
                               -60.0, 0.0)).transpose(0, 3, 1, 2)  # (B,H,i,j)
        scores = dot * dec * tri[None, None]
        y_intra = einsum_f32("bhij,bjhp->bihp", scores.astype(xb.dtype),
                             xb)
        # inter-chunk: y_i += exp(lcum_i) C_i . state
        ein = jnp.exp(jnp.clip(lcum, -60.0, 0.0))
        y_inter = einsum_f32("bihs,bhsp->bihp", cb,
                             state.astype(cb.dtype)) * \
            ein[..., None]
        # state pass: state = exp(l_last) state + sum_j exp(l_last - l_j) B_j xbar_j
        l_last = lcum[:, -1]                      # (B,H)
        carry_dec = jnp.exp(jnp.clip(l_last[:, None] - lcum, -60.0, 0.0))
        state = state * jnp.exp(jnp.clip(l_last, -60.0, 0.0))[:, :, None, None] \
            + jnp.einsum("bjhs,bjh,bjhp->bhsp", bb.astype(jnp.float32),
                         carry_dec, xb.astype(jnp.float32))
        return state, y_intra + y_inter

    # remat: recompute intra-chunk scores in backward (see core/lln.py).
    state, yc = jax.lax.scan(jax.checkpoint(step), state0, (xc, bc, cc, lc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(bsz, nc * c, h, p)
    return y[:, :l], state


def ssm_apply(p, x, cfg, *, state0=None, return_state: bool = False,
              conv_tail: Optional[jnp.ndarray] = None):
    """Full-sequence Mamba2 block.  x: (B, L, D) -> (B, L, D)."""
    di, h, p_dim, s, g = _dims(cfg)
    bsz, l, _ = x.shape
    dtype = cfg.cdtype
    z = dense(p["w_z"], x, dtype)
    xs = dense(p["w_x"], x, dtype)
    b_proj = dense(p["w_B"], x, dtype)
    c_proj = dense(p["w_C"], x, dtype)
    dt = dense(p["w_dt"], x, dtype).astype(jnp.float32)

    # Depthwise conv applied per piece: concatenating the (model-sharded) x
    # stream with the (replicated) B/C streams would force a gather/reshard
    # of the whole activation; channel-wise the pieces are independent.
    gs = g * s
    xs_raw, b_raw, c_raw = xs, b_proj, c_proj
    xs = _causal_conv(xs, p["conv_w"][:, :di], p["conv_b"][:di], dtype)
    b_proj = _causal_conv(b_proj, p["conv_w"][:, di:di + gs],
                          p["conv_b"][di:di + gs], dtype)
    c_proj = _causal_conv(c_proj, p["conv_w"][:, di + gs:],
                          p["conv_b"][di + gs:], dtype)

    dt = jax.nn.softplus(dt + p["dt_bias"][None, None])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))            # (H,) < 0
    log_a = dt * a[None, None]                               # (B,L,H)

    xh = xs.reshape(bsz, l, h, p_dim)
    xh = constrain(xh, "act_batch", None, "heads", None)
    xbar = xh.astype(jnp.float32) * dt[..., None]
    rep = h // g
    if (cfg.use_kernel or not on_cpu()) and state0 is None \
            and not return_state \
            and l % cfg.ssm_chunk == 0:
        # Pallas SSD kernel (training fwd; groups via index maps, no repeat),
        # per device over rows and heads under a mesh.  One group serves
        # every head, so it is replicated; more groups split with the heads.
        from repro.kernels import ssd_scan
        grp = "heads" if g > 1 else None
        axes = {"xbar": ("act_batch", None, "heads", None),
                "y": ("act_batch", None, "heads", None),
                "b": ("act_batch", None, grp, None),
                "c": ("act_batch", None, grp, None),
                "log_a": ("act_batch", None, "heads")}
        y = per_device(
            lambda xbar, b, c, log_a: {"y": ssd_scan(xbar, b, c, log_a,
                                                     cfg.ssm_chunk)},
            axes.__getitem__, xbar=xbar, b=b_proj.reshape(bsz, l, g, s),
            c=c_proj.reshape(bsz, l, g, s), log_a=log_a)["y"]
        state = None
    else:
        b_in = jnp.repeat(b_proj.reshape(bsz, l, g, s), rep, axis=2)
        c_in = jnp.repeat(c_proj.reshape(bsz, l, g, s), rep, axis=2)
        y, state = ssd_chunked(xbar, b_in, c_in, log_a, chunk=cfg.ssm_chunk,
                               state0=state0)
    y = y + xh.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(bsz, l, di).astype(dtype)
    y = y * jax.nn.silu(z)
    y = apply_norm(p["norm"], y, "rmsnorm")
    out = dense(p["out_w"], y, dtype)
    if return_state:
        tail = jnp.concatenate([xs_raw, b_raw, c_raw],
                               -1)[:, -(cfg.conv_width - 1):]
        return out, {"state": state, "conv": tail.astype(dtype)}
    return out


def ssm_cache_init(cfg, batch: int):
    di, h, p_dim, s, g = _dims(cfg)
    conv_dim = di + 2 * g * s
    return {"state": jnp.zeros((batch, h, s, p_dim), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim),
                              cfg.cdtype)}


def ssm_decode_chunk(p, x, cache, cfg, *, row_mask=None, commit_len=None):
    """Chunked T-token SSD decode under the serving contract.

    x: (B, T, D).  All T positions are scored (each sees exactly the
    tokens a sequential decode would have seen: the carried ``state`` /
    conv window plus the in-chunk prefix), but the cache folds only the
    accepted prefix: ``commit_len`` (B,) int32 in [0, T] selects how many
    tokens enter the recurrent state and the conv window per row
    (speculative partial commit), and ``row_mask`` (B,) bool freezes
    masked rows bitwise (their outputs are garbage and must be
    discarded) — the same contract as ``AttentionEngine.decode``.
    Returns (out (B, T, D), new cache).
    """
    from repro.core.lln import commit_lengths
    di, h, p_dim, s, g = _dims(cfg)
    bsz, t, _ = x.shape
    dtype = cfg.cdtype
    wdt = cfg.conv_width
    z = dense(p["w_z"], x, dtype)
    xs = dense(p["w_x"], x, dtype)
    b_proj = dense(p["w_B"], x, dtype)
    c_proj = dense(p["w_C"], x, dtype)
    dt = dense(p["w_dt"], x, dtype).astype(jnp.float32)

    # Causal conv over [cached window | chunk]: position t sees rows
    # t .. t+W-1 of the concatenation — the exact sliding window a
    # sequential one-token loop would assemble.
    conv_in = jnp.concatenate([xs, b_proj, c_proj], -1)       # (B,T,Cd)
    window = jnp.concatenate([cache["conv"].astype(dtype), conv_in], 1)
    conv_out = jnp.zeros((bsz, t, window.shape[-1]), dtype)
    for j in range(wdt):
        conv_out = conv_out + window[:, j:j + t] * \
            p["conv_w"][j].astype(dtype)[None, None, :]
    conv_out = jax.nn.silu(conv_out + p["conv_b"].astype(dtype)[None, None])
    xs = conv_out[..., :di]
    b_proj = conv_out[..., di:di + g * s]
    c_proj = conv_out[..., di + g * s:]

    dt = jax.nn.softplus(dt + p["dt_bias"][None, None])       # (B,T,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    log_a = dt * a[None, None]                                # (B,T,H)

    xh = xs.reshape(bsz, t, h, p_dim).astype(jnp.float32)
    xbar = xh * dt[..., None]
    rep = h // g
    b_in = jnp.repeat(b_proj.reshape(bsz, t, g, s), rep,
                      axis=2).astype(jnp.float32)
    c_in = jnp.repeat(c_proj.reshape(bsz, t, g, s), rep,
                      axis=2).astype(jnp.float32)

    # Score all T positions against the carried state (the intra-chunk
    # quadratic dual + the inter-chunk state term of ssd_chunked).
    lcum = jnp.cumsum(log_a, axis=1)                          # (B,T,H)
    dot = einsum_f32("bihs,bjhs->bhij", c_in, b_in)
    dec = jnp.exp(jnp.clip(lcum[:, :, None] - lcum[:, None, :],
                           -60.0, 0.0)).transpose(0, 3, 1, 2)
    tri = jnp.tril(jnp.ones((t, t), jnp.float32))
    scores = dot * dec * tri[None, None]
    y_intra = einsum_f32("bhij,bjhp->bihp", scores, xbar)
    ein = jnp.exp(jnp.clip(lcum, -60.0, 0.0))
    y_inter = einsum_f32("bihs,bhsp->bihp", c_in,
                         cache["state"]) * ein[..., None]
    y = y_intra + y_inter

    # Partial commit: only tokens j < commit_len[b] enter the recurrence.
    cl = commit_lengths(commit_len, row_mask, t) if commit_len is not None \
        else commit_lengths(jnp.full((bsz,), t, jnp.int32), row_mask, t)
    lcum0 = jnp.concatenate([jnp.zeros((bsz, 1, h), jnp.float32), lcum], 1)
    l_tot = jnp.take_along_axis(lcum0, cl[:, None, None].repeat(h, 2),
                                axis=1)[:, 0]                 # (B,H)
    take = (jnp.arange(t)[None, :] < cl[:, None])             # (B,T)
    carry_dec = jnp.where(take[..., None],
                          jnp.exp(jnp.clip(l_tot[:, None] - lcum,
                                           -60.0, 0.0)), 0.0)
    state = cache["state"] * \
        jnp.exp(jnp.clip(l_tot, -60.0, 0.0))[:, :, None, None] + \
        jnp.einsum("bjhs,bjh,bjhp->bhsp", b_in, carry_dec, xbar)
    # Conv window commit: rows cl .. cl+W-2 of the concatenation are the
    # last W-1 inputs a sequential decode of the accepted prefix saw.
    idx = cl[:, None] + jnp.arange(wdt - 1)[None, :]          # (B,W-1)
    conv_cache = jnp.take_along_axis(
        window, idx[:, :, None].astype(jnp.int32), axis=1)
    if row_mask is not None:
        keep = row_mask[:, None, None]
        state = jnp.where(keep[..., None], state, cache["state"])
        conv_cache = jnp.where(keep, conv_cache,
                               cache["conv"].astype(dtype))

    y = y + xh * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(bsz, t, di).astype(dtype)
    y = y * jax.nn.silu(z)
    y = apply_norm(p["norm"], y, "rmsnorm")
    out = dense(p["out_w"], y, dtype)
    new_cache = {"state": state, "conv": conv_cache.astype(cfg.cdtype)}
    return out, new_cache


def ssm_decode(p, x, cache, cfg):
    """One-token step.  x: (B, 1, D)."""
    di, h, p_dim, s, g = _dims(cfg)
    bsz = x.shape[0]
    dtype = cfg.cdtype
    z = dense(p["w_z"], x, dtype)
    xs = dense(p["w_x"], x, dtype)
    b_proj = dense(p["w_B"], x, dtype)
    c_proj = dense(p["w_C"], x, dtype)
    dt = dense(p["w_dt"], x, dtype).astype(jnp.float32)

    conv_in = jnp.concatenate([xs, b_proj, c_proj], -1)      # (B,1,Cd)
    window = jnp.concatenate([cache["conv"].astype(dtype), conv_in], 1)
    conv_out = jnp.einsum("bwc,wc->bc", window.astype(dtype),
                          p["conv_w"].astype(dtype)) + p["conv_b"].astype(dtype)
    conv_out = jax.nn.silu(conv_out)[:, None]
    xs = conv_out[..., :di]
    b_proj = conv_out[..., di:di + g * s]
    c_proj = conv_out[..., di + g * s:]

    dt = jax.nn.softplus(dt + p["dt_bias"][None, None])[:, 0]       # (B,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    decay = jnp.exp(dt * a[None])                                    # (B,H)

    xh = xs.reshape(bsz, h, p_dim).astype(jnp.float32)
    xbar = xh * dt[..., None]
    rep = h // g
    b_in = jnp.repeat(b_proj.reshape(bsz, g, s), rep, axis=1).astype(jnp.float32)
    c_in = jnp.repeat(c_proj.reshape(bsz, g, s), rep, axis=1).astype(jnp.float32)

    state = cache["state"] * decay[..., None, None] + \
        jnp.einsum("bhs,bhp->bhsp", b_in, xbar)
    y = jnp.einsum("bhs,bhsp->bhp", c_in, state)
    y = y + xh * p["d_skip"].astype(jnp.float32)[None, :, None]
    y = y.reshape(bsz, 1, di).astype(dtype)
    y = y * jax.nn.silu(z)
    y = apply_norm(p["norm"], y, "rmsnorm")
    out = dense(p["out_w"], y, dtype)
    new_cache = {"state": state, "conv": window[:, 1:].astype(cfg.cdtype)}
    return out, new_cache
