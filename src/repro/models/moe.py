"""Top-k routed Mixture-of-Experts FFN with expert parallelism.

Distribution strategy (see DESIGN.md §4): activations between blocks are
replicated over the 'model' axis (standard TP), so every model shard already
holds all tokens of its data shard.  Each model shard therefore:

  1. routes all local tokens (router is replicated),
  2. gathers the tokens assigned to *its own* expert slice into a
     capacity-bounded (E_loc, C, D) buffer (sort-based dispatch — no
     (T, E, C) one-hot einsum, so dispatch FLOPs stay negligible),
  3. runs its experts, scatters weighted outputs back to (T, D),
  4. psum over 'model' combines the contributions — the same collective
     class a TP-sharded dense MLP would need, so EP costs no extra
     collective; the shared experts join the same psum as a TP-sharded
     dense MLP computing a 'model'-sharded d_ff slice.

Expert weights are sharded (E over 'model') x (D over 'data'); the 'data'
shards are all-gathered just-in-time inside the shard_map (FSDP).

When no mesh is active (smoke tests), the same math runs in a single-device
local path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from .layers import dense_init, trunc_normal


def moe_init(key, cfg):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    ks = jax.random.split(key, 7)
    std = d ** -0.5
    p = {"router_w": trunc_normal(ks[0], (d, e), 0.02, jnp.float32)}
    p["exp_wi_gate"] = trunc_normal(ks[1], (e, d, f), std, cfg.pdtype)
    p["exp_wi_up"] = trunc_normal(ks[2], (e, d, f), std, cfg.pdtype)
    p["exp_wo"] = trunc_normal(ks[3], (e, f, d), f ** -0.5, cfg.pdtype)
    if cfg.n_shared_experts:
        fs = cfg.expert_d_ff * cfg.n_shared_experts
        p["shared_wi_gate"] = dense_init(ks[4], d, fs, cfg.pdtype)
        p["shared_wi_up"] = dense_init(ks[5], d, fs, cfg.pdtype)
        p["shared_wo"] = dense_init(ks[6], fs, d, cfg.pdtype)
    return p


def _route(x, router_w, top_k):
    """x: (T, D) -> (expert_idx (T, K), weights (T, K), aux_loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss (scatter-add, no (M, E) one-hot).
    e = router_w.shape[1]
    t = x.shape[0]
    me = jnp.mean(probs, axis=0)
    load = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / t
    aux = e * jnp.sum(me * load)
    return idx, w, aux


def _positions_in_expert(flat_e: jnp.ndarray, num_experts: int):
    """Rank of each routed slot within its expert (sort-based, O(M log M))."""
    m = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(num_experts), side="left")
    rank_sorted = jnp.arange(m) - starts[sorted_e]
    pos = jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    return pos


def _expert_ffn(xg, wi_gate, wi_up, wo, act: str, dtype):
    """xg: (E, C, D); weights: (E, D, F)/(E, F, D)."""
    g = jnp.einsum("ecd,edf->ecf", xg.astype(dtype), wi_gate.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", xg.astype(dtype), wi_up.astype(dtype))
    g = jax.nn.silu(g) if act.startswith("silu") else jax.nn.gelu(g)
    return jnp.einsum("ecf,efd->ecd", g * u, wo.astype(dtype))


def _moe_local(x, p, cfg, e0: int, e_loc: int, dtype):
    """Dispatch + expert compute for experts [e0, e0+e_loc) on tokens x (T,D).

    Returns this shard's *partial* output (T, D) (sum over shards completes
    the token outputs) and the aux loss.
    """
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    idx, w, aux = _route(x, p["router_w"], k)                # (T,K)
    flat_e = idx.reshape(-1)                                  # (M=T*K,)
    pos = _positions_in_expert(flat_e, e)
    cap = max(int(t * k * cfg.capacity_factor / e), 1)

    local = (flat_e >= e0) & (flat_e < e0 + e_loc) & (pos < cap)
    slot = jnp.where(local, (flat_e - e0) * cap + pos, e_loc * cap)
    # Gather tokens into (E_loc*C (+1 dump), D).
    tok_of_slot = jnp.zeros((e_loc * cap + 1,), jnp.int32).at[slot].set(
        jnp.repeat(jnp.arange(t, dtype=jnp.int32), k), mode="drop")
    filled = jnp.zeros((e_loc * cap + 1,), jnp.bool_).at[slot].set(
        local, mode="drop")
    xg = jnp.take(x, tok_of_slot, axis=0) * filled[:, None]
    xg = xg[:e_loc * cap].reshape(e_loc, cap, d)

    # Weights are always the *local* expert slice (shape E_loc, ...); e0 only
    # offsets the routing ids.  The meshless path passes e0=0, E_loc=E.
    assert p["exp_wi_gate"].shape[0] == e_loc, \
        (p["exp_wi_gate"].shape, e_loc)
    y = _expert_ffn(xg, p["exp_wi_gate"], p["exp_wi_up"], p["exp_wo"],
                    cfg.act, dtype)                           # (E_loc, C, D)

    # Scatter back with routing weights.
    y_flat = jnp.concatenate(
        [y.reshape(e_loc * cap, d), jnp.zeros((1, d), y.dtype)], 0)
    y_slots = jnp.take(y_flat, jnp.minimum(slot, e_loc * cap), axis=0)
    wv = (w.reshape(-1) * local.astype(jnp.float32))[:, None]
    contrib = (y_slots.astype(jnp.float32) * wv).reshape(t, k, d).sum(1)
    return contrib.astype(dtype), aux


def moe_apply(p, x, cfg) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, N, D) -> (out, aux_loss).  Mesh-aware (see module docstring)."""
    b, n, d = x.shape
    dtype = cfg.cdtype
    mesh = shd.current_mesh()
    xt = x.reshape(b * n, d)

    if mesh is None or "model" not in mesh.axis_names:
        out, aux = _moe_local(xt, p, cfg, 0, cfg.n_experts, dtype)
        if cfg.n_shared_experts:
            out = out + _shared_ffn(p, xt, cfg, dtype)
        return out.reshape(b, n, d), aux

    ep = mesh.devices.shape[list(mesh.axis_names).index("model")]
    e_loc = cfg.n_experts // ep
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # Token rows shard over the largest batch-axis prefix that divides T
    # (decode with global_batch=1 replicates the single token row).
    batch_axes = ()
    t_total, used = b * n, 1
    for a in fsdp:
        if t_total % (sizes[a] * used) == 0:
            batch_axes = batch_axes + (a,)
            used *= sizes[a]

    # Combine strategy: when the sequence divides the model axis, the
    # expert-partial sums are reduce-SCATTERED into the sequence-parallel
    # layout (half the bytes of a full all-reduce, and the residual stream
    # is already seq-sharded so no re-shard follows).  Decode (n==1) and
    # odd lengths fall back to a full psum.
    scatter = (n % ep == 0) and n > 1

    def shard_fn(xt, rw, wig, wiu, wog, swg=None, swu=None, swo=None):
        # xt: (T_loc, D) full-D tokens; expert weights sharded E/'model',
        # D/fsdp -> gather the FSDP shards just-in-time.
        pp = {"router_w": rw,
              "exp_wi_gate": jax.lax.all_gather(wig, fsdp, axis=1, tiled=True),
              "exp_wi_up": jax.lax.all_gather(wiu, fsdp, axis=1, tiled=True),
              "exp_wo": jax.lax.all_gather(wog, fsdp, axis=2, tiled=True)}
        midx = jax.lax.axis_index("model")
        out, aux = _moe_local(xt, pp, cfg, midx * e_loc, e_loc, dtype)
        if swg is not None:
            # Shared experts as a TP-sharded dense MLP ('model' shards f).
            sw = {"shared_wi_gate": jax.lax.all_gather(swg, fsdp, axis=0, tiled=True),
                  "shared_wi_up": jax.lax.all_gather(swu, fsdp, axis=0, tiled=True),
                  "shared_wo": jax.lax.all_gather(swo, fsdp, axis=1, tiled=True)}
            out = out + _shared_ffn(sw, xt, cfg, dtype)
        aux = jax.lax.pmean(aux, ("model",) + batch_axes)
        if scatter:
            out = out.reshape(-1, n, d)
            out = jax.lax.psum_scatter(out, "model", scatter_dimension=1,
                                       tiled=True)
            return out, aux
        return jax.lax.psum(out, "model"), aux

    espec = P("model", fsdp, None)
    ospec = P("model", None, fsdp)
    args = [xt, p["router_w"], p["exp_wi_gate"], p["exp_wi_up"], p["exp_wo"]]
    in_specs = [P(batch_axes, None), P(None, None), espec, espec, ospec]
    if cfg.n_shared_experts:
        args += [p["shared_wi_gate"], p["shared_wi_up"], p["shared_wo"]]
        in_specs += [P(fsdp, "model"), P(fsdp, "model"), P("model", fsdp)]
    out_spec = (P(batch_axes, "model", None) if scatter
                else P(batch_axes, None))
    smap = jax.shard_map(shard_fn, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=(out_spec, P()), check_vma=False)
    out, aux = smap(*args)
    return out.reshape(b, n, d), aux


def _shared_ffn(p, xt, cfg, dtype):
    g = jnp.einsum("td,df->tf", xt.astype(dtype),
                   p["shared_wi_gate"].astype(dtype))
    u = jnp.einsum("td,df->tf", xt.astype(dtype),
                   p["shared_wi_up"].astype(dtype))
    g = jax.nn.silu(g) if cfg.act.startswith("silu") else jax.nn.gelu(g)
    return jnp.einsum("tf,fd->td", g * u, p["shared_wo"].astype(dtype))
