"""Logical-axis sharding rules (MaxText-style) for params and activations.

Models never name mesh axes directly — they request *logical* axes
("act_batch", "heads", "ff", ...) via :func:`constrain`, and parameter
sharding is derived from path-based rules in :func:`param_specs`.  The
mapping logical->mesh is installed per run (train/serve/dryrun) with
:func:`logical_rules`; outside any rules context every constraint is a
no-op, so single-device smoke tests run the exact same model code.

Mesh axes: ("pod",) "data", "model".  Policy per arch (cfg.attn_shard):
* tp_heads  — attention heads over 'model' (Megatron TP);
* context   — heads not divisible by the model axis: softmax attention is
  sequence-sharded over 'model', LLN attention is replicated over 'model'
  (linear attention is ~1% of FLOPs, see DESIGN.md §4);
* replicate — model axis unused by attention (tiny models).

Every spec is divisibility-checked against the actual dim size and mesh —
axes that do not divide are dropped (never a sharding error, possibly a
less-sharded layout; the dry-run records what was actually achieved).
"""
from __future__ import annotations

import contextlib
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ACTIVE: dict | None = None
_MESH: Mesh | None = None


@contextlib.contextmanager
def logical_rules(mesh: Mesh, rules: dict[str, tuple]):
    """Install a logical->mesh axis mapping (and the mesh) for model code."""
    global _ACTIVE, _MESH
    prev, prev_mesh = _ACTIVE, _MESH
    _ACTIVE, _MESH = rules, mesh
    try:
        yield
    finally:
        _ACTIVE, _MESH = prev, prev_mesh


def current_mesh() -> Optional[Mesh]:
    return _MESH


def per_device(fn, axes, **args):
    """Call ``fn(**args)`` once per device of the active mesh, each device
    on its own block of every leaf.

    XLA cannot partition a Pallas TPU kernel, so under a mesh of more than
    one device the code that calls one runs inside ``shard_map``.  The
    caller names the layout: ``axes(name)`` gives the logical axes (as
    :func:`constrain` takes them) of the leaf whose path ends in ``name``,
    in ``args`` and in the dict ``fn`` returns, aligned to the leaf's
    trailing dims (so ``("act_batch", "heads")`` also fits an (H,) leaf).
    A logical axis splits over its rule's mesh axes only where they divide
    every dim that axis names in every leaf, so all blocks agree; else it
    is replicated.  ``fn`` must compute each block of a split axis on its
    own (rows and heads of attention do).  Outside a mesh, or on one
    device, ``fn`` runs as is.
    """
    mesh = _MESH
    if mesh is None or mesh.size == 1:
        return fn(**args)

    def local(a):
        with logical_rules(None, None):      # constraints name global axes
            return fn(**a)

    out = jax.eval_shape(local, args)
    flat, tree = jax.tree_util.tree_flatten_with_path((args, out))
    names = []
    for path, leaf in flat:
        key = path[-1]
        name = getattr(key, "key", getattr(key, "name", None))
        ndim = len(jnp.shape(leaf))
        want = tuple(axes(name))
        if ndim > len(want):
            raise ValueError(f"leaf {name!r} has rank {ndim} but only "
                             f"{len(want)} logical axes")
        names.append(want[len(want) - ndim:])
    dims: dict = {}
    for (_, leaf), ns in zip(flat, names):
        for n, d in zip(ns, jnp.shape(leaf)):
            if n is not None:
                dims.setdefault(n, []).append(d)
    split, used = {}, set()
    for n, sizes in dims.items():
        rule = (_ACTIVE or {}).get(n)
        kept = []
        for a in (rule if isinstance(rule, tuple) else (rule,)):
            if a is None or a in used or a not in mesh.axis_names:
                continue
            size = _axis_size(mesh, tuple(kept) + (a,))
            if all(d % size == 0 for d in sizes):
                kept.append(a)
        used.update(kept)
        split[n] = tuple(kept) if len(kept) > 1 else (kept[0] if kept
                                                      else None)
    specs = tree.unflatten(
        [P(*(split.get(n) for n in ns)) for ns in names])
    return jax.shard_map(local, mesh=mesh, in_specs=(specs[0],),
                         out_specs=specs[1], check_vma=False)(args)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    size = 1
    for a in axes:
        size *= sizes.get(a, 1)   # absent axes (e.g. 'pod' on 1-pod) drop
    return size


def fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec axes whose mesh size does not divide the dim size, and
    de-duplicate mesh axes across dims (first occurrence wins)."""
    out = []
    used: set = set()
    for dim, axes in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is None:
            out.append(None)
            continue
        cand = axes if isinstance(axes, tuple) else (axes,)
        kept = []
        for a in cand:
            if a in used or a not in mesh.axis_names:
                continue
            sz = _axis_size(mesh, tuple(kept) + (a,))
            if dim % sz == 0:
                kept.append(a)
                used.add(a)
        out.append(tuple(kept) if len(kept) > 1 else
                   (kept[0] if kept else None))
    return P(*out)


def constrain(x: jnp.ndarray, *logical_axes) -> jnp.ndarray:
    """Annotate activation sharding by logical axis names (no-op w/o rules,
    and inside :func:`per_device`, where a custom VJP's backward may be
    traced after that context has closed)."""
    if _ACTIVE is None or _MESH is None \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return x
    axes = tuple(_ACTIVE.get(a) if isinstance(a, str) else a
                 for a in logical_axes)
    spec = fit_spec(P(*axes), x.shape, _MESH)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, spec))


# ---------------------------------------------------------------------------
# Parameter sharding from path rules.
# ---------------------------------------------------------------------------

# (regex on 'a/b/c' path, spec builder).  First match wins.  Specs are
# written for the *unstacked* trailing dims; stacked layer params get a
# leading None automatically (detected by the 'layers' path component).
# FSDP axis is ('pod', 'data'): on the single-pod mesh 'pod' is absent and
# drops out; on the multi-pod mesh params/optimizer shard over both (ZeRO
# over DCN — what makes the 236B MoE fit, see EXPERIMENTS.md §Dry-run).
_FSDP = ("pod", "data")
_PARAM_RULES: list[tuple[str, tuple]] = [
    (r"embed/table$",        ("model", _FSDP)),        # (V, D)
    (r"lm_head$",            (_FSDP, "model")),        # (D, V)
    (r"(router|gate)_w$",    (_FSDP, None)),           # (D, E)
    (r"exp_(wi|wi_gate|wi_up)$", ("model", _FSDP, None)),     # (E, D, F)
    (r"exp_wo$",             ("model", None, _FSDP)),         # (E, F, D)
    (r"(o_w|wo|wo_shared|out_w)$", ("model", _FSDP)),         # (F|HD, D)
    (r"(conv_w)$",           (None, None)),
    (r"(a_log|d_skip|dt_bias)$", (None,)),
    (r"\w*(scale|bias)$",    (None,)),
    (r".*",                  (_FSDP, "model")),        # generic 2D (D, F)
]


def _spec_for_path(path: str, shape: tuple[int, ...]) -> P:
    stacked = path.startswith("layers/") or "/layers/" in path
    ndim = len(shape)
    for pat, axes in _PARAM_RULES:
        if re.search(pat, path):
            base = list(axes)
            break
    # Adjust rank: pad/truncate the trailing spec to the unstacked rank.
    core_rank = ndim - 1 if stacked else ndim
    if len(base) < core_rank:
        base = [None] * (core_rank - len(base)) + base
    base = base[-core_rank:] if core_rank else []
    if stacked:
        base = [None] + base
    return P(*base)


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(params, mesh: Mesh):
    """PartitionSpec pytree for a parameter tree (divisibility-fitted)."""
    def leaf_spec(kp, leaf):
        spec = _spec_for_path(_path_str(kp), leaf.shape)
        return fit_spec(spec, leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def param_shardings(params, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mesh))


# ---------------------------------------------------------------------------
# Per-arch logical rule tables.
# ---------------------------------------------------------------------------

def make_rules(cfg, *, multi_pod: bool, serve: bool = False) -> dict:
    """Logical->mesh mapping for one arch config (see module docstring).

    Key activations axes:
    * act_seq  — the residual stream's sequence axis *between* blocks.
      'model' = Megatron-style sequence parallelism (the remat stash and
      norms are 1/model_size per device; attention/MLP gather as needed).
      Disabled for SSM families whose chunk scan would slice a sharded dim.
    * attn_seq — the sequence axis *inside* attention: 'model' only for
      context-parallel softmax archs; None otherwise (TP archs shard heads,
      and LLN attention is cheap enough to replicate for CP archs).
    * act_seq_cache — decode KV-cache sequence axis: 'model' when kv heads
      cannot use the model axis (flash-decode style cache sharding).
    """
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    rules: dict[str, object] = {
        "act_batch": batch_axes,
        "act_seq": "model",
        "attn_seq": None,
        "act_seq_cache": None,
        "embed": None,
        "ff": "model",
        "vocab": "model",
        "kv_heads": "model",
        "heads": "model",
        "head_dim": None,
        "experts": "model",
        "state_d": None,
    }
    if cfg.attn_shard == "context":
        rules["heads"] = None
        rules["kv_heads"] = None
        rules["act_seq_cache"] = "model"
        if cfg.attn_impl == "softmax":
            rules["attn_seq"] = "model"
    elif cfg.attn_shard == "replicate":
        rules["heads"] = None
        rules["kv_heads"] = None
        # Tiny models: fold the model axis into batch when it divides.
        rules["act_batch"] = batch_axes + ("model",)
        rules["act_seq"] = None
    if cfg.family in ("ssm", "hybrid"):
        rules["act_seq"] = None     # SSD chunk scan must not slice a
        rules["attn_seq"] = None    # 'model'-sharded sequence dim
    return rules
