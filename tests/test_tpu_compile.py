"""Compile-only guards: every main-path Pallas kernel through the TPU v5e
compiler at real widths.

Interpret mode accepts block shapes and fast-memory use that the chip's
compiler refuses, so the CPU parity tests cannot catch a kernel that will
not compile on the chip.  These tests compile each kernel for one chip of a
described (not attached) ``v5e:2x2`` topology and check that the program
holds the Mosaic kernel (``tpu_custom_call``).  Nothing runs.  One more
guard compiles the pool's decode segment program and reads its loops.

Shapes are the serving/training ones: 128 (batch*head) rows of 1024 tokens
in 256-token blocks, head dims 64 (stablelm-1.6b, roberta-lln) and 128 with
GQA r = 8 (yi-9b).  Dtypes follow ``kernels/ops.py``: the pre-scaled
``qs``/``ks`` are fp32, model-layout ``q``/``k``/``v`` and cotangents bf16.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_diag import block_diag_bwd_pallas, block_diag_pallas
from repro.kernels.lln_attention import (lln_bidir_pallas, lln_causal_pallas,
                                         lln_decode_pallas,
                                         lln_diag_fused_pallas)
from repro.kernels.lln_backward import (lln_bidir_bwd_pallas,
                                        lln_causal_bwd_pallas,
                                        lln_diag_fused_bwd_pallas)
from repro.kernels.loglinear import loglin_causal_pallas
from repro.kernels.ssd import ssd_pallas

BH, N, BLK = 128, 1024, 256
F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _cases(d, r):
    """(name, fn, arg shapes) for head dim ``d`` and GQA ratio ``r``."""
    bg = BH // r
    q, kv = ((BH, N, d), F32), ((bg, N, d), F32)
    qm, km, vm = ((BH, N, d), BF16), ((bg, N, d), BF16), ((bg, N, d), BF16)
    g = ((BH, N, d), BF16)

    def causal_pair(qs, ks, v, gg):
        o, den = lln_causal_pallas(qs, ks, v, r=r, blk=BLK, return_res=True)
        return lln_causal_bwd_pallas(qs, ks, v, gg, o, den, r=r, blk=BLK)

    def bidir_pair(qs, ks, v, gg):
        o, s, z, den = lln_bidir_pallas(qs, ks, v, r=r, blk=BLK,
                                        return_res=True)
        return lln_bidir_bwd_pallas(qs, ks, v, gg, o, den, s, z, r=r,
                                    blk=BLK)

    def fused_pair(qs, ks, qq, kk, v, gg):
        o, den = lln_diag_fused_pallas(qs, ks, qq, kk, v, r=r, blk=BLK,
                                       return_res=True)
        return lln_diag_fused_bwd_pallas(qs, ks, qq, kk, v, gg, o, den, r=r,
                                         blk=BLK)

    t = 16   # ops pads a decode chunk to the bf16 sublane multiple
    return [
        ("lln_prefill_state",
         lambda qs, ks, v: lln_causal_pallas(qs, ks, v, r=r, blk=BLK,
                                             return_state=True),
         [q, kv, vm]),
        ("lln_decode",
         lambda qs, ks, v, s0, z0: lln_decode_pallas(qs, ks, v, s0, z0, r=r),
         [((BH, t, d), F32), ((bg, t, d), F32), ((bg, t, d), BF16),
          ((BH, d, d), F32), ((BH, 1, d), F32)]),
        ("block_diag",
         lambda qq, kk, v: block_diag_pallas(qq, kk, v, r=r, blk=BLK,
                                             causal=True),
         [qm, km, vm]),
        ("loglin_prefill_state",
         lambda qs, ks, v: loglin_causal_pallas(qs, ks, v, num_scales=4,
                                                scale_decay=0.5, r=r,
                                                blk=BLK, return_state=True),
         [q, kv, vm]),
        ("lln_bidir_fwd", lambda qs, ks, v: lln_bidir_pallas(
            qs, ks, v, r=r, blk=BLK), [q, kv, vm]),
        ("lln_causal_res_fwd_bwd", causal_pair, [q, kv, vm, g]),
        ("lln_bidir_res_fwd_bwd", bidir_pair, [q, kv, vm, g]),
        ("lln_diag_fused_res_fwd_bwd", fused_pair, [q, kv, qm, km, vm, g]),
        ("block_diag_bwd",
         lambda qq, kk, v, gg: block_diag_bwd_pallas(qq, kk, v, gg, r=r,
                                                     blk=BLK, causal=False),
         [qm, km, vm, g]),
        ("ssd",
         lambda la, xb, b, c: ssd_pallas(la, xb, b, c, r=r, blk=BLK),
         [((BH, 1, N), F32), ((BH, N, d), F32), ((bg, N, 128), BF16),
          ((bg, N, 128), BF16)]),
    ]


_NAMES = [c[0] for c in _cases(64, 1)]


@pytest.mark.parametrize("d,r", [(64, 1), (128, 8)], ids=["d64", "d128-r8"])
@pytest.mark.parametrize("name", _NAMES)
def test_kernel_compiles_for_v5e(one_chip, name, d, r):
    _, fn, shapes = next(c for c in _cases(d, r) if c[0] == name)
    _compile(fn, one_chip, *shapes)


def _loop_computations(hlo: str, fused: bool = True) -> list:
    """The instruction lines of every computation a while loop runs, with
    the computations those call (fusions, nested loops), transitively.
    ``fused=False`` leaves out the fusions' own computations, so each line
    is an instruction whose result the program keeps in memory."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line)
    calls = {n: set(re.findall(r"(?:body|condition|calls|to_apply)="
                               r"%([\w.\-]+)", "\n".join(
                                   line for line in body
                                   if fused or " fusion(" not in line)))
             for n, body in comps.items()}
    todo = [b for body in comps.values() for line in body
            for b in re.findall(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo.extend(calls.get(c, ()))
    return [line for c in seen for line in comps.get(c, ())]


def _segment_hlo(sharding, cfg, slots):
    """The compiled text of the pool's decode segment program for ``cfg``
    at ``slots`` slots and max_len 1024, on the chip of ``sharding``."""
    from repro.launch.steps import make_pool_setup

    setup = make_pool_setup(cfg, None, slots=slots, max_len=1024, segment=8)
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)
    row = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=sharding)
    return setup.segment_fn.lower(
        on_chip(jax.eval_shape(setup.model.init, jax.random.PRNGKey(0))),
        on_chip(jax.eval_shape(setup.cache_init)), row(jnp.int32),
        row(jnp.int32), row(jnp.int32), row(jnp.bool_),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding),
    ).compile().as_text()


def _results(lines, opcodes):
    """(shape, line) of each instruction in ``lines`` whose opcode is one of
    ``opcodes``: its result's dtype and dims, the dims of size 1 dropped."""
    out = []
    for line in lines:
        m = re.search(r"= (\w+)\[([\d,]*)\]\S* (\w[\w-]*)\(", line)
        if m and m.group(3) in opcodes:
            dims = tuple(int(x) for x in m.group(2).split(",") if x not in
                         ("", "1"))
            out.append(((m.group(1),) + dims, line.strip()[:160]))
    return out


@pytest.mark.parametrize("impl", ["lln_diag", "lln"])
def test_pool_segment_carries_caches_in_place(one_chip, impl):
    """The pool's decode segment at stablelm-1.6b widths (2 layers, 16
    slots, max_len 1024) copies neither the stacked diag tails nor the
    stacked LLN state inside its loops: the layer loop writes each layer's
    state into the carried stack and the tails take one row per step."""
    from repro.configs.registry import get_config

    layers, slots = 2, 16
    cfg = get_config("stablelm-1.6b", attn_impl=impl, n_layers=layers)
    hlo = _segment_hlo(one_chip, cfg, slots)
    h, d, blk = cfg.n_heads, cfg.hd, cfg.diag_block
    stacked = (f"bf16[{layers},{slots},{blk},{h},{d}]",
               f"f32[{layers},{slots},{h},{d},{d}]")
    body = _loop_computations(hlo)
    copies = [line.strip()[:160] for line in body
              if re.search(r"= (\S+?)\{[^}]*\} copy\(", line)
              and re.search(r"= (\S+?)\{", line).group(1) in stacked]
    assert not copies, copies
    assert any(" scatter(" in line for line in body)


@pytest.mark.parametrize("arch,slots,overrides", [
    ("stablelm-1.6b", 16, {}),
    ("yi-9b", 32, {"n_heads": 8, "n_kv_heads": 1, "d_ff": 2752}),
], ids=["stablelm-1.6b", "yi-9b-per-chip"])
def test_pool_segment_reads_diag_tail_in_place(one_chip, arch, slots,
                                               overrides):
    """Single-token LLN+Diag decode reads each layer's diag tail where the
    stacked cache holds it: no fusion or copy inside the segment's loops
    makes a per-layer tail (slots, block, kv heads, head size).  Shapes are
    one chip's: stablelm-1.6b whole (32 kv heads of 64), and yi-9b as one
    of four chips holds it under ``tp_heads`` (8 query heads over 1 kv
    head of 128)."""
    from repro.configs.registry import get_config

    cfg = get_config(arch, attn_impl="lln_diag", n_layers=2, **overrides)
    hlo = _segment_hlo(one_chip, cfg, slots)
    g, d, blk = cfg.n_kv_heads, cfg.hd, cfg.diag_block
    tail = tuple(x for x in ("bf16", slots, blk, g, d) if x != 1)
    made = [line for shape, line in _results(
                _loop_computations(hlo, fused=False), ("fusion", "copy"))
            if shape == tail]
    assert not made, made
