"""Operations and bytes: per model token, and per kernel call.

Model counts follow the usual convention: a matrix product of an (m, k)
and a (k, n) operand is 2mkn operations; a token's forward pass costs
twice the parameters it multiplies by, plus attention's own products;
training costs three forward passes (the backward is two), with nothing
counted for rematerialisation.  LLN attention per token and head: the
state update and its read (2 D Dv each), and the diagonal softmax over
the keys of the token's block (2 D and 2 Dv per key).

Kernel counts are those of one ``pallas_call`` at the shapes the cells
run, from its block specs: the operations its matrix products perform and
the bytes it must move between HBM and the core at least once (every
input read, every output written).  ``least_seconds`` is the larger of
operations over peak rate and bytes over HBM bandwidth.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak rates of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to {PEAKS.name} with its source")
    return table[device_kind]


def layer_params(sz: dict) -> int:
    """Parameters one layer multiplies each token by."""
    d, h, g, hd, ff = (sz["d"], sz["heads"], sz["kv_heads"], sz["head_dim"],
                       sz["d_ff"])
    mlp = (3 if sz["mlp"] == "swiglu" else 2) * d * ff
    return d * (h + 2 * g) * hd + h * hd * d + mlp


def attention_per_token(sz: dict, keys_in_block: float) -> float:
    """LLN state update and read plus the diagonal softmax over
    ``keys_in_block`` keys, for one token in one layer, all heads."""
    hd = sz["head_dim"]
    return sz["heads"] * (4 * hd * hd + 4 * hd * keys_in_block)


def head_flops(sz: dict) -> int:
    """The output projection of one position onto the vocabulary."""
    return 2 * sz["d"] * sz["vocab"]


def causal_block_mean(sz: dict) -> float:
    """Mean keys a causal token sees in its diagonal block."""
    return (sz["diag_block"] + 1) / 2


def prefill_flops(sz: dict, prompt_len: int) -> float:
    """Forward over a prompt; logits at its last position only."""
    per_tok = 2 * layer_params(sz) + attention_per_token(
        sz, causal_block_mean(sz))
    return sz["layers"] * per_tok * prompt_len + head_flops(sz)


def decode_flops(sz: dict) -> float:
    """One decoded token (its position's block half full on average)."""
    per_tok = 2 * layer_params(sz) + attention_per_token(
        sz, causal_block_mean(sz))
    return sz["layers"] * per_tok + head_flops(sz)


def train_flops(sz: dict, tokens: int) -> float:
    """One MLM training step over ``tokens`` bidirectional positions
    (logits at every position)."""
    per_tok = sz["layers"] * (2 * layer_params(sz) + attention_per_token(
        sz, sz["diag_block"])) + head_flops(sz)
    return 3.0 * per_tok * tokens


# ---------------------------------------------------------------------------
# Kernels.  Shapes: bh query rows (batch x heads), bg kv rows, n positions,
# d = dv = head size, blk the block.  qs/ks reach the kernels as float32,
# q/k/v/out in the compute dtype (2 bytes), states and normalisers float32.
# Where a dtype is not certain the smaller is counted, so that a share of
# the roofline can only be understated.
# ---------------------------------------------------------------------------

def _k(flops: float, nbytes: float) -> dict:
    return {"flops": float(flops), "bytes": float(nbytes)}


def lln_causal_call(bh, bg, n, d, blk, state=True) -> dict:
    """Causal LLN prefill kernel (chunked, VMEM-resident state)."""
    flops = bh * (n // blk) * (2 * blk * blk * d * 2 + 2 * blk * d * d * 2)
    nbytes = 4 * bh * n * d + 4 * bg * n * d + 2 * bg * n * d \
        + 2 * bh * n * d + (4 * bh * (d * d + d) if state else 0)
    return _k(flops, nbytes)


def block_diag_call(bh, bg, n, d, blk) -> dict:
    """Block-diagonal softmax forward: scores and values per block."""
    flops = bh * (n // blk) * (2 * blk * blk * d * 2)
    nbytes = 2 * bh * n * d * 2 + 2 * bg * n * d * 2
    return _k(flops, nbytes)


def lln_decode_call(bh, bg, t, d) -> dict:
    """LLN decode-chunk kernel over ``t`` (padded) tokens per row: reads
    and writes the (d, d) state of every row and head."""
    flops = bh * (2 * t * t * d * 2 + 2 * t * d * d * 2 + 2 * t * d)
    nbytes = 4 * bh * t * d + 4 * bg * t * d + 2 * bg * t * d \
        + 2 * bh * t * d + 2 * 4 * bh * (d * d + d)
    return _k(flops, nbytes)


def lln_reduce_call(bg, n, d) -> dict:
    """Bidirectional LLN summary state S = sum phi(k) v, z = sum phi(k)."""
    return _k(bg * 2 * n * d * d, 4 * bg * n * d + 2 * bg * n * d
              + 4 * bg * (d * d + d))


def lln_apply_call(bh, n, d) -> dict:
    """Bidirectional LLN output phi(q) S / phi(q) z."""
    return _k(bh * (2 * n * d * d + 2 * n * d),
              4 * bh * n * d + 4 * bh * (d * d + d) + 2 * bh * n * d)


def block_diag_bwd_call(bh, n, d, blk) -> dict:
    """Block-diagonal softmax backward: recompute scores, then dV, dP,
    dQ and dK, each a (blk x blk x d) product per block."""
    flops = bh * (n // blk) * (2 * blk * blk * d * 5)
    nbytes = 2 * bh * n * d * 4 + 2 * bh * n * d * 3
    return _k(flops, nbytes)


def bidir_dq_call(bh, n, d) -> dict:
    """Bidirectional LLN dq: (g / den) S^T and its normaliser term."""
    return _k(bh * (2 * n * d * d + 2 * n * d),
              4 * bh * n * d + 2 * bh * n * d * 2 + 4 * bh * n
              + 4 * bh * (d * d + d) + 4 * bh * n * d)


def bidir_reduce_call(bh, n, d) -> dict:
    """Bidirectional LLN backward summary: dS = phi(q)^T (g / den)."""
    return _k(bh * 2 * n * d * d,
              4 * bh * n * d + 2 * bh * n * d * 2 + 4 * bh * n
              + 4 * bh * (d * d + d))


def bidir_dkv_call(bg, n, d) -> dict:
    """Bidirectional LLN dk and dv from dS and dz."""
    return _k(bg * 2 * 2 * n * d * d,
              4 * bg * n * d + 2 * bg * n * d + 4 * bg * (d * d + d)
              + 4 * bg * n * d * 2)


def least_seconds(cost: dict, peak: dict) -> float:
    """The larger of operations over peak rate and bytes over HBM
    bandwidth: the least time the chip could take."""
    return max(cost["flops"] / peak["flops_per_s"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
