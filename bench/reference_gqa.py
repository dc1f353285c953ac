"""Plain float32 reference of a Llama-form GQA decoder with LLN+Diag
attention (Yi-9B: arXiv:2403.04652, https://huggingface.co/01-ai/Yi-9B).

The block, as the source's ``LlamaForCausalLM`` states it: RMSNorm before
attention and before the MLP, q/k/v projections without bias, rotary
positions over the whole head (two halves), grouped-query attention with
``num_key_value_heads`` kv heads (each repeated over its group of query
heads here, in the reference), a SwiGLU MLP (``silu(x W_gate) * x W_up``
then ``W_down``), a final RMSNorm and an untied output head.  Attention is
the paper's LLN+Diag (eq. 8 with the block-diagonal softmax of Sec. 4.2),
computed by ``bench/reference.py``'s own functions.  It imports nothing of
the program under test.

Every matrix product runs at ``Precision.HIGHEST``; ``fp8=True`` rounds each
product's operands to float8 e4m3 first (the precision control), as
``bench/reference.py`` does.  Departures, as there: the LLN normaliser has
no ``+eps``; the eq. 10 constants (a, b) for head size 128 are this
module's own fit (``reference.fit_constants(128)``), kept in
``bench/mm_constants_128.json``.

Parameter layout (shared with ``bench/weights.py``): per-layer arrays
stacked on a leading layer axis, dense weights ``(d_in, d_out)``, each
norm a ``scale`` alone.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import reference

F32 = jnp.float32

#: eq. 10's (a, b) for each head size this reference serves, as
#: ``reference.fit_constants`` gives them (``python3 -m
#: bench.reference_gqa --fit 128``).
MM_FILE = Path(__file__).resolve().parent / "mm_constants_128.json"

#: The form of block this reference computes, for the configuration keys
#: that could state another: a file that states another form is refused.
FORM = {"attn_impl": "lln_diag", "norm": "rmsnorm", "mlp": "swiglu",
        "hidden_act": "silu", "attention_bias": False,
        "tie_word_embeddings": False, "partial_rotary_factor": 1.0,
        "causal": True}


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a ``bench/configs/*.json`` file, with the
    keys ``bench/flops.py`` reads; a file that states another form or
    leaves a key of the form out is refused."""
    m = config["model"]
    other = {k: m.get(k, "(not stated)") for k in FORM if m.get(k) != FORM[k]}
    if other:
        raise ValueError(f"the GQA reference computes no block with {other}")
    return {
        "layers": int(m["num_hidden_layers"]),
        "d": int(m["hidden_size"]),
        "heads": int(m["num_attention_heads"]),
        "kv_heads": int(m["num_key_value_heads"]),
        "head_dim": int(m["head_dim"]),
        "d_ff": int(m["intermediate_size"]),
        "vocab": int(m["vocab_size"]),
        "vocab_rows": int(m["vocab_rows"]),
        "rotary_pct": 1.0,
        "rope_theta": float(m["rope_theta"]),
        "norm_eps": float(m["rms_norm_eps"]),
        "mlp": "swiglu",
        "diag_block": int(m["diag_block"]),
        "causal": True,
    }


def layout(sz: dict) -> dict:
    """Parameter shapes (float32) as nested dicts of ShapeDtypeStruct."""
    L, d, h, g, hd, ff = (sz["layers"], sz["d"], sz["heads"], sz["kv_heads"],
                          sz["head_dim"], sz["d_ff"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, F32)
    return {
        "embed": {"table": s(sz["vocab_rows"], d)},
        "layers": {"ln1": {"scale": s(L, d)}, "ln2": {"scale": s(L, d)},
                   "attn": {"q_w": s(L, d, h * hd), "k_w": s(L, d, g * hd),
                            "v_w": s(L, d, g * hd), "o_w": s(L, h * hd, d)},
                   "mlp": {"wi_gate": s(L, d, ff), "wi_up": s(L, d, ff),
                           "wo": s(L, ff, d)}},
        "final_norm": {"scale": s(d)},
        "lm_head": s(d, sz["vocab_rows"]),
    }


def mm_constants(head_dim: int) -> tuple[float, float]:
    """The fitted (a, b) of exactly this head size; no other size stands
    in."""
    with open(MM_FILE) as f:
        a, b = json.load(f)[str(head_dim)]
    return float(a), float(b)


def _rmsnorm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def _calibrate(q, k, weight, sz):
    """Eq. 10: alpha (H,) and beta (G,) from the root mean squares of q and
    k over the positions ``weight`` selects, as ``bench/reference.py``
    computes them, with this head size's own constants."""
    h, g = q.shape[-2], k.shape[-2]
    r = h // g
    a, b = mm_constants(sz["head_dim"])
    w = weight[..., None, None]
    cnt = jnp.sum(weight) * q.shape[-1]
    lead = tuple(range(q.ndim - 2)) + (q.ndim - 1,)
    sq = jnp.sqrt(jnp.sum(jnp.square(q) * w, axis=lead) / cnt)        # (H,)
    sk = jnp.sqrt(jnp.sum(jnp.square(k) * w, axis=lead) / cnt)        # (G,)
    sq_g = jnp.mean(sq.reshape(g, r), axis=-1)
    st = jnp.sqrt(jnp.maximum((jnp.square(sq_g) * jnp.square(sk) - b) / a,
                              1e-4))
    alpha = jnp.repeat(st, r) / (math.sqrt(2.0) * jnp.maximum(sq, 1e-4))
    beta = st / (math.sqrt(2.0) * jnp.maximum(sk, 1e-4))
    return jax.lax.stop_gradient(alpha), jax.lax.stop_gradient(beta)


def decoder_logits(params, tokens, prompt_len, at, sz, *, fp8=False,
                   qblock=256):
    """Logits (T, vocab rows) at positions ``at`` (T,) of one sequence.

    ``tokens`` (N,) is the prompt followed by the served tokens (N a
    multiple of ``qblock``, or less than it; padding after the last real
    token changes no earlier position).  Each layer's alpha/beta come from
    the first ``prompt_len`` positions alone, as a request's prefill sets
    them."""
    mm = reference._mm
    n = tokens.shape[0]
    qblock = min(qblock, n)
    pos = jnp.arange(n)
    in_prompt = (pos < prompt_len).astype(F32)
    h, g, hd, eps = sz["heads"], sz["kv_heads"], sz["head_dim"], \
        sz["norm_eps"]
    x = params["embed"]["table"][tokens]

    def layer(x, lp):
        a = _rmsnorm(lp["ln1"], x, eps)
        q = mm("nd,df->nf", a, lp["attn"]["q_w"], fp8).reshape(n, h, hd)
        k = mm("nd,df->nf", a, lp["attn"]["k_w"], fp8).reshape(n, g, hd)
        v = mm("nd,df->nf", a, lp["attn"]["v_w"], fp8).reshape(n, g, hd)
        q = reference._rope(q, pos, sz["rope_theta"], 1.0)
        k = reference._rope(k, pos, sz["rope_theta"], 1.0)
        alpha, beta = _calibrate(q, k, in_prompt, sz)
        att = reference._causal_attention(q, k, v, alpha, beta, sz, fp8,
                                          qblock)
        x = x + mm("nf,fd->nd", att.reshape(n, h * hd), lp["attn"]["o_w"],
                   fp8)
        return x + reference._mlp(lp["mlp"], _rmsnorm(lp["ln2"], x, eps),
                                  sz, fp8), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(params["final_norm"], x[at], eps)
    return mm("td,dv->tv", x, params["lm_head"], fp8)


if __name__ == "__main__":
    import sys

    # python3 -m bench.reference_gqa --fit 128: refit and rewrite the
    # constants file for these head sizes.
    if sys.argv[1:2] != ["--fit"] or len(sys.argv) < 3:
        sys.exit("usage: python3 -m bench.reference_gqa --fit <head_dim> ...")
    table = json.loads(MM_FILE.read_text()) if MM_FILE.exists() else {}
    for d in sys.argv[2:]:
        table[d] = list(reference.fit_constants(int(d)))
    MM_FILE.write_text(json.dumps(table, indent=1, sort_keys=True) + "\n")
