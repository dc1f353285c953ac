"""Plain float32 reference of the benchmark's models.

Written from the paper (Linear Log-Normal attention, eq. 8 and eq. 10; the
§4.2 hybrid with block-diagonal softmax) and the configuration files under
``bench/configs``; it imports nothing of the program under test.  Every
matrix product runs at ``Precision.HIGHEST`` so that a TPU computes it in
float32.  With ``fp8=True`` each product's operands are first rounded to
float8 e4m3 with one scale per tensor: that is the precision control, one
step below the bfloat16 the configurations compute in.

Departures from the paper's formulas, each below 1e-4 of the result at the
positions the benchmark compares:

* the LLN normaliser carries no ``+eps`` term (the program adds 1e-6 to a
  denominator that is at least 1e-2 once 256 keys are summed);
* the moment-matching constants (a, b) of eq. 10 come from this module's
  own fit (:func:`fit_constants`, the paper's procedure of Appendix A.7:
  a linear regression of the LLN attention matrix's log-variance on
  sigma-tilde squared over seeded Gaussian samples), kept in
  ``bench/mm_constants.json``.

Parameter layout (shared with ``bench/weights.py``): per-layer arrays are
stacked along a leading layer axis, dense weights are ``(d_in, d_out)``.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
NEG_INF = -1e30

#: eq. 10's (a, b) by head size, as :func:`fit_constants` gives them.
MM_FILE = Path(__file__).resolve().parent / "mm_constants.json"

#: Largest finite float8 e4m3 value.
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# Sizes and parameter layout.
# ---------------------------------------------------------------------------

#: The form of block this reference computes, for the configuration keys
#: that could state another: a file that states another form is refused.
FORM = {"attn_impl": "lln_diag", "use_qkv_bias": False,
        "position_embedding_type": "rotary", "pre_layer_norm": True,
        "embedding_layer_norm": False, "mlm_head_transform": False,
        "type_vocab_size": 0}


def sizes_of(config: dict) -> dict:
    """The reference's sizes from a ``bench/configs/*.json`` file."""
    m = config["model"]
    other = {k: m[k] for k in FORM if k in m and m[k] != FORM[k]}
    if other:
        raise ValueError(f"the reference computes no block with {other}")
    return {
        "layers": int(m["num_hidden_layers"]),
        "d": int(m["hidden_size"]),
        "heads": int(m["num_attention_heads"]),
        "kv_heads": int(m["num_key_value_heads"]),
        "head_dim": int(m["head_dim"]),
        "d_ff": int(m["intermediate_size"]),
        "vocab": int(m["vocab_size"]),
        "vocab_rows": int(m["vocab_rows"]),
        "rotary_pct": float(m["partial_rotary_factor"]),
        "rope_theta": float(m["rope_theta"]),
        "norm_eps": float(m["layer_norm_eps"]),
        "mlp": m["mlp"],
        "diag_block": int(m["diag_block"]),
        "causal": bool(m["causal"]),
    }


def layout(sz: dict) -> dict:
    """Parameter shapes (float32) as nested dicts of ShapeDtypeStruct."""
    L, d, h, g, hd, ff = (sz["layers"], sz["d"], sz["heads"], sz["kv_heads"],
                          sz["head_dim"], sz["d_ff"])
    s = lambda *shape: jax.ShapeDtypeStruct(shape, F32)
    norm = lambda *lead: {"scale": s(*lead, d), "bias": s(*lead, d)}
    if sz["mlp"] == "swiglu":
        mlp = {"wi_gate": s(L, d, ff), "wi_up": s(L, d, ff), "wo": s(L, ff, d)}
    else:
        mlp = {"wi": s(L, d, ff), "wo": s(L, ff, d)}
    return {
        "embed": {"table": s(sz["vocab_rows"], d)},
        "layers": {"ln1": norm(L), "ln2": norm(L),
                   "attn": {"q_w": s(L, d, h * hd), "k_w": s(L, d, g * hd),
                            "v_w": s(L, d, g * hd), "o_w": s(L, h * hd, d)},
                   "mlp": mlp},
        "final_norm": norm(),
        "lm_head": s(d, sz["vocab_rows"]),
    }


# ---------------------------------------------------------------------------
# Moment matching (eq. 10) and its constants.
# ---------------------------------------------------------------------------

def fit_constants(head_dim: int, n: int = 1024, samples: int = 32,
                  seed: int = 0) -> tuple[float, float]:
    """(a, b) of Var[ln P_LLN] = a * sigma_tilde^2 + b, by least squares
    over Gaussian q and k of ``n`` positions, with alpha = beta = 1 and
    sigma_q^2 = sigma_k^2 = sigma_tilde^2 / 2 at 15 points of sigma_tilde^2
    in [1, 36], ``samples`` draws at each; float64 on the host."""
    rng = np.random.default_rng([seed, head_dim, n])
    xs, ys = [], []
    for s2 in np.linspace(1.0, 36.0, 15):
        sig = math.sqrt(s2 / 2.0)
        for _ in range(samples):
            q = sig * rng.standard_normal((n, head_dim))
            k = sig * rng.standard_normal((n, head_dim))
            p = np.exp(q - q.max()) @ np.exp(k - k.max()).T
            logp = np.log(p) - np.log(p.sum(-1, keepdims=True))
            xs.append(s2)
            ys.append(logp.var())
    a, b = np.polyfit(xs, ys, 1)
    return float(a), float(b)


def mm_constants(head_dim: int) -> tuple[float, float]:
    """The fitted (a, b) of the nearest head size in
    ``bench/mm_constants.json`` (``python3 bench/reference.py --fit <d>``
    adds a head size)."""
    with open(MM_FILE) as f:
        table = {int(d): ab for d, ab in json.load(f).items()}
    a, b = table[min(table, key=lambda d: abs(d - head_dim))]
    return float(a), float(b)


# ---------------------------------------------------------------------------
# Numerics.
# ---------------------------------------------------------------------------

def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor (the value
    stays float32); the gradient passes straight through."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, fp8: bool):
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def _layernorm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta, pct):
    """Rotate the first ``pct`` of the head dim as two halves (GPT-NeoX
    layout).  x: (..., N, H, D); pos: (N,)."""
    d = x.shape[-1]
    rd = int(d * pct)
    rd -= rd % 2
    half = rd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def _calibrate(q, k, weight, sz):
    """Eq. 10: alpha (H,) and beta (G,) from the root mean squares of q and
    k over the positions ``weight`` selects ((..., N) of 0/1).  A query
    head's alpha uses its kv group's sigma-tilde and its own sigma_q."""
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


def _features(q, k, alpha, beta_h):
    """exp(alpha q) and exp(beta k), each shifted by its own maximum over
    positions and features (the shift cancels in eq. 8)."""
    aq = q * alpha[:, None]
    bk = k * beta_h[:, None]
    axes = (q.ndim - 3, q.ndim - 1)          # positions and features
    cq = jax.lax.stop_gradient(jnp.max(aq, axis=axes, keepdims=True))
    ck = jax.lax.stop_gradient(jnp.max(bk, axis=axes, keepdims=True))
    return jnp.exp(aq - cq), jnp.exp(bk - ck)


# ---------------------------------------------------------------------------
# Causal decoder (serving).
# ---------------------------------------------------------------------------

def _causal_attention(q, k, v, alpha, beta, sz, fp8, qblock):
    """0.5 * (causal LLN + causal block-diagonal softmax).  q: (N, H, D);
    k/v: (N, G, D).  Queries are taken ``qblock`` rows at a time."""
    n, h, d = q.shape
    r = h // k.shape[1]
    k, v = jnp.repeat(k, r, axis=1), jnp.repeat(v, r, axis=1)
    fq, fk = _features(q, k, alpha, jnp.repeat(beta, r))
    blk = sz["diag_block"]
    kpos = jnp.arange(n)

    def one(i0):
        rows = jax.lax.dynamic_slice_in_dim(jnp.arange(n), i0, qblock)
        fqb = jax.lax.dynamic_slice_in_dim(fq, i0, qblock)
        qb = jax.lax.dynamic_slice_in_dim(q, i0, qblock)
        seen = rows[:, None] >= kpos[None, :]                     # (Q, N)
        sc = _mm("ihd,jhd->hij", fqb, fk, fp8) * seen[None]
        lln = _mm("hij,jhv->ihv", sc, v, fp8) / jnp.sum(sc, -1).T[..., None]
        same = (rows[:, None] // blk) == (kpos[None, :] // blk)
        logit = _mm("ihd,jhd->hij", qb, k, fp8) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where((seen & same)[None], logit, NEG_INF), -1)
        diag = _mm("hij,jhv->ihv", p, v, fp8)
        return 0.5 * (lln + diag)

    out = jax.lax.map(one, jnp.arange(0, n, qblock))
    return out.reshape(n, h, -1)


def decoder_logits(params, tokens, prompt_len, at, sz, *, fp8=False,
                   qblock=512):
    """Logits (T, vocab rows) at positions ``at`` (T,) of one sequence.

    ``tokens`` (N,) is the prompt followed by the served tokens (N a
    multiple of ``qblock``; padding after the last real token changes no
    earlier position).  Each layer's alpha/beta come from the first
    ``prompt_len`` positions alone, as a request's prefill sets them."""
    n = tokens.shape[0]
    pos = jnp.arange(n)
    in_prompt = (pos < prompt_len).astype(F32)
    h, g, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    x = params["embed"]["table"][tokens]

    def layer(x, lp):
        a = _layernorm(lp["ln1"], x, sz["norm_eps"])
        q = _mm("nd,df->nf", a, lp["attn"]["q_w"], fp8).reshape(n, h, hd)
        k = _mm("nd,df->nf", a, lp["attn"]["k_w"], fp8).reshape(n, g, hd)
        v = _mm("nd,df->nf", a, lp["attn"]["v_w"], fp8).reshape(n, g, hd)
        q = _rope(q, pos, sz["rope_theta"], sz["rotary_pct"])
        k = _rope(k, pos, sz["rope_theta"], sz["rotary_pct"])
        alpha, beta = _calibrate(q, k, in_prompt, sz)
        att = _causal_attention(q, k, v, alpha, beta, sz, fp8, qblock)
        x = x + _mm("nf,fd->nd", att.reshape(n, h * hd),
                    lp["attn"]["o_w"], fp8)
        return x + _mlp(lp["mlp"], _layernorm(lp["ln2"], x, sz["norm_eps"]),
                        sz, fp8), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _layernorm(params["final_norm"], x[at], sz["norm_eps"])
    return _mm("td,dv->tv", x, params["lm_head"], fp8)


def _mlp(p, a, sz, fp8):
    lead = "bnd" if a.ndim == 3 else "nd"
    out = lead[:-1] + "f"
    if sz["mlp"] == "swiglu":
        gate = _mm(f"{lead},df->{out}", a, p["wi_gate"], fp8)
        up = _mm(f"{lead},df->{out}", a, p["wi_up"], fp8)
        hid = jax.nn.silu(gate) * up
    else:
        hid = jax.nn.gelu(_mm(f"{lead},df->{out}", a, p["wi"], fp8),
                          approximate=True)
    return _mm(f"{out},fd->{lead}", hid, p["wo"], fp8)


# ---------------------------------------------------------------------------
# Bidirectional encoder (MLM training).
# ---------------------------------------------------------------------------

def _bidir_attention(q, k, v, alpha, beta, sz, fp8):
    """0.5 * (bidirectional LLN + block-diagonal softmax).  (B, N, H, D)."""
    b, n, h, d = q.shape
    r = h // k.shape[2]
    k, v = jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2)
    fq, fk = _features(q, k, alpha, jnp.repeat(beta, r))
    s = _mm("bnhd,bnhv->bhdv", fk, v, fp8)
    z = jnp.sum(fk, axis=1)
    lln = _mm("bnhd,bhdv->bnhv", fq, s, fp8) / \
        jnp.einsum("bnhd,bhd->bnh", fq, z, precision=HIGHEST)[..., None]
    blk = sz["diag_block"]
    nb = n // blk
    qb = q.reshape(b, nb, blk, h, d)
    kb = k.reshape(b, nb, blk, h, d)
    vb = v.reshape(b, nb, blk, h, -1)
    p = jax.nn.softmax(_mm("bgihd,bgjhd->bghij", qb, kb, fp8) / math.sqrt(d),
                       -1)
    diag = _mm("bghij,bgjhv->bgihv", p, vb, fp8).reshape(b, n, h, -1)
    return 0.5 * (lln + diag)


def encoder_hidden(params, tokens, sz, *, fp8=False):
    """Final hidden states (B, N, d); alpha/beta pool every row and
    position of the batch, one pair per layer."""
    b, n = tokens.shape
    pos = jnp.arange(n)
    h, g, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    x = params["embed"]["table"][tokens]
    everywhere = jnp.ones((b, n), F32)

    def layer(x, lp):
        a = _layernorm(lp["ln1"], x, sz["norm_eps"])
        q = _mm("bnd,df->bnf", a, lp["attn"]["q_w"], fp8).reshape(b, n, h, hd)
        k = _mm("bnd,df->bnf", a, lp["attn"]["k_w"], fp8).reshape(b, n, g, hd)
        v = _mm("bnd,df->bnf", a, lp["attn"]["v_w"], fp8).reshape(b, n, g, hd)
        q = _rope(q, pos, sz["rope_theta"], sz["rotary_pct"])
        k = _rope(k, pos, sz["rope_theta"], sz["rotary_pct"])
        alpha, beta = _calibrate(q, k, everywhere, sz)
        att = _bidir_attention(q, k, v, alpha, beta, sz, fp8)
        x = x + _mm("bnf,fd->bnd", att.reshape(b, n, h * hd),
                    lp["attn"]["o_w"], fp8)
        return x + _mlp(lp["mlp"], _layernorm(lp["ln2"], x, sz["norm_eps"]),
                        sz, fp8), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    return _layernorm(params["final_norm"], x, sz["norm_eps"])


def mlm_loss(params, batch, sz, *, fp8=False, chunk=2048):
    """Mean cross-entropy over the masked positions; logits over the padded
    vocabulary rows, with rows past ``vocab`` excluded.  The logits are
    formed ``chunk`` positions at a time and recomputed in the backward."""
    x = encoder_hidden(params, batch["inputs"], sz, fp8=fp8)
    d = x.shape[-1]
    chunk = math.gcd(chunk, x.shape[0] * x.shape[1])
    flat = x.reshape(-1, chunk, d)
    tgt = batch["targets"].reshape(-1, chunk)
    msk = batch["mask"].reshape(-1, chunk)
    real = jnp.arange(sz["vocab_rows"]) < sz["vocab"]

    @jax.checkpoint
    def piece(carry, xs):
        hx, t, m = xs
        logits = _mm("cd,dv->cv", hx, params["lm_head"], fp8)
        logits = jnp.where(real[None], logits, NEG_INF)
        nll = jax.nn.logsumexp(logits, -1) - \
            jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return carry + jnp.sum(nll * m), None

    total, _ = jax.lax.scan(piece, jnp.zeros((), F32), (flat, tgt, msk))
    return total / jnp.maximum(jnp.sum(batch["mask"]), 1.0)


# ---------------------------------------------------------------------------
# AdamW (decoupled weight decay, global-norm clipping) and its schedule.
# ---------------------------------------------------------------------------

def lr_at(step, opt: dict):
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then cosine to
    ``min_ratio * peak_lr`` at ``total_steps``."""
    step = jnp.asarray(step, F32)
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    warm_lr = peak * step / max(warm, 1)
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (opt["min_ratio"] + (1 - opt["min_ratio"]) * 0.5 *
                  (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < warm, warm_lr, cos)


def adamw(params, grads, m, v, step, opt: dict):
    """One AdamW update at 0-based ``step``, with global-norm clipping where
    ``clip_norm`` is not None; returns (params, m, v, the gradients as the
    update used them)."""
    if opt["clip_norm"] is not None:
        leaves = jax.tree_util.tree_leaves(grads)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
        grads = jax.tree_util.tree_map(lambda x: x * scale, grads)
    lr = lr_at(step, opt)
    t = step + 1.0
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, grads)
    v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x, v,
                               grads)

    def upd(p, mm, vv):
        mh = mm / (1 - b1 ** t)
        vh = vv / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + opt["eps"])
                         + opt["weight_decay"] * p)
    return jax.tree_util.tree_map(upd, params, m, v), m, v, grads


if __name__ == "__main__":
    import sys

    # python3 bench/reference.py --fit 64 [128 ...]: refit and rewrite the
    # constants file for these head sizes.
    if sys.argv[1:2] != ["--fit"] or len(sys.argv) < 3:
        sys.exit("usage: python3 bench/reference.py --fit <head_dim> ...")
    table = json.loads(MM_FILE.read_text()) if MM_FILE.exists() else {}
    for d in sys.argv[2:]:
        table[d] = list(fit_constants(int(d)))
    MM_FILE.write_text(json.dumps(table, indent=1, sort_keys=True) + "\n")
