"""Quickstart: LLN attention as a drop-in module, in 60 lines.

Demonstrates the paper's three pieces on raw tensors:
  1. moment matching (eq. 10) — solve (alpha, beta) from input statistics;
  2. LLN attention (eq. 8) — linear-complexity, log-normal score matrix;
  3. the LLN+Diag hybrid (§4.2) via the AttentionEngine's full-sequence
     attention, identical to what every assigned architecture uses
     internally.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import AttentionEngine, lln_causal, solve_alpha_beta
from repro.core.metrics import attention_log_moments, lognormality_score
from repro.core.moment_matching import (constants_for_dim, lln_attn_matrix,
                                        softmax_attn_matrix)
from repro.kernels.registry import AttnSpec

key = jax.random.PRNGKey(0)
B, N, H, D = 2, 512, 8, 64

# --- 1. moment matching ----------------------------------------------------
sigma_q = sigma_k = 1.0
a, b = constants_for_dim(D)
alpha, beta = solve_alpha_beta(sigma_q, sigma_k, a, b)
print(f"moment-matched alpha={float(alpha):.2f} beta={float(beta):.2f} "
      f"(paper Fig. 9 range: 2.0-2.2)")

# --- 2. the induced attention matrix is log-normal, like softmax's ---------
kq, kk = jax.random.split(key)
q2, k2 = jax.random.normal(kq, (N, D)), jax.random.normal(kk, (N, D))
p_sm = softmax_attn_matrix(q2, k2)
p_lln = lln_attn_matrix(q2, k2, float(alpha), float(beta))
print(f"Var[ln P]  softmax={float(attention_log_moments(p_sm)[1]):.3f}  "
      f"lln={float(attention_log_moments(p_lln)[1]):.3f}")
print(f"log-normality (QQ corr)  softmax={lognormality_score(p_sm):.4f}  "
      f"lln={lognormality_score(p_lln):.4f}")

# --- 3. linear-complexity attention on (B, N, H, D) tensors ----------------
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (B, N, H, D), jnp.bfloat16)
k = jax.random.normal(kk, (B, N, H, D), jnp.bfloat16)
v = jax.random.normal(kv, (B, N, H, D), jnp.bfloat16)



def engine(impl):
    return AttentionEngine(spec=AttnSpec(impl=impl, causal=True), heads=H,
                           kv_heads=H, head_dim=D, v_dim=D)


out_lln = lln_causal(q, k, v, alpha, beta, chunk=128)      # pure LLN
out_hybrid = engine("lln_diag").attention(q, k, v)        # §4.2 hybrid,
out_sa = engine("softmax").attention(q, k, v)             # auto moment-match

cos = jnp.sum(out_hybrid.astype(jnp.float32) * out_sa.astype(jnp.float32)) / (
    jnp.linalg.norm(out_hybrid.astype(jnp.float32))
    * jnp.linalg.norm(out_sa.astype(jnp.float32)))
print(f"outputs: lln {out_lln.shape}, hybrid {out_hybrid.shape}; "
      f"cos(hybrid, softmax) = {float(cos):.3f}")
print("OK")
