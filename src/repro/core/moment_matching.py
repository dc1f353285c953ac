"""Moment matching between LLN and Softmax attention (paper Appendix A.7).

Prop. 4.1 (broad regime): Var[ln P^(LLN)] ~= a * sigma_tilde^2 + b, with
sigma_tilde^2 = alpha^2 sigma_q^2 + beta^2 sigma_k^2.  The softmax attention
matrix has Var[ln P^(SM)] = sigma_q^2 sigma_k^2 (+ C_cross) (Prop. 3.1).

Matching the variances (eq. 34) and splitting symmetrically
(alpha^2 sigma_q^2 = beta^2 sigma_k^2 = sigma_tilde^2 / 2) gives eq. 10:

    alpha = sigma_tilde / (sqrt(2) * sigma_q)
    beta  = sigma_tilde / (sqrt(2) * sigma_k)
    sigma_tilde = sqrt((sigma_q^2 sigma_k^2 - b) / a)

(a, b) are fit once by linear regression of the *measured* LLN log-variance
against sigma_tilde^2 on synthetic Gaussian inputs (the paper's "linear
interpolation on randomly generated Gaussian samples").  The defaults below
were produced by :func:`fit_lln_constants` with d=64, n=1024 over
sigma_tilde^2 in [1, 36] (the paper's range of interest, App. A.7) and can be
regenerated with ``python -m repro.core.moment_matching``.

Length-aware extension (serving): the fit depends on the sequence length N
the attention matrix is formed over, so :data:`FITTED_CONSTANTS_N` carries
(a, b) on a grid over N as well as d, and :func:`solve_alpha_beta` accepts
``n=`` plus a beta(n) log-length temperature schedule (:func:`length_gain`)
that counteracts the dilution a linear-attention recurrence develops as the
context outgrows the calibration length ("Critical attention scaling" /
"The Devil in Linear Transformer", PAPERS.md).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Broad-regime constants fit on sigma_tilde^2 in [1, 36], N=1024 (regenerate
# via __main__).  Keyed by head_dim; nearest entry is used for other dims.
# Note: with these constants and sigma_q = sigma_k = 1, eq. 10 yields
# alpha = beta ~= 2.1-2.3 — reproducing the paper's observed moment-matching
# range (2, 2.2) in Fig. 9.
FITTED_CONSTANTS: dict[int, Tuple[float, float]] = {
    64: (0.1935, -0.7577),
    128: (0.1706, -0.7442),
}
DEFAULT_A, DEFAULT_B = FITTED_CONSTANTS[64]

# Length-aware fit: (a, b) on a grid over sequence length N as well as head
# dim, produced by ``python -m repro.core.moment_matching --grid`` (seeded,
# num_seeds=4).  Used by length-aware calibration
# (``constants_for_dim(d, n=...)``); plain callers keep the legacy
# FITTED_CONSTANTS defaults above (stable since the seed) so length-unaware
# paths are bit-identical to before the grid existed.  The (64, 1024)
# entry was re-fit under JAX 0.9.0 (b moved by 0.05 from the older stack's
# fit); the others date from the older stack.
CALIB_LEN = 1024  # reference length n0 the schedules are anchored at
FITTED_CONSTANTS_N: dict[int, dict[int, Tuple[float, float]]] = {
    64: {256: (0.1994, -0.7749), 1024: (0.1908, -0.7236),
         4096: (0.1837, -0.6729)},
    128: {256: (0.1674, -0.7008), 1024: (0.1620, -0.6534),
          4096: (0.1601, -0.6568)},
}


def constants_for_dim(head_dim: int, n: int | None = None,
                      ) -> Tuple[float, float]:
    """Nearest calibrated (a, b) for a head dimension.

    With ``n`` (a static sequence length) ABOVE the calibration length,
    picks the nearest-N entry of the length-aware grid
    :data:`FITTED_CONSTANTS_N` (nearest in log N).  With ``n=None`` or
    ``n <= CALIB_LEN`` returns the legacy defaults unchanged, so
    length-aware calibration reduces exactly to the fixed calibration at
    or below the calibration length.
    """
    best = min(FITTED_CONSTANTS, key=lambda d: abs(d - head_dim))
    if n is None or int(n) <= CALIB_LEN:
        return FITTED_CONSTANTS[best]
    grid = FITTED_CONSTANTS_N[best]
    ln = float(np.log(max(int(n), 1)))
    bn = min(grid, key=lambda m: abs(float(np.log(m)) - ln))
    return grid[bn]


# ---------------------------------------------------------------------------
# Attention-matrix constructors on raw Gaussian inputs (analysis-scale only).
# ---------------------------------------------------------------------------

def softmax_attn_matrix(q: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """P^(SM) (eq. 6) for q,k: (N, d).  Returns (N, N) rows summing to 1."""
    scores = (q @ k.T) / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    return jax.nn.softmax(scores, axis=-1)


def lln_attn_matrix(q: jnp.ndarray, k: jnp.ndarray, alpha: float,
                    beta: float) -> jnp.ndarray:
    """P^(LLN) (eq. 9) for q,k: (N, d).  Returns (N, N) rows summing to 1."""
    fq = jnp.exp(alpha * q - jnp.max(alpha * q))
    fk = jnp.exp(beta * k - jnp.max(beta * k))
    scores = fq @ fk.T
    return scores / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-30)


def log_variance(p: jnp.ndarray) -> jnp.ndarray:
    """Variance of ln(P) — the log-normal shape parameter estimate."""
    logp = jnp.log(jnp.clip(p, 1e-30, None))
    return jnp.var(logp)


# ---------------------------------------------------------------------------
# (a, b) calibration — paper App. A.7.
# ---------------------------------------------------------------------------

def fit_lln_constants(
    d: int = 64,
    n: int = 1024,
    sigma_tilde_sq: np.ndarray | None = None,
    num_seeds: int = 4,
    seed: int = 0,
) -> Tuple[float, float]:
    """Fit Var[ln P^(LLN)] = a * sigma_tilde^2 + b on Gaussian samples.

    Uses alpha = beta = 1 and sigma_q = sigma_k = sigma_tilde / sqrt(2), so the
    abscissa is exactly sigma_tilde^2 = alpha^2 s_q^2 + beta^2 s_k^2.
    """
    if sigma_tilde_sq is None:
        sigma_tilde_sq = np.linspace(1.0, 36.0, 15)
    xs, ys = [], []
    key = jax.random.PRNGKey(seed)
    for s2 in sigma_tilde_sq:
        sig = float(np.sqrt(s2 / 2.0))
        for _ in range(num_seeds):
            key, kq, kk = jax.random.split(key, 3)
            q = sig * jax.random.normal(kq, (n, d), jnp.float32)
            k = sig * jax.random.normal(kk, (n, d), jnp.float32)
            p = lln_attn_matrix(q, k, 1.0, 1.0)
            xs.append(s2)
            ys.append(float(log_variance(p)))
    a, b = np.polyfit(np.asarray(xs), np.asarray(ys), 1)
    return float(a), float(b)


def fit_lln_constants_grid(
    d: int = 64,
    ns: Tuple[int, ...] = (256, 1024, 4096),
    num_seeds: int = 4,
    seed: int = 0,
) -> dict[int, Tuple[float, float]]:
    """Length-aware fit: (a, b) per sequence length N (FITTED_CONSTANTS_N)."""
    return {n: fit_lln_constants(d=d, n=n, num_seeds=num_seeds, seed=seed)
            for n in ns}


# ---------------------------------------------------------------------------
# beta(n) log-length temperature schedule.
# ---------------------------------------------------------------------------

def length_gain(n, beta_n: float = 0.0, calib_len: int = CALIB_LEN):
    """Multiplicative gain g(n) on (alpha, beta) for a row at depth n.

    g(n) = sqrt(1 + beta_n * ln(n / n0)) for n > n0, and exactly 1 for
    n <= n0 (= ``calib_len``), so the schedule is the identity at or below
    the calibration length.  Scaling both alpha and beta by g inflates the
    matched log-variance sigma_tilde^2 by (1 + beta_n ln(n/n0)) — the
    logit-scale beta ~ log n temperature growth "Critical attention scaling"
    shows attention needs, which counteracts the 1/N dilution of new tokens
    in the linear recurrence.  ``n`` may be a traced per-row (B,) position
    array; the result broadcasts like n.
    """
    if beta_n <= 0.0:
        return jnp.ones_like(jnp.asarray(n, jnp.float32))
    nf = jnp.maximum(jnp.asarray(n, jnp.float32), 1.0)
    ratio = jnp.maximum(nf / float(max(calib_len, 1)), 1.0)
    return jnp.sqrt(1.0 + float(beta_n) * jnp.log(ratio))


def solve_alpha_beta(
    sigma_q: jnp.ndarray,
    sigma_k: jnp.ndarray,
    a: float = DEFAULT_A,
    b: float = DEFAULT_B,
    min_sigma_tilde_sq: float = 1e-4,
    n=None,
    beta_n: float = 0.0,
    calib_len: int = CALIB_LEN,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. 10.  sigma_q/sigma_k: scalars or per-head arrays; gradients blocked
    (moment matching is a calibration, not a learning signal).

    ``n`` (optional) is the sequence length / row depth the calibration is
    for: the solved (alpha, beta) are scaled by the beta(n) schedule
    :func:`length_gain` (identity when ``beta_n=0`` or ``n <= calib_len``).
    Pass a (B,)-shaped ``n`` for per-row length-aware calibration; the gain
    broadcasts against per-head solutions as (B, 1).
    """
    sq = jax.lax.stop_gradient(jnp.asarray(sigma_q, jnp.float32))
    sk = jax.lax.stop_gradient(jnp.asarray(sigma_k, jnp.float32))
    sigma_sm_sq = jnp.square(sq) * jnp.square(sk)
    st = jnp.sqrt(jnp.maximum((sigma_sm_sq - b) / a, min_sigma_tilde_sq))
    alpha = st / (jnp.sqrt(2.0) * jnp.maximum(sq, 1e-4))
    beta = st / (jnp.sqrt(2.0) * jnp.maximum(sk, 1e-4))
    if n is not None and beta_n > 0.0:
        gain = length_gain(n, beta_n, calib_len)
        if gain.ndim and alpha.ndim > gain.ndim:   # (B,) gain vs (B, H) sol
            gain = gain[..., None]
        alpha = alpha * gain
        beta = beta * gain
    return alpha, beta


# ---------------------------------------------------------------------------
# Running input statistics (per-head EMA of sigma_q / sigma_k).
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QKStats:
    """Per-head EMA of query/key standard deviations (batchnorm-style)."""
    sigma_q: jnp.ndarray   # (H,)
    sigma_k: jnp.ndarray   # (H,)

    @staticmethod
    def init(heads: int) -> "QKStats":
        return QKStats(sigma_q=jnp.ones((heads,), jnp.float32),
                       sigma_k=jnp.ones((heads,), jnp.float32))


def _masked_rms(x: jnp.ndarray, mask: jnp.ndarray | None) -> jnp.ndarray:
    """Per-head RMS over (B, N, D) of a (B, N, H, D) tensor, optionally
    excluding padded positions via a (B, N) mask."""
    x2 = jnp.square(x.astype(jnp.float32))
    if mask is None:
        return jnp.sqrt(jnp.mean(x2, axis=(0, 1, 3)))
    m = jnp.asarray(mask, jnp.float32)[:, :, None, None]
    num = jnp.sum(x2 * m, axis=(0, 1, 3))
    den = jnp.maximum(jnp.sum(m) * x.shape[-1], 1.0)
    return jnp.sqrt(num / den)


def update_stats(stats: QKStats, q: jnp.ndarray, k: jnp.ndarray,
                 decay: float = 0.99,
                 mask: jnp.ndarray | None = None) -> QKStats:
    """EMA update from a (B, N, H, D) batch; gradients blocked.

    ``mask`` (optional, (B, N), 1 = real token) excludes padded positions
    from the per-head RMS so ragged batches don't pollute the EMA toward
    zero (padding contributes exact-zero q/k rows).
    """
    sq = jax.lax.stop_gradient(_masked_rms(q, mask))
    sk = jax.lax.stop_gradient(_masked_rms(k, mask))
    return QKStats(sigma_q=decay * stats.sigma_q + (1 - decay) * sq,
                   sigma_k=decay * stats.sigma_k + (1 - decay) * sk)


def matched_alpha_beta(stats: QKStats, a: float = DEFAULT_A,
                       b: float = DEFAULT_B) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return solve_alpha_beta(stats.sigma_q, stats.sigma_k, a, b)


if __name__ == "__main__":
    import sys
    if "--grid" in sys.argv:
        for d in sorted(FITTED_CONSTANTS_N):
            got = fit_lln_constants_grid(d=d)
            print(f"d={d}: " + ", ".join(
                f"n={n}: ({a:.4f}, {b:.4f})" for n, (a, b) in got.items()))
    else:
        a, b = fit_lln_constants()
        print(f"fit: a={a:.4f} b={b:.4f}  "
              f"(defaults: a={DEFAULT_A} b={DEFAULT_B})")
