"""Core library: the paper's contribution as composable JAX modules."""
from .attention import (KVCache, LLNDecodeState, decode_lln_chunk,
                        decode_softmax, flash_softmax, naive_softmax)
from .diag import block_diag_attn
from .lln import LLNState, lln_bidir, lln_causal, lln_causal_scan
from .moment_matching import (DEFAULT_A, DEFAULT_B, constants_for_dim,
                              fit_lln_constants, solve_alpha_beta)
from .engine import AttentionEngine, AttentionState

__all__ = [
    "AttentionEngine", "AttentionState",
    "KVCache", "LLNDecodeState", "LLNState",
    "flash_softmax", "naive_softmax",
    "decode_lln_chunk", "decode_softmax", "block_diag_attn",
    "lln_bidir", "lln_causal", "lln_causal_scan",
    "DEFAULT_A", "DEFAULT_B", "constants_for_dim", "fit_lln_constants",
    "solve_alpha_beta",
]
