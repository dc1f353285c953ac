"""Architecture configuration schema.

One dataclass covers all assigned families; family-specific fields are
ignored by other families.  Every assigned architecture provides both its
full (paper-exact) config and a reduced smoke config of the same family.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense | moe | mla_moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None   # default d_model // n_heads

    # --- attention ---------------------------------------------------------
    attn_impl: str = "softmax"       # softmax | lln | lln_diag (paper
                                     # technique) | log_linear (Fenwick
                                     # multi-scale LLN state)
    diag_block: int = 256
    lln_chunk: int = 256
    use_kernel: bool = False         # training forward on the CPU backend:
                                     # True runs the Pallas kernels (in
                                     # interpret mode), False the jnp
                                     # reference.  Accelerators always run
                                     # the kernels.
    use_serve_kernel: bool = True    # legacy escape: False maps to
                                     # attn_backend="ref" (the seed jnp
                                     # serving path), kept for benchmarking
    attn_backend: str = "auto"       # kernels/registry.py backend:
                                     # auto | pallas | scan | ref
    qk_norm: bool = False
    lln_fixed_ab: float = 0.0        # fixed alpha=beta (paper §A.8.4); 0=dynamic
    lln_per_row_calib: bool = False  # moment-match each batch row alone
                                     # ((B,H) alpha/beta — the continuous-
                                     # batching admission setting)
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0          # stablelm 0.25; chatglm 0.5 ("2d" RoPE)
    softmax_chunk: int = 1024

    # --- long-context robustness (length-aware LLN serving) -----------------
    lln_beta_n: float = 0.0          # beta(n) log-length temperature schedule
                                     # coefficient: alpha/beta gain
                                     # sqrt(1 + beta_n*ln(n/calib_len)) past
                                     # the calibration length (0 = off)
    lln_calib_len: int = 1024        # reference length n0 the schedule is
                                     # anchored at (identity for n <= n0)
    lln_renorm: float = 0.0          # drift renorm threshold on the carried
                                     # |z| magnitude: rescale (s, z) against
                                     # the per-row log-scale when max|z|
                                     # exceeds it (0 = off)
    lln_num_scales: int = 4          # log_linear only: Fenwick pyramid depth
                                     # L — level l holds a dyadic span of 2^l
                                     # closed lln_chunk granules (L=1 == lln)
    lln_scale_decay: float = 0.5     # log_linear only: per-level mix weight
                                     # w_l = decay^l (1.0 == flat == lln)

    # --- speculative decoding ------------------------------------------------
    draft_layers: int = 0            # tied first-k-layers draft (0 = off;
                                     # n_layers = tied full model)
    spec_k: int = 0                  # draft tokens per verify chunk (0 = off)

    # --- MoE ----------------------------------------------------------------
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    expert_d_ff: int = 0
    capacity_factor: float = 1.25
    first_dense_layers: int = 0      # deepseek-v2: first layer keeps dense FFN
    router_aux_coef: float = 0.001

    # --- MLA (deepseek-v2) ---------------------------------------------------
    kv_lora: int = 0
    q_lora: int = 0
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128

    # --- SSM (mamba2 / zamba2) ----------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    conv_width: int = 4
    ssm_chunk: int = 256
    shared_attn_period: int = 6      # zamba2: shared attn block cadence

    # --- enc-dec / vlm frontends ---------------------------------------------
    enc_layers: int = 0              # seamless: encoder depth
    frontend_dim: int = 0            # stub embedding dim (audio frames / patches)
    num_prefix_tokens: int = 0       # vlm: image patch count

    # --- norm / act / misc ---------------------------------------------------
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    act: str = "silu_glu"            # silu_glu | gelu_glu | gelu
    tie_embeddings: bool = False
    embed_scale: bool = False        # gemma-style sqrt(d_model) embed scaling
    logit_softcap: float = 0.0

    # --- dtypes / remat / microbatching --------------------------------------
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "full"              # full | dots | none
    grad_accum: int = 1              # microbatches per step (activation peak /N)
    cast_params_once: bool = False   # bf16-cast before FSDP gathers (2x comm)
    scan_unroll: bool = False        # unroll layer scans (roofline probes:
                                     # makes HLO cost_analysis trip-count-exact)

    # --- distribution policy -------------------------------------------------
    attn_shard: str = "tp_heads"     # tp_heads | context | replicate
    vocab_pad_to: int = 256

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_to
        return ((self.vocab + m - 1) // m) * m

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode

    @property
    def is_serve(self) -> bool:
        return self.kind in ("prefill", "decode")


# The four assigned LM shapes (identical for all 10 archs).
SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("train_4k", 4096, 256, "train"),
    ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    ShapeSpec("decode_32k", 32768, 128, "decode"),
    ShapeSpec("long_500k", 524288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in SHAPES}
