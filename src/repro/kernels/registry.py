"""Attention backend registry: one declarative spec, one resolution.

:class:`AttnSpec` declares an attention configuration; :func:`resolve` is
the one function that reads the platform and turns a backend name into
the implementation that runs.  Four named backends:

``auto``
    Compiled backends (TPU) run the Pallas kernels; the CPU runs each op's
    designated twin (interpreted Pallas for the training forward, the
    chunked ``lax.scan`` twin for prefill, the jnp twin for decode), and
    ragged sequence lengths fall back to the jnp reference.
``pallas``
    Force the Pallas kernel (interpret mode on CPU, so the kernel path is a
    first-class testable target everywhere).  Raises on ragged lengths —
    there is no kernel for those.
``scan``
    Force the chunked ``lax.scan`` / grouped-einsum twin (kernel layout, no
    repeated KV).  For ops with no dedicated twin this is the core chunked
    scan; the full-sequence forward (``AttentionEngine.attention``) runs it
    in model layout.
``ref``
    Force the jnp reference (``core/lln.py`` / ``core/diag.py`` — model
    layout, repeated KV) for serving; for the training forward it is the
    quadratic oracle from ``kernels/ref.py``.

The per-op twin and ragged tables live next to the kernels in
``kernels/ops.py``, which hands ``auto`` to :func:`resolve`; this module
owns the *policy* (spec validation + backend resolution) and the
spec-level entry points the :class:`~repro.core.engine.AttentionEngine`
calls (:func:`attention`, :func:`prefill`, :func:`decode_chunk`,
:func:`diag_fwd`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

IMPLS = ("softmax", "lln", "lln_diag", "log_linear")
BACKENDS = ("auto", "pallas", "scan", "ref")
CALIBRATIONS = ("batch", "per_row")
PRECISIONS = ("float32", "bfloat16", "float16")
TRAIN_TWINS = ("pallas", "scan")


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Declarative description of one attention configuration, validated
    in one place.  The spec is hashable and cheap — build one per layer
    call (``AttnSpec.from_cfg``) or inline in tests.

    Attributes:
      impl: ``softmax`` | ``lln`` | ``lln_diag`` (paper §4.2 hybrid) |
        ``log_linear`` (Fenwick multi-scale LLN state, causal-only).
      causal: decoder (True) vs encoder (False) masking.
      r: GQA ratio ``H // G`` (1 = MHA; k/v carry ``G = H // r`` heads).
      backend: ``auto`` | ``pallas`` | ``scan`` | ``ref`` — see module
        docstring.
      train_twin: what ``auto`` runs the full-sequence forward
        (``AttentionEngine.attention``) as on the CPU: ``pallas`` (the
        kernels, interpreted) or ``scan`` (the model-layout jnp math, which
        also takes a padding mask).  ``from_cfg`` takes it from
        :func:`train_twin_of`.  Off the CPU ``auto`` runs the kernels whatever
        it says.
      precision: dtype name for cached tensors (KV cache / diag tails);
        accumulators are always fp32.
      calibration: ``batch`` pools moment-matching statistics over the
        whole (batch, seq) like the paper's training setting; ``per_row``
        measures each batch row alone and yields (B, H)/(B, G) constants —
        the continuous-batching admission setting.
      lln_chunk: chunk of the causal LLN scan.
      diag_block: block size of the §4.2 diagonal component (also the
        decode tail length).
      softmax_chunk: key-chunk of the flash softmax path.
      fixed_ab: fixed alpha=beta (paper §A.8.4 ablation); 0 = dynamic
        moment matching.
      mm_a / mm_b: moment-matching constants; None = calibrated defaults
        for the head dim.
      beta_n: beta(n) log-length temperature schedule coefficient — the
        effective (alpha, beta) of a row at depth n are scaled by
        ``sqrt(1 + beta_n * ln(n / calib_len))`` past the calibration
        length (0 = off; see ``core/moment_matching.py:length_gain``).
      calib_len: reference length n0 the schedule is anchored at; the
        schedule is the identity for n <= calib_len.
      renorm: drift renormalization threshold on the carried LLN ``z``
        magnitude — decode rescales (s, z) against the per-row log-scale
        when ``max|z|`` crosses it (0 = off; semantics-preserving, see
        ``core/lln.py:decode_chunk``).  For ``log_linear`` the shift is
        repaid through each bucket's reference constant
        (``core/loglinear.py``).
      num_scales: ``log_linear`` only — number of Fenwick pyramid levels
        L; level ``l`` summarizes a dyadic span of ``2^l`` closed
        granules (``lln_chunk`` tokens each).  ``num_scales=1`` is
        exactly plain ``lln``.
      scale_decay: ``log_linear`` only — per-level mix weight
        ``scale_decay ** l`` (the open bucket and intra-chunk keys score
        at weight 1).  ``scale_decay=1`` is exactly plain ``lln``; the
        default 0.5 equalizes per-level mass so recent tokens outweigh
        distant ones.
    """
    impl: str = "softmax"
    causal: bool = True
    r: int = 1
    backend: str = "auto"
    train_twin: str = "pallas"
    precision: str = "float32"
    calibration: str = "batch"
    lln_chunk: int = 128
    diag_block: int = 256
    softmax_chunk: int = 1024
    fixed_ab: float = 0.0
    mm_a: Optional[float] = None
    mm_b: Optional[float] = None
    beta_n: float = 0.0
    calib_len: int = 1024
    renorm: float = 0.0
    num_scales: int = 4
    scale_decay: float = 0.5

    def __post_init__(self):
        if self.impl not in IMPLS:
            raise ValueError(
                f"AttnSpec.impl must be one of {IMPLS}, got {self.impl!r}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"AttnSpec.backend must be one of {BACKENDS}, "
                f"got {self.backend!r}")
        if self.train_twin not in TRAIN_TWINS:
            raise ValueError(
                f"AttnSpec.train_twin must be one of {TRAIN_TWINS}, "
                f"got {self.train_twin!r}")
        if self.calibration not in CALIBRATIONS:
            raise ValueError(
                f"AttnSpec.calibration must be one of {CALIBRATIONS}, "
                f"got {self.calibration!r}")
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"AttnSpec.precision must be one of {PRECISIONS}, "
                f"got {self.precision!r}")
        if self.r < 1:
            raise ValueError(f"AttnSpec.r (GQA ratio) must be >= 1, "
                             f"got {self.r}")
        if self.impl == "softmax" and self.backend == "pallas":
            raise ValueError(
                "softmax attention has no Pallas kernel; use backend "
                "'auto', 'scan' (flash) or 'ref' (naive quadratic)")
        for name in ("lln_chunk", "diag_block", "softmax_chunk"):
            if getattr(self, name) < 1:
                raise ValueError(f"AttnSpec.{name} must be positive")
        if self.fixed_ab < 0:
            raise ValueError("AttnSpec.fixed_ab must be >= 0")
        if self.beta_n < 0:
            raise ValueError("AttnSpec.beta_n must be >= 0")
        if self.renorm < 0:
            raise ValueError("AttnSpec.renorm must be >= 0")
        if self.calib_len < 1:
            raise ValueError("AttnSpec.calib_len must be positive")
        if self.num_scales < 1:
            raise ValueError("AttnSpec.num_scales must be >= 1")
        if self.scale_decay <= 0:
            raise ValueError("AttnSpec.scale_decay must be > 0")
        if self.impl == "log_linear" and not self.causal:
            raise ValueError(
                "log_linear attention is causal-only (the Fenwick bucket "
                "pyramid is a running prefix summary)")

    @classmethod
    def from_cfg(cls, cfg, causal: bool = True,
                 r: Optional[int] = None) -> "AttnSpec":
        """Build the spec an :class:`ArchConfig` implies.

        ``cfg.attn_backend`` selects the backend; :func:`train_twin_of` the
        full-sequence forward's CPU twin.  ``r`` overrides the GQA ratio
        (MLA runs full heads regardless of ``cfg.n_kv_heads``).
        """
        return cls(impl=cfg.attn_impl, causal=causal,
                   r=r if r is not None else cfg.n_heads // cfg.n_kv_heads,
                   backend=cfg.attn_backend, train_twin=train_twin_of(cfg),
                   precision=str(cfg.compute_dtype),
                   calibration=("per_row" if getattr(
                       cfg, "lln_per_row_calib", False) else "batch"),
                   lln_chunk=cfg.lln_chunk, diag_block=cfg.diag_block,
                   softmax_chunk=cfg.softmax_chunk,
                   fixed_ab=cfg.lln_fixed_ab,
                   beta_n=getattr(cfg, "lln_beta_n", 0.0),
                   calib_len=getattr(cfg, "lln_calib_len", 1024),
                   renorm=getattr(cfg, "lln_renorm", 0.0),
                   num_scales=getattr(cfg, "lln_num_scales", 4),
                   scale_decay=getattr(cfg, "lln_scale_decay", 0.5))


def train_twin_of(cfg) -> str:
    """The CPU twin of a training forward (attention and the Mamba2 SSD
    scan): the kernels, interpreted, where ``cfg.use_kernel`` asks; the
    jnp scan otherwise.  The one reader of ``use_kernel``."""
    return "pallas" if cfg.use_kernel else "scan"


# ---------------------------------------------------------------------------
# Backend resolution — the one place that reads the platform.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Resolution:
    """A concrete dispatch decision: which implementation kind runs, and
    whether a Pallas kernel runs in interpret mode."""
    kind: str            # "pallas" | "scan" | "ref"
    interpret: bool = False


def on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def resolve(backend: str, *, ragged: bool = False,
            cpu_twin: str = "scan") -> Resolution:
    """Resolve a backend name to a concrete implementation kind.

    Args:
      backend: one of :data:`BACKENDS`.
      ragged: sequence length not divisible by the op's chunk/block — no
        kernel or twin exists; ``auto`` falls back to the jnp reference and
        explicit ``pallas``/``scan`` raise.
      cpu_twin: the kind ``auto`` selects on the CPU container (per-op:
        the training forwards run the Pallas kernel interpreted, the
        serving ops run their ``lax.scan``/jnp twins).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown attention backend {backend!r}; "
                         f"expected one of {BACKENDS}")
    if backend == "auto":
        if ragged:
            return Resolution("ref")
        if on_cpu():
            return Resolution(cpu_twin, interpret=True)
        return Resolution("pallas")
    if backend in ("pallas", "scan"):
        if ragged:
            raise ValueError(
                f"backend={backend!r} has no ragged-length path "
                "(sequence length must be a chunk/block multiple); "
                "use backend='auto' or 'ref'")
        if backend == "pallas":
            return Resolution("pallas", interpret=on_cpu())
        return Resolution("scan")
    return Resolution("ref")


# ---------------------------------------------------------------------------
# Spec-level entry points (what the AttentionEngine calls).  These import
# kernels.ops lazily: ops imports this module for `resolve`.
# ---------------------------------------------------------------------------

def attention(spec: AttnSpec, q, k, v, alpha, beta, **kw):
    """Full-sequence LLN / LLN+Diag / log-linear attention under
    ``spec.backend`` — the kernel route of ``AttentionEngine.attention``,
    which also owns softmax (no Pallas kernel) and the model-layout jnp
    route."""
    from . import ops
    if spec.impl == "lln":
        return ops.lln_attention(q, k, v, alpha, beta, spec.causal,
                                 spec.lln_chunk, backend=spec.backend, **kw)
    if spec.impl == "lln_diag":
        return ops.lln_diag_attention(q, k, v, alpha, beta, spec.causal,
                                      spec.diag_block, backend=spec.backend,
                                      **kw)
    if spec.impl == "log_linear":
        return ops.loglin_attention(q, k, v, alpha, beta, spec.causal,
                                    spec.lln_chunk,
                                    num_scales=spec.num_scales,
                                    scale_decay=spec.scale_decay,
                                    backend=spec.backend, **kw)
    raise ValueError(f"registry.attention does not handle {spec.impl!r}")


def prefill(spec: AttnSpec, q, k, v, alpha, beta):
    """State-emitting causal LLN prefill under ``spec.backend``.
    Returns ``(out, s, z, c_k)`` in the decode-state layout."""
    from . import ops
    return ops.lln_prefill(q, k, v, alpha, beta, chunk=spec.lln_chunk,
                           backend=spec.backend)


def loglin_prefill(spec: AttnSpec, q, k, v, alpha, beta):
    """State-emitting causal log-linear prefill under ``spec.backend``.
    Returns ``(out, s, z, c_k, sl, zl, cl)`` — the open-bucket LLN state
    plus the Fenwick bucket pyramid (``core/loglinear.py`` layout)."""
    from . import ops
    return ops.loglin_prefill(q, k, v, alpha, beta, chunk=spec.lln_chunk,
                              num_scales=spec.num_scales,
                              scale_decay=spec.scale_decay,
                              backend=spec.backend)


def decode_chunk(spec: AttnSpec, state, q, k, v, alpha, beta,
                 row_mask=None, commit_len=None, pos=None):
    """Advance an ``LLNState`` over T tokens under ``spec.backend``.
    ``commit_len`` (B,) folds only the accepted prefix (speculative
    verify — see ``ops.lln_decode_chunk``).  ``log_linear`` specs route
    to :func:`ops.loglin_decode_chunk` and additionally need ``pos``
    (B,) — the per-row depth that determines each row's bucket layout."""
    from . import ops
    if spec.impl == "log_linear":
        return ops.loglin_decode_chunk(state, q, k, v, alpha, beta,
                                       pos=pos, granule=spec.lln_chunk,
                                       num_scales=spec.num_scales,
                                       scale_decay=spec.scale_decay,
                                       row_mask=row_mask,
                                       backend=spec.backend,
                                       commit_len=commit_len,
                                       renorm=spec.renorm or None)
    return ops.lln_decode_chunk(state, q, k, v, alpha, beta,
                                row_mask=row_mask, backend=spec.backend,
                                commit_len=commit_len,
                                renorm=spec.renorm or None)


def commit_chunk(spec: AttnSpec, state, k, v, beta,
                 row_mask=None, commit_len=None, pos=None):
    """Fold a scored chunk's accepted prefix into an ``LLNState`` under
    ``spec.backend`` — the single-pass speculative-verify commit (no
    scoring; see ``ops.lln_commit_chunk``)."""
    from . import ops
    if spec.impl == "log_linear":
        return ops.loglin_commit_chunk(state, k, v, beta,
                                       pos=pos, granule=spec.lln_chunk,
                                       num_scales=spec.num_scales,
                                       row_mask=row_mask,
                                       backend=spec.backend,
                                       commit_len=commit_len,
                                       renorm=spec.renorm or None)
    return ops.lln_commit_chunk(state, k, v, beta,
                                row_mask=row_mask, backend=spec.backend,
                                commit_len=commit_len,
                                renorm=spec.renorm or None)


def diag_fwd(spec: AttnSpec, q, k, v):
    """Inference block-diagonal softmax (the §4.2 diag component) under
    ``spec.backend``."""
    from . import ops
    return ops.block_diag_fwd(q, k, v, spec.diag_block, spec.causal,
                              backend=spec.backend)
