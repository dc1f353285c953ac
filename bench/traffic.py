"""Traffic from a mix's parameters and a seed.

One generator serves every serving mix: it reads ``arrivals``,
``prompt_len`` and ``gen_len`` from a ``bench/traffic/*.json`` file.  The
set of sizes is drawn at evenly spaced quantiles, block by block, so every
seed gets the same work and only its order within each block (and the
prompt tokens) change with the seed: run-to-run spread then measures the
system, not the draw.  The MLM batches are the paper's objective (15% of positions
masked: 80% replaced by a mask id, 10% by a random token, 10% kept) over
a seeded first-order Markov corpus.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    """One request of a backlog, due when the window opens."""
    rid: int
    prompt: np.ndarray
    gen_len: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``'s distribution:
    ``values`` with ``weights``, ``lognormal`` (``median``, ``sigma``) or
    ``uniform``, clipped to [``min``, ``max``]."""
    kind = spec["kind"]
    u = _quantiles(n)
    if kind == "choice":
        w = np.asarray(spec["weights"], float)
        cum = np.cumsum(w / w.sum())
        idx = np.minimum(np.searchsorted(cum, u, side="right"), len(w) - 1)
        out = np.asarray(spec["values"])[idx]
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        out = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    elif kind == "uniform":
        out = np.floor(spec["min"] + u * (spec["max"] - spec["min"] + 1))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    if "min" in spec:
        out = np.clip(out, spec["min"], spec["max"])
    return out.astype(np.int64)


def request_count(mix: dict, seconds: float) -> int:
    a = mix["arrivals"]
    if a["kind"] != "backlog":
        raise ValueError(f"unknown arrival process {a['kind']!r}")
    return max(1, int(math.ceil(a["per_second"] * seconds)))


def block_size(mix: dict, seconds: float) -> int:
    """Requests per block: each run of ``block`` consecutive requests
    holds the same sizes (``arrivals.block``; the whole backlog where the
    mix names none), so that whatever prefix of the backlog a window
    serves, every seed serves nearly the same work."""
    n = request_count(mix, seconds)
    return min(n, int(mix["arrivals"].get("block", n)))


def prompt_lengths(mix: dict, seconds: float) -> list[int]:
    """Every prompt length a run of ``seconds`` sends, whatever its seed."""
    return sorted({int(x) for x in _lengths(mix["prompt_len"],
                                            block_size(mix, seconds))})


def _blocked(spec: dict, n: int, block: int, rng) -> np.ndarray:
    """``n`` lengths: every block holds the ``block`` quantiles of
    ``spec`` in an order drawn from ``rng``."""
    base = _lengths(spec, block)
    return np.concatenate([rng.permutation(base)
                           for _ in range(-(-n // block))])[:n]


def arrivals(mix: dict, seed: int, seconds: float, vocab: int
             ) -> list[Arrival]:
    """The requests of one run, in admission order: a ``backlog`` of
    ``per_second * seconds`` requests, all due when the window opens, more
    than the window serves."""
    n = request_count(mix, seconds)
    block = block_size(mix, seconds)
    rng = np.random.default_rng([int(seed), 1])
    plens = _blocked(mix["prompt_len"], n, block, rng)
    glens = _blocked(mix["gen_len"], n, block, rng)
    return [Arrival(rid=i,
                    prompt=rng.integers(0, vocab, size=int(plens[i]),
                                        dtype=np.int32),
                    gen_len=int(glens[i]))
            for i in range(n)]


def mlm_batches(vocab: int, batch: int, seq: int, count: int, seed: int,
                mask_rate: float = 0.15, branching: int = 32) -> list[dict]:
    """``count`` distinct masked-LM batches; the mask id is ``vocab - 1``
    and the loss mask marks the masked positions."""
    rng = np.random.default_rng([int(seed), 2])
    nexts = rng.integers(0, vocab, size=(vocab, branching))
    cum = np.cumsum(rng.dirichlet(np.ones(branching) * 0.5, size=vocab), 1)
    mask_id = vocab - 1
    out = []
    for _ in range(count):
        toks = np.empty((batch, seq), np.int32)
        state = rng.integers(0, vocab, size=batch)
        for t in range(seq):
            toks[:, t] = state
            pick = (cum[state] < rng.random(batch)[:, None]).sum(axis=1)
            state = nexts[state, np.minimum(pick, branching - 1)]
        masked = rng.random((batch, seq)) < mask_rate
        u = rng.random((batch, seq))
        inputs = toks.copy()
        inputs[masked & (u < 0.8)] = mask_id
        swap = masked & (u >= 0.8) & (u < 0.9)
        inputs[swap] = rng.integers(0, vocab, size=int(swap.sum()))
        out.append({"inputs": inputs, "targets": toks,
                    "mask": masked.astype(np.float32)})
    return out
