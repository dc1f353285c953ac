"""The decode segment's collective operations, read from the run's trace.

Under a mesh, XLA puts collectives into the pool's decode segment program
(``jit__segment``): the all-reduce after each split attention and MLP
block, the gathers of the vocabulary-split sampling, the sentinel's
reductions.  A TPU trace names each op by its HLO text
(``%all-reduce.41 = bf16[32,1,4096]{..} all-reduce(..), ..``), so a
collective is known by its opcode there: ``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute`` or ``all-to-all``, each also in
its asynchronous ``-start``/``-done`` halves.  Its model scope is the one
of the op it partitions, in its ``op_name`` (``bench/spans.py``).

:func:`summary` returns, over the window that ``bench.window`` marks:
its ``window_s``; and per device (the mean over the devices traced) the
``executions`` of the segment program, ``collective_s`` (the union of the
collective ops' intervals inside them), ``by_scope`` (the same by the
innermost model scope each op holds, ``none`` where it holds none) and
the collective ``calls``; None for a run without a trace.
"""
from __future__ import annotations

import bisect
import functools
import re

from bench import spans, trace

OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
           "collective-permute", "all-to-all")
_COLLECTIVE = re.compile(r"\s(?:%s)(?:-start|-done)?\(" % "|".join(OPCODES))
NO_SCOPE = "none"
_MODEL = frozenset(spans.MODEL_SCOPES)


def is_collective(name: str) -> bool:
    """Whether an op's HLO text is a collective."""
    return _COLLECTIVE.search(name) is not None


def scope_of(op_name) -> str:
    """The innermost model scope (``spans.MODEL_SCOPES``) an ``op_name``
    holds, or ``none``."""
    held = [part for part in re.split(r"[/;]", op_name or "")
            if spans.scopes_of(part) & _MODEL]
    return min(spans.scopes_of(held[-1]) & _MODEL) if held else NO_SCOPE


def reduce(devices: list, host: list, op_names: dict) -> dict:
    """The reduction on plain data, in ``bench/spans.py:load``'s form."""
    win = [(s, s + d) for n, s, d, _ in host if n == trace.WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0]
    executions = total = calls = 0.0
    by_scope: dict = {}
    for lines in devices:
        mods = sorted((max(s, lo), min(s + d, hi))
                      for name, s, d in lines.get(trace.MODULES_LINE, ())
                      if spans.SEGMENT in name and min(s + d, hi) > max(s, lo))
        executions += len(mods)
        starts = [a for a, _ in mods]
        found: dict = {}
        for name, s, d in lines.get(trace.OPS_LINE, ()):
            if not is_collective(name):
                continue
            i = bisect.bisect_right(starts, s) - 1
            for ma, mb in mods[max(i, 0):i + 2]:
                a, b = max(s, ma), min(s + d, mb)
                if b > a:
                    found.setdefault(scope_of(op_names.get(name)),
                                     []).append((a, b))
                    calls += 1
        for scope, ivs in found.items():
            got = spans._length(ivs) / 1e9
            by_scope[scope] = by_scope.get(scope, 0.0) + got
        total += spans._length([iv for ivs in found.values()
                                for iv in ivs]) / 1e9
    ndev = max(len(devices), 1)
    return {"window_s": (hi - lo) / 1e9,
            "executions": executions / ndev, "collective_s": total / ndev,
            "calls": calls / ndev,
            "by_scope": {k: v / ndev for k, v in sorted(by_scope.items())}}


@functools.lru_cache(maxsize=4)
def _reduced(path: str) -> dict:
    return reduce(*spans.load(path))


def summary(rec: dict):
    """The reduction of the run's own trace (``spans.summary``'s rule), or
    None for a run without a trace."""
    tr = rec.get("trace")
    path = spans.newest() if tr else None
    if path is None:
        return None
    got = _reduced(path)
    if abs(got["window_s"] - tr["window_s"]) > 1e-6:
        raise ValueError(f"{path} holds a window of {got['window_s']} s, "
                         f"the run's was {tr['window_s']} s")
    return got
