"""What the metric readers share: the trace summary's lookups, the peaks of
the run's device, and the shapes a cell's kernels run at."""
from __future__ import annotations

import re

from bench import flops, reference


def peak(rec: dict) -> dict:
    return flops.peaks(rec["device_kind"])


def sizes(rec: dict) -> dict:
    return reference.sizes_of(rec["config"])


def device_seconds(rec: dict, table: str, needle: str):
    """(executions, device seconds) of the trace's programs or ops whose
    name holds ``needle``; None without a trace or a match."""
    tr = rec.get("trace")
    if not tr:
        return None
    hits = [t for name, t in tr[table].items() if needle in name]
    if not hits:
        return None
    return (sum(t["count"] for t in hits), sum(t["seconds"] for t in hits))


#: An array type in an op's HLO text: ``bf16[512,16,64]``.
_TYPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)\[([0-9,]*)\]")

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def _shapes(text: str) -> list:
    return [tuple(int(x) for x in dims.split(",") if x)
            for _, dims in _TYPE.findall(text)]


def kernel_calls(rec: dict) -> list:
    """The trace's Pallas kernel ops as ``(operand shapes, result shapes,
    calls, device seconds)``.  A v5e trace names each op by its HLO text
    (``%closed_call.24 = (bf16[512,16,64]{..}, ..) custom-call(f32[..] ..),
    custom_call_target="tpu_custom_call", ..``), not by the kernel
    function, so a kernel is known by the shapes it takes and returns."""
    tr = rec.get("trace")
    if not tr:
        return []
    out = []
    for text, t in tr["ops"].items():
        if KERNEL_TARGET not in text or " custom-call(" not in text:
            continue
        head, _, rest = text.partition(" custom-call(")
        operands = rest.partition(", custom_call_target=")[0]
        out.append((_shapes(operands), _shapes(head.partition(" = ")[2]),
                    t["count"], t["seconds"]))
    return out


def roofline(rec: dict, kernels: list):
    """Percent of the least time over the device time, summed over every
    call of the kernels in ``kernels``: ``(operand shapes, result shapes,
    cost of one call)`` each, a call matching where both shape lists equal
    the op's.  None where the trace holds none of them."""
    least = spent = 0.0
    p = peak(rec)
    for operands, results, calls, seconds in kernel_calls(rec):
        for want_in, want_out, cost in kernels:
            if operands == list(want_in) and results == list(want_out):
                least += calls * flops.least_seconds(cost, p)
                spent += seconds
                break
    return 100.0 * least / spent if spent else None


def idle_share(rec: dict):
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
