"""Keep a small part of a four-chip traced run of the yi-9b cell for
``test_tp4.py``.

    python3 bench/tests/record_tp4.py <run.xplane.pb> <record.json> <out>

Run after ``bench/run.py --workload yi9b-tp4-decode-backlog --trace 1``
on the chip, with the run's trace (``.bench_out/trace``) and its record
(``.bench_out/records``); no chip is needed.  The whole 20-second trace of
four chips is hundreds of megabytes, so what is kept is one segment
boundary of it: from the dispatch of the first segment after which the
batcher admitted a request to the dispatch of the next, with a
``bench.window`` span over exactly that stretch, in the form
``record_spans.load_extract`` reads (device ops and modules of every
chip, the host's ``bench.*`` and ``batcher.*`` spans with their
arguments, each op's ``op_name``), names stored once, gzipped.  Beside it:
the record's numbers that the model-step metrics read (``chips``, the
window, its counters) and the run's result line.
"""
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
WINDOW = "bench.window"
SEGMENT = "batcher.segment"
PREFILL = "batcher.prefill"


def stretch(host: list) -> tuple:
    """(start, end) in ns of the first segment boundary in the window whose
    admission ran a prefill."""
    lo, hi = next((s, s + d) for n, s, d, _ in host if n == WINDOW)
    segs = sorted(s for n, s, d, _ in host if n == SEGMENT and lo <= s < hi)
    fills = [s for n, s, d, _ in host if n == PREFILL]
    for a, b in zip(segs[1:], segs[2:]):
        if any(a <= s < b for s in fills):
            return a, b
    raise ValueError("no admission between two segments in the window")


def extract(pb: str, record: str, out: str) -> None:
    from bench import spans, trace
    devices, host, op_names = spans.load(pb)
    lo, hi = stretch(host)
    names = {}

    def keep(events):
        return [[names.setdefault(n, len(names)), max(s, lo),
                 min(s + d, hi) - max(s, lo)]
                for n, s, d in events if s < hi and s + d > lo]

    window = [WINDOW, lo, hi - lo]
    bench_host = [e for e in trace.load(pb)[1] if e[0] != WINDOW]
    doc = {"devices": [{line: keep(evs) for line, evs in lines.items()}
                       for lines in devices],
           "host": keep([window] + bench_host),
           "spans": [[names.setdefault(n, len(names)), s, d, args]
                     for n, s, d, args in host
                     if n != WINDOW and s < hi and s + d > lo]
           + [[names.setdefault(WINDOW, len(names)), lo, hi - lo, {}]]}
    doc["op_names"] = {str(i): op_names[n] for n, i in names.items()
                       if op_names.get(n)}
    doc["names"] = sorted(names, key=names.get)
    with open(record) as f:
        rec = json.load(f)
    doc["record"] = {k: rec[k] for k in ("chips", "device_kind", "window_s",
                                         "memory_peak_bytes", "correct")}
    doc["record"]["serve"] = {"window": rec["serve"]["window"]}
    doc["record"]["collectives"] = rec.get("collectives")
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    extract(*sys.argv[1:4])
