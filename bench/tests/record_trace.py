"""Record the small v5e trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/pool-v5e.json.gz

On one chip: the stablelm pool cut to 2 layers, 4 slots and a backlog of
256-token prompts, traced through a window of about one second, exactly as
a ``--trace 1`` run traces its cell.  What is kept is the trace's window as
:func:`bench.trace.load` reads it (device ops and modules, the host's
``bench.*`` spans), names stored once, gzipped: the whole ``.xplane.pb``
is some megabytes.  ``--extract <xplane.pb>`` makes the file from a trace
recorded before, with no chip.
"""
import copy
import glob
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def extract(pb: str, out: str) -> None:
    """Write the window of trace ``pb`` as ``load`` returns it to ``out``."""
    from bench import trace
    devices, host = trace.load(pb)
    lo, hi = next((s, s + d) for n, s, d in host if n == trace.WINDOW)
    names = {}

    def ev(events):
        return [[names.setdefault(n, len(names)), s, d]
                for n, s, d in events if s < hi and s + d > lo]

    dev = [{line: ev(evs) for line, evs in lines.items()}
           for lines in devices]
    doc = {"host": ev(host), "devices": dev,
           "names": sorted(names, key=names.get)}
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)


def load_extract(path: str) -> tuple[list, list]:
    """(device lines, host spans) of a file :func:`extract` wrote."""
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    names = doc["names"]
    ev = lambda evs: [(names[i], s, d) for i, s, d in evs]
    return ([{line: ev(evs) for line, evs in lines.items()}
             for lines in doc["devices"]], ev(doc["host"]))


def main(out: str) -> int:
    from bench import harness, serve
    from bench.tests import smoke

    harness.require_chips(1)
    c = smoke.backlog_cell()
    c["config"] = copy.deepcopy(smoke._load("configs",
                                            "stablelm-1.6b-lln_diag.json"))
    c["config"]["model"]["num_hidden_layers"] = 2
    c["config"]["program"]["overrides"]["n_layers"] = 2
    c["mix"].update({"pool": {"slots": 4, "segment": 8, "max_len": 512},
                     "prompt_len": {"kind": "choice", "values": [256],
                                    "weights": [1]},
                     "gen_len": {"kind": "uniform", "min": 16, "max": 24}})
    pool = serve.Pool(c, 5, 1.0)
    got = pool.serve(5, 1.0, True)
    assert got["trace"]["busy_s"] > 0
    src = sorted(glob.glob(str(serve.TRACE_DIR / "plugins" / "profile" / "*"
                               / "*.xplane.pb")))[-1]
    extract(src, out)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    if sys.argv[1] == "--extract":
        extract(sys.argv[2], sys.argv[3])
        sys.exit(0)
    sys.exit(main(sys.argv[1]))
