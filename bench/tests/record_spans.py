"""Record the small v5e traces that ``test_spans.py`` reads.

    python3 bench/tests/record_spans.py bench/tests/data/spans-v5e.json.gz

On one chip, two windows of about one second each, traced exactly as a
``--trace 1`` run traces its cell: the stablelm pool cut to 2 layers and 4
slots over a backlog of 256-token prompts (as ``record_trace.py``), and the
RoBERTa-LLN train step cut to 2 layers and a batch of 8.  What is kept is
each window as :func:`bench.spans.load` reads it: device ops and modules,
the host's ``bench.window`` and ``batcher.*`` spans with their arguments,
and the ``op_name`` of every op, names stored once, gzipped.  The pool's
window sits at the top level in the form ``record_trace.load_extract``
reads, so ``test_trace.py`` reduces it too; the train step's sits under
``"train"``.  ``--extract <pool.xplane.pb> <train.xplane.pb> <out>`` makes
the file from traces recorded before, with no chip; ``<out> --keep <dir>``
keeps the two ``.xplane.pb`` files of a recording there (by default in
``.bench_out/spans``).
"""
import copy
import glob
import gzip
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _window(pb: str) -> dict:
    from bench import spans, trace
    devices, host, op_names = spans.load(pb)
    bench_host = trace.load(pb)[1]
    lo, hi = next((s, s + d) for n, s, d, _ in host if n == trace.WINDOW)
    names = {}

    def ev(events):
        return [[names.setdefault(n, len(names)), s, d]
                for n, s, d in events if s < hi and s + d > lo]

    dev = [{line: ev(evs) for line, evs in lines.items()}
           for lines in devices]
    doc = {"host": ev(bench_host), "devices": dev,
           "spans": [[names.setdefault(n, len(names)), s, d, args]
                     for n, s, d, args in host
                     if s < hi and s + d > lo]}
    doc["op_names"] = {str(i): op_names[n] for n, i in names.items()
                       if op_names.get(n)}
    doc["names"] = sorted(names, key=names.get)
    return doc


def extract(pool_pb: str, train_pb: str, out: str) -> None:
    """Write both windows to ``out``."""
    doc = _window(pool_pb)
    doc["train"] = _window(train_pb)
    with gzip.open(out, "wt") as f:
        json.dump(doc, f)


def load_extract(path: str, part: str = "pool") -> tuple:
    """``(devices, spans, op_names, bench spans)`` of one window of a file
    :func:`extract` wrote: the first three as :func:`bench.spans.load`
    returns them, the last as :func:`bench.trace.load` does."""
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    if part == "train":
        doc = doc["train"]
    names = doc["names"]
    ev = lambda evs: [(names[i], s, d) for i, s, d in evs]
    devices = [{line: ev(evs) for line, evs in lines.items()}
               for lines in doc["devices"]]
    host = [(names[i], s, d, args) for i, s, d, args in doc["spans"]]
    op_names = {names[int(i)]: v for i, v in doc["op_names"].items()}
    return devices, host, op_names, ev(doc["host"])


def _newest(directory: Path) -> str:
    return sorted(glob.glob(str(directory / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]


def record(keep: str) -> tuple:
    """Trace the two windows on the chip; returns the paths of the two
    ``.xplane.pb`` files, copied into ``keep``."""
    from bench import harness, serve, train
    from bench.tests import smoke

    harness.require_chips(1)
    Path(keep).mkdir(parents=True, exist_ok=True)
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
    pool_pb = shutil.copy(_newest(serve.TRACE_DIR),
                          Path(keep) / "pool.xplane.pb")
    del pool

    t = {"name": "spans-train", "chips": 1,
         "config": copy.deepcopy(smoke._load("configs", "roberta-lln.json")),
         "mix": copy.deepcopy(smoke._load("traffic", "mlm512.json"))}
    t["config"]["model"]["num_hidden_layers"] = 2
    t["config"]["program"].setdefault("overrides", {})["n_layers"] = 2
    t["mix"].update({"batch": 8, "batches": 4})
    tr = train.Trainer(t)
    step, state, batches, _ = tr.start(5, tr.batches(5))
    w = tr.window(step, state, batches, 1.0, True)
    assert w["trace"]["busy_s"] > 0
    train_pb = shutil.copy(_newest(train.TRACE_DIR),
                           Path(keep) / "train.xplane.pb")
    return str(pool_pb), str(train_pb)


def main(out: str, keep: str) -> int:
    extract(*record(keep), out)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    if sys.argv[1] == "--extract":
        extract(*sys.argv[2:5])
        sys.exit(0)
    keep = sys.argv[3] if sys.argv[2:3] == ["--keep"] \
        else str(ROOT / ".bench_out" / "spans")
    sys.exit(main(sys.argv[1], keep))
