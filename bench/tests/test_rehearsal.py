"""Every driver, called directly at the configurations' smoke sizes on the
CPU for a 2-second window, prints a line with the contract's keys; and a
cell, a configuration and a metric added as files are found by name."""
import json
import shutil
import time

import jax
import pytest

from bench import harness, serve, train
from bench.tests import smoke

KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def line_of(cell_name, c, driver, trace):
    bench = harness.load_benchmark()
    c = dict(c, name=cell_name)
    rec = driver.run(c, seed=2**33 + 7, seconds=2.0, trace=trace,
                     t_start=time.perf_counter())
    rec["device_kind"] = "TPU v5 lite"   # the peaks table's row; CPU times
    return rec, harness.result_line(bench, c, rec, jax.devices(), trace)


@pytest.mark.parametrize("cell,make,driver", [
    ("stablelm-decode-backlog", smoke.backlog_cell, serve),
    ("roberta-mlm512", smoke.train_cell, train),
])
def test_driver_line(cell, make, driver):
    rec, line = line_of(cell, make(), driver, trace=False)
    assert KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] and line["attempted"] > 0 and not line["failed"]
    want = {m["name"] for m in harness.metrics_of(harness.load_benchmark(),
                                                  cell, False)}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    json.dumps(line)


def test_traced_line_has_window_and_breakdown_keys():
    rec, line = line_of("stablelm-decode-backlog", smoke.backlog_cell(),
                        serve, trace=True)
    assert line["device"]["window_s"] > 0
    assert "slot_occupancy.decode" in line["metrics"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench")
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    bench = harness.load_benchmark(tmp_path)
    conf = dict(bench["configs"][0], name="stablelm-copy",
                file="bench/configs/stablelm-copy.json")
    shutil.copy(tmp_path / bench["configs"][0]["file"], tmp_path / conf["file"])
    bench["configs"].append(conf)
    bench["workloads"].append({
        "name": "stablelm-new-mix", "config": "stablelm-copy",
        "traffic": "new-mix", "chips": 1, "why": "a test cell"})
    bench["end_to_end"][1]["workloads"].append("stablelm-new-mix")
    bench["per_layer"].append({
        "name": "answer.new", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving loop",
        "moves": "serve_tokens_per_s", "workloads": ["stablelm-new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = json.loads((tmp_path / "bench/traffic/decode-backlog.json")
                     .read_text())
    mix["arrivals"]["per_second"] = 7
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/stablelm-new-mix.json").write_text(
        '{"max_logit_gap": 1.0}')
    (tmp_path / "bench/metrics/answer.new.py").write_text(
        "def read(rec):\n    return 42.0\n")

    bench = harness.load_benchmark(tmp_path)
    c = harness.cell(bench, "stablelm-new-mix", root=tmp_path)
    assert c["mix"]["arrivals"]["per_second"] == 7
    assert c["config"]["name"] == "stablelm-1.6b-lln_diag"
    assert c["limits"] == {"max_logit_gap": 1.0}
    names = [m["name"] for m in harness.metrics_of(bench, "stablelm-new-mix",
                                                   True)]
    assert names == ["answer.new"]
    assert harness.reader("answer.new", root=tmp_path)({}) == 42.0
    assert [m["name"] for m in harness.metrics_of(
        bench, "stablelm-new-mix", False)] == ["setup_s",
                                               "serve_tokens_per_s"]
