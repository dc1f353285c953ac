"""The reader of the program's spans and scopes, on plain events and on
traces recorded on a v5e."""
import os

import pytest

from bench import harness, spans, trace
from bench.tests import record_spans

MS = 1_000_000  # nanoseconds


def _by_hand():
    # Window 0-100 ms.  Segment program 10-60 ms: attn op 10-30, an
    # unscoped copy 30-40, the layer loop (a container) 10-55, an mlp op
    # 40-55.  An eviction op 70-75 outside the segment.  Host: admit 0-10
    # with a prefill 2-8, segment 10-12, sync 12-62, harvest 62-80 and its
    # eviction 70-76, deadlines 80-100.
    dev = [{trace.OPS_LINE: [("fusion.1", 10 * MS, 20 * MS),
                             ("copy.2", 30 * MS, 10 * MS),
                             ("%while.3 = (s32[]) while(%t)", 10 * MS,
                              45 * MS),
                             ("fusion.4", 40 * MS, 15 * MS),
                             ("fusion.5", 70 * MS, 5 * MS)],
            trace.MODULES_LINE: [("jit__segment(1)", 10 * MS, 50 * MS),
                                 ("jit__evict(2)", 70 * MS, 5 * MS)]}]
    host = [("bench.window", 0, 100 * MS, {}),
            ("batcher.admit", 0, 10 * MS, {}),
            ("batcher.prefill", 2 * MS, 6 * MS, {"rows": 3,
                                                 "prompt_len": 256}),
            ("batcher.segment", 10 * MS, 2 * MS, {"segment": 0,
                                                  "active_rows": 4}),
            ("batcher.sync", 12 * MS, 50 * MS, {}),
            ("batcher.harvest", 62 * MS, 18 * MS, {"emitted": 30,
                                                   "slot_steps": 32}),
            ("batcher.evict", 70 * MS, 6 * MS, {}),
            ("batcher.deadlines", 80 * MS, 20 * MS, {}),
            ("batcher.harvest", 120 * MS, 1 * MS, {"emitted": 5,
                                                   "slot_steps": 32})]
    ops = {"fusion.1": "jit(_segment)/while/body/attn/lln_decode/mul",
           "copy.2": "jit(_segment)/while/body/dynamic_update_slice",
           "%while.3 = (s32[]) while(%t)": "jit(_segment)/while",
           "fusion.4": "jit(_segment)/while/body/transpose(jvp(mlp))/dot",
           "fusion.5": "jit(_evict)/select_n"}
    return dev, host, ops


def test_reduction_by_hand():
    s = spans.reduce(*_by_hand())
    assert s["window_s"] == pytest.approx(0.1)
    # Gaps 0-10 (prefill open at 5), 55-70 (harvest at 62.5), 75-100
    # (deadlines at 87.5).
    assert s["idle"] == pytest.approx({"batcher.prefill": 0.010,
                                       "batcher.harvest": 0.015,
                                       "batcher.deadlines": 0.025})
    assert s["scopes"] == pytest.approx({"attn": 0.020, "mlp": 0.015,
                                         "lln_decode": 0.020})
    # The segment's 50 ms: 35 under attn or mlp, 10 of the copy, 5 of the
    # loop alone (55-60: no op).
    seg = s["segment"]
    assert seg["executions"] == 1
    assert seg["device_s"] == pytest.approx(0.050)
    assert seg["scoped_s"] == pytest.approx(0.035)
    assert seg["residual_s"] == pytest.approx(0.015)
    assert seg["outside_ops_s"] == pytest.approx(0.005)
    assert seg["residual_ops"] == [["copy.2", pytest.approx(0.010)]]
    # Spans started in the window only: the second harvest is past it.
    assert s["spans"]["batcher.harvest"] == {
        "count": 1, "args": {"emitted": 30, "slot_steps": 32}}
    assert s["spans"]["batcher.prefill"]["args"]["rows"] == 3


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        spans.reduce([], [("batcher.admit", 0, 1, {})], {})


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/while/body/closed_call/attn/block_diag/mul",
     {"attn", "block_diag"}),
    ("jit(f)/transpose(jvp(mlm_head))/reduce_sum", {"mlm_head"}),
    ("mul;attn", {"attn"}),
    ("jit(f)/jvp()/while/body/dynamic_slice", set()),
    (None, set())])
def test_scopes_of(op_name, want):
    assert spans.scopes_of(op_name) == want


@pytest.mark.parametrize("name,want", [
    ("%while.55 = (s32[], bf16[24,16,256,32,64]) while(%tuple.113), "
     "condition=%region_13, body=%region_2", True),
    ("%call.3 = f32[8] call(f32[8] %p), to_apply=%f", True),
    ("%copy.299 = bf16[24,16,256,32,64] copy(bf16[24,16,256,32,64] "
     "%get-tuple-element.2041)", False),
    ("%lln_decode.7 = (bf16[128,16,64]) custom-call(f32[128,16,64] %p), "
     'custom_call_target="tpu_custom_call"', False)])
def test_is_container(name, want):
    # The carry's copies hold the loop's op_name, not its opcode.
    assert spans.is_container(name) is want


def test_a_program_without_spans_or_scopes_reads_nothing(monkeypatch):
    dev, host, ops = _by_hand()
    bare = spans.reduce(dev, [h for h in host if h[0] == "bench.window"],
                        {})
    rec = {"kind": "serve", "trace": {"window_s": bare["window_s"]}}
    monkeypatch.setattr(spans, "newest", lambda: "x.xplane.pb")
    monkeypatch.setattr(spans, "_reduced", lambda path: bare)
    for name in ("admit_idle_ms.decode", "boundary_idle_ms.decode",
                 "carry_device_ms.decode", "pool_occupancy.decode"):
        assert harness.reader(name)(rec) is None
    rec = {"kind": "train", "trace": {"window_s": bare["window_s"]},
           "train": {"steps": 3}}
    for name in ("attn_device_ms.train", "head_device_ms.train"):
        assert harness.reader(name)(rec) is None


def test_another_runs_trace_is_refused(monkeypatch):
    s = spans.reduce(*_by_hand())
    monkeypatch.setattr(spans, "newest", lambda: "x.xplane.pb")
    monkeypatch.setattr(spans, "_reduced", lambda path: s)
    assert spans.summary({"trace": {"window_s": 0.1 + 5e-7}}) is s
    with pytest.raises(ValueError):
        spans.summary({"trace": {"window_s": 0.1 + 2e-6}})
    assert spans.summary({"trace": None}) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "spans-v5e.json.gz")


@pytest.fixture(scope="module", params=["pool", "train"])
def recorded(request):
    """(part, the reduction of the program's spans, the trace summary,
    the ``bench.*`` spans) of one window of the recorded file."""
    devices, host, op_names, bench_host = record_spans.load_extract(
        RECORDED, request.param)
    return (request.param, spans.reduce(devices, host, op_names),
            trace.reduce_events(devices, bench_host), bench_host)


def test_idle_split_by_span_adds_up(recorded):
    _, s, tr, _ = recorded
    assert s["window_s"] == tr["window_s"]
    assert sum(s["idle"].values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"], abs=1e-9)


def test_scopes_fit_in_busy_time(recorded):
    _, s, tr, _ = recorded
    assert s["scopes"]
    assert all(0 < v <= tr["busy_s"] + 1e-12 for v in s["scopes"].values())


def test_segment_splits_into_scoped_and_residual(recorded):
    part, s, tr, _ = recorded
    seg = s["segment"]
    if part == "train":
        assert seg["executions"] == 0
        return
    assert seg["executions"] > 0 and seg["scoped_s"] > 0
    # The segment's device time, as segment_device_ms reads it.
    programs = tr["programs"]
    device = sum(t["seconds"] for n, t in programs.items()
                 if spans.SEGMENT in n)
    assert seg["device_s"] == pytest.approx(device, rel=1e-9)
    assert seg["scoped_s"] + seg["residual_s"] == pytest.approx(
        device, rel=1e-9)
    assert 0 <= seg["outside_ops_s"] <= seg["residual_s"]


def test_all_six_readers_read_the_recorded_windows(recorded, monkeypatch):
    part, s, tr, bench_host = recorded
    monkeypatch.setattr(spans, "newest", lambda: "x.xplane.pb")
    monkeypatch.setattr(spans, "_reduced", lambda path: s)
    if part == "pool":
        rec = {"kind": "serve", "trace": tr}
        names = ("admit_idle_ms.decode", "boundary_idle_ms.decode",
                 "carry_device_ms.decode", "pool_occupancy.decode")
    else:
        lo = next(s for n, s, d in bench_host if n == trace.WINDOW)
        steps = sum(1 for n, s, d in bench_host
                    if n == "bench.step" and s >= lo)
        rec = {"kind": "train", "trace": tr, "train": {"steps": steps}}
        names = ("attn_device_ms.train", "head_device_ms.train")
    for name in names:
        value = harness.reader(name)(rec)
        assert value is not None and value > 0, name
    if part == "pool":
        assert harness.reader("pool_occupancy.decode")(rec) <= 100
