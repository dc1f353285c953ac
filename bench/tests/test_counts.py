"""Operation and byte counts against hand counts, and the peaks table."""
import pytest

from bench import flops, harness, reference
from bench.tests import smoke


def stablelm():
    return reference.sizes_of(smoke._load("configs",
                                          "stablelm-1.6b-lln_diag.json"))


def test_layer_params_by_hand():
    # q, k, v, o: 4 * 2048 * 2048; SwiGLU: 3 * 2048 * 5632.
    assert flops.layer_params(stablelm()) == 4 * 2048 * 2048 + \
        3 * 2048 * 5632


def test_decode_flops_by_hand():
    sz = stablelm()
    attn = 32 * (4 * 64 * 64 + 4 * 64 * 128.5)
    per_layer = 2 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + attn
    assert flops.decode_flops(sz) == 24 * per_layer + 2 * 2048 * 100352


def test_decode_kernel_bytes_by_hand():
    # One row, one head, t = 16, d = 64: qs and ks f32, v and out bf16,
    # the (64, 64) state and (64,) normaliser read and written in f32.
    c = flops.lln_decode_call(1, 1, 16, 64)
    assert c["bytes"] == 4 * 16 * 64 * 2 + 2 * 16 * 64 * 2 + \
        2 * 4 * (64 * 64 + 64)
    assert c["flops"] == 2 * 16 * 16 * 64 * 2 + 2 * 16 * 64 * 64 * 2 + \
        2 * 16 * 64


def test_least_seconds_takes_the_binding_bound():
    p = flops.peaks("TPU v5 lite")
    assert flops.least_seconds({"flops": 197e12, "bytes": 1.0}, p) == 1.0
    assert flops.least_seconds({"flops": 1.0, "bytes": 819e9}, p) == 1.0


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("cpu")


def test_cpu_platform_is_refused():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)


def test_kernel_found_by_its_shapes_in_the_hlo_text():
    # A v5e trace names the decode kernel's op by its HLO text; at 16 slots
    # of 32 heads the reader must find it and divide its least time by it.
    from bench import harness, readout
    c = harness.cell(harness.load_benchmark(), "stablelm-decode-backlog")
    text = ("%closed_call.24 = (bf16[512,16,64]{2,1,0:T(8,128)(2,1)}, "
            "f32[512,64,64]{2,1,0:T(8,128)}, f32[512,1,64]{2,1,0:T(1,128)}) "
            "custom-call(f32[512,16,64]{2,1,0} %pad.1, f32[512,16,64]{2,1,0} "
            "%pad.2, bf16[512,16,64]{2,1,0:T(8,128)(2,1)} %pad.3, "
            "f32[512,64,64]{2,1,0} %b.1, f32[512,1,64]{2,1,0} %b.2), "
            'custom_call_target="tpu_custom_call", '
            "frontend_attributes={kernel_metadata={}}")
    other = text.replace("512,16,64", "512,32,64")
    rec = {"kind": "serve", "device_kind": "TPU v5 lite",
           "config": c["config"], "mix": dict(c["mix"], pool={
               "slots": 16, "segment": 8, "max_len": 1024}),
           "trace": {"ops": {text: {"count": 10, "seconds": 0.01},
                             other: {"count": 5, "seconds": 9.0}}}}
    cost = flops.lln_decode_call(512, 512, 16, 64)
    want = 100 * 10 * cost["bytes"] / 819e9 / 0.01
    assert harness.reader("lln_decode_roofline")(rec) == pytest.approx(want)
    del rec["trace"]["ops"][text]
    assert harness.reader("lln_decode_roofline")(rec) is None
    assert readout.kernel_calls({"trace": None}) == []
