"""The four-chip yi-9b cell's additions on the CPU: its configuration, the
GQA reference against the program, the reference's own constants, the
sharded weights, the mesh driver at a smoke size, and the readers of the
metrics it adds."""
import copy
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import (collectives, flops, harness, readout, reference,
                   reference_gqa, serve_mesh, spans, trace, weights)
from bench.program import FIELDS, check_layout, program_config
from bench.tests import smoke

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "yi9b-tp4-decode-backlog"

#: A Yi-shaped model small enough for the CPU: RMSNorm, SwiGLU, full
#: rotary, 16 query heads over 4 kv heads (GQA r = 4) of the published
#: head size 128, so that the reference's fitted constants apply as they
#: do at full size and the kv heads split over four devices.
SMOKE_MODEL = {"num_hidden_layers": 2, "hidden_size": 256,
               "num_attention_heads": 16, "num_key_value_heads": 4,
               "head_dim": 128, "intermediate_size": 256, "vocab_size": 512,
               "vocab_rows": 512, "diag_block": 16}
SMOKE_PROGRAM = {"n_heads": 16, "n_kv_heads": 4, "head_dim": 128,
                 "d_model": 256, "d_ff": 256}


def yi_config() -> dict:
    return smoke._load("configs", "yi-9b-lln_diag.json")


def smoke_cell(limit: float = 1e9) -> dict:
    """The cell at the smoke sizes: ``bench/tests/smoke.py``'s backlog mix
    through the mesh driver, over four devices."""
    conf = copy.deepcopy(yi_config())
    conf["model"].update(SMOKE_MODEL)
    conf["program"]["smoke"] = True
    conf["program"]["overrides"].update(SMOKE_PROGRAM)
    mix = dict(smoke.backlog_cell()["mix"], driver="serve_mesh")
    return {"name": "smoke-tp4", "chips": 4, "config": conf, "mix": mix,
            "limits": {"max_logit_gap": limit}}


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.pathsep.join([ROOT, os.path.join(ROOT, "src")])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# The configuration and the reference's form.
# ---------------------------------------------------------------------------

def test_configuration_is_the_programs_yi9b_at_full_size():
    conf = yi_config()
    cfg = program_config(conf)          # raises where a FIELDS size differs
    assert all(k in conf["model"] for k in FIELDS)
    assert cfg.norm == "rmsnorm" and not cfg.tie_embeddings
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.hd) == (8, 128)
    from repro.models import build_model
    sz = reference_gqa.sizes_of(conf)
    check_layout(build_model(cfg), reference_gqa.layout(sz))


def test_another_block_form_is_refused():
    for key, value in (("norm", "layernorm"), ("attention_bias", True),
                       ("tie_word_embeddings", True)):
        conf = copy.deepcopy(yi_config())
        conf["model"][key] = value
        with pytest.raises(ValueError):
            reference_gqa.sizes_of(conf)
    conf = copy.deepcopy(yi_config())
    del conf["model"]["norm"]
    with pytest.raises(ValueError):
        reference_gqa.sizes_of(conf)


def test_constants_128_file_is_the_fit():
    assert reference_gqa.mm_constants(128) == pytest.approx(
        reference.fit_constants(128), rel=1e-9)
    with pytest.raises(KeyError):           # no nearest size stands in
        reference_gqa.mm_constants(64)


# ---------------------------------------------------------------------------
# The reference against the program (float32, products at HIGHEST).
# ---------------------------------------------------------------------------

def _program_logits(sz, params, seq, prompt_len):
    """The program's logits at positions prompt_len - 1 .. len(seq) - 2:
    the pool's prefill of the prompt, then single-token decodes of the
    rest of ``seq`` through its cache."""
    from repro.launch.steps import make_pool_setup
    conf = smoke_cell()["config"]
    cfg = program_config(conf).replace(compute_dtype="float32")
    setup = make_pool_setup(cfg, None, slots=1, max_len=64, segment=4)
    model = setup.model
    logits, caches = jax.jit(lambda p, t: model.prefill(
        p, {"inputs": t}, 64))(params, jnp.asarray(seq[None, :prompt_len]))
    out = [np.asarray(logits, np.float64).reshape(-1)]
    step = jax.jit(lambda p, c, t, pos: model.decode(p, c, t, pos))
    for i in range(prompt_len, len(seq) - 1):
        lg, caches = step(params, caches, jnp.asarray(seq[i:i + 1]),
                          jnp.asarray([i], jnp.int32))
        out.append(np.asarray(lg, np.float64).reshape(-1))
    return np.stack(out)


@pytest.fixture(scope="module")
def compared():
    """(program logits, reference logits, the same with the program's own
    eq. 10 constants), at a 32-token prompt and 20 decoded tokens, crossing
    diag-block boundaries, on seeded weights."""
    conf = smoke_cell()["config"]
    sz = reference_gqa.sizes_of(conf)
    params = weights.make(reference_gqa.layout(sz), 11)
    rng = np.random.default_rng(3)
    plen, n = 32, 53
    seq = rng.integers(0, sz["vocab"], size=n).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog = _program_logits(sz, params, seq, plen)
        tokens = np.zeros((64,), np.int32)
        tokens[:n - 1] = seq[:-1]
        at = jnp.arange(plen - 1, n - 1)

        def ref(mm):
            orig = reference_gqa.mm_constants
            reference_gqa.mm_constants = mm
            try:
                return np.asarray(reference_gqa.decoder_logits(
                    params, jnp.asarray(tokens), plen, at, sz, qblock=16),
                    np.float64)
            finally:
                reference_gqa.mm_constants = orig

        from repro.core.moment_matching import constants_for_dim
        own = ref(reference_gqa.mm_constants)
        programs = ref(lambda d: constants_for_dim(d))
    return prog, own, programs


def test_reference_matches_prefill_and_decode_with_the_programs_constants(
        compared):
    # Same eq. 10 constants: the two sides differ only in the order of
    # float32 sums (chunked prefill state, decode state, tails, alpha and
    # beta from summed squares) and the program's 1e-6 in the LLN
    # normaliser: 2.4e-4 of the logits' scale here, held under 1e-3.  A
    # wrong mask, head map or normaliser errs by the logits' whole scale.
    prog, _, programs = compared
    assert np.abs(prog - programs).max() < 1e-3 * np.abs(programs).max()


def test_reference_matches_prefill_and_decode(compared):
    # The reference's own fit of eq. 10 for head size 128 (a 0.1662,
    # b -0.7029) differs from the program's table (0.1706, -0.7442), so
    # alpha and beta differ by a few percent, and the logits by 1.9e-3 of
    # their scale here: held under 1e-2.
    prog, own, _ = compared
    scale = np.abs(own).max()
    assert np.abs(prog - own).max() < 1e-2 * scale
    # Every position's greedy token is the reference's.
    assert np.array_equal(prog.argmax(-1), own.argmax(-1))


# ---------------------------------------------------------------------------
# Four host devices: the sharded weights and the driver.
# ---------------------------------------------------------------------------

def test_sharded_weights_equal_weights_make_bitwise():
    out = _run("""
        import jax, numpy as np
        from bench import reference_gqa, serve_mesh, weights
        from bench.tests.test_tp4 import smoke_cell
        sz = reference_gqa.sizes_of(smoke_cell()["config"])
        lay = reference_gqa.layout(sz)
        mesh = serve_mesh.mesh_of(4)
        got = serve_mesh.make_sharded(lay, 2**33 + 5, mesh)
        want = weights.make(lay, 2**33 + 5)
        split = [len(x.sharding.device_set) for x in jax.tree.leaves(got)]
        assert max(split) == 4, split
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want)))
        print("BITWISE_OK")
    """)
    assert "BITWISE_OK" in out


def test_driver_line_over_four_devices():
    # A run as bench/run.py makes it, then the readings of two more seeds
    # in one process: each prints a correct line, and the precision
    # control scored on the same sample reads at least three times the
    # program.  With the limit set between them by bench/control.py's rule,
    # the same checks pass the program and fail the control.
    out = _run("""
        import json, time, jax
        from bench import harness, serve_mesh
        from bench.tests.test_tp4 import CELL, smoke_cell
        bench = harness.load_benchmark()
        c = dict(smoke_cell(), name=CELL)
        rec = serve_mesh.run(c, seed=2**33 + 7, seconds=2.0, trace=False,
                             t_start=time.perf_counter())
        rec["device_kind"] = "TPU v5 lite"
        line = harness.result_line(bench, c, rec, jax.devices()[:4], False)
        print(json.dumps({"line": line, "chips": rec["chips"],
                          "control": rec["control"]}))
        serve_mesh.readings(c, [2**33 + 8, 2**33 + 9], 2.0,
                            t_start=time.perf_counter())
    """)
    rows = [json.loads(x) for x in out.strip().splitlines()[-3:]]
    for got in rows:
        line = got["line"]
        assert line["correct"] and line["attempted"] > 0
        assert not line["failed"]
        assert line["checks"]["compiles_in_window"]["value"] == 0
        assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
        assert line["device"]["count"] == 4
        program = line["checks"]["max_logit_gap"]["value"]
        control = got["control"]["checks"]["max_logit_gap"]["value"]
        assert control > 3 * program, (control, program)
    assert rows[0]["chips"] == 4
    assert [r["seed"] for r in rows[1:]] == [2**33 + 8, 2**33 + 9]
    lower = max(r["line"]["checks"]["max_logit_gap"]["value"] for r in rows)
    upper = min(r["control"]["checks"]["max_logit_gap"]["value"]
                for r in rows)
    limit = lower ** (1 / 3) * upper ** (2 / 3)
    for r in rows:
        got = {k: v["value"] for k, v in r["line"]["checks"].items()}
        state = {"failed": got["failed_requests"],
                 "compiles": got["compiles_in_window"], "sample": [1]}
        cell = {"limits": {"max_logit_gap": limit}}
        assert serve_mesh.checks_of(
            cell, state, [np.array([got["max_logit_gap"]])])[1]
        assert not serve_mesh.checks_of(
            cell, state,
            [np.array([r["control"]["checks"]["max_logit_gap"]["value"]])])[1]


def test_a_program_without_fixed_pool_shardings_stops_at_setup(
        monkeypatch):
    from bench import serve_mesh
    from repro.launch import steps
    real = steps.make_pool_setup
    monkeypatch.setattr(steps, "make_pool_setup", lambda *a, **k:
                        __import__("dataclasses").replace(real(*a, **k),
                                                          rows_fn=None))
    c = smoke_cell()
    c["chips"] = 1
    with pytest.raises(RuntimeError, match="fixed shardings"):
        serve_mesh.Pool(c, 1, 2.0)


# ---------------------------------------------------------------------------
# The new readers.
# ---------------------------------------------------------------------------

MS = 1_000_000


def _by_hand():
    """Two devices; window 0-100 ms; one segment execution per device over
    10-90 ms.  Device 0: an all-reduce under ``attn`` (20-25), one under
    ``mlp`` (30-34), a matmul (40-60), an all-gather outside the segment
    (92-95).  Device 1: the same all-reduces 2 ms longer each."""
    ar_attn = "%all-reduce.1 = bf16[32,1,4096]{2,0,1} all-reduce(%fusion.1)"
    ar_mlp = "%all-reduce.2 = bf16[32,1,4096]{2,0,1} all-reduce(%fusion.2)"
    ag = "%all-gather.3 = s32[4,1,32]{2,1,0} all-gather(%bitcast.4)"
    dot = "%fusion.7 = bf16[32,1,4096]{2,1,0} fusion(%p.1, %p.2)"
    op_names = {ar_attn: "jit(_segment)/while/body/attn/dot_general",
                ar_mlp: "jit(_segment)/while/body/mlp/dot_general",
                ag: "jit(_segment)/sample/reduce", dot: "jit(_segment)/mlp"}
    mod = ("jit__segment", 10 * MS, 80 * MS)
    dev = [{"XLA Modules": [mod],
            "XLA Ops": [(ar_attn, 20 * MS, 5 * MS), (ar_mlp, 30 * MS, 4 * MS),
                        (dot, 40 * MS, 20 * MS), (ag, 92 * MS, 3 * MS)]},
           {"XLA Modules": [mod],
            "XLA Ops": [(ar_attn, 20 * MS, 7 * MS),
                        (ar_mlp, 30 * MS, 6 * MS)]}]
    host = [("bench.window", 0, 100 * MS, {}),
            ("batcher.segment", 5 * MS, 2 * MS, {"devices": 2})]
    return dev, host, op_names


def test_collectives_by_hand():
    got = collectives.reduce(*_by_hand())
    assert got["executions"] == 1 and got["calls"] == 2
    assert got["collective_s"] == pytest.approx((9 + 13) / 2 / 1e3)
    assert got["by_scope"] == {"attn": pytest.approx(0.006),
                               "mlp": pytest.approx(0.005)}


def test_collective_reader_reads_per_execution(monkeypatch):
    got = collectives.reduce(*_by_hand())
    rec = {"kind": "serve", "collectives": got}
    assert harness.reader("collective_device_ms.decode")(rec) == \
        pytest.approx(11.0)
    none = dict(got, calls=0, collective_s=0.0, by_scope={})
    assert harness.reader("collective_device_ms.decode")(
        {"kind": "serve", "collectives": none}) is None
    assert harness.reader("collective_device_ms.decode")(
        {"kind": "serve", "trace": None}) is None


SERVING_READERS = ("segment_device_ms", "idle_share.decode",
                   "admit_idle_ms.decode", "boundary_idle_ms.decode",
                   "carry_device_ms.decode", "pool_occupancy.decode")


@pytest.mark.parametrize("metric", SERVING_READERS)
def test_serving_reader_reads_per_chip(metric, monkeypatch):
    # Each device of a mesh runs every segment, so a reading of the four
    # chips' trace is one chip's: on two devices whose segment, ops and
    # idle differ (_by_hand, device 1's segment 20 ms shorter), the
    # reader gives the mean of what it gives on each device alone.
    dev, host, op_names = _by_hand()
    dev[1]["XLA Modules"] = [("jit__segment", 10 * MS, 60 * MS)]
    host = host + [("batcher.admit", 1 * MS, 3 * MS, {}),
                   ("batcher.prefill", 1 * MS, 2 * MS,
                    {"rows": 2, "prompt_len": 256}),
                   ("batcher.sync", 90 * MS, 1 * MS, {}),
                   ("batcher.harvest", 91 * MS, 1 * MS,
                    {"emitted": 12, "slot_steps": 16})]

    def read(devices):
        s = spans.reduce(devices, host, op_names)
        monkeypatch.setattr(spans, "newest", lambda: "x.xplane.pb")
        monkeypatch.setattr(spans, "_reduced", lambda path: s)
        bench_host = [(n, a, d) for n, a, d, _ in host
                      if n.startswith("bench.")]
        rec = {"kind": "serve",
               "trace": trace.reduce_events(devices, bench_host)}
        return harness.reader(metric)(rec)

    both, alone = read(dev), [read([d]) for d in dev]
    assert None not in alone, alone
    assert both == pytest.approx(sum(alone) / 2)


def _serve_record(trace):
    conf = yi_config()
    mix = smoke._load("traffic", "decode-backlog-tp4.json")
    return {"kind": "serve", "chips": 4, "config": conf, "mix": mix,
            "device_kind": "TPU v5 lite", "window_s": 20.0, "trace": trace,
            "serve": {"window": {"row_steps": 20000,
                                 "prefills": [[256, 32], [512, 3]]}}}


def test_mfu_tp4_divides_by_every_chip():
    rec = _serve_record({"window_s": 20.0})
    sz = reference_gqa.sizes_of(rec["config"])
    ops = 20000 * flops.decode_flops(sz) + 32 * flops.prefill_flops(
        sz, 256) + 3 * flops.prefill_flops(sz, 512)
    assert harness.reader("mfu.decode.tp4")(rec) == pytest.approx(
        100 * ops / (20.0 * 4 * 197e12))
    assert harness.reader("mfu.decode.tp4")(dict(rec, chips=None)) is None
    assert harness.reader("mfu.decode.tp4")(dict(rec, trace=None)) is None


def test_decode_roofline_tp4_finds_the_per_device_kernel():
    # The kernel's op at model=4 (the shapes of a described v5e:2x2
    # compile of the 48-layer segment): 32 slots x 8 query heads over
    # 32 x 1 kv rows, T 16, head size 128.
    text = ("%lln_decode.1 = (bf16[256,16,128]{2,1,0:T(8,128)(2,1)}, "
            "f32[256,128,128]{2,1,0:T(8,128)}, f32[256,1,128]{2,1,0:T(1,128)}"
            ") custom-call(f32[256,16,128]{2,1,0} %pad.111, "
            "f32[32,16,128]{2,1,0} %pad.112, bf16[32,16,128]{2,1,0} "
            "%pad.113, f32[256,128,128]{2,1,0} %bitcast.397, "
            "f32[256,1,128]{2,1,0} %bitcast.406), "
            'custom_call_target="tpu_custom_call"')
    rec = _serve_record({"ops": {text: {"count": 100, "seconds": 0.5}}})
    cost = flops.lln_decode_call(256, 32, 16, 128)
    want = 100 * 100 * flops.least_seconds(cost, flops.peaks(
        "TPU v5 lite")) / 0.5
    assert harness.reader("lln_decode_roofline.tp4")(rec) == \
        pytest.approx(want)
    # One chip's whole-model shapes are another kernel call.
    assert readout.roofline(rec, [([(1024, 16, 128)], [], cost)]) is None
