"""The program's host spans and device scopes.

A 2-layer, 4-slot pool runs under ``jax.profiler.trace`` on the CPU (a CPU
trace keeps host spans and their arguments): every documented
``batcher.*`` span appears, nested as ``launch/batcher.py`` documents, with
its counters.  The lowered segment, prefill and train-step programs carry
every documented ``jax.named_scope``.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ArchConfig, ShapeSpec
from repro.configs.registry import get_config
from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
from repro.launch.faults import FaultEvent, FaultPlan
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_pool_setup, make_train_setup
from repro.models import build_model

SPANS = ("batcher.admit", "batcher.prefill", "batcher.first_token",
         "batcher.scatter", "batcher.resume", "batcher.segment",
         "batcher.sync", "batcher.harvest", "batcher.evict",
         "batcher.deadlines", "batcher.snapshot")
#: Spans opened inside ``batcher.admit``.
IN_ADMIT = ("batcher.prefill", "batcher.first_token", "batcher.scatter",
            "batcher.resume")
#: Spans opened at the top of the serving loop, inside no other.
TOP = ("batcher.admit", "batcher.segment", "batcher.sync",
       "batcher.harvest", "batcher.deadlines", "batcher.snapshot")
ARGS = {"batcher.prefill": {"prompt_len", "rows"},
        "batcher.segment": {"segment", "active_rows"},
        "batcher.harvest": {"emitted", "slot_steps"}}

SEGMENT_SCOPES = {"embed", "attn", "mlp", "lm_head", "sample", "sentinel",
                  "lln_decode", "diag_tail"}
PREFILL_SCOPES = {"embed", "attn", "mlp", "lm_head", "lln_prefill",
                  "block_diag", "diag_tail"}
TRAIN_SCOPES = {"embed", "attn", "mlp", "mlm_head", "optimizer",
                "lln_bidir", "lln_bidir_bwd", "block_diag"}


def _cfg():
    return ArchConfig(
        name="tracing-test", family="dense", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab=128, head_dim=16,
        attn_impl="lln_diag", diag_block=8, lln_chunk=8, softmax_chunk=16,
        compute_dtype="float32", param_dtype="float32", remat="none",
        tie_embeddings=True)


@pytest.fixture(scope="module")
def pool():
    cfg = _cfg()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    setup = make_pool_setup(cfg, None, slots=4, max_len=48, segment=3)
    ContinuousBatcher(setup, params).warmup([8, 16])
    return setup, params


def _host_spans(directory) -> list:
    """``(name, start_ns, end_ns, args)`` of the trace's batcher spans."""
    path = sorted(glob.glob(f"{directory}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("batcher."))
    return out


def _traced(directory, eng, reqs, **kw):
    jax.profiler.start_trace(str(directory))
    try:
        stats = eng.run(reqs, key=jax.random.PRNGKey(7), **kw)
    finally:
        jax.profiler.stop_trace()
    return stats, _host_spans(directory)


@pytest.fixture(scope="module")
def clean(pool, tmp_path_factory):
    """A run with no faults: (stats, spans)."""
    setup, params = pool
    reqs = synthetic_traffic(10, 128, [8, 16], [2, 5, 9], seed=3)
    return _traced(tmp_path_factory.mktemp("clean"),
                   ContinuousBatcher(setup, params), reqs)


@pytest.fixture(scope="module")
def faulted(pool, tmp_path_factory):
    """A run with snapshots and a poisoned row, so that every span opens
    (quarantine, resume, snapshot): (stats, spans)."""
    setup, params = pool
    mgr = CheckpointManager(str(tmp_path_factory.mktemp("snap")), keep_n=2,
                            interval=1)
    eng = ContinuousBatcher(setup, params, snapshot_mgr=mgr,
                            snapshot_every=1)
    reqs = synthetic_traffic(6, 128, [8, 16], [9], seed=4)
    plan = FaultPlan(events=[FaultEvent(kind="nan", segment=1, row=0)])
    stats, spans = _traced(tmp_path_factory.mktemp("faulted"), eng, reqs,
                           fault_plan=plan)
    assert stats.recoveries >= 1
    return stats, spans


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("name", SPANS)
def test_span_recorded(faulted, name):
    assert any(s[0] == name for s in faulted[1])


def test_spans_nest_as_documented(faulted):
    spans = faulted[1]
    admits = [s for s in spans if s[0] == "batcher.admit"]
    for s in spans:
        others = [p for p in spans if p is not s and _inside(s, p)]
        if s[0] in IN_ADMIT:
            assert any(p in admits for p in others), s[0]
        if s[0] in TOP:
            assert not others, (s[0], [p[0] for p in others])
        if s[0] == "batcher.evict":
            # After the harvest, or inside the deadline sweep.
            assert {p[0] for p in others} <= {"batcher.deadlines"}


@pytest.mark.parametrize("name", sorted(ARGS))
def test_span_counters(clean, name):
    got = [s[3] for s in clean[1] if s[0] == name]
    assert got and all(ARGS[name] <= set(a) for a in got)


def test_harvest_counts_the_emitted_row_steps(clean):
    stats, spans = clean
    harvests = [s[3] for s in spans if s[0] == "batcher.harvest"]
    segments = [s[3] for s in spans if s[0] == "batcher.segment"]
    # Each request's first token comes from its prefill; every other token
    # is one row-step of a segment.
    decoded = sum(len(o) - 1 for o in stats.outputs.values())
    assert sum(h["emitted"] for h in harvests) == decoded
    assert sum(h["slot_steps"] for h in harvests) == \
        stats.decode_steps * 4
    assert len(harvests) == len(segments) == stats.segments
    assert sum(p[3]["rows"] for p in spans
               if p[0] == "batcher.prefill") == stats.admitted


def test_request_stamps(clean):
    stats = clean[0]
    assert set(stats.times) == set(stats.outputs)
    for rid, t in stats.times.items():
        assert stats.statuses[rid] == "done"
        assert (t["enqueued"] <= t["admitted"] <= t["first_token"]
                <= t["finished"])


def _scopes(text: str) -> set:
    """Every part of every location name in lowered text, stripped of
    transformations (``transpose(jvp(attn))`` -> ``attn``)."""
    out = set()
    for name in re.findall(r'loc\("([^"]*)"', text):
        for part in re.split(r"[/;]", name):
            while (m := re.match(r"^[\w-]+\((.*)\)$", part)):
                part = m.group(1)
            out.add(part)
    return out


def _lowered(which: str, pool) -> str:
    setup, params = pool
    if which == "segment":
        i32 = jnp.zeros((4,), jnp.int32)
        low = setup.segment_fn.lower(params, setup.cache_init(), i32, i32,
                                     i32, jnp.zeros((4,), jnp.bool_),
                                     jax.random.PRNGKey(0))
    elif which == "prefill":
        low = setup.prefill_fn(16, 2).lower(params,
                                            jnp.zeros((2, 16), jnp.int32))
    else:
        cfg = get_config("roberta-lln", smoke=True, use_kernel=True)
        mesh = make_mesh((1, 1), ("data", "model"))
        with mesh:
            ts = make_train_setup(cfg, ShapeSpec("t", 32, 2, "train"), mesh,
                                  multi_pod=False)
            low = ts.step_fn.lower(ts.state_struct, ts.batch)
    return low.as_text(debug_info=True)


@pytest.mark.parametrize("which,want", [("segment", SEGMENT_SCOPES),
                                        ("prefill", PREFILL_SCOPES),
                                        ("train", TRAIN_SCOPES)])
def test_programs_carry_their_scopes(pool, which, want):
    assert want <= _scopes(_lowered(which, pool))
