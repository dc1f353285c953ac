"""Distribution: sharding rules, straggler watchdog, elastic mesh logic,
and true multi-device behaviour via subprocesses (8 host-platform devices).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.distributed.elastic import viable_mesh_shapes
from repro.distributed.straggler import StepWatchdog
from repro.launch.mesh import make_smoke_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestShardingRules:
    def test_fit_spec_divisibility(self):
        mesh = make_smoke_mesh(1, 1)
        spec = shd.fit_spec(P("data", "model"), (7, 5), mesh)
        assert spec == P(None, None) or spec == P("data", "model")

    def test_fit_spec_dedup(self):
        mesh = make_smoke_mesh(1, 1)
        spec = shd.fit_spec(P(("data", "model"), None, "model"), (4, 4, 4),
                            mesh)
        flat = [a for s in spec if s for a in
                (s if isinstance(s, tuple) else (s,))]
        assert len(flat) == len(set(flat))

    def test_param_specs_cover_all_archs(self):
        mesh = make_smoke_mesh(1, 1)
        from repro.models import build_model
        for arch in ("yi-9b", "deepseek-v2-236b", "mamba2-130m",
                     "zamba2-7b", "paligemma-3b", "seamless-m4t-medium"):
            cfg = get_config(arch, smoke=True)
            model = build_model(cfg)
            tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            specs = shd.param_specs(tree, mesh)
            assert (len(jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P)))
                    == len(jax.tree_util.tree_leaves(tree)))

    def test_make_rules_policies(self):
        cfg = get_config("qwen3-14b")
        r = shd.make_rules(cfg, multi_pod=False)
        assert r["heads"] is None and r["attn_seq"] == "model"
        cfg = get_config("yi-9b")
        r = shd.make_rules(cfg, multi_pod=True)
        assert r["heads"] == "model" and r["act_batch"] == ("pod", "data")
        cfg = get_config("mamba2-130m")
        r = shd.make_rules(cfg, multi_pod=False)
        assert "model" in r["act_batch"]


class TestStraggler:
    def test_watchdog_flags_outlier(self):
        import time as _time
        wd = StepWatchdog(k=3.0, warmup_steps=1)
        calls = []
        wd.on_anomaly = calls.append
        for i in range(8):
            wd.start()
            wd._t0 -= 0.01          # pretend 10ms steps
            wd.stop(i)
        wd.start()
        wd._t0 -= 1.0               # 1s straggler
        rep = wd.stop(99)
        assert rep is not None and rep.step == 99 and calls


class TestElastic:
    def test_viable_shapes(self):
        shapes = viable_mesh_shapes(128, prefer_model=16)
        assert shapes[0] == (8, 16)
        assert (128, 1) in shapes

    def test_reshard_between_meshes_subprocess(self):
        """Save on a (2,4) mesh, restore + reshard on (4,2): the elastic
        restart path with a genuinely different device assignment."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np, tempfile, os
            from repro.checkpoint.checkpointer import save, restore
            from repro.distributed.sharding import param_shardings
            d = tempfile.mkdtemp()
            from repro.launch.mesh import make_mesh
            mesh1 = make_mesh((2, 4), ("data", "model"))
            tree = {"layers": {"q_w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
            tree = jax.device_put(tree, param_shardings(tree, mesh1))
            save(d, 1, tree)
            mesh2 = make_mesh((4, 2), ("data", "model"))
            template = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), tree)
            out = restore(d, 1, template, param_shardings(template, mesh2))
            q = out["layers"]["q_w"]
            assert len(q.sharding.device_set) == 8
            np.testing.assert_allclose(np.asarray(q),
                                       np.arange(64).reshape(8, 8))
            print("RESHARD_OK")
        """)
        assert "RESHARD_OK" in out

    def test_reshard_serving_pool_decode_parity_subprocess(self):
        """Elastic-serving path: a live continuous-batching pool
        (``AttentionState`` caches with row axis 1) built on a (2,4) mesh
        survives losing devices — ``make_degraded_mesh`` on the surviving
        prefix + ``reshard_state`` of params AND pool caches onto the
        smaller mesh, then a full decode segment emits token-for-token
        the same stream as the healthy mesh would have."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ArchConfig
            from repro.distributed.elastic import (make_degraded_mesh,
                                                   reshard_state)
            from repro.launch.mesh import make_mesh
            from repro.launch.steps import make_pool_setup
            from repro.models import build_model

            cfg = ArchConfig(
                name="elastic-pool", family="dense", n_layers=2,
                d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                head_dim=16, attn_impl="lln_diag", diag_block=8,
                lln_chunk=8, softmax_chunk=16, lln_fixed_ab=2.1,
                compute_dtype="float32", param_dtype="float32",
                remat="none", tie_embeddings=True)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(0))
            prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                        cfg.vocab, jnp.int32)
            tok = jnp.zeros((2,), jnp.int32).at[0].set(7)
            pos = jnp.zeros((2,), jnp.int32).at[0].set(8)
            remaining = jnp.zeros((2,), jnp.int32).at[0].set(4)
            active = jnp.asarray([True, False])
            key = jax.random.PRNGKey(2)

            mesh1 = make_mesh((2, 4), ("data", "model"))
            with mesh1:
                setup1 = make_pool_setup(cfg, mesh1, slots=2, max_len=32,
                                         segment=4)
                def build(setup):
                    _, sc = setup.prefill_fn(8)(params, prompt)
                    return setup.admit_fn(setup.cache_init(), sc,
                                          jnp.asarray([0], jnp.int32))
                # Reference segment on the healthy mesh (donates caches).
                out1 = setup1.segment_fn(params, build(setup1), tok, pos,
                                         remaining, active, key)
                toks_ref, em_ref = np.asarray(out1[5]), np.asarray(out1[6])
                caches = build(setup1)          # fresh copy to carry over

            # 3 of 8 devices die -> largest pow-2 prefix of 5 is 4.
            mesh2 = make_degraded_mesh(jax.devices()[:5], prefer_model=2)
            assert mesh2.devices.size == 4, mesh2
            params2 = reshard_state(params, mesh2)
            with mesh2:
                setup2 = make_pool_setup(cfg, mesh2, slots=2, max_len=32,
                                         segment=4)
                # The pool's programs take its caches only as its
                # cache_init lays them out.
                caches2 = jax.device_put(caches, jax.tree_util.tree_map(
                    lambda s: s.sharding, jax.eval_shape(setup2.cache_init)))
                out2 = setup2.segment_fn(params2, caches2, tok, pos,
                                         remaining, active, key)
            np.testing.assert_array_equal(em_ref, np.asarray(out2[6]))
            np.testing.assert_array_equal(toks_ref[:, 0],
                                          np.asarray(out2[5])[:, 0])
            print("ELASTIC_POOL_OK", mesh2.shape)
        """)
        assert "ELASTIC_POOL_OK" in out

    def test_degraded_mesh_subprocess(self):
        out = _run_subprocess("""
            import jax
            from repro.distributed.elastic import make_degraded_mesh
            # 8 devices, pretend 3 died -> largest pow2 prefix of 5 = 4
            mesh = make_degraded_mesh(jax.devices()[:5], prefer_model=4)
            assert mesh.devices.size == 4, mesh
            print("DEGRADED_OK", mesh.shape)
        """)
        assert "DEGRADED_OK" in out


class TestMultiDeviceTraining:
    def test_sharded_train_step_subprocess(self):
        """Two real pjit train steps on an (2,4) mesh: loss finite, state
        sharded, gradients synchronized."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.configs.base import ShapeSpec
            from repro.launch.steps import make_train_setup
            from repro.models import build_model, synthetic_batch
            from repro.optim import adamw_init

            cfg = get_config("yi-9b", smoke=True, attn_impl="lln_diag")
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            shape = ShapeSpec("t", 32, 4, "train")
            with mesh:
                setup = make_train_setup(cfg, shape, mesh, multi_pod=False)
                model = build_model(cfg)
                params = model.init(jax.random.PRNGKey(0))
                state = jax.device_put(
                    {"params": params, "opt": adamw_init(params)},
                    setup.state_shardings)
                batch = synthetic_batch(cfg, 4, 32)
                batch = jax.device_put(batch, {k: v.sharding for k, v in setup.batch.items()})
                losses = []
                for _ in range(2):
                    state, metrics = setup.step_fn(state, batch)
                    losses.append(float(metrics["loss"]))
                assert all(np.isfinite(l) for l in losses), losses
                w = state["params"]["layers"]["attn"]["q_w"]
                assert len(w.sharding.device_set) == 8
                print("TRAIN_OK", losses)
        """)
        assert "TRAIN_OK" in out

    def test_serve_decode_sharded_subprocess(self):
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.configs.base import ShapeSpec
            from repro.launch.steps import make_serve_setup
            from repro.models import build_model, synthetic_batch

            cfg = get_config("yi-9b", smoke=True)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            shape = ShapeSpec("s", 48, 4, "decode")
            with mesh:
                setup = make_serve_setup(cfg, shape, mesh, multi_pod=False)
                model = build_model(cfg)
                params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                                        setup.params_shardings)
                batch = synthetic_batch(cfg, 4, 48, text_seq=32)
                logits, caches = setup.prefill_fn(params, batch)
                caches = jax.device_put(caches, setup.cache_shardings)
                tok = jnp.argmax(logits[:, -1] if logits.ndim == 3 else logits,
                                 -1).astype(jnp.int32)
                for i in range(3):
                    logits, caches = setup.decode_fn(
                        params, caches, tok, jnp.asarray(32 + i, jnp.int32))
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                assert np.all(np.isfinite(np.asarray(logits, np.float32)))
                print("SERVE_OK")
        """)
        assert "SERVE_OK" in out


class TestHeadParallelKernels:
    def test_pool_and_train_match_one_device_subprocess(self):
        """Pallas kernels (interpreted) under a model=4 mesh run per device
        over their heads: pool greedy tokens and prefill logits, and the
        kernel-path training loss, match the one-device mesh; parameters
        are built already split over the model axis."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs.base import ArchConfig
            from repro.distributed.sharding import logical_rules, make_rules
            from repro.launch.batcher import ContinuousBatcher, synthetic_traffic
            from repro.launch.mesh import make_mesh
            from repro.launch.steps import init_params, make_pool_setup
            from repro.models import build_model, synthetic_batch

            cfg = ArchConfig(
                name="hp", family="dense", n_layers=2, d_model=64,
                n_heads=8, n_kv_heads=4, head_dim=16, d_ff=128, vocab=128,
                attn_impl="lln_diag", diag_block=8, lln_chunk=8,
                softmax_chunk=16, compute_dtype="float32", remat="none",
                attn_backend="pallas", use_kernel=True)
            reqs = synthetic_traffic(4, cfg.vocab, (8, 16), (3, 6))

            def serve(mesh):
                with mesh:
                    setup = make_pool_setup(cfg, mesh, slots=2, max_len=24,
                                            segment=4)
                    params = init_params(setup.model, mesh, 0)
                    logits, _ = setup.prefill_fn(8)(
                        params, jnp.asarray(reqs[0].prompt[None]))
                    stats = ContinuousBatcher(setup, params).run(reqs)

                    def loss_fn(p, b):
                        with logical_rules(mesh, make_rules(
                                cfg, multi_pod=False)):
                            return build_model(cfg).loss(p, b)
                    loss = jax.jit(loss_fn)(params,
                                            synthetic_batch(cfg, 2, 16))
                return params, np.asarray(logits), stats.outputs, float(loss)

            _, l1, o1, loss1 = serve(make_mesh((1, 1), ("data", "model")))
            p4, l4, o4, loss4 = serve(make_mesh((1, 4), ("data", "model")))
            assert len(p4["layers"]["attn"]["q_w"].sharding.device_set) == 4
            np.testing.assert_allclose(l4, l1, rtol=1e-4, atol=1e-4)
            assert all(np.array_equal(o1[r], o4[r]) for r in o1), (o1, o4)
            assert abs(loss1 - loss4) < 1e-4, (loss1, loss4)
            print("HEAD_PARALLEL_OK")
        """, devices=4)
        assert "HEAD_PARALLEL_OK" in out

    def test_rows_and_heads_split_grads_match_subprocess(self):
        """On a data=2, model=4 mesh each device's kernel call gets half
        the rows, and a quarter of the heads where query and kv heads both
        divide (kv heads that do not divide keep the heads whole but still
        split the rows); the Mamba2 SSD kernel splits the same way.  Loss
        and every gradient leaf of the kernel path match one device."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            import repro.kernels as kernels
            from repro.configs import get_config
            from repro.configs.base import ArchConfig
            from repro.distributed.sharding import logical_rules, make_rules
            from repro.kernels import registry
            from repro.launch.mesh import make_mesh
            from repro.launch.steps import init_params
            from repro.models import build_model, synthetic_batch

            blocks = []
            real_attn, real_ssd = registry.attention, kernels.ssd_scan

            def attn_spy(spec, q, *a, **k):
                blocks.append(q.shape)
                return real_attn(spec, q, *a, **k)

            def ssd_spy(xbar, *a, **k):
                blocks.append(xbar.shape)
                return real_ssd(xbar, *a, **k)

            registry.attention, kernels.ssd_scan = attn_spy, ssd_spy

            def grads(cfg, mesh, batch):
                model = build_model(cfg)
                rules = make_rules(cfg, multi_pod=False)
                with mesh:
                    params = init_params(model, mesh, 0)

                    def f(p, b):
                        with logical_rules(mesh, rules):
                            return jax.value_and_grad(model.loss)(p, b)
                    blocks.clear()
                    loss, g = jax.jit(f)(params, batch)
                return float(loss), g, set(blocks)

            dense = dict(name="rh", family="dense", n_layers=2, d_model=64,
                         n_heads=8, head_dim=16, d_ff=128, vocab=128,
                         attn_impl="lln_diag", diag_block=8, lln_chunk=8,
                         softmax_chunk=16, compute_dtype="float32",
                         remat="none", attn_backend="pallas", use_kernel=True)
            mamba = get_config("mamba2-130m", smoke=True).replace(
                use_kernel=True, compute_dtype="float32")
            cases = [   # (config, batch, seq, per-device kernel block)
                (ArchConfig(n_kv_heads=4, **dense), 4, 16, {(2, 16, 2, 16)}),
                (ArchConfig(n_kv_heads=2, **dense), 4, 16, {(2, 16, 8, 16)}),
                # replicate: rows over data x model; tp_heads: heads too
                (mamba, 8, 32, {(1, 32, 4, 32)}),
                (mamba.replace(attn_shard="tp_heads"), 4, 32,
                 {(2, 32, 1, 32)}),
            ]
            for cfg, rows, seq, want in cases:
                batch = synthetic_batch(cfg, rows, seq)
                l1, g1, whole = grads(cfg, make_mesh((1, 1),
                                                     ("data", "model")), batch)
                l8, g8, seen = grads(cfg, make_mesh((2, 4), ("data", "model")),
                                     batch)
                # Tracing the output shapes also sees the whole block once.
                assert seen - whole == want, (cfg.name, seen, want)
                assert abs(l1 - l8) < 1e-5, (cfg.name, l1, l8)
                for a, b in zip(jax.tree_util.tree_leaves(g1),
                                jax.tree_util.tree_leaves(g8)):
                    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                               rtol=2e-4, atol=2e-6)
            print("ROWS_HEADS_OK")
        """, devices=8)
        assert "ROWS_HEADS_OK" in out


class TestPoolOverMesh:
    def test_pool_compiles_nothing_after_warmup_and_matches_one_device_subprocess(
            self):
        """A Yi-shaped pool (RMSNorm, SwiGLU, GQA r = 4, ``lln_diag``)
        under a (1, 4) mesh: after ``warmup`` two runs of staggered
        admissions (mixed prompt lengths, same-length groups), rows that
        finish mid-segment or at prefill, and evictions lower no program;
        its greedy tokens and prefill logits equal the one-device pool's,
        and the pool's caches, parameters and per-slot vectors keep the
        shardings its programs take."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.configs.base import ArchConfig
            from repro.launch.batcher import ContinuousBatcher, Request
            from repro.launch.mesh import make_mesh
            from repro.launch.steps import init_params, make_pool_setup

            lowered = []
            jax.monitoring.register_event_duration_secs_listener(
                lambda event, duration, **kw: lowered.append(
                    kw.get("fun_name")) if event ==
                "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)
            cfg = ArchConfig(
                name="yi-shaped", family="dense", n_layers=2, d_model=64,
                n_heads=16, n_kv_heads=4, head_dim=16, d_ff=128, vocab=128,
                norm="rmsnorm", act="silu_glu", rope_theta=5e6,
                attn_impl="lln_diag", attn_shard="tp_heads", diag_block=8,
                lln_chunk=8, softmax_chunk=16, compute_dtype="float32",
                remat="none")
            rng = np.random.default_rng(0)
            # (length, budget); budget 1 finishes at prefill, inside a
            # same-length group as well as alone.
            shapes = [(16, 5), (16, 9), (16, 1), (8, 3), (8, 12), (16, 7),
                      (16, 2), (8, 6), (8, 1), (16, 11), (8, 4), (16, 10)]

            def traffic(order):
                return [Request(rid=i, gen_len=shapes[j][1],
                                prompt=rng.integers(0, cfg.vocab,
                                                    size=shapes[j][0]
                                                    ).astype(np.int32))
                        for i, j in enumerate(order)]

            runs = [traffic(range(12)), traffic(range(11, -1, -1))]

            def serve(mesh):
                setup = make_pool_setup(cfg, mesh, slots=4, max_len=32,
                                        segment=4)
                params = init_params(setup.model, mesh or make_mesh(
                    (1, 1), ("data", "model")), 0)
                eng = ContinuousBatcher(setup, params)
                eng.warmup([8, 16])
                lowered.clear()
                outs = [eng.run(r, key=jax.random.PRNGKey(3)).outputs
                        for r in runs]
                got = list(lowered)
                logits, _ = setup.prefill_fn(16, 2)(params, jnp.asarray(
                    np.stack([runs[0][0].prompt, runs[0][1].prompt])))
                caches = setup.cache_init()
                rows = setup.rows_init()
                return got, outs, np.asarray(logits), caches, rows, params

            _, o1, l1, _, _, _ = serve(None)
            mesh = make_mesh((1, 4), ("data", "model"))
            n4, o4, l4, caches, rows, params = serve(mesh)
            assert n4 == [], n4
            for a, b in zip(o1, o4):
                assert a.keys() == b.keys()
                assert all(np.array_equal(a[r], b[r]) for r in a), (a, b)
            np.testing.assert_allclose(l4, l1, rtol=1e-4, atol=1e-4)
            s = caches["layers"].s
            assert s.sharding.spec == P(None, "data", "model", None, None)
            assert all(r.sharding.spec == P() for r in rows)
            assert len(params["layers"]["attn"]["q_w"].sharding.device_set) == 4
            print("POOL_MESH_OK")
        """, devices=4)
        assert "POOL_MESH_OK" in out
