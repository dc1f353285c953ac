"""Launch plumbing: the compile-cache location, and which attention path
the training forward takes on an accelerator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import registry
from repro.launch import compile_cache
from repro.models import attention_block, ssm


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


class TestCompileCache:
    def test_env_dir_wins_and_nothing_else_is_set(self, monkeypatch,
                                                  restore_cache_dir):
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
        assert compile_cache.enable_compile_cache() == "/some/cache"
        assert jax.config.jax_compilation_cache_dir is None

    def test_default_is_fixed_path_in_checkout(self, monkeypatch,
                                               restore_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
        assert (compile_cache.CHECKOUT_CACHE_DIR.parent / "chip_smoke.py"
                ).exists()
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path


class TestKernelsOnAccelerator:
    """Off the CPU the training forward runs the Pallas kernels whatever
    ``use_kernel`` says; on the CPU ``use_kernel`` still chooses."""

    def test_attention_uses_kernels_off_cpu(self, monkeypatch):
        def kind(cfg):
            spec = attention_block.attn_engine(cfg, causal=False).spec
            return registry.resolve(spec.backend,
                                    cpu_twin=spec.train_twin).kind

        cfg = get_config("roberta-lln", smoke=True)
        assert not cfg.use_kernel
        assert kind(cfg) == "scan"            # the jnp route
        assert kind(cfg.replace(use_kernel=True)) == "pallas"
        monkeypatch.setattr(registry, "on_cpu", lambda: False)
        assert kind(cfg) == "pallas"
        assert kind(cfg.replace(attn_backend="ref")) == "ref"

    @pytest.mark.parametrize("off_cpu", [False, True])
    def test_ssd_kernel_off_cpu(self, monkeypatch, off_cpu):
        from repro.kernels import ops
        calls = []
        real = ops.ssd_pallas

        def spy(*a, **k):              # the kernel, interpreted on this CPU
            calls.append(1)
            return real(*a, **{**k, "interpret": True})

        monkeypatch.setattr(ops, "ssd_pallas", spy)
        monkeypatch.setattr(registry, "on_cpu", lambda: not off_cpu)
        cfg = get_config("mamba2-130m", smoke=True)
        key = jax.random.PRNGKey(0)
        p = ssm.ssm_init(key, cfg)
        x = jax.random.normal(key, (1, 2 * cfg.ssm_chunk, cfg.d_model))
        y = ssm.ssm_apply(p, x, cfg)
        assert bool(calls) == off_cpu
        assert np.all(np.isfinite(np.asarray(y, jnp.float32)))
