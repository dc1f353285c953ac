"""Where the benchmark meets the program under test.

The drivers take from the program its configuration registry, its entry
points, its device memory statistics and its compile events; everything
else (weights, traffic, the reference, the counts) is the benchmark's own.
"""
from __future__ import annotations

#: Configuration-file keys and the program's ``ArchConfig`` field each
#: must equal, so that the program runs the configuration the file states.
FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "hd", "intermediate_size": "d_ff", "vocab_size": "vocab",
    "vocab_rows": "padded_vocab", "partial_rotary_factor": "rotary_pct",
    "rope_theta": "rope_theta", "diag_block": "diag_block",
    "attn_impl": "attn_impl",
}


def program_config(config: dict):
    """The program's ``ArchConfig`` for a ``bench/configs/*.json`` file,
    checked against the sizes the file states."""
    from repro.configs.registry import get_config
    prog = config["program"]
    cfg = get_config(prog["arch"], smoke=bool(prog.get("smoke")),
                     **prog.get("overrides", {}))
    model = config["model"]
    wrong = {k: (model[k], getattr(cfg, f)) for k, f in FIELDS.items()
             if k in model and getattr(cfg, f) != model[k]}
    if wrong:
        raise ValueError(f"the program's {prog['arch']} differs from "
                         f"{config['name']}: {wrong}")
    return cfg


def check_layout(model, layout) -> None:
    """The program's parameter tree must be the layout the benchmark makes
    weights for: same paths, same shapes, same dtypes."""
    import jax
    got = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = lambda t: {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                      for p, x in jax.tree_util.tree_leaves_with_path(t)}
    g, w = flat(got), flat(layout)
    if g != w:
        diff = sorted(set(g.items()) ^ set(w.items()))
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{diff[:6]}")


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device of the process."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class CompileCounter:
    """Counts programs lowered (each then compiled, or loaded from the
    persistent cache) between :meth:`start` and :meth:`stop`."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.on = False
        self.in_window = 0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.in_window += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def start(self):
        self.on = True

    def stop(self):
        self.on = False
