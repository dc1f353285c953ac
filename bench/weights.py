"""Random weights from a seed, made on the device in one jitted call.

The same function feeds the program under test and the reference, so both
see the same numbers without the reference taking anything the program
made.  Layer norms start at scale 1 and bias 0; every other array is
normal with standard deviation 1/sqrt(fan-in) (the embedding table:
1/sqrt(width)), which keeps every layer's output of order one.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key for any non-negative integer seed (wider than 32 bits
    too) and a stream number, so that weights and data draw apart."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0]) >> 1),
                              int(words[1]) >> 1)


def _std(path, shape) -> float:
    names = [getattr(p, "key", "") for p in path]
    if "embed" in names:
        return 1.0 / math.sqrt(shape[-1])
    return 1.0 / math.sqrt(shape[-2])


def make(layout, seed: int):
    """Arrays shaped like ``layout`` (a tree of ShapeDtypeStruct) from
    ``seed``, on the default device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def build(key):
        out = []
        for i, (path, s) in enumerate(flat):
            name = getattr(path[-1], "key", "")
            if name == "scale":
                out.append(jnp.ones(s.shape, s.dtype))
            elif name == "bias":
                out.append(jnp.zeros(s.shape, s.dtype))
            else:
                x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                      jnp.float32)
                out.append((x * _std(path, s.shape)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key_of(seed))
