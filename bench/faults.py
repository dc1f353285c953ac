"""Faults planted in the timed path, for the tests and the chip readings
that show the correctness check catches them.  Each wraps one of the
program's entry points and leaves the program's files untouched."""
from __future__ import annotations

import dataclasses


def altered_token(setup):
    """Serving: the first token each segment emits is replaced by its
    successor in the vocabulary, where the segment produces it."""
    import jax.numpy as jnp
    seg, vocab = setup.segment_fn, setup.cfg.vocab

    def segment(*a, **k):
        out = list(seg(*a, **k))
        toks = out[5]
        out[5] = toks.at[0].set((toks[0] + 1) % vocab)
        return tuple(out)
    return dataclasses.replace(setup, segment_fn=segment)


def stale_segment_state(setup):
    """Serving: each decode segment hands back the caches it was given, so
    the next segment decodes from a stale state."""
    import jax
    import jax.numpy as jnp
    seg = setup.segment_fn

    def segment(params, caches, *a, **k):
        keep = jax.tree_util.tree_map(jnp.copy, caches)
        out = list(seg(params, caches, *a, **k))
        out[0] = keep
        return tuple(out)
    return dataclasses.replace(setup, segment_fn=segment)


def unchanged_state(step):
    """Training: the step computes and reports its loss but returns the
    state it was given."""
    import jax
    import jax.numpy as jnp

    def run(state, batch):
        keep = jax.tree_util.tree_map(jnp.copy, state)
        _, metrics = step(state, batch)
        return keep, metrics
    return run


def half_batch(step):
    """Training: the second half of the batch's rows is left out of the
    loss, whose mean is taken over the rest."""
    import jax.numpy as jnp

    def run(state, batch):
        rows = batch["mask"].shape[0]
        keep = (jnp.arange(rows) < rows // 2)[:, None]
        return step(state, {**batch, "mask": batch["mask"] * keep})
    return run
