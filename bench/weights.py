"""Seeded random weights, made on the device in one jitted call.

The program states the tree it serves (``jax.eval_shape`` of its
``init_params``: names, shapes and the types it serves them in); the
values are drawn here, from the run's seed, so that the reference is
given nothing the program has made.  Matrices are drawn with standard
deviation ``fan_in ** -0.5`` (the input axis is the second from last),
norm scales around 1, and embedding rows with deviation
``hidden ** -0.5`` so that the residual stream starts near unit size
under the program's ``sqrt(hidden)`` embedding scale.
"""
from __future__ import annotations

import numpy as np


def key_for(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed, 64 bits or more."""
    import jax
    word = np.random.SeedSequence([int(seed), stream]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def make_params(model, seed: int):
    import jax
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    hidden = model.cfg.d_model

    def leaf(key, path, s):
        name = getattr(path[-1], "key", str(path[-1]))
        z = jax.random.normal(key, s.shape, s.dtype)
        if name == "scale":
            return (1.0 + 0.1 * z).astype(s.dtype)
        if name == "embed":
            return (z * hidden ** -0.5).astype(s.dtype)
        if len(s.shape) >= 2:
            return (z * s.shape[-2] ** -0.5).astype(s.dtype)
        return (0.1 * z).astype(s.dtype)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(k, p, s) for k, (p, s) in zip(keys, flat)])

    return jax.jit(build)(key_for(seed))
