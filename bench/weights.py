"""Seeded weights of a model, made on the device in one jitted call.

The tree has the layout the program's model takes, as the family's
``leaf_shapes`` lists it (``embed``, layers stacked on a leading axis,
``final_norm``, ``lm_head`` when untied), so the same weights feed the
program and the reference.  Both make them here, from the seed alone: the
reference takes nothing the program made.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .spec import family_of


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A non-negative seed of up to 64 bits as two uint32 words."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.uint32(seed & 0xFFFFFFFF), np.uint32(seed >> 32)


def nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


@partial(jax.jit, static_argnums=(0,))
def _init(s, lo, hi):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo), hi)
    dt = jnp.dtype(s.dtype)
    flat = {}
    for i, (path, (shape, kind)) in enumerate(sorted(family_of(s).leaf_shapes(s).items())):
        if kind == "ones":
            flat[path] = jnp.ones(shape, dt)
        else:
            k = jax.random.fold_in(key, i)
            flat[path] = (jax.random.normal(k, shape, jnp.float32) * s.init_std).astype(dt)
    return nest(flat)


def init_params(s, seed: int) -> dict:
    """The weights of ``seed`` for the family shape ``s``, in the
    configuration's dtype, on the device: each leaf ``normal`` draws
    N(0, init_std^2) from the seed folded with its index in the sorted leaf
    list, ``ones`` is ones."""
    return _init(s, *seed_words(seed))


@jax.jit
def _diff_norms(params, x0):
    x, x0 = flatten(params), flatten(x0)
    return {k: jnp.sqrt(jnp.sum((x[k].astype(jnp.float32) - x0[k].astype(jnp.float32)) ** 2))
            for k in x0}


def change_norms(s, params: dict, seed: int) -> dict:
    """``{leaf path: ||params - init(seed)||_2}`` as host floats.

    The initial weights come out of their own jitted call, so they are
    rounded to the configuration's dtype: regenerated inside the same
    program, XLA may keep them in float32 (excess precision), and the
    difference would then read the init's rounding and not the training."""
    out = _diff_norms(params, init_params(s, seed))
    return {k: float(v) for k, v in jax.device_get(out).items()}
