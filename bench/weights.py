"""Weights and keys made from the benchmark's seed.

The benchmark, not the program, makes the weights: one jitted call turns the
seed into every parameter, on the device and in its served dtype. The plain
reference calls the same generator on a template it builds itself
(``reference.template``), so it shares nothing with the program but the
seed. A leaf's values depend only on the seed, its path and its shape.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# leaves set to a constant, by the last name of their path
_ONES = ("scale", "D", "gate_norm")
_ZEROS = ("bias", "conv_b", "dt_bias")


def seed_key_data(seed: int) -> np.ndarray:
    """The threefry key data of a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _leaf(key, path, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name in _ZEROS:
        return jnp.zeros(shape, dtype)
    if name == "A_log":  # per-head decay rates 1..16, as Mamba-2 sets them
        return jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32)),
            shape,
        ).astype(dtype)
    # fan-in scaled normal; a stacked leaf (layers, in, out) scales by "in"
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)).astype(
        dtype
    )


def make_generator(template, out_shardings=None):
    """``gen(key_data) -> {path: array}`` for ``template`` = {path: (shape,
    dtype)}, jitted once; the key data is an argument, so every seed runs
    the same compiled program."""
    paths = sorted(template)

    def gen(key_data):
        key = jax.random.wrap_key_data(key_data)
        return {
            p: _leaf(key, p, tuple(template[p][0]), jnp.dtype(template[p][1]))
            for p in paths
        }

    return jax.jit(gen, out_shardings=out_shardings)


def path_of(key_path) -> str:
    """'a/b/c' for a jax tree key path."""
    return "/".join(
        str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k))))
        for k in key_path
    )


def template_of(tree) -> dict:
    """{path: (shape, dtype name)} of a tree of arrays or shape structs."""
    return {
        path_of(p): (tuple(l.shape), jnp.dtype(l.dtype).name)
        for p, l in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def to_tree(flat: dict, like):
    """Put {path: array} into the structure of ``like``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[path_of(p)] for p, _ in leaves]
    )
