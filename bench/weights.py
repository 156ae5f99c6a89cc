"""Seeded weights for a cell, made by the benchmark and not by the program.

The program's parameter layout (``{"client", "aux", "server"}`` with
per-layer leaves stacked on a leading axis) is read from the abstract
shapes the program reports; every leaf is then drawn here from the seed,
on the device, in the type it is trained in, in one jitted call.  The
reference draws the same weights with the same function.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# scales of 1 (RMSNorm weights) and small random biases; every other leaf
# is a matrix drawn N(0, 1/fan_in), fan_in its second-to-last axis, except
# the embedding table, whose rows are d-wide vectors
ONES = ("ln", "ln_f", "q_norm", "k_norm")
BIASES = ("bq", "bk", "bv")
BIAS_STD = 0.02


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, including ones above 2**32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _draw(key, name: str, spec):
    last = name.rsplit("/", 1)[-1]
    if last in ONES:
        return jnp.ones(spec.shape, spec.dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    z = jax.random.normal(k, spec.shape, jnp.float32)
    if last in BIASES:
        return (z * BIAS_STD).astype(spec.dtype)
    fan_in = spec.shape[-1] if last == "embed" else spec.shape[-2]
    return (z * fan_in ** -0.5).astype(spec.dtype)


def param_fn(abstract):
    """``key -> params`` filling the abstract parameter tree ``abstract``;
    traceable, so callers jit it alone or inside a larger init."""
    names, specs = [], []
    for path, spec in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        names.append(leaf_name(path))
        specs.append(spec)
    treedef = jax.tree_util.tree_structure(abstract)

    def draw(key):
        leaves = [_draw(key, n, s) for n, s in zip(names, specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return draw


def make_params(abstract, seed: int):
    """The weights of ``seed`` in one jitted call on the default device."""
    return jax.jit(param_fn(abstract))(seed_key(seed))
