"""Shared utilities: dtypes, PRNG plumbing, pytree helpers, compile cache."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "int8": jnp.int8,
}


def dtype_of(name: str):
    return DTYPES[name]


def bytes_of(tree) -> int:
    """Total bytes of all arrays / ShapeDtypeStructs in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


def split_like(key, tree):
    """One PRNG key per leaf of ``tree`` (a dict of names)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(keys))


def tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def tree_unstack(tree, n):
    return [jax.tree_util.tree_map(lambda x: x[i], tree) for i in range(n)]


def tree_zeros_like(tree):
    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_scale(a, s):
    return jax.tree_util.tree_map(lambda x: x * s, a)


def tree_allfinite(tree) -> jnp.ndarray:
    leaves = [jnp.all(jnp.isfinite(l)) for l in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(l.dtype, jnp.floating)]
    return jnp.all(jnp.stack(leaves)) if leaves else jnp.asarray(True)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    the cache (JAX reads it itself) and nothing else is configured;
    otherwise the cache lives at ``<repo>/.jax_cache``.  Entry points call
    this at start-up, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    # a fixed path inside the checkout: JAX keys cache entries on it
    path = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                        os.pardir, os.pardir, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
