"""Layer 1 primitives: walk, hash, and lower jaxprs without real arrays.

Everything here operates on abstract traces (``jax.make_jaxpr`` /
``jax.eval_shape`` / ``jit(...).lower`` over ``ShapeDtypeStruct`` trees) —
no device buffers are allocated and no XLA compilation happens, so the
full method x codec matrix audits in seconds where the bitwise test sweep
takes minutes.
"""
from __future__ import annotations

import hashlib
import re
import warnings
from typing import Iterator, List, Optional, Tuple

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

# Host-callback primitives that must never appear inside the donated chunk
# body: each one forces a device->host sync per scan iteration, destroying
# exactly the dispatch win run_compiled exists for (and breaking donation
# on some backends).
CALLBACK_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "debug_print",
     "callback"})

# 8-byte dtypes that must never leak into the compiled path (the repo's
# numerics contract is float32 end to end; fp64 doubles every wire payload
# and silently disables most TPU fast paths).
WIDE_DTYPES = frozenset({"float64", "complex128", "int64", "uint64"})


def _subjaxprs(params) -> Iterator:
    """Yield every Jaxpr / ClosedJaxpr nested in an eqn's params (scan
    bodies, cond branches, pjit calls, custom_* rules)."""
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over every equation, sub-jaxprs included."""
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def find_callbacks(jaxpr) -> List[str]:
    """Names of host-callback primitives anywhere in the jaxpr."""
    return [eqn.primitive.name for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in CALLBACK_PRIMITIVES]


def find_wide_dtypes(jaxpr) -> List[Tuple[str, str]]:
    """(primitive, dtype) pairs for every equation producing a 64-bit
    value anywhere in the jaxpr (float64 leaks and friends)."""
    out = []
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            dt = getattr(getattr(var, "aval", None), "dtype", None)
            if dt is not None and str(dt) in WIDE_DTYPES:
                out.append((eqn.primitive.name, str(dt)))
    return out


_HEX_ADDR = re.compile(r"0x[0-9a-fA-F]+")


def fingerprint(fn, *specs) -> str:
    """Structural hash of ``fn``'s jaxpr when traced over ``specs``
    (ShapeDtypeStruct pytrees).  The pretty-printed jaxpr already uses
    canonical variable names; object addresses (closure reprs in params)
    are masked so the hash depends only on program structure.  Two
    constructions of the same (method, codec, config) must hash
    identically — a drifting hash means every invocation would silently
    retrace and recompile (rule R001; wired into benchmarks/perf_bench.py
    as the recompilation guard)."""
    txt = _HEX_ADDR.sub("0x", str(jax.make_jaxpr(fn)(*specs)))
    return hashlib.sha256(txt.encode()).hexdigest()


_ALIAS_ATTR = re.compile(r"tf\.aliasing_output")


def donation_report(fn, specs, donate_argnums=(0,)) -> Tuple[int, int,
                                                             List[str]]:
    """Lower ``jit(fn, donate_argnums=...)`` abstractly and report how
    donation fared: ``(aliased, donatable, dropped_warnings)``.

    ``aliased`` counts input buffers the lowering actually aliased into
    outputs (``tf.aliasing_output`` annotations in the StableHLO);
    ``donatable`` counts the leaves of the donated arguments; any
    "donated buffers were not usable" warnings JAX emitted are captured
    verbatim.  ``aliased < donatable`` means some donated carry leaf is
    silently copied every dispatch — rule D001."""
    donatable = sum(len(jax.tree_util.tree_leaves(specs[i]))
                    for i in donate_argnums)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(*specs)
        text = lowered.as_text()
    dropped = [str(w.message) for w in caught
               if "donated buffers were not usable" in str(w.message)]
    aliased = len(_ALIAS_ATTR.findall(text))
    return aliased, donatable, dropped


def spec_tree(tree):
    """A ShapeDtypeStruct mirror of any array pytree (concrete or already
    abstract) — the currency every audit in this package trades in."""
    import jax.numpy as jnp

    def spec(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))

    return jax.tree_util.tree_map(spec, tree)


def specs_equal(a, b) -> Optional[str]:
    """None when two spec pytrees agree leaf for leaf (shape AND dtype),
    else a human-readable description of the first mismatch."""
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb:
        return f"tree structure differs: {ta} != {tb}"
    for i, (x, y) in enumerate(zip(la, lb)):
        if tuple(x.shape) != tuple(y.shape) or x.dtype != y.dtype:
            return (f"leaf {i}: {tuple(x.shape)}/{x.dtype} != "
                    f"{tuple(y.shape)}/{y.dtype}")
    return None
