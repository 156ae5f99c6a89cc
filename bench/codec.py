"""Plain reference of a mix's uplink codec.

A mix names its uplink codec (``"codec"`` in ``bench/traffic/*.json``);
``bench/codecs/<codec>.py`` holds the codec's semantics as
``roundtrip(upload, key) -> what the server receives``, in float32
``jax.numpy``, importing nothing of the program, and, for a codec the
program runs as a kernel, that kernel's name as ``KERNEL``.
:func:`module` finds it by name, and raises for a codec that has no such
file.  The reference
draws a stochastic codec's bits from :func:`upload_key`, made from the
run's seed, the round and the client; the program draws other bits, so
the two agree in distribution and not bit for bit.

The quantizing codecs of ``repro.transport`` cut the ``[R, C]`` view of
an upload (every leading axis folded into rows) into tiles of
``TILE_ROWS`` x ``TILE_COLS``, one scale each.
"""
from __future__ import annotations

import functools
import importlib.util
import os

import jax

import weights

CODECS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "codecs")
TILE_ROWS = 8
TILE_COLS = 128
_UPLINK_SALT = 0xC0DEC


def tiles(rows: int, cols: int):
    """Row and column tiles of an ``[rows, cols]`` payload."""
    return -(-rows // TILE_ROWS), -(-cols // TILE_COLS)


@functools.lru_cache(maxsize=None)
def module(name: str):
    """``bench/codecs/<name>.py``."""
    path = os.path.join(CODECS_DIR, f"{name}.py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(CODECS_DIR)
                       if f.endswith(".py"))
        raise ValueError(f"the benchmark has no reference for the uplink "
                         f"codec {name!r} (no {path}); known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_codec_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def uplink(name: str, dtype=None):
    """``(upload, key) -> what the server receives`` for the codec
    ``name``, jitted; cast to ``dtype`` where one is given."""
    roundtrip = module(name).roundtrip
    if dtype is None:
        return jax.jit(roundtrip)
    return jax.jit(lambda x, key: roundtrip(x, key).astype(dtype))


def upload_key(seed: int, rnd: int, client: int):
    """The reference's key for the upload of ``client`` in round ``rnd``
    of the run with ``seed``."""
    key = jax.random.fold_in(weights.seed_key(seed), _UPLINK_SALT)
    return jax.random.fold_in(jax.random.fold_in(key, rnd), client)

