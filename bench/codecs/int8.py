"""The uplink codec "int8", as ``repro.transport.Int8Codec`` documents it.

The upload ``[..., C]`` is viewed as ``[R, C]`` (every leading axis folded
into rows) and cut into tiles of ``codec.TILE_ROWS`` x ``codec.TILE_COLS``
(8 x 128), the last tiles padded with zeros.  Each tile has its own scale,
its absolute maximum over 127 (at least ``SCALE_FLOOR / 127``); each
element becomes the int8 ``floor(x / scale + u)``, ``u`` uniform on
[0, 1), clipped to [-127, 127] (stochastic rounding, so the mean of the
decoded value is ``x``), and decodes to that integer times its tile's
scale.  Plain float32 ``jax.numpy``.

``KERNEL`` is the program's kernel that encodes each upload, one call an
upload, which a traced run counts (``codec_missing``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import codec

KERNEL = "quantize_2d"
INT8_MAX = 127.0
SCALE_FLOOR = 1e-12


def _pad(x2):
    r, c = x2.shape
    nr, nc = codec.tiles(r, c)
    return jnp.pad(x2, ((0, nr * codec.TILE_ROWS - r),
                        (0, nc * codec.TILE_COLS - c)))


def _tiled(x2):
    nr, nc = codec.tiles(*x2.shape)
    return _pad(x2).reshape(nr, codec.TILE_ROWS, nc, codec.TILE_COLS)


def encode(x, key):
    """``(q, scales)``: int8-valued float32 ``[R, C]`` and ``[nR, nC]``
    per-tile scales of ``x``."""
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    r, c = x2.shape
    t = _tiled(x2)
    scales = jnp.maximum(jnp.max(jnp.abs(t), axis=(1, 3)),
                         SCALE_FLOOR) / INT8_MAX
    u = jax.random.uniform(key, t.shape, jnp.float32)
    q = jnp.clip(jnp.floor(t / scales[:, None, :, None] + u),
                 -INT8_MAX, INT8_MAX)
    return q.reshape(_pad(x2).shape)[:r, :c], scales


def decode(q, scales, shape):
    """The float32 payload of ``shape`` that ``(q, scales)`` stand for."""
    r, c = q.shape
    t = _tiled(q) * scales[:, None, :, None]
    return t.reshape(_pad(q).shape)[:r, :c].reshape(shape)


def roundtrip(x, key):
    """What the server receives for the upload ``x``, in float32."""
    return decode(*encode(x, key), x.shape)
