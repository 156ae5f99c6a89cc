"""Per-tile quantization Pallas-TPU kernel for the wire transport layer.

The uplink codecs (``repro.transport``) compress the smashed activations
crossing the client->server wire.  Quantizing a whole payload with one
scale lets a single outlier blow up the error of every other element, so
the kernel computes an independent absmax scale per (bt, bc) tile — the
scale side-channel costs 4 bytes per tile (~0.1% at the default 8x128
tile) and keeps the quantization error proportional to the *local* range.

Stochastic rounding makes the quantizer unbiased (E[decode(encode(x))]=x),
which matters because the server *trains* on the decoded activations:
biased rounding accumulates over thousands of optimizer steps.  Two
randomness paths share one rounding math:

  - **caller bits** (CPU / ``interpret=True``): a uint32 ``[R, C]`` tensor
    from ``jax.random.bits`` — bit-identical to the pure-jnp oracle in
    `kernels/ref.py`, which the tests compare against exactly;
  - **in-kernel PRNG** (real TPU): a scalar-prefetched seed drives
    ``pltpu.prng_seed(seed, block)`` + ``pltpu.prng_random_bits`` per block,
    so no payload-sized uint32 tensor is ever materialized — inside the
    compiled chunk scan (``Trainer.run_compiled``) the random bits live
    only in VMEM for the lifetime of one block.

``use_inkernel_prng()`` tells the transport codecs which path the current
backend takes.

Formats:
  - ``int8``: round(x/scale) to [-127, 127], scale = tile absmax / 127.
  - ``fp8``:  x/scale cast to float8_e4m3fn, scale = tile absmax / 448.
    Stochastic rounding drops the 20 low mantissa bits of the fp32
    bit pattern after adding 20 random bits — exact for e4m3-normal
    values, and the carry into the exponent is precisely the round-up.

Grid/BlockSpec conventions: payloads are flattened to a [R, C] view (last
axis minor).  Grid (nG, nC): step (g, j) quantizes the ``_GROUP`` row
tiles g*8 .. g*8+7 of column tile j.  Their scales go to column j of a
(_GROUP, nC) block of the [nG*8, nC] fp32 scale output — the one shape
Mosaic accepts for it, as the block's last dim is the whole array's — and
that block stays resident while j sweeps, so j runs in order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT8_MAX = 127.0
FP8_MAX = 448.0                  # float8_e4m3fn largest finite value
_MANTISSA_DROP = 20              # fp32 (23) -> e4m3 (3) mantissa bits
_SCALE_FLOOR = 1e-12             # all-zero tiles: keep scale finite
_GROUP = 8                       # row tiles per grid step: one scale sublane


def _stochastic_int8(y, bits):
    """floor(y + u), u ~ U[0,1) from the top 24 bits of ``bits``.  The
    24-bit value goes through int32 (exact), since Mosaic has no
    uint32 -> float32 conversion."""
    u = lax.bitcast_convert_type(bits >> 8, jnp.int32).astype(jnp.float32) \
        * (1.0 / (1 << 24))
    return jnp.floor(y + u)


def _stochastic_fp8(y, bits):
    """Unbiased fp32 -> e4m3 rounding via the mantissa bit trick."""
    b = lax.bitcast_convert_type(y, jnp.uint32)
    b = (b + (bits & jnp.uint32((1 << _MANTISSA_DROP) - 1))) \
        & jnp.uint32((0xFFFFFFFF << _MANTISSA_DROP) & 0xFFFFFFFF)
    y = lax.bitcast_convert_type(b, jnp.float32)
    return jnp.clip(y, -FP8_MAX, FP8_MAX)


def _quant_tile(x, bits, *, fmt: str, stochastic: bool):
    """One (bt, bc) tile's quantization math -> (q tile, scalar scale),
    shared by the caller-bits and in-kernel-PRNG kernels so the two paths
    differ ONLY in where the random bits come from."""
    x = x.astype(jnp.float32)
    qmax = INT8_MAX if fmt == "int8" else FP8_MAX
    # multiply by the precomputed reciprocal: XLA rewrites division by a
    # constant into this anyway, but only under jit — doing it explicitly
    # keeps jitted/eager/interpret runs bit-identical (the ref oracle and
    # the kernel tests rely on exact equality).
    scale = jnp.maximum(jnp.max(jnp.abs(x)), _SCALE_FLOOR) * (1.0 / qmax)
    y = x / scale
    if fmt == "int8":
        q = _stochastic_int8(y, bits) if stochastic else jnp.round(y)
        return jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8), scale
    y = _stochastic_fp8(y, bits) if stochastic \
        else jnp.clip(y, -FP8_MAX, FP8_MAX)
    return y.astype(jnp.float8_e4m3fn), scale


def _quant_block(x_ref, q_ref, s_ref, bits, *, bt: int, fmt: str,
                 stochastic: bool):
    """Quantize the ``_GROUP`` row tiles of one (_GROUP*bt, bc) block and
    write their scales into column ``j`` of the (_GROUP, nC) scale block,
    which stays resident while ``j`` sweeps the row group."""
    col = lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    row = lax.broadcasted_iota(jnp.int32, s_ref.shape, 0)
    scales = s_ref[...]
    for t in range(_GROUP):
        rows = slice(t * bt, (t + 1) * bt)
        q, scale = _quant_tile(x_ref[rows, :], bits[rows, :], fmt=fmt,
                               stochastic=stochastic)
        q_ref[rows, :] = q
        scales = jnp.where((row == t) & (col == pl.program_id(1)), scale,
                           scales)
    s_ref[...] = scales


def _quant_kernel(x_ref, bits_ref, q_ref, s_ref, *, bt: int, fmt: str,
                  stochastic: bool):
    _quant_block(x_ref, q_ref, s_ref, bits_ref[...], bt=bt, fmt=fmt,
                 stochastic=stochastic)


def _quant_kernel_prng(seed_ref, x_ref, q_ref, s_ref, *, bt: int, fmt: str):
    """TPU-native stochastic path: the per-core PRNG is seeded from the
    scalar-prefetched seed + the block's flat grid index, so every block
    draws an independent stream and no ``[R, C]`` bits tensor exists."""
    pltpu.prng_seed(seed_ref[0],
                    pl.program_id(0) * pl.num_programs(1) + pl.program_id(1))
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    _quant_block(x_ref, q_ref, s_ref, bits, bt=bt, fmt=fmt, stochastic=True)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def use_inkernel_prng() -> bool:
    """True when quantize_2d should take the in-kernel PRNG path (real
    TPU): callers pass a scalar ``seed`` instead of materializing a
    payload-sized uint32 ``bits`` tensor.  Off-TPU the caller-bits path
    keeps CPU tests bitwise against ``kernels/ref.py``."""
    return not _interpret()


def quantize_2d(x, bits=None, *, seed=None, fmt: str = "int8", bt: int = 8,
                bc: int = 128, stochastic: bool = True, interpret=None):
    """Per-tile quantization of a [R, C] array.

    Returns ``(q, scales)``: ``q`` is [R, C] int8 (or float8_e4m3fn),
    ``scales`` is [ceil(R/bt), ceil(C/bc)] fp32.  Randomness, when
    ``stochastic``: EITHER ``bits`` — a caller-supplied uint32 [R, C]
    array (the interpret/CPU path, bitwise against ``kernels/ref.py``) —
    OR ``seed`` — an int32 scalar driving the in-kernel TPU PRNG, which
    never materializes the bits (real-TPU only; pick the path with
    :func:`use_inkernel_prng`).  ``bits`` is ignored when not
    ``stochastic``.  Tiles are padded with zeros, which cannot raise a
    tile's absmax; rows pad to whole groups of ``_GROUP`` tiles.
    """
    if interpret is None:
        interpret = _interpret()
    r, c = x.shape
    nr, nc = pl.cdiv(r, bt), pl.cdiv(c, bc)
    ng = pl.cdiv(nr, _GROUP)
    rp, cp = ng * _GROUP * bt, nc * bc
    if (rp, cp) != (r, c):
        x = jnp.pad(x, ((0, rp - r), (0, cp - c)))
        if bits is not None:
            bits = jnp.pad(bits, ((0, rp - r), (0, cp - c)))
    out_dtype = jnp.int8 if fmt == "int8" else jnp.float8_e4m3fn
    out_shape = [
        jax.ShapeDtypeStruct((rp, cp), out_dtype),
        jax.ShapeDtypeStruct((ng * _GROUP, nc), jnp.float32),
    ]
    block = (_GROUP * bt, bc)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    if stochastic and seed is not None:
        if interpret:
            raise ValueError(
                "the in-kernel PRNG path (seed=...) needs a real TPU; "
                "pass caller bits under interpret=True")
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ng, nc),
            in_specs=[pl.BlockSpec(block, lambda i, j, s: (i, j))],
            out_specs=[
                pl.BlockSpec(block, lambda i, j, s: (i, j)),
                pl.BlockSpec((_GROUP, nc), lambda i, j, s: (i, 0)),
            ],
        )
        q, scales = pl.pallas_call(
            functools.partial(_quant_kernel_prng, bt=bt, fmt=fmt),
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=params,
            name="quantize_2d",
        )(jnp.asarray(seed, jnp.int32).reshape(1), x)
        return q[:r, :c], scales[:nr]

    if stochastic and bits is None:
        raise ValueError("stochastic quantize_2d needs bits=<uint32 [R,C]> "
                         "or seed=<int32 scalar>")
    if bits is None:                        # rounding ignores the bits
        bits = jnp.zeros((rp, cp), jnp.uint32)
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, bt=bt, fmt=fmt,
                          stochastic=stochastic),
        grid=(ng, nc),
        in_specs=[
            pl.BlockSpec(block, lambda i, j: (i, j)),
            pl.BlockSpec(block, lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec(block, lambda i, j: (i, j)),
            pl.BlockSpec((_GROUP, nc), lambda i, j: (i, 0)),
        ],
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
        name="quantize_2d",
    )(x, bits.astype(jnp.uint32))
    return q[:r, :c], scales[:nr]


def dequantize_2d(q, scales, *, bt: int = 8, bc: int = 128,
                  dtype=jnp.float32):
    """Exact inverse map of ``quantize_2d``'s scaling (plain jnp: the
    per-element multiply needs no kernel and matches on every backend).

    The scale map is applied by reshaping the payload into its
    [nR, bt, nC, bc] tile view and broadcasting the [nR, nC] scales across
    it — one fused multiply, no materialized [R, C] fp32 scale map (the
    old double-``jnp.repeat`` built that map AND the product; elementwise
    the result is bitwise-identical, asserted in tests/test_transport.py).
    """
    r, c = q.shape
    nr, nc = scales.shape
    rp, cp = nr * bt, nc * bc
    if (rp, cp) != (r, c):                  # pad the (narrow) payload only
        q = jnp.pad(q, ((0, rp - r), (0, cp - c)))
    tiles = q.reshape(nr, bt, nc, bc).astype(jnp.float32)
    y = (tiles * scales[:, None, :, None]).reshape(rp, cp)
    return y[:r, :c].astype(dtype)
