"""Fused linear + cross-entropy Pallas-TPU kernel (vocab-tiled online LSE).

The LM-head loss over huge vocabularies (152k for the qwen archs) is the
memory hot spot of the auxiliary-head update: the plain path materializes
[T, V] logits in HBM (T=BS tokens).  This kernel computes the per-row
log-sum-exp of ``x @ w`` without ever materializing the logits: each
(token-block, vocab-block) grid step computes one [bt, bv] logit tile in
VMEM on the MXU and folds it into running (max, sumexp) accumulators held
in VMEM scratch across the minor vocab grid axis.  The caller computes
each row's picked logit, x_t . w[:, label_t], from the gathered label
columns (``ops.fused_ce``).

Dtypes: the MXU is fed the operands' own dtype (bf16 on the training
path) with ``preferred_element_type=float32``, so every logit, the LSE
and the softmax are f32.  In backward the f32 probability tile is cast
to ``w``'s dtype before the dx and dw matmuls (f32 inputs stay f32
throughout).

Backward recomputes the logit tile from the saved row-wise LSE.  When the
token axis is one block (``bt == T``, the aux head's case), one kernel
computes each probability tile once, writes that vocab block's dw and
accumulates dx in VMEM: 4 matmuls per token and vocab entry in all,
counting the forward.  Otherwise two kernels recompute the tile, one
accumulating dx over the vocab axis, one dw over the token axis.

Grid/BlockSpec conventions:
  fwd    : grid (nt, nv), v minor - scratch (m, l) persists per row.
  bwd    : grid (nv,)     (bt == T) - dx accumulates in scratch.
  bwd dx : grid (nt, nv), v minor - dx tile accumulates in scratch.
  bwd dw : grid (nv, nt), t minor - dw tile accumulates in scratch.
The vocab grid is ``cdiv(V, bv)`` with no padded copy of ``w``: the last
block reads past V, and its logits are masked to -inf in forward and its
weight columns to zero in backward (writes past V are dropped).  The
token axis is zero-padded to a multiple of ``bt``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _by_block(step, j, nv, *, bv: int, v_real: int):
    """Run ``step(masked)``: masked only on a ragged last vocab block."""
    if v_real % bv == 0:
        step(False)
        return

    @pl.when(j < nv - 1)
    def _full():
        step(False)

    @pl.when(j == nv - 1)
    def _last():
        step(True)


def _in_vocab(shape, j, *, bv: int, v_real: int):
    """Mask of the columns of vocab block ``j`` that lie inside V."""
    return lax.broadcasted_iota(jnp.int32, shape, 1) < v_real - j * bv


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, lse_ref, m_scr, l_scr, *, bv: int,
                v_real: int):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    def step(masked):
        logits = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32)      # [bt, bv]
        if masked:
            logits = jnp.where(
                _in_vocab(logits.shape, j, bv=bv, v_real=v_real),
                logits, NEG_INF)
        m_prev = m_scr[...]                                       # [bt, 1]
        m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
        l_scr[...] = (l_scr[...] * jnp.exp(m_prev - m_new)
                      + jnp.sum(jnp.exp(logits - m_new), -1, keepdims=True))
        m_scr[...] = m_new

    _by_block(step, j, nv, bv=bv, v_real=v_real)

    @pl.when(j == nv - 1)
    def _emit():
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])


def _pad_tokens(bt, *arrays):
    t = arrays[0].shape[0]
    tp = pl.cdiv(t, bt) * bt
    if tp == t:
        return arrays
    return tuple(jnp.pad(a, ((0, tp - t),) + ((0, 0),) * (a.ndim - 1))
                 for a in arrays)


def fused_ce_fwd(x, w, *, bt: int, bv: int, interpret: bool):
    """Per-row log-sum-exp of x @ w in f32.  x:[T,d] w:[d,V] -> [T]."""
    t, d = x.shape
    v = w.shape[1]
    (x,) = _pad_tokens(bt, x)
    nt, nv = x.shape[0] // bt, pl.cdiv(v, bv)

    lse = pl.pallas_call(
        functools.partial(_fwd_kernel, bv=bv, v_real=v),
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bt, 1), jnp.float32),
            pltpu.VMEM((bt, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_ce_fwd",
    )(x, w)
    return lse[:t, 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _p_tile(x, w, lab, lse, j, *, bv):
    """softmax - onehot of the logit tile x @ w, in ``w``'s dtype."""
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse)
    hit = lax.broadcasted_iota(jnp.int32, p.shape, 1) == lab - j * bv
    return jnp.where(hit, p - 1.0, p).astype(w.dtype)


def _w_tile(w_ref, j, masked, *, bv, v_real):
    """The vocab block of ``w``; past V (ragged last block) zero, so the
    columns read beyond the array add nothing to dx."""
    w = w_ref[...]
    if masked:
        w = jnp.where(_in_vocab(w.shape, j, bv=bv, v_real=v_real), w,
                      jnp.zeros_like(w))
    return w


def _dx_dot(p, w):
    """[bt, bv] x [d, bv]^T -> [bt, d] in f32."""
    return lax.dot_general(p, w, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _bwd_kernel(s_ref, x_ref, xt_ref, w_ref, lab_ref, lse_ref, dx_ref,
                dw_ref, acc, *, bv, v_real):
    """Single token block: one probability tile per vocab block feeds
    both that block's dw and the dx accumulator."""
    j = pl.program_id(0)
    nv = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    def step(masked):
        w = _w_tile(w_ref, j, masked, bv=bv, v_real=v_real)
        p = _p_tile(x_ref[...], w, lab_ref[...], lse_ref[...], j, bv=bv)
        dw = jnp.dot(xt_ref[...], p, preferred_element_type=jnp.float32)
        dw_ref[...] = (dw * s_ref[0]).astype(dw_ref.dtype)
        acc[...] += _dx_dot(p, w)

    _by_block(step, j, nv, bv=bv, v_real=v_real)

    @pl.when(j == nv - 1)
    def _emit():
        dx_ref[...] = (acc[...] * s_ref[0]).astype(dx_ref.dtype)


def _dx_kernel(s_ref, x_ref, w_ref, lab_ref, lse_ref, dx_ref, acc, *,
               bv, v_real):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    def step(masked):
        w = _w_tile(w_ref, j, masked, bv=bv, v_real=v_real)
        p = _p_tile(x_ref[...], w, lab_ref[...], lse_ref[...], j, bv=bv)
        acc[...] += _dx_dot(p, w)

    _by_block(step, j, nv, bv=bv, v_real=v_real)

    @pl.when(j == nv - 1)
    def _emit():
        dx_ref[...] = (acc[...] * s_ref[0]).astype(dx_ref.dtype)


def _dw_kernel(s_ref, x_ref, xt_ref, w_ref, lab_ref, lse_ref, dw_ref, acc,
               *, bv, v_real):
    j, i = pl.program_id(0), pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    def step(masked):
        w = _w_tile(w_ref, j, masked, bv=bv, v_real=v_real)
        p = _p_tile(x_ref[...], w, lab_ref[...], lse_ref[...], j, bv=bv)
        acc[...] += jnp.dot(xt_ref[...], p,
                            preferred_element_type=jnp.float32)

    _by_block(step, j, pl.num_programs(0), bv=bv, v_real=v_real)

    @pl.when(i == nt - 1)
    def _emit():
        dw_ref[...] = (acc[...] * s_ref[0]).astype(dw_ref.dtype)


def fused_ce_bwd(x, w, labels, lse, scale, *, bt: int, bv: int,
                 interpret: bool):
    """(dx, dw) of ``scale * sum_rows(lse - picked)`` from the saved
    per-row lse; ``scale`` is the mean's 1/T times the cotangent.  Padded
    rows are zero in ``x``, so they add nothing to dw."""
    t, d = x.shape
    v = w.shape[1]
    x, labels, lse = _pad_tokens(bt, x, labels, lse)
    tp = x.shape[0]
    nt, nv = tp // bt, pl.cdiv(v, bv)
    xt = x.T
    lab2 = labels.astype(jnp.int32)[:, None]
    lse2 = lse[:, None]
    s = jnp.asarray(scale, jnp.float32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    common = dict(bv=bv, v_real=v)
    dx_shape = jax.ShapeDtypeStruct((tp, d), x.dtype)
    dw_shape = jax.ShapeDtypeStruct((d, v), w.dtype)

    if nt == 1:
        dx, dw = pl.pallas_call(
            functools.partial(_bwd_kernel, **common),
            grid=(nv,),
            in_specs=[
                smem,
                pl.BlockSpec((tp, d), lambda j: (0, 0)),
                pl.BlockSpec((d, tp), lambda j: (0, 0)),
                pl.BlockSpec((d, bv), lambda j: (0, j)),
                pl.BlockSpec((tp, 1), lambda j: (0, 0)),
                pl.BlockSpec((tp, 1), lambda j: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tp, d), lambda j: (0, 0)),
                pl.BlockSpec((d, bv), lambda j: (0, j)),
            ],
            out_shape=[dx_shape, dw_shape],
            scratch_shapes=[pltpu.VMEM((tp, d), jnp.float32)],
            interpret=interpret,
            name="fused_ce_bwd",
        )(s, x, xt, w, lab2, lse2)
        return dx[:t], dw

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, **common),
        grid=(nt, nv),
        in_specs=[
            smem,
            pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda i, j: (i, 0)),
        out_shape=dx_shape,
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_dx",
    )(s, x, w, lab2, lse2)

    dw = pl.pallas_call(
        functools.partial(_dw_kernel, **common),
        grid=(nv, nt),
        in_specs=[
            smem,
            pl.BlockSpec((bt, d), lambda j, i: (i, 0)),
            pl.BlockSpec((d, bt), lambda j, i: (0, i)),
            pl.BlockSpec((d, bv), lambda j, i: (0, j)),
            pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((d, bv), lambda j, i: (0, j)),
        out_shape=dw_shape,
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        interpret=interpret,
        name="fused_ce_bwd_dw",
    )(s, x, xt, w, lab2, lse2)

    return dx[:t], dw
