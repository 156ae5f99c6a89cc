"""Jit'd public wrappers for the Pallas kernels.

Each op selects ``interpret=True`` off-TPU through one backend probe,
``_interpret`` (which ``fused_ce_native`` also reads, for
``models.model.head_ce`` to choose the fused CE), wires a
``custom_vjp`` so the ops are drop-in replacements inside training
losses, and picks MXU-aligned block sizes from the problem shape.

  - ``fused_ce``      : Pallas forward AND backward (both vocab-tiled), on
                        the operands' own dtype with f32 accumulation.
                        ``_ce_blocks`` makes the whole token axis one block
                        when it fits VMEM (T = 1,024 at r = 128), and
                        backward is then a single kernel that computes
                        each probability tile once for dw and dx.
  - ``ssm_scan``      : Pallas forward; backward recomputes through the
                        chunked associative-scan reference (O(chunk) memory).
  - ``swa_attention`` : Pallas forward; backward recomputes through the
                        reference (used on the serving path, grad rarely
                        needed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import AxisType

from repro.kernels import fused_ce as _ce
from repro.kernels import ssm_scan as _ssm
from repro.kernels import swa_attention as _swa
from repro.kernels import ref as ref  # noqa: F401  (re-export for tests)


def _interpret() -> bool:
    """The backend probe: off a TPU every kernel runs in interpret mode."""
    return jax.default_backend() != "tpu"


def fused_ce_native(x) -> bool:
    """True when ``fused_ce`` on ``x`` runs as a compiled Mosaic kernel:
    on a TPU (``_interpret``) and in a program that no mesh partitions
    automatically.  ``x``'s type carries the mesh its program's inputs
    are sharded over; XLA cannot partition a Mosaic kernel, so a program
    sharded over several devices (the cohort engine's ``--mesh host``,
    clients split over "data") keeps the XLA path.  Axes that a
    ``shard_map`` made manual do not count."""
    if _interpret():
        return False
    mesh = jax.typeof(x).sharding.mesh
    return all(n == 1 or kind == AxisType.Manual
               for n, kind in zip(mesh.axis_sizes, mesh.axis_types))


def audit_specs():
    """The kernels' declared audit surface: ``(name, fn, arg_specs)`` for
    every public Pallas op, shaped to tile each kernel's grid at least
    once.  ``repro.analysis`` traces these abstractly (interpret mode —
    no accelerator, no real arrays) and runs the compiled-path hygiene
    rules (C001 no host callbacks, C002 no float64) over the jaxprs, so
    a kernel edit that leaks a debug callback or a wide dtype fails the
    static gate before any benchmark runs."""
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    return (
        ("fused_ce", fused_ce,
         (S((16, 8), f32), S((8, 128), f32), S((16,), i32))),
        ("ssm_scan", ssm_scan,
         (S((1, 16, 8), f32), S((1, 16, 8), f32), S((8, 4), f32),
          S((1, 16, 4), f32), S((1, 16, 4), f32), S((8,), f32))),
        ("swa_attention", lambda q, k, v: swa_attention(q, k, v, 8),
         (S((1, 16, 2, 8), f32), S((1, 16, 2, 8), f32),
          S((1, 16, 2, 8), f32))),
    )


# VMEM a fused CE grid step may plan for, of v5e's 16 MiB default scope
_CE_VMEM_BYTES = 14 * 2 ** 20


def _ce_vmem_bytes(bt: int, d: int, bv: int) -> int:
    """VMEM of one fused CE grid step, fitted to what Mosaic allocates on
    v5e (compiled for a described v5e chip, it refuses bt x bv = 1024 x
    4096 at d = 128, and backward at 8192 x 512, d = 128, and at 1024 x
    1024, d = 1024, for want of VMEM): the f32
    [bt, bv] logit tile, ~512 + 4d bytes a token for the token-indexed
    blocks and the dx accumulator, and the w and dw blocks, double-
    buffered, at f32 width."""
    return 4 * bt * bv + bt * (512 + 4 * d) + 8 * d * bv


def _ce_blocks(t: int, d: int, v: int):
    """(bt, bv) for ``fused_ce``.  The whole token axis is one block when
    it fits ``_CE_VMEM_BYTES``, so ``w`` streams through VMEM once per
    pass and backward takes the single-kernel path; the vocab block is
    then the widest of 2048, 1024, 512 that fits (wider blocks spend less
    per grid step and on the cross-lane reductions).  Otherwise the
    widest vocab block of 512, 256, 128 that leaves room for 128 tokens,
    with the largest multiple of 128 tokens that fits; a head too wide
    for even that raises."""
    vp = pl.cdiv(v, 128) * 128
    for bv in (2048, 1024, 512):
        bv = min(bv, vp)
        if _ce_vmem_bytes(t, d, bv) <= _CE_VMEM_BYTES:
            return t, bv
    for bv in (512, 256, 128):
        bv = min(bv, vp)
        fixed = _ce_vmem_bytes(0, d, bv)
        per_token = _ce_vmem_bytes(1, d, bv) - fixed
        bt = (_CE_VMEM_BYTES - fixed) // per_token // 128 * 128
        if bt >= 128:
            return min(bt, t), bv
    raise ValueError(f"fused_ce: no block of 128 tokens fits "
                     f"{_CE_VMEM_BYTES} bytes of VMEM at width {d}")


# ---------------------------------------------------------------------------
# fused_ce
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_ce(x, w, labels, block=None):
    """Mean cross-entropy of ``x @ w`` vs labels.  x:[T,d] w:[d,V] lab:[T].

    The [T, V] logits never reach HBM: the kernels keep each logit tile
    in VMEM (kernels/fused_ce.py).  The MXU takes x and w in their own
    dtype and accumulates in f32; the picked logits come from the
    gathered label columns of w, in f32."""
    loss, _ = _fused_ce_fwd(x, w, labels, block)
    return loss


def _fused_ce_fwd(x, w, labels, block):
    t, d = x.shape
    bt, bv = block or _ce_blocks(t, d, w.shape[1])
    lse = _ce.fused_ce_fwd(x, w, bt=bt, bv=bv, interpret=_interpret())
    w_lab = jnp.take(w, labels, axis=1).T                         # [T, d]
    picked = jnp.sum(x.astype(jnp.float32) * w_lab.astype(jnp.float32), -1)
    return jnp.mean(lse - picked), (x, w, labels, lse)


def _fused_ce_bwd(block, res, g):
    x, w, labels, lse = res
    t, d = x.shape
    bt, bv = block or _ce_blocks(t, d, w.shape[1])
    dx, dw = _ce.fused_ce_bwd(x, w, labels, lse, g / t, bt=bt, bv=bv,
                              interpret=_interpret())
    return dx, dw, None


fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


# ---------------------------------------------------------------------------
# ssm_scan
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssm_scan(u, dt, a, b_mat, c_mat, d_vec, chunk: int = 128):
    """Mamba-1 selective scan (see kernels/ssm_scan.py)."""
    cs = chunk if u.shape[1] % chunk == 0 else u.shape[1]
    return _ssm.ssm_scan(u, dt, a, b_mat, c_mat, d_vec, chunk=cs,
                         interpret=_interpret())


def _ssm_fwd(u, dt, a, b_mat, c_mat, d_vec, chunk):
    return ssm_scan(u, dt, a, b_mat, c_mat, d_vec, chunk), \
        (u, dt, a, b_mat, c_mat, d_vec)


def _ssm_bwd(chunk, res, g):
    from repro.models.layers import selective_scan
    u, dt, a, b_mat, c_mat, d_vec = res
    _, vjp = jax.vjp(
        lambda *args: selective_scan(*args, chunk=chunk), u, dt, a, b_mat,
        c_mat, d_vec)
    return vjp(g)


ssm_scan.defvjp(_ssm_fwd, _ssm_bwd)


# ---------------------------------------------------------------------------
# swa_attention
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def swa_attention(q, k, v, window: int):
    """Sliding-window causal flash attention (see kernels/swa_attention.py)."""
    return _swa.swa_attention(q, k, v, window=window, interpret=_interpret())


def _swa_fwd(q, k, v, window):
    return swa_attention(q, k, v, window), (q, k, v)


def _swa_bwd(window, res, g):
    from repro.kernels.ref import swa_attention as ref_swa
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: ref_swa(q_, k_, v_, window=window),
                     q, k, v)
    return vjp(g)


swa_attention.defvjp(_swa_fwd, _swa_bwd)
