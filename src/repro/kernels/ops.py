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
  - ``flash_attention``: causal or sliding-window training attention
                        through JAX's Pallas splash attention (forward,
                        dQ and dKV kernels), one MQA kernel per KV group,
                        visiting only the blocks its mask needs.
  - ``grouped_matmul``: the MoE layer's per-expert matmuls over rows
                        sorted by expert; on a TPU JAX's Pallas ``gmm``
                        (megablox), elsewhere ``lax.ragged_dot``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
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


def _ce_single_bwd_bytes(t: int) -> int:
    """VMEM the single-kernel backward holds beyond ``_ce_vmem_bytes``,
    2 KiB a token, fitted to what Mosaic allocates on a v5e chip: it
    refused that kernel vmapped over 4 clients at T = 4,096, bv = 512,
    d = 128 (17.22 MiB of a 16 MiB scope, where ``_ce_vmem_bytes`` gives
    12.5; a compile for a described v5e without the chip's compiler
    flags accepts it) and runs it at T = 1,024 with d = 128, bv = 2,048.
    The whole token axis is one block there, its [T, 1] label and LSE
    blocks among it."""
    return 2048 * t


def _ce_blocks(t: int, d: int, v: int):
    """(bt, bv) for ``fused_ce``.  The whole token axis is one block when
    it fits ``_CE_VMEM_BYTES`` with the single-kernel backward's own
    VMEM, so ``w`` streams through VMEM once per pass and backward takes
    the single-kernel path; the vocab block is then the widest of 2048,
    1024, 512 that fits (wider blocks spend less per grid step and on the
    cross-lane reductions).  Otherwise the widest vocab block of 512,
    256, 128 that leaves room for 128 tokens, with the largest multiple
    of 128 tokens that fits and is short of the whole axis (the
    two-kernel backward); a head too wide for even that raises."""
    vp = pl.cdiv(v, 128) * 128
    for bv in (2048, 1024, 512):
        bv = min(bv, vp)
        if (_ce_vmem_bytes(t, d, bv) + _ce_single_bwd_bytes(t)
                <= _CE_VMEM_BYTES):
            return t, bv
    for bv in (512, 256, 128):
        bv = min(bv, vp)
        fixed = _ce_vmem_bytes(0, d, bv)
        per_token = _ce_vmem_bytes(1, d, bv) - fixed
        bt = (_CE_VMEM_BYTES - fixed) // per_token // 128 * 128
        if bt >= t:
            bt = pl.cdiv(t, 2 * 128) * 128
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


# ---------------------------------------------------------------------------
# grouped_matmul
# ---------------------------------------------------------------------------

# the scope that names the grouped matmuls on the device trace
GROUPED_MATMUL = "grouped_matmul"


def _gmm_tile(dim: int, most: int) -> int:
    """The widest multiple of 128 up to ``most`` that divides ``dim``."""
    for t in range(most, 127, -128):
        if dim % t == 0:
            return t
    return 128


def _gmm_tiling(m: int, k: int, n: int):
    """(tm, tk, tn) for ``gmm`` and ``tgmm``: 128 rows a tile, since a
    group's last tile is computed whole and a held expert gets ~256 rows
    of a 2,048-token chunk; the widest k and n tiles up to 1,024 that
    divide them, so a grid step does enough MXU work to hide its
    overhead (d 2,304 -> 768, 896 -> 896)."""
    return 128, _gmm_tile(k, 1024), _gmm_tile(n, 1024)


def _zero_tail(y, sizes):
    """``y`` [M, n] with the rows past the last group set to zero."""
    rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    return jnp.where(rows < jnp.sum(sizes), y, jnp.zeros((), y.dtype))


def _gmm(x, w, sizes, transpose_rhs=False):
    """``x[rows of g] @ w[g]`` (``w[g].T`` with ``transpose_rhs``) through
    the Pallas ``gmm``, rows past the last group zero."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    out = gmm(x, w, sizes, x.dtype, _gmm_tiling,
              transpose_rhs=transpose_rhs, interpret=_interpret())
    return _zero_tail(out, sizes)


@jax.custom_vjp
def _grouped_matmul_kernel(x, w, sizes):
    """``grouped_matmul`` through the Pallas ``gmm`` and ``tgmm``; the
    backward keeps only the operands: the input gradient is the groups'
    matmul with ``w[g].T``, the weight gradient the per-group
    ``x.T @ g``."""
    with jax.named_scope(GROUPED_MATMUL):
        return _gmm(x, w, sizes)


def _grouped_matmul_fwd(x, w, sizes):
    return _grouped_matmul_kernel(x, w, sizes), (x, w, sizes)


def _grouped_matmul_bwd(res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
    x, w, sizes = res
    with jax.named_scope(GROUPED_MATMUL):
        g = _zero_tail(g, sizes)
        return (_gmm(g, w, sizes, transpose_rhs=True),
                tgmm(x.swapaxes(0, 1), g, sizes, w.dtype, _gmm_tiling,
                     interpret=_interpret()),
                None)


_grouped_matmul_kernel.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(x, w, sizes):
    """``x[rows of group g] @ w[g]`` for each group.  x: [M, k] sorted by
    group; w: [G, k, n]; sizes: [G] int32 rows per group, summing to at
    most M.  Rows past the last group come out zero, and so does the
    input gradient there: those rows of ``x`` are never read.

    Selected from what the code observes: where ``fused_ce_native``
    holds (a TPU, no mesh partitioning the program) JAX's Pallas ``gmm``
    and ``tgmm`` (megablox) behind a custom VJP, which visit only the
    tiles the groups cover and, under ``vmap`` over clients, run once per
    client (the TPU's ``ragged_dot`` takes no batch dimension); elsewhere
    ``lax.ragged_dot``, differentiated by JAX."""
    if fused_ce_native(x):
        return _grouped_matmul_kernel(x, w, sizes)
    with jax.named_scope(GROUPED_MATMUL):
        out = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)
        return _zero_tail(out, sizes)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

# queries and keys of one block of the flash attention kernels (forward,
# dQ and dKV): a [512, 512] f32 score tile in VMEM
ATTN_BLOCK = 512

# the ``jax.monitoring`` event each attention call records as it is
# traced (``repro.telemetry.attention`` tallies it)
ATTENTION_EVENT = "/repro/attention/traced"


def note_attention(path: str, pairs: int = 0, grid_pairs: int = 0) -> None:
    """Record one traced attention call: its ``path`` ("kernel" or "xla")
    and, for the kernel, the block pairs it computes of its grid's."""
    jax.monitoring.record_event(ATTENTION_EVENT, path=path, pairs=pairs,
                                grid_pairs=grid_pairs)


def flash_attention_engages(q, k, *, causal: bool, kv_len, q_offset,
                            chunk: int) -> bool:
    """True when ``models.layers.attention`` runs ``flash_attention``:
    where Mosaic kernels run compiled (``fused_ce_native``: a TPU, no
    mesh partitioning the program), for causal self-attention from
    position 0 over every key (no ``kv_len``: decode keeps XLA), whose
    query axis exceeds one of the XLA path's q-chunks (``chunk``, where
    that path would scan) and is a whole number of ``ATTN_BLOCK``s."""
    s = q.shape[1]
    return (causal and kv_len is None and isinstance(q_offset, int)
            and q_offset == 0 and k.shape[1] == s and s > chunk
            and s % ATTN_BLOCK == 0 and fused_ce_native(q))


@functools.lru_cache(maxsize=16)
def _flash_kernel(group: int, s: int, window: int, block: int,
                  interpret: bool):
    """The splash attention kernel of one KV group: ``group`` query heads
    over one K/V head, S = ``s``, causal, masked to the last ``window``
    keys where nonzero; built once per shape (its block maps are
    constants, made outside any trace)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa
    mask = (sa.LocalMask((s, s), window_size=(window - 1, 0), offset=0)
            if window else sa.CausalMask((s, s)))
    sizes = sa.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mqa_single_device(
            sa.MultiHeadMask([mask] * group), block_sizes=sizes,
            interpret=interpret)


def flash_block_pairs(kernel, s: int, block: int):
    """(computed, all) (q-block, kv-block) pairs of ``kernel``'s forward
    grid, read from its mask's block map."""
    bm = np.asarray(kernel.fwd_mask_info.block_mask)
    return int(np.count_nonzero(bm)) // bm.shape[0], (s // block) ** 2


def flash_attention(q, k, v, window: int = 0, block: int = ATTN_BLOCK):
    """Causal attention, sliding over the last ``window`` keys where
    nonzero, through JAX's Pallas splash attention kernels: a flash
    forward and dQ / dKV backward kernels that skip every block pair the
    mask leaves empty, so no score tile reaches HBM.  q: [B,S,H,hd];
    k, v: [B,S,KH,hd] at their own KV heads: one MQA kernel per group of
    H/KH query heads, vmapped over groups and batch.  q is scaled by
    1/sqrt(hd) before the kernel (in f32, rounded once to its dtype);
    Q.K^T takes the operands' dtype with f32 accumulation, the softmax
    runs in f32."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    kernel = _flash_kernel(h // kh, s, window, block, _interpret())
    note_attention("kernel", *flash_block_pairs(kernel, s, block))
    qs = (q.astype(jnp.float32) * (1.0 / math.sqrt(hd))).astype(q.dtype)
    qg = qs.reshape(b, s, kh, h // kh, hd).transpose(0, 2, 3, 1, 4)
    kg, vg = (x.transpose(0, 2, 1, 3) for x in (k, v))   # [B,KH,S,hd]
    out = jax.vmap(jax.vmap(kernel))(qg, kg, vg)         # [B,KH,H/KH,S,hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
