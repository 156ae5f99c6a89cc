"""Functional neural-net ops shared by all architectures.

Pure functions over explicit parameter pytrees; no global state.  All
reductions that affect numerics (softmax, norms, scan states) run in fp32
regardless of the activation dtype.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dt)


def silu(x):
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# RoPE (incl. M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------


def rope_inv_freq(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_inv_freq(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's frequency table (HF ``_compute_yarn_parameters``): slots
    below the low correction dim keep RoPE's frequency, slots above the
    high one are divided by ``yarn.factor``, a linear ramp between."""
    def correction_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                          / head_dim)
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (yarn.factor * pos_freqs) * ramp
            + 1.0 / pos_freqs * (1.0 - ramp))


def _rope_angles(pos, inv_freq):
    # pos [...,S] float -> angles [...,S, hd/2]
    return pos[..., None].astype(jnp.float32) * inv_freq


def apply_rope(x, pos, theta: float,
               mrope_sections: Optional[Tuple[int, ...]] = None, yarn=None):
    """Rotate q/k.  x: [B,S,H,hd]. pos: [B,S] (or [3,B,S] for M-RoPE).
    ``yarn`` (a ``configs.base.Yarn``) takes YaRN's table and scales cos
    and sin by its attention factor."""
    hd = x.shape[-1]
    if yarn is None:
        inv_freq = rope_inv_freq(hd, theta)        # [hd/2]
    else:
        inv_freq = jnp.asarray(yarn_inv_freq(hd, theta, yarn), jnp.float32)
    if mrope_sections is None:
        ang = _rope_angles(pos, inv_freq)          # [B,S,hd/2]
    else:
        # M-RoPE: split the hd/2 frequency slots into (t, h, w) sections,
        # each driven by its own position stream pos[i].
        assert sum(mrope_sections) == hd // 2, (mrope_sections, hd)
        parts = []
        start = 0
        for i, sec in enumerate(mrope_sections):
            parts.append(_rope_angles(pos[i], inv_freq[start:start + sec]))
            start += sec
        ang = jnp.concatenate(parts, axis=-1)      # [B,S,hd/2]
    cos = jnp.cos(ang)[:, :, None, :]              # [B,S,1,hd/2]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_pos_ids(num_image_tokens: int, b: int, s, offset):
    """Deterministic M-RoPE position streams (t,h,w) for the VLM stub.

    The first ``num_image_tokens`` positions are a square patch grid
    (t=0, h/w = grid coords); text continues with equal streams.  Both the
    client and server stages reconstruct these from (shape, offset) — no
    position metadata accompanies the smashed data.
    """
    pos = jnp.arange(s) + offset
    p = num_image_tokens
    side = max(1, int(math.isqrt(max(p, 1))))
    is_img = pos < p
    t = jnp.where(is_img, 0, pos - p)
    hh = jnp.where(is_img, pos // side, pos - p)
    ww = jnp.where(is_img, pos % side, pos - p)
    ids = jnp.stack([t, hh, ww])                   # [3,S]
    return jnp.broadcast_to(ids[:, None, :], (3, b, s))


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, kh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, hd)).reshape(
        b, s, kh * n_rep, hd)


def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    e = jnp.where(mask, e, 0.0)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-30)


# f32 scores one (batch, head) pair of a q-chunk may hold: a long key axis
# takes shorter q-chunks (512 up to 1,024 keys, 128 at 4,096)
SCORES_PER_CHUNK = 1 << 19


def attention(q, k, v, *, causal: bool, window: int = 0,
              q_offset=0, kv_len=None, chunk: int = 512, unroll: bool = False):
    """Multi-head attention with GQA, causal & sliding-window masking.

    q: [B,Sq,H,hd]; k,v: [B,Skv,KH,hd].  ``q_offset`` is the absolute
    position of q[0] (prefill chunks / decode).  ``kv_len`` (scalar array
    or None) masks out unwritten cache slots during decode.
    For long sequences the q axis is processed in chunks of at most
    ``chunk`` queries (fewer where ``SCORES_PER_CHUNK`` asks) by a
    ``lax.scan`` so the score matrix never materializes at [Sq,Skv]; each
    chunk's block is checkpointed, so the backward pass recomputes one
    chunk's scores at a time instead of keeping every chunk's.  Where
    ``ops.flash_attention_engages`` (a TPU, no mesh, causal training or
    prefill longer than one q-chunk) the block-sparse flash kernel
    ``ops.flash_attention`` runs instead.  Each traced call records its
    path (``ops.note_attention``).
    """
    from repro.kernels import ops
    b, sq, h, hd = q.shape
    skv, kh = k.shape[1], k.shape[2]
    chunk = min(chunk, max(128, SCORES_PER_CHUNK // skv))
    if ops.flash_attention_engages(q, k, causal=causal, kv_len=kv_len,
                                   q_offset=q_offset, chunk=chunk):
        return ops.flash_attention(q, k, v, window)
    ops.note_attention("xla")
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    scale = 1.0 / math.sqrt(hd)
    kv_pos = jnp.arange(skv)

    def block(args):
        qc, off = args                              # qc [B,Cq,H,hd]
        cq = qc.shape[1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qc.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        q_pos = off + jnp.arange(cq)
        mask = jnp.ones((cq, skv), dtype=bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        if kv_len is not None:
            mask &= kv_pos[None, :] < kv_len
        w = _masked_softmax(scores, mask[None, None])
        return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32)).astype(q.dtype)

    if sq <= chunk:
        return block((q, jnp.asarray(q_offset)))
    assert sq % chunk == 0, (sq, chunk)
    nc = sq // chunk
    qs = q.reshape(b, nc, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    offs = jnp.asarray(q_offset) + jnp.arange(nc) * chunk
    out = lax.scan(lambda _, x: (None, jax.checkpoint(block)(x)), None,
                   (qs, offs), unroll=unroll or 1)[1]   # [nc,B,chunk,H,hd]
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


# ---------------------------------------------------------------------------
# MoE: dropless top-k token-choice routing over the experts a chip holds
# ---------------------------------------------------------------------------


def moe_route(x, router_w, *, k: int):
    """Softmax router over every expert, in float32 (its matrix product
    at full precision, as the router's parameter is stored).  x: [T,d].
    Returns the top-k
    ``(gates [T,k], experts [T,k])``, gates renormalised over the k
    chosen, and the load-balance loss over all router outputs (Switch /
    OLMoE style: E * sum_e top-1 share_e * mean prob_e)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)             # [T,E]
    e = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    top1 = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), 0)
    aux = e * jnp.sum(top1 * jnp.mean(probs, 0))
    return gates, idx, aux


# tokens whose expert rows one pass of the grouped matmuls holds; a layer
# with more tokens runs them a chunk at a time, which bounds the sorted
# buffers (chunk * min(k, held) rows) without dropping a row
MOE_TOKEN_CHUNK = 2048


def moe_ffn(x, params, *, k: int, first: int, held: int,
            chunk: int = MOE_TOKEN_CHUNK):
    """SwiGLU experts ``[first, first + held)`` of a top-k router, dropless.

    x: [T,d]; params["w1"/"w3"]: [held,d,f], ["w2"]: [held,f,d].  Per
    chunk of ``chunk`` tokens, the token-choice pairs routed to held
    experts are sorted by expert into a buffer of chunk*min(k, held) rows
    (every pair fits: a token picks an expert at most once) and run
    through grouped matmuls over the rows each expert received; each
    output row, weighted by its gate, is added back to its token.
    Pairs routed to experts held elsewhere add nothing here.  Returns
    ``(y [T,d], aux, stats)``: the load-balance loss over all router
    outputs, and ``stats`` ``{"rows": pairs run here, "max_rows": the
    busiest held expert's rows}``.
    """
    from repro.kernels import ops
    t, d = x.shape
    gates, idx, aux = moe_route(x, params["router"], k=k)
    local = idx - first
    expert = jnp.where((local >= 0) & (local < held), local, held)

    def part(xc, ec, gc):
        tc = xc.shape[0]
        flat = ec.reshape(-1)                                   # [tc*k]
        order = jnp.argsort(flat, stable=True)[:tc * min(k, held)]
        sizes = jnp.bincount(flat, length=held + 1)[:held].astype(jnp.int32)
        # a pair held elsewhere reads the zero row tc and is dropped at
        # the scatter
        tok = jnp.where(flat[order] < held, order // k, tc)
        xs = jnp.concatenate([xc, jnp.zeros((1, d), xc.dtype)])[tok]
        hidden = (silu(ops.grouped_matmul(xs, params["w1"], sizes))
                  * ops.grouped_matmul(xs, params["w3"], sizes))
        # the gate weighs a row before w2 (linear), so the backward keeps
        # the narrow hidden rows rather than the d-wide outputs
        g = gc.reshape(-1)[order][:, None]
        out = ops.grouped_matmul((hidden * g).astype(x.dtype), params["w2"],
                                 sizes)
        y = jnp.zeros((tc, d), jnp.float32).at[tok].add(
            out.astype(jnp.float32), mode="drop")
        return y.astype(x.dtype), sizes

    if t <= chunk:
        y, sizes = part(x, expert, gates)
        sizes = sizes[None]
    else:
        assert t % chunk == 0, (t, chunk)
        n = t // chunk
        y, sizes = lax.map(lambda a: jax.checkpoint(part)(*a),
                           (x.reshape(n, chunk, d),
                            expert.reshape(n, chunk, k),
                            gates.reshape(n, chunk, k)))
        y = y.reshape(t, d)
    stats = {"rows": jnp.sum(sizes), "max_rows": jnp.max(jnp.sum(sizes, 0))}
    return y, aux, stats


# ---------------------------------------------------------------------------
# Causal depthwise conv (mamba)
# ---------------------------------------------------------------------------


def causal_conv1d(x, w, b):
    """x: [B,S,C]; w: [C,K]; depthwise causal conv + bias."""
    k = w.shape[-1]
    out = lax.conv_general_dilated(
        x, w.T[:, None, :],                 # [K,1,C] -> spec below
        window_strides=(1,), padding=[(k - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return out + b


def conv1d_decode(x, state, w, b):
    """Single-step depthwise conv.  x: [B,C]; state: [B,K-1,C] (oldest first)."""
    k = w.shape[-1]
    full = jnp.concatenate([state, x[:, None, :]], axis=1)      # [B,K,C]
    out = jnp.einsum("bkc,ck->bc", full, w) + b
    return out, full[:, 1:, :]


# ---------------------------------------------------------------------------
# Mamba-1 selective scan (reference path; Pallas kernel in repro.kernels)
# ---------------------------------------------------------------------------


def selective_scan(u, dt, a, b_mat, c_mat, d_vec, *, chunk: int = 128,
                   h0=None, return_state: bool = False):
    """Mamba-1 scan.  u,dt: [B,S,D]; a: [D,N]; b_mat,c_mat: [B,S,N]; d_vec: [D].

    h_t = exp(dt_t a) h_{t-1} + dt_t b_t u_t;  y_t = c_t . h_t + d u_t.
    Chunked: lax.scan over chunks, associative_scan within a chunk, so peak
    memory is O(B * chunk * D * N).
    """
    bsz, s, dim = u.shape
    n = a.shape[-1]
    if s % chunk:
        chunk = s  # small sequences: single chunk
    nc = s // chunk
    # the [B,chunk,D,N] discretized tensors are built *inside* the chunk body
    # so peak memory is O(B*chunk*D*N), never O(B*S*D*N).
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, dim)
    uf = u.astype(jnp.float32).reshape(bsz, nc, chunk, dim)
    bm = b_mat.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cm = c_mat.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    def chunk_step(h, inputs):
        dt_c, u_c, b_c, c_c = inputs                # [B,chunk,D], [B,chunk,N]
        da_c = jnp.exp(dt_c[..., None] * a)         # [B,chunk,D,N]
        db_c = (dt_c * u_c)[..., None] * b_c[:, :, None, :]
        acc_a, acc_b = lax.associative_scan(combine, (da_c, db_c), axis=1)
        h_t = acc_a * h[:, None] + acc_b            # [B,chunk,D,N]
        y = jnp.einsum("bldn,bln->bld", h_t, c_c)
        return h_t[:, -1], y

    if h0 is None:
        h0 = jnp.zeros((bsz, dim, n), jnp.float32)
    h_last, ys = lax.scan(chunk_step, h0,
                          (dtf.transpose(1, 0, 2, 3),
                           uf.transpose(1, 0, 2, 3),
                           bm.transpose(1, 0, 2, 3),
                           cm.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3).reshape(bsz, s, dim)
    y = y + uf.reshape(bsz, s, dim) * d_vec
    y = y.astype(u.dtype)
    if return_state:
        return y, h_last
    return y


def selective_scan_decode(u, dt, a, b_mat, c_mat, d_vec, h):
    """One step.  u,dt: [B,D]; b_mat,c_mat: [B,N]; h: [B,D,N] -> (y, h')."""
    dtf = dt.astype(jnp.float32)
    da = jnp.exp(dtf[..., None] * a)                            # [B,D,N]
    h = da * h + dtf[..., None] * b_mat[:, None, :] * u.astype(jnp.float32)[..., None]
    y = jnp.einsum("bdn,bn->bd", h, c_mat.astype(jnp.float32))
    y = y + u.astype(jnp.float32) * d_vec
    return y.astype(u.dtype), h


# ---------------------------------------------------------------------------
# Mamba-2 (SSD, chunked dual form)
# ---------------------------------------------------------------------------


def ssd_scan(x, dt, a_log, b_mat, c_mat, *, chunk: int = 128,
             h0=None, return_state: bool = False):
    """Mamba-2 SSD.  x: [B,S,H,P]; dt: [B,S,H]; a_log: [H] (A = -exp(a_log));
    b_mat, c_mat: [B,S,N] (single group).

    h_t = exp(dt_t A_h) h_{t-1} + (dt_t x_t) outer b_t ;  y_t = h_t . c_t.
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    if s % chunk:
        chunk = s
    nc = s // chunk
    # all O(chunk^2) intra-chunk tensors live *inside* the chunk body, so
    # peak memory is O(B*chunk^2*H) not O(B*S*chunk*H).
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    xr = x.astype(jnp.float32).reshape(bsz, nc, chunk, h, p)
    bm = b_mat.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cm = c_mat.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    a_neg = -jnp.exp(a_log.astype(jnp.float32))
    iq = jnp.arange(chunk)
    mask = (iq[:, None] >= iq[None, :])

    def chunk_step(hc, inp):
        dt_c, x_c, b_c, c_c = inp            # [B,Q,H], [B,Q,H,P], [B,Q,N]
        la_cum = jnp.cumsum(dt_c * a_neg, axis=1)                # [B,Q,H]
        xb = x_c * dt_c[..., None]                               # [B,Q,H,P]
        # intra-chunk (attention-like)
        cb = jnp.einsum("bin,bjn->bij", c_c, b_c)                # [B,Q,Q]
        decay = la_cum[:, :, None, :] - la_cum[:, None, :, :]    # [B,i,j,H]
        scores = cb[..., None] * jnp.exp(
            jnp.where(mask[None, :, :, None], decay, -jnp.inf))
        y_intra = jnp.einsum("bijh,bjhp->bihp", scores, xb)
        # inter-chunk from the carried state
        y_inter = jnp.einsum("bin,bih,bhnp->bihp", c_c,
                             jnp.exp(la_cum), hc)
        # update state
        tail = la_cum[:, -1:, :] - la_cum                        # [B,Q,H]
        sc = jnp.einsum("bjn,bjh,bjhp->bhnp", b_c, jnp.exp(tail), xb)
        h_new = hc * jnp.exp(la_cum[:, -1])[:, :, None, None] + sc
        return h_new, y_intra + y_inter

    if h0 is None:
        h0 = jnp.zeros((bsz, h, n, p), jnp.float32)
    h_last, ys = lax.scan(chunk_step, h0,
                          (dtf.transpose(1, 0, 2, 3),
                           xr.transpose(1, 0, 2, 3, 4),
                           bm.transpose(1, 0, 2, 3),
                           cm.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, s, h, p).astype(x.dtype)
    if return_state:
        return y, h_last
    return y


def ssd_decode(x, dt, a_log, b_mat, c_mat, h):
    """One step.  x: [B,H,P]; dt: [B,H]; b_mat,c_mat: [B,N]; h: [B,H,N,P]."""
    a = jnp.exp(dt.astype(jnp.float32) * (-jnp.exp(a_log.astype(jnp.float32))))
    xb = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    h = h * a[:, :, None, None] + jnp.einsum("bn,bhp->bhnp",
                                             b_mat.astype(jnp.float32), xb)
    y = jnp.einsum("bhnp,bn->bhp", h, c_mat.astype(jnp.float32))
    return y.astype(x.dtype), h


# ---------------------------------------------------------------------------
# Cross entropy (reference; Pallas fused kernel in repro.kernels)
# ---------------------------------------------------------------------------


def cross_entropy(logits, labels):
    """logits [..., V] fp-any, labels [...] int -> mean CE (fp32)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
