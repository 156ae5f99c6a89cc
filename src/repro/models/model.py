"""Split transformer model: client stage | cut | server stage (+ aux head).

The model is organized exactly as the paper's split: the *client stage* owns
the embedding/frontend and the first ``cut`` blocks; the *server stage* owns
the remaining blocks, the final norm and the LM head.  The *auxiliary
network* (paper §IV-A) attaches to the cut-layer output and produces a valid
task loss so the client trains without server gradients.

Depth is a ``lax.scan`` over block params stacked on a leading axis, so
80-layer configs lower/compile in O(1).  Hybrid (Zamba2) stages interleave a
*shared* attention block every ``attn_every`` backbone layers via a
grouped double-scan; the shared block's weights are identical at every site
(scanned caches, closure params).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.common import dtype_of
from repro.configs.base import ModelConfig
from repro.core.stages import AUX_CE
from repro.models import layers as L
from repro.models.blocks import (BLOCKS, Ctx, attn_cache_spec, block_cache_spec,
                                 block_kind, dense_apply, dense_init)

# ---------------------------------------------------------------------------
# Stage plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StagePlan:
    kind: str
    n_layers: int
    groups: int = 0          # hybrid: #complete (attn_every)-groups
    tail: int = 0            # hybrid: leftover backbone layers

    @property
    def n_shared_sites(self) -> int:
        return self.groups


def stage_plans(cfg: ModelConfig):
    cut = cfg.resolved_cut
    kind = block_kind(cfg)
    if cfg.family == "hybrid":
        e = cfg.attn_every
        assert cut % e == 0, f"hybrid cut {cut} must be a multiple of {e}"
        client = StagePlan(kind, cut, groups=cut // e, tail=0)
        rest = cfg.num_layers - cut
        server = StagePlan(kind, rest, groups=rest // e, tail=rest % e)
    else:
        client = StagePlan(kind, cut)
        server = StagePlan(kind, cfg.num_layers - cut)
    return client, server


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _stack_init(init_fn, cfg, key, n, dtype):
    return jax.vmap(lambda k: init_fn(cfg, k, dtype))(jax.random.split(key, n))


def _stage_init(cfg: ModelConfig, plan: StagePlan, key, dtype):
    init_fn, _ = BLOCKS[plan.kind]
    k1, k2 = jax.random.split(key)
    p = {"blocks": _stack_init(init_fn, cfg, k1, plan.n_layers, dtype)}
    if cfg.family == "hybrid":
        p["shared_attn"] = dense_init(cfg, k2, dtype)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = dtype_of(cfg.dtype)
    cplan, splan = stage_plans(cfg)
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    client: Dict[str, Any] = {"blocks_stage": _stage_init(cfg, cplan, ks[0], dtype)}
    if cfg.family == "audio":
        client["frontend_w"] = (jax.random.normal(ks[1], (cfg.frontend_dim, d))
                                * cfg.frontend_dim ** -0.5).astype(dtype)
        client["frontend_b"] = jnp.zeros((d,), dtype)
    else:
        client["embed"] = (jax.random.normal(ks[1], (cfg.vocab_size, d))
                           * d ** -0.5).astype(dtype)
    server = {
        "blocks_stage": _stage_init(cfg, splan, ks[2], dtype),
        "ln_f": jnp.ones((d,), dtype),
        "head": (jax.random.normal(ks[3], (d, cfg.vocab_size))
                 * d ** -0.5).astype(dtype),
    }
    aux = aux_init(cfg, ks[4], dtype)
    return {"client": client, "aux": aux, "server": server}


def abstract_params(cfg: ModelConfig):
    """Param ShapeDtypeStructs without allocating (for the dry-run)."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# Auxiliary network (paper §IV-A; TPU-idiomatic low-rank variant)
# ---------------------------------------------------------------------------


def aux_init(cfg: ModelConfig, key, dtype):
    d, v, r = cfg.d_model, cfg.vocab_size, cfg.aux_rank
    k1, k2 = jax.random.split(key)
    if cfg.aux_kind == "mlp":      # full-width head (paper's MLP analogue)
        return {"ln": jnp.ones((d,), dtype),
                "up": (jax.random.normal(k1, (d, v)) * d ** -0.5).astype(dtype)}
    # "lowrank": the 1x1-conv analogue — channel mixing at reduced width
    return {"ln": jnp.ones((d,), dtype),
            "down": (jax.random.normal(k1, (d, r)) * d ** -0.5).astype(dtype),
            "up": (jax.random.normal(k2, (r, v)) * r ** -0.5).astype(dtype)}


def aux_logits_fn(cfg: ModelConfig, ap) -> Callable:
    def f(x):
        xn = L.rmsnorm(x, ap["ln"])
        if "down" in ap:
            xn = xn @ ap["down"]
        return xn @ ap["up"]
    return f


# ---------------------------------------------------------------------------
# Stage application
# ---------------------------------------------------------------------------


def _scan_blocks(cfg, kind, params, x, ctx: Ctx, caches):
    """Scan one homogeneous block stack.  caches: stacked pytree or None."""
    _, apply_fn = BLOCKS[kind]

    def body(carry, xs):
        xx, aux = carry
        p, c = xs if caches is not None else (xs, None)
        xx, nc, a = apply_fn(cfg, p, xx, ctx, c)
        return (xx, aux + a), nc

    if cfg.remat and ctx.mode == "train":
        body = jax.checkpoint(body)
    xs = (params, caches) if caches is not None else params
    (x, aux), new_caches = lax.scan(body, (x, jnp.float32(0.0)), xs,
                                    unroll=cfg.dryrun_unroll or 1)
    return x, aux, new_caches


def _tree_take(tree, sl):
    return jax.tree_util.tree_map(lambda a: a[sl], tree)


def _tree_regroup(tree, g, e):
    return jax.tree_util.tree_map(lambda a: a.reshape(g, e, *a.shape[1:]), tree)


def stage_apply(cfg: ModelConfig, plan: StagePlan, sp, x, ctx: Ctx,
                caches=None):
    """Run a stage.  caches: {"blocks": stacked, "shared": stacked} or None.

    In "prefill" mode blocks *emit* caches even when given none, so the
    collected scan outputs form the stage cache.  In "decode" mode ``caches``
    must be provided and is threaded through as scan xs/ys.
    """
    emit = ctx.mode in ("prefill", "decode")
    if cfg.family != "hybrid":
        bc = caches["blocks"] if caches is not None else None
        x, aux, nbc = _scan_blocks(cfg, plan.kind, sp["blocks"], x, ctx, bc)
        return x, aux, ({"blocks": nbc} if emit else None)

    # hybrid: groups of `attn_every` backbone layers, each followed by the
    # shared attention block (weights shared across sites, caches per site).
    g, e, tail = plan.groups, cfg.attn_every, plan.tail
    shared_p = sp["shared_attn"]
    blocks = sp["blocks"]
    grouped = _tree_regroup(_tree_take(blocks, slice(0, g * e)), g, e)
    bc = caches["blocks"] if caches is not None else None
    sc = caches["shared"] if caches is not None else None
    bc_head = (_tree_regroup(_tree_take(bc, slice(0, g * e)), g, e)
               if bc is not None else None)

    def group_body(carry, xs):
        xx, aux = carry
        if caches is not None:
            pg, bcg, scg = xs
        else:
            pg, (bcg, scg) = xs, (None, None)
        xx, a1, nbcg = _scan_blocks(cfg, plan.kind, pg, xx, ctx, bcg)
        xx, nscg, a2 = dense_apply(cfg, shared_p, xx, ctx, scg)
        return (xx, aux + a1 + a2), (nbcg, nscg)

    xs = (grouped, bc_head, sc) if caches is not None else grouped
    (x, aux), (nbc_head, nsc) = lax.scan(group_body, (x, jnp.float32(0.0)),
                                         xs, unroll=cfg.dryrun_unroll or 1)

    nbc_tail = None
    if tail:
        tail_p = _tree_take(blocks, slice(g * e, None))
        bc_tail = _tree_take(bc, slice(g * e, None)) if bc is not None else None
        x, a3, nbc_tail = _scan_blocks(cfg, plan.kind, tail_p, x, ctx, bc_tail)
        aux = aux + a3
    if not emit:
        return x, aux, None
    flat_head = jax.tree_util.tree_map(
        lambda a: a.reshape(g * e, *a.shape[2:]), nbc_head)
    if tail:
        nbc_all = jax.tree_util.tree_map(
            lambda h, t: jnp.concatenate([h, t], 0), flat_head, nbc_tail)
    else:
        nbc_all = flat_head
    return x, aux, {"blocks": nbc_all, "shared": nsc}


# ---------------------------------------------------------------------------
# Embedding / frontends
# ---------------------------------------------------------------------------


def embed_inputs(cfg: ModelConfig, cp, inputs: Dict[str, Any]):
    """inputs -> (x [B,S,d], pos_ids or None)."""
    if cfg.family == "audio":
        x = inputs["features"] @ cp["frontend_w"] + cp["frontend_b"]
        return x, None
    x = cp["embed"][inputs["tokens"]]
    if cfg.family == "vlm":
        # stub frontend (by assignment): precomputed patch embeddings for the
        # first `num_image_tokens` positions.
        img = inputs["image_embeds"].astype(x.dtype)       # [B,P,d]
        p = img.shape[1]
        x = jnp.concatenate([img, x[:, p:]], axis=1)
        return x, None     # M-RoPE positions are reconstructed per stage
    return x, None


# ---------------------------------------------------------------------------
# Losses (chunked over sequence so [B,S,V] never materializes)
# ---------------------------------------------------------------------------


def chunked_ce(x, logits_fn, labels, chunk: int = 128, unroll: bool = False):
    b, s, _ = x.shape
    if s <= chunk:
        return L.cross_entropy(logits_fn(x), labels)
    nc = s // chunk
    assert s % chunk == 0, (s, chunk)
    xs = x.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
    ys = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(acc, inp):
        xc, yc = inp
        return acc + L.cross_entropy(logits_fn(xc), yc), None

    total, _ = lax.scan(body, jnp.float32(0.0), (xs, ys), unroll=unroll or 1)
    return total / nc


def head_ce(cfg: ModelConfig, pre, head_w, labels):
    """CE of ``pre @ head_w`` vs labels; Pallas fused kernel when enabled.

    pre: [B,S,r]; head_w: [r,V]; labels: [B,S].  The fused kernel never
    materializes [B*S, V] logits (see kernels/fused_ce.py).
    """
    if cfg.use_pallas:
        from repro.kernels import ops
        t = pre.shape[0] * pre.shape[1]
        return ops.fused_ce(pre.reshape(t, -1), head_w, labels.reshape(t))
    return chunked_ce(pre, lambda xc: xc @ head_w, labels,
                      unroll=cfg.dryrun_unroll)


# ---------------------------------------------------------------------------
# Public forward passes
# ---------------------------------------------------------------------------

MOE_AUX_COEF = 0.01


def client_forward(cfg: ModelConfig, cp, inputs, ctx: Ctx, caches=None):
    cplan, _ = stage_plans(cfg)
    x, pos_ids = embed_inputs(cfg, cp, inputs)
    if pos_ids is not None:
        ctx = dataclasses.replace(ctx, pos_ids=pos_ids)
    x, aux, nc = stage_apply(cfg, cplan, cp["blocks_stage"], x, ctx, caches)
    return x, aux, nc


def server_forward(cfg: ModelConfig, sp, smashed, ctx: Ctx, caches=None,
                   pos_ids=None):
    _, splan = stage_plans(cfg)
    if pos_ids is not None:
        ctx = dataclasses.replace(ctx, pos_ids=pos_ids)
    x, aux, nc = stage_apply(cfg, splan, sp["blocks_stage"], smashed, ctx, caches)
    return x, aux, nc


def server_logits_fn(cfg: ModelConfig, sp) -> Callable:
    def f(x):
        return L.rmsnorm(x, sp["ln_f"]) @ sp["head"]
    return f


def client_loss(cfg: ModelConfig, cp, ap, inputs, labels, ctx: Ctx):
    """Local loss through the auxiliary head (Eq. 5). Returns (loss, smashed)."""
    smashed, moe_aux, _ = client_forward(cfg, cp, inputs, ctx)
    with jax.named_scope(AUX_CE):
        pre = L.rmsnorm(smashed, ap["ln"])
        if "down" in ap:
            pre = pre @ ap["down"]
        loss = head_ce(cfg, pre, ap["up"], labels)
    return loss + MOE_AUX_COEF * moe_aux, smashed


def server_loss(cfg: ModelConfig, sp, smashed, labels, ctx: Ctx,
                pos_ids=None):
    """Server loss on (stop-gradient'ed) smashed data (Eq. 7)."""
    x, moe_aux, _ = server_forward(cfg, sp, smashed, ctx, pos_ids=pos_ids)
    loss = head_ce(cfg, L.rmsnorm(x, sp["ln_f"]), sp["head"], labels)
    return loss + MOE_AUX_COEF * moe_aux


def full_forward(cfg: ModelConfig, params, inputs, ctx: Ctx):
    """Merged inference model (aggregated client stage + server stage)."""
    smashed, _, _ = client_forward(cfg, params["client"], inputs, ctx)
    x, _, _ = server_forward(cfg, params["server"], smashed, ctx)
    return x


# ---------------------------------------------------------------------------
# Serving: prefill / decode with split caches
# ---------------------------------------------------------------------------


def _stage_cache_spec(cfg, plan: StagePlan, batch, cache_len, dtype):
    spec = {"blocks": jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((plan.n_layers,) + s.shape, s.dtype),
        block_cache_spec(cfg, plan.kind, batch, cache_len, dtype))}
    if cfg.family == "hybrid":
        spec["shared"] = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((plan.n_shared_sites,) + s.shape,
                                           s.dtype),
            attn_cache_spec(cfg, batch, cache_len, dtype))
    return spec


def decode_cache_specs(cfg: ModelConfig, batch: int, cache_len: int):
    dtype = dtype_of(cfg.dtype)
    cplan, splan = stage_plans(cfg)
    return {"client": _stage_cache_spec(cfg, cplan, batch, cache_len, dtype),
            "server": _stage_cache_spec(cfg, splan, batch, cache_len, dtype)}


def init_decode_caches(cfg: ModelConfig, batch: int, cache_len: int):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  decode_cache_specs(cfg, batch, cache_len))


def _pad_attn_caches(caches, cache_len: int):
    """Grow the k/v cache seq dim (stacked layout [L,B,S,KH,hd]) to
    ``cache_len`` so decode has room to append without ring-wrapping."""
    def f(path, leaf):
        name = str(getattr(path[-1], "key", ""))
        if name in ("k", "v") and leaf.ndim >= 4 and leaf.shape[2] < cache_len:
            pad = [(0, 0)] * leaf.ndim
            pad[2] = (0, cache_len - leaf.shape[2])
            return jnp.pad(leaf, pad)
        return leaf
    return jax.tree_util.tree_map_with_path(f, caches)


def prefill(cfg: ModelConfig, params, inputs, *, window: int = 0,
            cache_len: int = 0):
    """Full-sequence forward producing caches + last-token logits.

    ``cache_len``: if > prompt length, attention caches are padded so decode
    can append ``cache_len - S`` tokens before the ring buffer wraps.
    """
    ctx = Ctx(cfg, "prefill", pos=0, window=window)
    cplan, splan = stage_plans(cfg)
    x, pos_ids = embed_inputs(cfg, params["client"], inputs)
    if pos_ids is not None:
        ctx = dataclasses.replace(ctx, pos_ids=pos_ids)
    x, _, ccache = stage_apply(cfg, cplan, params["client"]["blocks_stage"],
                               x, ctx)
    y, _, scache = stage_apply(cfg, splan, params["server"]["blocks_stage"],
                               x, ctx)
    logits = server_logits_fn(cfg, params["server"])(y[:, -1:, :])
    caches = {"client": ccache, "server": scache}
    if cache_len and not window:
        caches = _pad_attn_caches(caches, cache_len)
    return logits[:, 0], caches


def decode_step(cfg: ModelConfig, params, token, pos, caches, *,
                window: int = 0):
    """One-token decode through the split model.

    token: [B] int32; pos: scalar int32 (current absolute position);
    caches: as from ``init_decode_caches``/``prefill``.
    """
    ctx = Ctx(cfg, "decode", pos=pos, window=window)
    if cfg.family == "vlm":
        inputs = {"tokens": token[:, None],
                  "image_embeds": jnp.zeros((token.shape[0], 0, cfg.d_model),
                                            dtype_of(cfg.dtype))}
    else:
        inputs = {"tokens": token[:, None]}
    cplan, splan = stage_plans(cfg)
    x, pos_ids = embed_inputs(cfg, params["client"], inputs)
    if pos_ids is not None:
        ctx = dataclasses.replace(ctx, pos_ids=pos_ids)
    x, _, ncc = stage_apply(cfg, cplan, params["client"]["blocks_stage"], x,
                            ctx, caches["client"])
    x, _, nsc = stage_apply(cfg, splan, params["server"]["blocks_stage"], x,
                            ctx, caches["server"])
    logits = server_logits_fn(cfg, params["server"])(x)[:, 0]
    return logits, {"client": ncc, "server": nsc}
