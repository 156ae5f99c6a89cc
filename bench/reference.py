"""Plain reference of CSE-FSL (Alg. 1 and 2) for a dense Qwen-style model.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no scans over layers or rounds, no kernels, one
client step at a time.  It imports nothing of the program.  Per round:

  1. every client runs ``h`` SGD steps on its local loss through the
     auxiliary head (RMSNorm, rank-r down projection, vocabulary up
     projection, mean cross entropy);
  2. it recomputes the smashed data of its last batch with its updated
     stage (Alg. 1 line 9) and uploads it through the mix's uplink codec
     (``bench/codecs/<codec>.py``: the identity for "none");
  3. the server takes one SGD step per upload, in client order;
  4. FedAvg replaces every client's stage and head by their mean.

Parameters are stored in the configuration's type (bfloat16) between
steps, as the configuration states; all arithmetic is float32.  The
gradient of each leaf is taken at its stored type, so it is rounded to
bfloat16 before the update, far below the bfloat16 rounding of the
updated parameter itself.

``precision="fp8"`` is the control: the operands of every matrix
product are rounded to float8_e4m3fn first (straight-through in the
backward pass), the precision below the configuration's.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import codec

HI = lax.Precision.HIGHEST


def _round(x, precision):
    if precision == "fp32":
        return x
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + lax.stop_gradient(q - x)


def _mm(a, b, precision):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HI)


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding, rotate-half form; x: [B, S, H, hd]."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(m, p, x, precision):
    b, s, d = x.shape
    h, kh, hd = m["heads"], m["kv_heads"], m["head_dim"]
    a = p["attn"]
    xn = _rmsnorm(x, a["ln"], m["eps"])
    q = _mm(xn, a["wq"], precision)
    k = _mm(xn, a["wk"], precision)
    v = _mm(xn, a["wv"], precision)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kh, hd)
    v = v.reshape(b, s, kh, hd)
    if m["qk_norm"]:
        q = _rmsnorm(q, a["q_norm"], m["eps"])
        k = _rmsnorm(k, a["k_norm"], m["eps"])
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", _round(q, precision),
                        _round(k, precision), precision=HI) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(w, precision),
                   _round(v, precision), precision=HI)
    x = x + _mm(o.reshape(b, s, h * hd), a["wo"], precision)
    f = p["mlp"]
    xn = _rmsnorm(x, f["ln"], m["eps"])
    hid = jax.nn.silu(_mm(xn, f["w1"], precision)) * _mm(xn, f["w3"],
                                                         precision)
    return x + _mm(hid, f["w2"], precision)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _ce(logits, labels):
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def aux_loss(m, ap, x, labels, precision="fp32"):
    """Client local loss through the auxiliary head (Eq. 5)."""
    ap = _f32(ap)
    pre = _mm(_rmsnorm(x, ap["ln"], m["eps"]), ap["down"], precision)
    return _ce(_mm(pre, ap["up"], precision), labels)


def head_loss(m, top, x, labels, precision="fp32"):
    """Server loss after its last layer: final norm, LM head, CE (Eq. 7)."""
    top = _f32(top)
    x = _rmsnorm(x, top["ln_f"], m["eps"])
    return _ce(_mm(x, top["head"], precision), labels)


def _sgd(params, grads, lr):
    return jax.tree_util.tree_map(
        lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32)
                      ).astype(p.dtype), params, grads)


def _unstack(blocks, n):
    return [jax.tree_util.tree_map(lambda a: a[i], blocks) for i in range(n)]


def _restack(layers):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


class Reference:
    """Runs rounds of CSE-FSL one client or server step at a time, and
    each step one layer at a time: the forward pass keeps every layer's
    input, the backward pass walks the layers in reverse through each
    layer's vector-Jacobian product and updates that layer at once.  Every
    layer has the same shapes, so one compiled layer program serves all
    of them.  ``m`` holds the model's numbers (``bench/models``)."""

    def __init__(self, m, mix, precision="fp32"):
        self.m = m
        self.lr = float(mix["lr"])
        self.uplink = codec.uplink(mix["codec"])
        P = precision

        def layer(p, x):
            return _layer(m, _f32(p), x, P)

        self._layer = jax.jit(layer)
        self._layer_vjp = jax.jit(
            lambda p, x, g: jax.vjp(layer, p, x)[1](g))
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._embed_grad = jax.jit(lambda e, t, g: jax.vjp(
            lambda e: e[t].astype(jnp.float32), e)[1](g)[0])
        self._aux = jax.jit(jax.value_and_grad(
            lambda ap, x, y: aux_loss(m, ap, x, y, P), argnums=(0, 1)))
        self._head = jax.jit(jax.value_and_grad(
            lambda top, x, y: head_loss(m, top, x, y, P), argnums=(0, 1)))
        self._sgd = jax.jit(_sgd)

    def _forward(self, layers, x):
        xs = [x]
        for p in layers:
            xs.append(self._layer(p, xs[-1]))
        return xs

    def _backward(self, layers, xs, g):
        """Updates ``layers`` in place; returns the input's gradient."""
        for i in reversed(range(len(layers))):
            gp, g = self._layer_vjp(layers[i], xs[i], g)
            layers[i] = self._sgd(layers[i], gp, self.lr)
        return g

    def client_step(self, c, tokens, labels):
        """One local SGD step of client ``c`` ({"embed", "layers",
        "aux"}); returns its loss."""
        xs = self._forward(c["layers"], self._embed(c["embed"], tokens))
        loss, (ga, g) = self._aux(c["aux"], xs[-1], labels)
        c["aux"] = self._sgd(c["aux"], ga, self.lr)
        g = self._backward(c["layers"], xs, g)
        c["embed"] = self._sgd(c["embed"],
                               self._embed_grad(c["embed"], tokens, g),
                               self.lr)
        return loss

    def smashed(self, c, tokens):
        return self._forward(c["layers"], self._embed(c["embed"], tokens))[-1]

    def server_step(self, s, smashed, labels):
        """One server SGD step on an upload ({"layers", "top"})."""
        xs = self._forward(s["layers"], smashed)
        loss, (gt, g) = self._head(s["top"], xs[-1], labels)
        s["top"] = self._sgd(s["top"], gt, self.lr)
        self._backward(s["layers"], xs, g)
        return loss

    def run(self, params, traffic, rounds):
        """``rounds`` rounds from ``params`` ({"client", "aux", "server"},
        one copy each, in the program's layout) on ``traffic``'s batches.
        Returns the params after the last FedAvg, in that layout, and
        per-round ``(client_loss, server_loss)``, each the mean over
        clients as the program reports them; ``self.uploads`` then holds
        ``(round, client, upload)`` of every upload before the codec."""
        m = self.m
        self.uploads = []
        cp, ap, sp = params["client"], params["aux"], params["server"]
        glob = {"embed": cp["embed"], "aux": ap,
                "layers": _unstack(cp["blocks_stage"]["blocks"], m["cut"])}
        server = {"layers": _unstack(sp["blocks_stage"]["blocks"],
                                     m["layers"] - m["cut"]),
                  "top": {"ln_f": sp["ln_f"], "head": sp["head"]}}
        del params, cp, ap, sp
        x, y = traffic.x, traffic.y
        losses = []
        for rnd in range(rounds):
            plan = traffic.round_plan(rnd)
            clients, uploads, closs = [], [], []
            for c in range(traffic.n):
                cl = {"embed": glob["embed"], "aux": glob["aux"],
                      "layers": list(glob["layers"])}
                ls = [self.client_step(cl, jnp.asarray(x[rows]),
                                       jnp.asarray(y[rows]))
                      for rows in plan[c]]
                last = plan[c, -1]
                sm = self.smashed(cl, jnp.asarray(x[last]))
                self.uploads.append((rnd, c, sm))
                sm = self.uplink(sm, codec.upload_key(traffic.seed, rnd, c))
                uploads.append((sm, jnp.asarray(y[last])))
                clients.append(cl)
                closs.append(jnp.mean(jnp.stack(ls)))
            sl = [self.server_step(server, sm, lb) for sm, lb in uploads]
            del uploads
            glob = fedavg(clients)
            del clients
            losses.append((float(jnp.mean(jnp.stack(closs))),
                           float(jnp.mean(jnp.stack(sl)))))
        out = {"client": {"embed": glob["embed"],
                          "blocks_stage": {"blocks": _restack(
                              glob["layers"])}},
               "aux": glob["aux"],
               "server": {"blocks_stage": {"blocks": _restack(
                   server["layers"])}, **server["top"]}}
        return out, losses


def fedavg(trees):
    """Eq. 14: the clients' mean, stored at the parameters' type."""
    def mean(*xs):
        acc = xs[0].astype(jnp.float32)
        for a in xs[1:]:
            acc = acc + a.astype(jnp.float32)
        return (acc / len(xs)).astype(xs[0].dtype)

    return jax.tree_util.tree_map(mean, *trees)
