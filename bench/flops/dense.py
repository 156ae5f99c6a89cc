"""Model FLOPs of one CSE-FSL round for the dense family.

Only matrix products count, from their shapes, at 2 FLOPs a
multiply-add.  Causal attention counts the (S+1)/2 keys a query needs on
average, not the masked-out half.  Backward costs twice the forward
(input and weight gradients), less the input gradient the algorithm
never needs: the server stage's first projections (its input is
stop-gradient smashed data).  Recomputation for rematerialisation does
not count.  Per round of ``n`` clients, ``h`` local steps of ``B x S``
tokens each:

  client     fwd+bwd of the client stage and the aux head on n*h*B*S tokens
  recompute  fwd of the client stage on n*B*S tokens (Alg. 1 line 9)
  server     fwd+bwd of the server stage and the LM head on n*B*S tokens
"""
from __future__ import annotations


def layer_forward(m: dict, seq: int) -> float:
    """Forward FLOPs of one decoder layer per token at sequence ``seq``."""
    d, hd = m["d"], m["head_dim"]
    q, kv = m["heads"] * hd, m["kv_heads"] * hd
    proj = 2 * d * (q + 2 * kv) + 2 * q * d
    attn = 2 * 2 * q * (seq + 1) / 2
    mlp = 3 * 2 * d * m["ff"]
    return proj + attn + mlp


def aux_forward(m: dict) -> float:
    return 2 * m["d"] * m["aux_rank"] + 2 * m["aux_rank"] * m["vocab"]


def head_forward(m: dict) -> float:
    return 2 * m["d"] * m["vocab"]


def client_step(m: dict, seq: int) -> float:
    """Per token: fwd+bwd of the client stage plus the aux head."""
    return 3 * (m["cut"] * layer_forward(m, seq) + aux_forward(m))


def server_step(m: dict, seq: int) -> float:
    """Per token: fwd+bwd of the server stage plus the head, without the
    input gradient of its first q/k/v projections."""
    d, hd = m["d"], m["head_dim"]
    unneeded = 2 * d * (m["heads"] + 2 * m["kv_heads"]) * hd
    fwd = (m["layers"] - m["cut"]) * layer_forward(m, seq) + head_forward(m)
    return 3 * fwd - unneeded


def round_flops(m: dict, mix: dict) -> dict:
    n, h, b, s = mix["clients"], mix["h"], mix["batch"], mix["seq"]
    parts = {
        "client": n * h * b * s * client_step(m, s),
        "recompute": n * b * s * m["cut"] * layer_forward(m, s),
        "server": n * b * s * server_step(m, s),
    }
    parts["total"] = sum(parts.values())
    return parts
