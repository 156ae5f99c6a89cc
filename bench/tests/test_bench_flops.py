"""bench/flops/dense.py against XLA's own count of the program's client and
server losses, forward and backward, at a reduced size on the CPU.

XLA counts every operation it runs, so it reads above the matmul-only
count: the masked half of causal attention (added back here), and the
element-wise work of norms, rotary embedding, softmax and cross entropy,
which this size keeps within 10% of the total.  It never reads below:
the count holds only products the algorithm needs.
"""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402

TOLERANCE = 0.10


@pytest.fixture(scope="module")
def small():
    cfg = json.load(open(os.path.join(BENCH, "configs", "qwen3-0.6b.json")))
    cfg.update(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               vocab_size=1024)
    cfg["assumed"] = dict(cfg["assumed"], cut_layer=2, aux_rank=32,
                          remat=False)
    family = harness.load_module(os.path.join(BENCH, "models", "dense.py"),
                                 "bench_models_dense")
    flops = harness.load_module(os.path.join(BENCH, "flops", "dense.py"),
                                "bench_flops_dense")
    # unrolled layer scans, so XLA's count visits every layer
    return (family.program_config(cfg).with_(dryrun_unroll=True),
            family.numbers(cfg), flops)


def _xla_flops(fn, *specs):
    import jax
    cost = jax.jit(fn).lower(*specs).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"]


@pytest.mark.parametrize("stage", ["client", "server"])
def test_round_flops_match_xla(small, stage):
    import jax
    import jax.numpy as jnp
    cfg, m, fl = small
    b, s = 2, 128
    bundle = harness.program_bundle(cfg)
    p = jax.eval_shape(bundle.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
    q = m["heads"] * m["head_dim"]
    masked = 3 * 2 * q * (s - 1)        # per token and layer, fwd + bwd
    if stage == "client":
        xla = _xla_flops(jax.value_and_grad(
            lambda cp, ap, t: bundle.client_loss(cp, ap, {"tokens": t},
                                                 t)[0], argnums=(0, 1)),
            p["client"], p["aux"], toks)
        ours = b * s * fl.client_step(m, s)
        layers = m["cut"]
    else:
        sm = jax.ShapeDtypeStruct((b, s, m["d"]), jnp.bfloat16)
        xla = _xla_flops(jax.value_and_grad(bundle.server_loss),
                         p["server"], sm, toks)
        ours = b * s * fl.server_step(m, s)
        layers = m["layers"] - m["cut"]
    ours += b * s * layers * masked
    assert ours <= xla <= (1 + TOLERANCE) * ours, (xla, ours, xla / ours)


def test_round_splits_client_and_server_tokens(small):
    _, m, fl = small
    mix = {"clients": 4, "h": 8, "batch": 2, "seq": 128}
    parts = fl.round_flops(m, mix)
    tokens = 4 * 2 * 128
    assert parts["client"] == pytest.approx(8 * tokens
                                            * fl.client_step(m, 128))
    assert parts["server"] == pytest.approx(tokens * fl.server_step(m, 128))
    assert parts["recompute"] == pytest.approx(
        tokens * m["cut"] * fl.layer_forward(m, 128))
    assert parts["total"] == pytest.approx(
        parts["client"] + parts["server"] + parts["recompute"])
