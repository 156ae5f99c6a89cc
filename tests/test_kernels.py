"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels run in ``interpret=True`` on CPU (TPU is the compile target); every
sweep asserts allclose against ``repro.kernels.ref``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fused_ce as ce_mod
from repro.kernels import ops, ref
from repro.kernels import ssm_scan as ssm_mod
from repro.kernels import swa_attention as swa_mod


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# fused cross-entropy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,d,v", [(8, 16, 64), (128, 64, 256),
                                   (256, 32, 512), (64, 128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ce_forward(t, d, v, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = _rand(k1, (t, d), dtype)
    w = _rand(k2, (d, v), dtype)
    labels = jax.random.randint(k3, (t,), 0, v)
    got = ops.fused_ce(x, w, labels)
    want = ref.fused_ce(x, w, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-3 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("t,d,v", [(32, 16, 96), (128, 64, 256)])
def test_fused_ce_grads(t, d, v):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    x = _rand(k1, (t, d), jnp.float32)
    w = _rand(k2, (d, v), jnp.float32)
    labels = jax.random.randint(k3, (t,), 0, v)
    gx, gw = jax.grad(lambda a, b: ops.fused_ce(a, b, labels),
                      argnums=(0, 1))(x, w)
    rx, rw = jax.grad(lambda a, b: ref.fused_ce(a, b, labels),
                      argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-4, atol=1e-5)


def test_fused_ce_vocab_not_multiple_of_block():
    """Vocab-tail masking: v deliberately not a multiple of bv, and the
    kernel reads past V with no padded copy of w."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    t, d, v = 16, 8, 130
    x = _rand(k1, (t, d), jnp.float32)
    w = _rand(k2, (d, v), jnp.float32)
    labels = jax.random.randint(k3, (t,), 0, v)
    lse = ce_mod.fused_ce_fwd(x, w, bt=8, bv=128, interpret=True)
    lf = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    want_lse = np.log(np.exp(lf - lf.max(-1, keepdims=True)).sum(-1)) \
        + lf.max(-1)
    np.testing.assert_allclose(np.asarray(lse), want_lse, rtol=1e-5)
    want = np.mean(want_lse - lf[np.arange(t), np.asarray(labels)])
    np.testing.assert_allclose(
        float(ops.fused_ce(x, w, labels, (8, 128))), want, rtol=1e-5)


@pytest.mark.parametrize("t,d,v", [(32, 16, 300), (64, 32, 200)])
def test_fused_ce_bf16_grads(t, d, v):
    """bf16 operands feed the MXU as bf16 with f32 accumulation; the
    gradients come back in the operands' dtype."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    x = _rand(k1, (t, d), jnp.bfloat16)
    w = _rand(k2, (d, v), jnp.bfloat16)
    labels = jax.random.randint(k3, (t,), 0, v)
    loss, (gx, gw) = jax.value_and_grad(
        lambda a, b: ops.fused_ce(a, b, labels, (t, 128)),
        argnums=(0, 1))(x, w)
    assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
    rx, rw = ref.fused_ce_grads(x, w, labels)
    np.testing.assert_allclose(float(loss), float(ref.fused_ce(x, w, labels)),
                               rtol=1e-5)
    for got, want in ((gx, rx), (gw, rw)):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=1e-3)


def test_fused_ce_single_and_two_kernel_backward_agree():
    """One token block (one kernel: the probability tile feeds dw and dx)
    against four token blocks (the dx and dw kernels), on the same
    ragged-vocab shape."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    t, d, v = 64, 16, 300
    x = _rand(k1, (t, d), jnp.float32)
    w = _rand(k2, (d, v), jnp.float32)
    labels = jax.random.randint(k3, (t,), 0, v)
    lse = ce_mod.fused_ce_fwd(x, w, bt=t, bv=128, interpret=True)
    one = ce_mod.fused_ce_bwd(x, w, labels, lse, 0.5 / t, bt=t, bv=128,
                              interpret=True)
    two = ce_mod.fused_ce_bwd(x, w, labels, lse, 0.5 / t, bt=16, bv=128,
                              interpret=True)
    rx, rw = ref.fused_ce_grads(x, w, labels, g=0.5)
    for a, b, r in zip(one, two, (rx, rw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("t,d,v,one_block", [
    (1024, 128, 151_936, True),      # the rank-128 aux head, B=2, S=512
    (1024, 1024, 151_936, True),     # the server head's width
    (1024, 16, 300, True),
    (65_536, 128, 151_936, False),   # too many tokens for one VMEM block
    (65_536, 4096, 151_936, False),  # a wide head: narrower vocab blocks
])
def test_fused_ce_blocks(t, d, v, one_block):
    bt, bv = ops._ce_blocks(t, d, v)
    assert bv % 128 == 0 and bv < v + 128
    assert (bt == t) == one_block
    assert ops._ce_vmem_bytes(bt, d, bv) <= ops._CE_VMEM_BYTES
    if not one_block:
        assert bt % 128 == 0 and bt < t


def test_fused_ce_blocks_refuse_a_head_too_wide_for_vmem():
    with pytest.raises(ValueError, match="VMEM"):
        ops._ce_blocks(1024, 16_384, 151_936)


def test_head_ce_selection(monkeypatch):
    """The fused kernel only on a TPU, and only for a narrow head: the
    rank-128 aux head takes it, the server's 1,024-wide head does not."""
    from repro.models import model
    pre = lambda width: jnp.zeros((2, 4, width), jnp.bfloat16)
    assert not model.fused_ce_wins(pre(128))      # CPU: the XLA path
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert model.fused_ce_wins(pre(128))
    assert model.fused_ce_wins(pre(model.FUSED_CE_MAX_WIDTH))
    assert not model.fused_ce_wins(pre(1024))


def test_client_loss_fused_matches_chunked(monkeypatch):
    """``client_loss`` through the fused kernel (interpret mode) against
    the chunked XLA path: bf16 parameters, three clients vmapped, a
    vocabulary that is no multiple of the vocab block; the loss and the
    gradients of the aux head and of the client stage agree to bf16."""
    from repro.configs.registry import get_config
    from repro.models import model
    from repro.models.blocks import Ctx
    cfg = get_config("qwen3-0.6b").reduced().with_(
        d_model=64, num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=2100,
        aux_rank=16, dtype="bfloat16")
    n, b, s = 3, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(5), n)
    params = jax.vmap(lambda k: model.init_params(cfg, k))(keys)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (n, b, s), 0,
                                cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=-1)
    ctx = Ctx(cfg, "train")
    bv = ops._ce_blocks(b * s, cfg.aux_rank, cfg.vocab_size)[1]
    assert bv < cfg.vocab_size and cfg.vocab_size % bv   # a ragged last block

    def loss_and_grads():
        def one(cp, ap, tok, lab):
            return jax.value_and_grad(
                lambda c, a: model.client_loss(cfg, c, a, {"tokens": tok},
                                               lab, ctx)[0],
                argnums=(0, 1))(cp, ap)
        return jax.jit(jax.vmap(one))(params["client"], params["aux"],
                                      tokens, labels)

    chunked = loss_and_grads()
    monkeypatch.setattr(model, "fused_ce_wins",
                        lambda pre: pre.shape[-1] <= model.FUSED_CE_MAX_WIDTH)
    fused = loss_and_grads()
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(chunked[0]),
                               rtol=1e-2)
    (fc, fa), (cc, ca) = fused[1], chunked[1]
    assert set(fa) == {"ln", "down", "up"}
    leaves = {**{f"aux/{k}": (fa[k], ca[k]) for k in fa},
              "client/embed": (fc["embed"], cc["embed"]),
              "client/blocks": (jax.tree_util.tree_leaves(fc["blocks_stage"]),
                                jax.tree_util.tree_leaves(cc["blocks_stage"]))}
    for name, (got, want) in leaves.items():
        got = jnp.concatenate([jnp.ravel(g) for g in jax.tree.leaves(got)])
        want = jnp.concatenate([jnp.ravel(g) for g in jax.tree.leaves(want)])
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert got.shape == want.shape
        rel = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert rel < 2e-2, (name, rel)


# ---------------------------------------------------------------------------
# mamba-1 selective scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,d,n", [(1, 16, 8, 4), (2, 64, 32, 16),
                                     (2, 128, 64, 16), (1, 32, 128, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_matches_ref(b, s, d, n, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    u = _rand(ks[0], (b, s, d), dtype)
    dt = jax.nn.softplus(_rand(ks[1], (b, s, d), jnp.float32)) * 0.1
    a = -jnp.exp(_rand(ks[2], (d, n), jnp.float32) * 0.3)
    b_mat = _rand(ks[3], (b, s, n), jnp.float32)
    c_mat = _rand(ks[4], (b, s, n), jnp.float32)
    d_vec = _rand(ks[5], (d,), jnp.float32)
    got = ssm_mod.ssm_scan(u, dt.astype(dtype), a, b_mat.astype(dtype),
                           c_mat.astype(dtype), d_vec,
                           bd=min(128, d), chunk=min(128, s), interpret=True)
    want = ref.ssm_scan(u, dt, a, b_mat, c_mat, d_vec)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_ssm_scan_chunk_boundary_state_carry():
    """The VMEM state must carry across sequence chunks (grid minor axis)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    b, s, d, n = 1, 64, 8, 4
    u = _rand(ks[0], (b, s, d), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, s, d), jnp.float32)) * 0.2
    a = -jnp.exp(_rand(ks[2], (d, n), jnp.float32) * 0.3)
    b_mat = _rand(ks[3], (b, s, n), jnp.float32)
    c_mat = _rand(ks[4], (b, s, n), jnp.float32)
    d_vec = jnp.zeros((d,), jnp.float32)
    # chunk=16 -> 4 chunks; identical result to single-chunk run
    got = ssm_mod.ssm_scan(u, dt, a, b_mat, c_mat, d_vec, bd=8, chunk=16,
                           interpret=True)
    want = ssm_mod.ssm_scan(u, dt, a, b_mat, c_mat, d_vec, bd=8, chunk=64,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_ssm_ops_gradient_matches_reference_scan():
    from repro.models.layers import selective_scan
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    b, s, d, n = 1, 32, 16, 8
    u = _rand(ks[0], (b, s, d), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, s, d), jnp.float32)) * 0.1
    a = -jnp.exp(_rand(ks[2], (d, n), jnp.float32) * 0.3)
    b_mat = _rand(ks[3], (b, s, n), jnp.float32)
    c_mat = _rand(ks[4], (b, s, n), jnp.float32)
    d_vec = _rand(ks[5], (d,), jnp.float32)

    f_ops = lambda u_: jnp.sum(ops.ssm_scan(u_, dt, a, b_mat, c_mat, d_vec, 16))
    f_ref = lambda u_: jnp.sum(selective_scan(u_, dt, a, b_mat, c_mat, d_vec,
                                              chunk=16))
    np.testing.assert_allclose(np.asarray(jax.grad(f_ops)(u)),
                               np.asarray(jax.grad(f_ref)(u)),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# sliding-window attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,s,h,kh,hd,window", [
    (1, 128, 2, 2, 16, 32),
    (1, 256, 4, 2, 32, 64),     # GQA 2:1
    (2, 128, 4, 1, 16, 128),    # GQA 4:1, window == bk
    (1, 256, 2, 2, 64, 200),    # window not a multiple of bk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_swa_attention_matches_ref(b, s, h, kh, hd, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = _rand(ks[0], (b, s, h, hd), dtype)
    k = _rand(ks[1], (b, s, kh, hd), dtype)
    v = _rand(ks[2], (b, s, kh, hd), dtype)
    got = swa_mod.swa_attention(q, k, v, window=window, bq=64, bk=64,
                                interpret=True)
    want = ref.swa_attention(q, k, v, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_swa_matches_dense_attention_when_window_covers_seq():
    """window >= s: sliding-window == plain causal attention."""
    from repro.models.layers import attention
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    b, s, h, hd = 1, 128, 2, 32
    q = _rand(ks[0], (b, s, h, hd), jnp.float32)
    k = _rand(ks[1], (b, s, h, hd), jnp.float32)
    v = _rand(ks[2], (b, s, h, hd), jnp.float32)
    got = swa_mod.swa_attention(q, k, v, window=s, bq=64, bk=64,
                                interpret=True)
    want = attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# per-tile quantize: grid over groups of row tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("shape", [(1, 130), (5, 1024), (64, 256),
                                   (70, 300), (131, 129)])
def test_quantize_caller_bits_bitwise_on_ragged_shapes(fmt, shape):
    """The kernel visits row tiles eight at a time and writes their scales
    into one resident block per group.  On a single row tile, on exactly
    one group, and on ragged multi-group shapes the caller-bits path stays
    bitwise equal to the oracle, and the scales keep their
    [ceil(R/8), ceil(C/128)] wire shape."""
    from repro.kernels import quantize as qk
    x = jax.random.normal(jax.random.PRNGKey(4), shape, jnp.float32) * 3
    x = x.at[0, 0].set(50.0)                 # one outlier tile
    bits = jax.random.bits(jax.random.PRNGKey(5), shape, jnp.uint32)
    qa, sa = qk.quantize_2d(x, bits, fmt=fmt)
    qb, sb = ref.quantize_2d(x, bits, fmt=fmt)
    assert sa.shape == (-(-shape[0] // 8), -(-shape[1] // 128))
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))
    np.testing.assert_array_equal(np.asarray(qa, np.float32),
                                  np.asarray(qb, np.float32))
