"""The block-sparse flash attention path (``kernels.ops.flash_attention``,
JAX's Pallas splash attention kernels, interpret mode here) against the
XLA path of ``models.layers.attention``, the rule that picks between
them, and the trace-time tally that counts which one a program took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, NamedSharding, PartitionSpec

from repro.configs.base import FSLConfig
from repro.configs.registry import get_config
from repro.core.bundle import transformer_bundle
from repro.core.trainer import Trainer
from repro.kernels import ops
from repro.models import layers as L
from repro.telemetry import Telemetry
from repro.telemetry import attention as tally


@pytest.fixture(autouse=True)
def _tallied():
    tally.install()


def _rel_max(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


def _block_pairs(s, block, window):
    """(q-block, kv-block) pairs holding any unmasked score, from the
    dense causal (and window) mask."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    keep = (j <= i) & ((j > i - window) if window else True)
    nb = s // block
    return int(keep.reshape(nb, block, nb, block).any(axis=(1, 3)).sum())


@pytest.mark.parametrize("heads", [(8, 1), (2, 2)], ids=["gqa", "mha"])
@pytest.mark.parametrize("window", [160, 0], ids=["sliding", "full"])
def test_flash_attention_matches_the_xla_path(window, heads):
    """S 512 in 128-blocks, bf16, B 2: the kernel's value and the
    gradients of q, k and v agree with ``layers.attention`` to bf16's
    rounding.  A window of 160 leaves partial blocks on both sides of the
    band; the tally counts one kernel call and the block pairs the dense
    mask holds."""
    h, kh = heads
    b, s, hd, block = 2, 512, 128, 128
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, s, kh, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, s, kh, hd)), jnp.bfloat16)
    ct = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)

    def through(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)
        return fn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    flash = lambda q, k, v: ops.flash_attention(q, k, v, window,
                                                 block=block)
    before = tally.snapshot()
    flash(q, k, v)
    counted = tally.snapshot() - before
    kernel = through(flash)
    xla = through(lambda q, k, v: L.attention(q, k, v, causal=True,
                                              window=window, chunk=128))
    for got, want in zip(jax.tree_util.tree_leaves(kernel),
                         jax.tree_util.tree_leaves(xla)):
        assert got.dtype == want.dtype == jnp.bfloat16
        assert _rel_max(got, want) < 0.02
    assert counted.kernel == 1 and counted.xla == 0
    assert counted.grid_pairs == (s // block) ** 2
    assert counted.pairs == _block_pairs(s, block, window)


def test_flash_attention_block_share_at_mellum_shapes():
    """S 4,096 in 512-blocks: a 1,024-key window computes 21 of the 64
    block pairs, full causal attention 36 (read from the kernel's mask,
    not run)."""
    shares = [ops.flash_block_pairs(ops._flash_kernel(8, 4096, w, 512, True),
                                    4096, 512) for w in (1024, 0)]
    assert shares == [(21, 64), (36, 64)]
    assert [_block_pairs(4096, 512, w) for w in (1024, 0)] == [21, 36]


def _engages(q_shape, kv_len=None, q_offset=0, causal=True, sharding=None):
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=sharding)
    got = {}

    def probe(q):
        got["kernel"] = ops.flash_attention_engages(
            q, q, causal=causal, kv_len=kv_len, q_offset=q_offset,
            chunk=min(512, max(128, L.SCORES_PER_CHUNK // q.shape[1])))
        return q
    jax.eval_shape(probe, q)
    return got["kernel"]


def test_engagement_rule(monkeypatch):
    """XLA off a TPU; with the backend probe on the chip's branch, the
    kernel for causal training longer than one q-chunk, and XLA for
    decode (``kv_len``), an offset prefill chunk, one q-chunk or less
    (S 512: the qwen cells), a length off the kernel's block, a
    non-causal encoder, and a program a mesh partitions."""
    train = (2, 4096, 8, 128)
    assert not _engages(train)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert _engages(train)
    assert _engages((2, 1024, 8, 128))
    assert not _engages(train, kv_len=jnp.int32(7))
    assert not _engages(train, q_offset=512)
    assert not _engages((2, 512, 8, 128))
    assert not _engages((2, 1280, 8, 128))
    assert not _engages(train, causal=False)
    mesh = AbstractMesh((4,), ("data",), axis_types=(AxisType.Auto,))
    assert not _engages(train, sharding=NamedSharding(
        mesh, PartitionSpec("data")))


def test_attention_routes_and_tallies_each_path(monkeypatch):
    """``layers.attention`` runs the kernel where the rule holds (here
    with ``fused_ce_native`` steered, the kernel in interpret mode) and
    XLA elsewhere, and the tally names the path of each call."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 1024, 2, 128)), jnp.float32)
    kv = q[:, :, :1]
    before = tally.snapshot()
    want = L.attention(q, kv, kv, causal=True, window=300)
    assert (tally.snapshot() - before).xla == 1
    monkeypatch.setattr(ops, "fused_ce_native", lambda x: True)
    before = tally.snapshot()
    got = L.attention(q, kv, kv, causal=True, window=300)
    L.attention(q[:, :1], kv, kv, causal=False, q_offset=5, kv_len=6)
    counted = tally.snapshot() - before
    assert (counted.kernel, counted.xla) == (1, 1)
    assert counted.pairs == _block_pairs(1024, 512, 300) == 3
    assert _rel_max(got, want) < 1e-5


def _mellum_chunk_trace(s):
    """Trace (not run) the reduced Mellum2 chunk program at S ``s``; the
    attention tally of that trace."""
    cfg = get_config("mellum2-12b-a2.5b").reduced()
    n, h, b, pool = 2, 1, 1, 8
    tr = Trainer(transformer_bundle(cfg),
                 FSLConfig(num_clients=n, h=h, lr=0.05))
    state = jax.eval_shape(tr.init)
    tokens = jax.ShapeDtypeStruct((pool, s), jnp.int32)
    before = tally.snapshot()
    tr.pool_chunk_fn.trace(state, ({"tokens": tokens}, tokens),
                           jax.ShapeDtypeStruct((1, n, h, b), jnp.int32),
                           jax.ShapeDtypeStruct((1,), jnp.float32))
    return tally.snapshot() - before


def test_mellum_chunk_program_traces_every_attention_call_through_the_kernel(
        monkeypatch):
    """With the probe saying the kernels run compiled, the chunk program
    of a model with sliding and full layers traces no attention call
    through XLA at S 1,024; at S 512 (one q-chunk) none through the
    kernel."""
    monkeypatch.setattr(ops, "fused_ce_native", lambda x: True)
    long = _mellum_chunk_trace(1024)
    assert long.kernel > 0 and long.xla == 0
    assert 0 < long.block_share < 1
    short = _mellum_chunk_trace(512)
    assert short.kernel == 0 and short.xla > 0


def test_telemetry_reports_the_attention_tally_with_the_compile_counts():
    tele = Telemetry()
    q = jnp.zeros((1, 64, 2, 8), jnp.float32)
    jax.jit(lambda q: L.attention(q, q, q, causal=True)).lower(q)
    tele.note_compiles()
    assert tele.counters[("attention_traced_total", (("path", "xla"),))] >= 1
    assert tele.counters[("attention_traced_total",
                          (("path", "kernel"),))] == 0
    assert tele.gauges[("attention_block_share", ())] == 0.0
    assert tele.attention_tally().xla >= 1
    assert 'repro_attention_traced_total{path="kernel"} 0' in \
        tele.prometheus_text()
