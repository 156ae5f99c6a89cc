"""Compile rehearsals for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with JAX, so each test here lowers and
compiles a kernel or the training chunk program at real widths for one
chip of a described ``v5e:2x2`` topology.  Nothing runs: these tests
catch what interpret mode hides (illegal block shapes, casts and PRNG
calls Mosaic refuses, programs that overflow the chip's 16 GiB) without
a chip.  The topology is described inside a fixture, never at import,
and the tests skip where it cannot be described.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels import fused_ce as ce_mod
from repro.kernels import ops
from repro.kernels import quantize as qk
from repro.kernels import ssm_scan as ssm_mod
from repro.kernels import swa_attention as swa_mod

HBM_BYTES = 16 * 2 ** 30                    # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but never read back here: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("path", ["inkernel_prng", "caller_bits"])
def test_quantize_compiles(one_chip, fmt, path):
    """The uplink codec kernel at a real 1024x1024 fp32 payload."""
    x = _spec((1024, 1024), jnp.float32, one_chip)
    if path == "inkernel_prng":
        fn = lambda x, s: qk.quantize_2d(x, seed=s, fmt=fmt, interpret=False)
        rnd = _spec((), jnp.int32, one_chip)
    else:
        fn = lambda x, b: qk.quantize_2d(x, b, fmt=fmt, interpret=False)
        rnd = _spec((1024, 1024), jnp.uint32, one_chip)
    compiled = _compile(fn, x, rnd)
    assert "quantize_2d" in compiled.as_text()


def test_fused_ce_forward_and_backward_compile(one_chip):
    """The LM-head cross entropy at qwen3's 151,936-token vocabulary and
    the server head's width, one token block."""
    t, d, v = 1024, 1024, 151_936
    bt, bv = ops._ce_blocks(t, d, v)
    x = _spec((t, d), jnp.bfloat16, one_chip)
    w = _spec((d, v), jnp.bfloat16, one_chip)
    lab = _spec((t,), jnp.int32, one_chip)
    lse = _spec((t,), jnp.float32, one_chip)
    fwd = _compile(lambda x, w: ce_mod.fused_ce_fwd(
        x, w, bt=bt, bv=bv, interpret=False), x, w)
    bwd = _compile(lambda x, w, lab, lse: ce_mod.fused_ce_bwd(
        x, w, lab, lse, 1.0 / t, bt=bt, bv=bv, interpret=False),
        x, w, lab, lse)
    assert "fused_ce_fwd" in fwd.as_text()
    assert "fused_ce_bwd" in bwd.as_text()


def test_fused_ce_aux_head_compiles_vmapped(one_chip, monkeypatch):
    """The aux head's CE as the chunk program runs it: rank 128, T = 1,024
    tokens, 151,936 classes, bf16, value and gradient vmapped over 4
    clients (a grid axis), through ``ops.fused_ce`` with the backend
    probe steered to the chip's branch."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, t, d, v = 4, 1024, 128, 151_936
    assert ops._ce_blocks(t, d, v)[0] == t          # one token block
    fn = jax.vmap(jax.value_and_grad(ops.fused_ce, argnums=(0, 1)))
    text = _compile(fn, _spec((n, t, d), jnp.bfloat16, one_chip),
                    _spec((n, d, v), jnp.bfloat16, one_chip),
                    _spec((n, t), jnp.int32, one_chip)).as_text()
    assert "fused_ce_fwd" in text and "fused_ce_bwd" in text
    assert "fused_ce_bwd_dx" not in text            # the single-kernel path


def test_fused_ce_two_kernel_backward_compiles(one_chip):
    """Too many tokens for one VMEM block: the dx and dw kernels."""
    t, d, v = 16_384, 128, 151_936
    bt, bv = ops._ce_blocks(t, d, v)
    assert bt < t
    text = _compile(lambda x, w, lab, lse: ce_mod.fused_ce_bwd(
        x, w, lab, lse, 1.0 / t, bt=bt, bv=bv, interpret=False),
        _spec((t, d), jnp.bfloat16, one_chip),
        _spec((d, v), jnp.bfloat16, one_chip),
        _spec((t,), jnp.int32, one_chip),
        _spec((t,), jnp.float32, one_chip)).as_text()
    assert "fused_ce_bwd_dx" in text and "fused_ce_bwd_dw" in text


def test_ssm_scan_compiles(one_chip):
    """Mamba-1 scan at d_inner 8192, state 16, 1024 steps."""
    b, s, d, n = 1, 1024, 8192, 16
    f32 = jnp.float32
    args = [_spec(sh, f32, one_chip) for sh in
            ((b, s, d), (b, s, d), (d, n), (b, s, n), (b, s, n), (d,))]
    compiled = _compile(lambda *a: ssm_mod.ssm_scan(*a, chunk=128,
                                                    interpret=False), *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_swa_attention_compiles(one_chip):
    """Sliding-window attention: 8192 tokens, 16 heads of 128, window
    4096."""
    q = _spec((1, 8192, 16, 128), jnp.bfloat16, one_chip)
    compiled = _compile(lambda q, k, v: swa_mod.swa_attention(
        q, k, v, window=4096, interpret=False), q, q, q)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_full_width_chunk_program_fits_one_chip(one_chip, monkeypatch,
                                                codec):
    """The donated 2-round chunk program that ``chip_smoke.py`` runs:
    qwen3-0.6b at full width, cse_fsl, 4 clients, h=2, B=8, S=128,
    batches gathered in-scan from the device pool.  The aux head's CE
    runs the fused kernels in both codecs; the int8 uplink carries the
    quantize kernel on its in-kernel PRNG path, as on the chip."""
    from repro.configs.base import FSLConfig
    from repro.configs.registry import get_config
    from repro.core.bundle import transformer_bundle
    from repro.core.trainer import Trainer
    # steer the backend probes to the chip's branch (this process sees a
    # CPU): native kernels, seed-driven randomness, the aux head's fused CE
    monkeypatch.setattr(qk, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, h, b, s, pool = 4, 2, 8, 128, 2048
    fsl = FSLConfig(num_clients=n, h=h, lr=0.1, method="cse_fsl",
                    codec=codec)
    tr = Trainer(transformer_bundle(get_config("qwen3-0.6b")), fsl)
    place = lambda x: _spec(x.shape, x.dtype, one_chip)
    state = jax.tree_util.tree_map(place, jax.eval_shape(tr.init))
    tokens = _spec((pool, s), jnp.int32, one_chip)
    compiled = tr.pool_chunk_fn.lower(
        state, ({"tokens": tokens}, tokens),
        _spec((2, n, h, b), jnp.int32, one_chip),
        _spec((2,), jnp.float32, one_chip)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB > 16 GiB"
    text = compiled.as_text()
    assert "fused_ce_fwd" in text and "fused_ce_bwd" in text
    assert ("quantize_2d" in text) == (codec == "int8")


def test_sharded_cohort_chunk_keeps_the_xla_ce(topo, monkeypatch):
    """The cohort engine's ``--mesh host`` chunk program on all four chips
    of the described topology, 8 clients split over "data" (reduced
    qwen3-0.6b).  XLA cannot partition a Mosaic kernel, so the aux head's
    CE keeps the XLA path in a sharded program: it compiles with the
    backend probe on the chip's branch, holds no fused CE kernel, and no
    chip gathers the other chips' stacked aux ``up``."""
    from repro.configs.base import FSLConfig
    from repro.configs.registry import get_config
    from repro.core.bundle import transformer_bundle
    from repro.core.trainer import Trainer
    from repro.sharding import state_specs
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    n, h, b, s, pool = 8, 2, 8, 128, 2048
    fsl = FSLConfig(num_clients=n, h=h, lr=0.1, method="cse_fsl")
    tr = Trainer(transformer_bundle(get_config("qwen3-0.6b").reduced()), fsl)
    abstract = jax.eval_shape(tr.init)
    state = jax.tree_util.tree_map(
        lambda x, spec: _spec(x.shape, x.dtype, NamedSharding(mesh, spec)),
        abstract, state_specs(abstract, mesh=mesh, fsdp_server=False))
    rep = NamedSharding(mesh, PartitionSpec())
    tokens = _spec((pool, s), jnp.int32, rep)
    text = tr.pool_chunk_fn.lower(
        state, ({"tokens": tokens}, tokens),
        _spec((2, n, h, b), jnp.int32, rep),
        _spec((2,), jnp.float32, rep)).compile().as_text()
    assert "fused_ce" not in text
    up = ",".join(map(str, abstract["clients"]["params"]["aux"]["up"].shape))
    gathered = {shape for line in text.splitlines() if "all-gather" in line
                for shape in re.findall(r"\[([\d,]+)\]",
                                        line.split("all-gather")[0])}
    assert up not in gathered, f"the stacked aux up [{up}] is all-gathered"
