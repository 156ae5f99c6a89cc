"""The chunk program names its stages (``repro.core.stages``).

Each stage is a ``jax.named_scope`` placed where its work is; the name
reaches every op's ``op_name`` metadata, backward ops included, and a
trace reduction takes the innermost stage of an op's name path.  These
tests compile the benchmark's client-heavy chunk program at tiny widths
(cse_fsl, n=4, h=8, batches gathered from the device pool) and read the
op names XLA keeps, matched as the benchmark's trace reduction matches
them (``bench/stages.py`` ``stage_of``).
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.contracts import _fingerprint_jaxpr, find_callbacks
from repro.configs.base import FSLConfig, ModelConfig
from repro.core.bundle import transformer_bundle
from repro.core.stages import (AUX_CE, CLIENT_STEP, CLIENT_UPDATE, FEDAVG,
                               SERVER_CONSUME, STAGES)
from repro.core.trainer import Trainer
from repro.telemetry import Telemetry

TINY = ModelConfig(name="tiny_dense", family="dense", num_layers=2,
                   d_model=32, num_heads=2, num_kv_heads=1, head_dim=16,
                   d_ff=64, vocab_size=128, qk_norm=True, qkv_bias=False,
                   rope_theta=1e6, cut_layer=1, aux_rank=8,
                   dtype="bfloat16", remat=True, swa_window=0)
N, H, B, S, POOL, ROUNDS = 4, 8, 2, 16, 64, 2
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)

from stages import stage_of  # noqa: E402  (the benchmark's reduction)


def _trainer(telemetry=None):
    fsl = FSLConfig(num_clients=N, h=H, lr=0.1, method="cse_fsl",
                    codec="none")
    return Trainer(transformer_bundle(TINY), fsl, telemetry=telemetry)


def _specs(tr):
    tok = jax.ShapeDtypeStruct((POOL, S), jnp.int32)
    return (jax.eval_shape(tr.init), ({"tokens": tok}, tok),
            jax.ShapeDtypeStruct((ROUNDS, N, H, B), jnp.int32),
            jax.ShapeDtypeStruct((ROUNDS,), jnp.float32))


@pytest.fixture(scope="module")
def op_names():
    tr = _trainer()
    text = tr.pool_chunk_fn.lower(*_specs(tr)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("stage", STAGES)
def test_stage_is_named_in_the_chunk_program(op_names, stage):
    paths = [n for n in op_names if stage_of(n) == stage]
    assert paths, f"no op of the chunk program sits under {stage!r}"
    backward = [n for n in paths if "transpose(" in n]
    if stage in (CLIENT_STEP, AUX_CE, SERVER_CONSUME):
        # the backward pass carries the scope through the name stack
        assert backward, f"no backward op under {stage!r}"
    if stage in (AUX_CE, CLIENT_UPDATE):
        # carved out of the client step that encloses them
        assert any(CLIENT_STEP in n.split(stage)[0] for n in paths)
    if stage == FEDAVG:
        assert all("cond" in n for n in paths)


def test_scopes_leave_the_program_unchanged_by_telemetry():
    """Rule T001 on this program: the chunk traces to the same jaxpr with
    telemetry on and off, and holds no host callback."""
    on, off = _trainer(Telemetry()), _trainer()
    jaxpr_on = jax.make_jaxpr(on.pool_chunk_fn)(*_specs(on))
    jaxpr_off = jax.make_jaxpr(off.pool_chunk_fn)(*_specs(off))
    assert not find_callbacks(jaxpr_on)
    assert _fingerprint_jaxpr(jaxpr_on) == _fingerprint_jaxpr(jaxpr_off)
