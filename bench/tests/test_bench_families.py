"""What a configuration's family and a mix's codec bring to a cell, on the
CPU at a tiny size: the family's own reference, found by name; the
reference int8 codec (``bench/codecs/int8.py``); the quantize kernel's bytes and
its roofline share from a hand-made trace; the numbers that hold the
program's codec to it; and tiny cells with an int8 uplink and with QKV
bias, run end to end against their reference and its fp8 control.
"""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import codec  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402
from test_bench_harness import TINY_CELL, _tiny_checkout  # noqa: E402

INT8 = codec.module("int8")
QUANTIZE_ROOFLINE = harness.load_module(
    os.path.join(BENCH, "metrics", "quantize_roofline.py"),
    "test_quantize_roofline")

INT8_CELL = "tiny.tiny-int8"
BIAS_CELL = "tiny-bias.tiny-mix"
PROBE_CELL = "tiny-probe.tiny-mix"
BARE_CELL = "tiny-bare.tiny-mix"

# the probe family: the dense family under another name, whose reference
# counts its calls; a later family arrives as such files alone
PROBE_FAMILY = '''
import importlib.util, os
_spec = importlib.util.spec_from_file_location(
    "probe_dense", os.path.join(os.path.dirname(__file__), "dense.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)
numbers, program_config = _dense.numbers, _dense.program_config
CALLS = []


def reference(numbers, mix, precision="fp32"):
    CALLS.append((mix["codec"], precision))
    return _dense.reference(numbers, mix, precision)
'''


def _write(root, path, obj):
    with open(os.path.join(root, path), "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def _families_checkout(dst):
    """The tiny checkout of ``test_bench_harness`` plus, by files alone, an
    int8 mix, a QKV-bias configuration, the probe family and a family
    module that brings no reference."""
    root = _tiny_checkout(dst)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(root, "bench", "configs",
                                      "tiny.json")))
    mix = json.load(open(os.path.join(root, "bench", "traffic",
                                      "tiny-mix.json")))
    bias = dict(cfg, name="tiny-bias",
                assumed=dict(cfg["assumed"], qk_norm=False, qkv_bias=True))
    probe = dict(cfg, name="tiny-probe", family="probe")
    bare = dict(cfg, name="tiny-bare", family="bare")
    # CPU readings over seeds 1-8 and 2**31 + 12345.  The int8 cell
    # (B=4, S=64, so the two sides' independent rounding noise averages
    # over more tokens): the program's loss_gap 0.00057-0.00196, the fp8
    # control's 0.00446-0.01528; at B=2, S=32 the two overlapped
    # (0.00388 against 0.00705).  The bias cell: the program's loss_gap
    # 0.00049-0.00198, the control's 0.00811-0.02981; delta_gap of
    # half_batch at least 0.35, of altered at least 0.14.  The int8
    # cell's codec_gap: the program 0.0046-0.0153, codec_nearest
    # 0.281-0.290, codec_skipped 1.
    limits = {"limits": {"loss_gap": 0.004, "delta_gap": 0.1}}
    files = {
        "bench/traffic/tiny-int8.json": dict(mix, h=1, codec="int8",
                                             batch=4, seq=64,
                                             samples_per_client=16),
        f"bench/workloads/{INT8_CELL}.json": {
            "limits": {"loss_gap": 0.003, "delta_gap": 0.1,
                       "codec_gap": 0.05, "codec_missing": 0.5}},
        "bench/configs/tiny-bias.json": bias,
        "bench/configs/tiny-probe.json": probe,
        "bench/configs/tiny-bare.json": bare,
        "bench/models/probe.py": PROBE_FAMILY,
        "bench/models/bare.py": "def numbers(config):\n    return {}\n",
        f"bench/workloads/{BIAS_CELL}.json": limits,
        f"bench/workloads/{PROBE_CELL}.json": limits,
        f"bench/workloads/{BARE_CELL}.json": limits,
    }
    for path, obj in files.items():
        _write(root, path, obj)
    for fam in ("probe", "bare"):
        _write(root, f"bench/flops/{fam}.py",
               open(os.path.join(BENCH, "flops", "dense.py")).read())
    for name, c in (("tiny-bias", bias), ("tiny-probe", probe),
                    ("tiny-bare", bare)):
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for cell, config, traffic in ((INT8_CELL, "tiny", "tiny-int8"),
                                  (BIAS_CELL, "tiny-bias", "tiny-mix"),
                                  (PROBE_CELL, "tiny-probe", "tiny-mix"),
                                  (BARE_CELL, "tiny-bare", "tiny-mix")):
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "test"})
    _write(root, "BENCHMARK.json", bench)
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    import repro.common
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.common, "enable_compile_cache", lambda: None)
        yield _families_checkout(str(tmp_path_factory.mktemp("families")))


def _run_cell(root, cell, seed):
    args = harness.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", "0.2", "--trace", "0"])
    return harness.run(args, 0.0, root, require_tpu=False)


# ---------------------------------------------------------------------------
# The family's reference, found by name
# ---------------------------------------------------------------------------


def test_new_family_brings_its_own_reference(checkout):
    cell = harness.load_cell(checkout, PROBE_CELL)
    assert cell.family.CALLS == []
    abstract = harness.Program(cell).abstract
    harness.run_reference(cell, 5, abstract)
    harness.run_reference(cell, 5, abstract, precision="fp8")
    assert cell.family.CALLS == [("none", "fp32"), ("none", "fp8")]


def test_family_without_reference_is_refused(checkout):
    with pytest.raises(harness.BenchError, match="no reference"):
        harness.load_cell(checkout, BARE_CELL)


def test_dense_reference_through_the_seam_is_the_plain_one(checkout):
    """The cell's family hands ``run_reference`` the dense ``Reference``
    itself: the same losses and change norms, bit for bit, as calling it
    directly on the seed's weights and traffic."""
    import jax
    import reference
    import traffic
    import weights
    cell = harness.load_cell(checkout, TINY_CELL)
    abstract = harness.Program(cell).abstract
    losses, d = harness.run_reference(cell, 11, abstract)
    tr = traffic.Traffic(cell.mix, cell.numbers["vocab"], 11)
    ref = reference.Reference(cell.numbers, cell.mix)
    params, direct = ref.run(weights.make_params(abstract, 11), tr,
                             cell.mix["rounds_per_chunk"])
    assert losses == direct
    assert d == harness.deltas(jax.tree_util.tree_map(lambda a: a[None],
                                                      params),
                               weights.make_params(abstract, 11))


def test_reference_refuses_a_codec_it_cannot_follow(checkout):
    cell = harness.load_cell(checkout, TINY_CELL)
    with pytest.raises(ValueError, match="no reference for the uplink"):
        cell.family.reference(cell.numbers, dict(cell.mix, codec="topk"))
    # and a cell whose mix names such a codec is refused before any run
    mix = os.path.join(checkout, "bench", "traffic", "tiny-mix.json")
    kept = open(mix).read()
    try:
        _write(checkout, "bench/traffic/tiny-mix.json",
               dict(cell.mix, codec="topk"))
        with pytest.raises(harness.BenchError, match="codecs/topk.py"):
            harness.load_cell(checkout, TINY_CELL)
    finally:
        _write(checkout, "bench/traffic/tiny-mix.json", kept)


# ---------------------------------------------------------------------------
# The reference int8 codec
# ---------------------------------------------------------------------------


def _payload(seed, shape=(2, 24, 200)):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=shape).astype(np.float32)
    x.reshape(-1)[3 * shape[-1] + 7] = 40.0     # one outlier in one tile
    return x


def test_int8_codec_scales_per_tile():
    import jax
    x = _payload(0)
    q, scales = INT8.encode(x, jax.random.PRNGKey(0))
    x2 = x.reshape(-1, 200)
    assert scales.shape == codec.tiles(48, 200) == (6, 2)
    pad = np.zeros((48, 256), np.float32)
    pad[:, :200] = x2
    absmax = np.abs(pad.reshape(6, 8, 2, 128)).max(axis=(1, 3))
    np.testing.assert_allclose(np.asarray(scales), absmax / 127, rtol=1e-6)
    # the outlier sets only its own tile's scale
    assert float(scales[0, 0]) == pytest.approx(40.0 / 127)
    assert float(scales[1:, :].max()) < 6.0 / 127
    q = np.asarray(q)
    assert np.array_equal(q, np.round(q)) and np.abs(q).max() <= 127


def test_int8_codec_error_is_under_one_step():
    import jax
    x = _payload(1)
    y = np.asarray(INT8.roundtrip(x, jax.random.PRNGKey(3)))
    _, scales = INT8.encode(x, jax.random.PRNGKey(3))
    step = np.repeat(np.repeat(np.asarray(scales), 8, 0), 128, 1)[:48, :200]
    err = np.abs(y - x).reshape(48, 200)
    assert (err < step * (1 + 1e-6)).all()
    assert y.shape == x.shape and y.dtype == np.float32


def test_int8_codec_is_unbiased():
    """The mean of many round trips of one payload is the payload, within
    a few standard errors: each element's error is uniform-ish within one
    step, so 2,000 draws leave a standard error under 0.012 steps."""
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(_payload(2, (16, 256)))
    keys = jax.random.split(jax.random.PRNGKey(5), 2000)
    mean = jax.jit(lambda x: jnp.mean(jax.vmap(
        lambda k: INT8.roundtrip(x, k))(keys), axis=0))(x)
    _, scales = INT8.encode(x, keys[0])
    step = np.repeat(np.repeat(np.asarray(scales), 8, 0), 128, 1)
    bias = np.abs(np.asarray(mean) - np.asarray(x)) / step
    assert bias.max() < 0.06 and bias.mean() < 0.015
    # without the random offset the rounding would be biased: floor
    # alone is off by half a step on average
    floor = jnp.floor(x / jnp.asarray(step)) * jnp.asarray(step)
    assert float(jnp.mean(jnp.abs(floor - x) / step)) > 0.4


def test_uplink_codecs_by_name():
    import jax
    x = _payload(4)
    assert np.array_equal(codec.uplink("none")(x, None), x)
    y = codec.uplink("int8")(x, codec.upload_key(2 ** 31 + 7, 1, 3))
    assert y.shape == x.shape and not np.array_equal(np.asarray(y), x)
    a = codec.upload_key(9, 0, 1)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(codec.upload_key(9, 1, 0)))
    with pytest.raises(ValueError, match="no reference for the uplink "
                                         "codec 'fp8'"):
        codec.uplink("fp8")


def test_uplink_decodes_to_the_type_asked():
    # codec_gap compares received uploads in the model's type; the cast
    # is part of the jitted round trip, so its result leaves the program
    # rounded to that type
    import jax.numpy as jnp
    x = _payload(4)
    key = codec.upload_key(5, 2, 1)
    y = codec.uplink("int8", jnp.bfloat16)(x, key)
    assert y.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(y, np.float32), np.asarray(
        codec.uplink("int8")(x, key).astype(jnp.bfloat16), np.float32))


# ---------------------------------------------------------------------------
# The quantize kernel's bytes and roofline share
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,dtype", [((1024, 1024), "bfloat16"),
                                         ((100, 200), "float32")])
def test_quantize_bytes_are_the_kernels_operands(shape, dtype):
    """The byte count against the operands of the ``pallas_call`` that
    ``quantize_2d`` makes: its padded payload in, int8 values and float32
    scales out (the caller-bits path adds a bits operand, which the
    in-kernel PRNG the chip runs does not have)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import quantize as qk
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    bits = jax.ShapeDtypeStruct(shape, jnp.uint32)
    jaxpr = jax.make_jaxpr(lambda x, b: qk.quantize_2d(
        x, b, interpret=True))(x, bits)
    call, = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    nbytes = lambda v: int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
    payload, bits_in = call.invars
    want = nbytes(payload) + sum(nbytes(v) for v in call.outvars)
    assert QUANTIZE_ROOFLINE.quantize_bytes(
        *shape, jnp.dtype(dtype).itemsize) == want
    if shape == (1024, 1024):
        # one upload of the int8 cell: 2 MiB read, 1 MiB and 4 KiB written
        assert want == 2 ** 21 + 2 ** 20 + 4096


def _kernel_trace():
    """Two ``quantize_2d`` calls as a vmapped kernel shows them, one as
    its custom call and one as the custom fusion XLA makes of a call that
    writes in place, inside the loop of the vmap; a reduce of the
    kernel's output under the same path; another op; another kernel."""
    dev = "/device:TPU:0"
    pallas = 'custom-call(%p), custom_call_target="tpu_custom_call"'
    loop = "jit(c)/while/body/vmap(quantize_2d)/while"
    ops = [("%while.7 = (s32[]) while(%t)", 1.0, 1.5),
           (f"%closed_call.1 = (s8[1024,1024]) {pallas}", 1.0, 1.00001),
           ("%closed_call.2 = (s8[2,1024,1024]) fusion(%a, %b), "
            "kind=kCustom, calls=%fused_computation.4", 1.2, 1.20001),
           ("%reduce.5 = f32[8] reduce(%c), dimensions={1}", 1.3, 1.31),
           ("%fusion.3 = bf16[8] fusion(%a), kind=kLoop", 1.6, 1.7),
           (f"%custom-call.9 = (f32[8]) {pallas}", 2.0, 2.5)]
    paths = [f"{loop}:",
             f"{loop}/body/closed_call/pallas_call:",
             f"{loop}/body/closed_call/pallas_call:",
             f"{loop}/body/closed_call/pallas_call:",
             "jit(c)/while/body/server_consume/add:",
             "jit(c)/while/body/client_step/jvp(aux_ce)/fused_ce_fwd/"
             "pallas_call:"]
    host = [("bench/chunk", 0.5, 3.0)]
    return {"ops": {dev: ops}, "modules": {dev: []}, "host": host,
            "paths": {dev: paths}}


def _roofline(cell, summary):
    ctx = harness.MetricContext(cell=cell, peaks={"hbm_bw": 819e9},
                                chips=1, summary=summary, rounds_traced=1,
                                meter={}, metered_rounds=0, state_bytes=0)
    only = dataclasses.replace(cell, per_layer=[
        {"name": "quantize_roofline", "unit": "%"}])
    return harness.per_layer_metrics(only, ctx)


def test_quantize_roofline_from_a_hand_made_trace(checkout):
    s = devtrace.summarize_events(_kernel_trace())
    # the two kernel calls, not the vmap's loop that holds them, the
    # reduce beside them, nor the other Pallas kernel
    assert s.kernel("quantize_2d") == (2, pytest.approx(2e-5))
    assert s.kernel("fused_ce_fwd") == (1, pytest.approx(0.5))
    cell = harness.load_cell(os.path.join(os.path.dirname(BENCH)),
                             "qwen3-0.6b.server-int8")
    got = _roofline(cell, s)["quantize_roofline"]
    per_call = 2 * 512 * 1024 * 3 + 4096
    assert got == {"value": pytest.approx(
        100 * 2 * per_call / 2e-5 / 819e9), "unit": "%"}
    assert 0 < got["value"] <= 100


def test_quantize_roofline_is_absent_without_the_kernel(checkout):
    ev = _kernel_trace()
    ev["paths"] = {d: [p.replace("quantize_2d", "other") for p in ps]
                   for d, ps in ev["paths"].items()}
    s = devtrace.summarize_events(ev)
    assert s.kernel("quantize_2d") == (0, 0)
    cell = harness.load_cell(os.path.dirname(BENCH),
                             "qwen3-0.6b.server-int8")
    assert _roofline(cell, s) == {}


# ---------------------------------------------------------------------------
# Tiny cells with an int8 uplink and with QKV bias, end to end
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", [INT8_CELL, BIAS_CELL])
def test_tiny_cell_is_correct_and_its_control_is_not(checkout, cell):
    res = _run_cell(checkout, cell, 2 ** 31 + 99)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2
    c = harness.load_cell(checkout, cell)
    abstract = harness.Program(c).abstract
    ref = harness.run_reference(c, 7, abstract)
    ok, checks = harness.checks_of(c, *harness.compare(
        *harness.run_reference(c, 7, abstract, precision="fp8"), *ref))
    assert not ok, checks


def test_bias_cell_moves_its_biases(checkout):
    cell = harness.load_cell(checkout, BIAS_CELL)
    assert cell.numbers["qkv_bias"] and not cell.numbers["qk_norm"]
    prog = harness.Program(cell).start(4)
    prog.chunk(None)
    d = prog.param_deltas()
    for leaf in ("bq", "bk", "bv"):
        moved = [k for k in d if k.endswith("/" + leaf)]
        assert moved and all(min(d[k]) > 0 for k in moved), moved


def test_int8_cell_bills_coded_uploads(checkout):
    """``uplink_mib_per_round`` of the int8 mix is n uploads of the
    codec's wire bytes: one int8 a value and a float32 scale a tile."""
    import jax
    import jax.numpy as jnp
    from repro.core.accounting import CommMeter
    from repro.transport import get_codec
    cell = harness.load_cell(checkout, INT8_CELL)
    prog = harness.Program(cell).start(3)
    meter = CommMeter()
    rounds = len(prog.chunk(meter))
    mix, d = cell.mix, cell.numbers["d"]
    rows = mix["batch"] * mix["seq"]
    upload = rows * d + 4 * np.prod(codec.tiles(rows, d))
    assert upload == get_codec("int8").wire_bytes(
        jax.ShapeDtypeStruct((mix["batch"], mix["seq"], d), jnp.bfloat16))
    assert meter.counts["uplink_smashed"] / rounds == \
        mix["clients"] * upload


def _quantize_calls(prog):
    """``quantize_2d`` kernels in the traced chunk program of ``prog``."""
    import jax
    tr = prog.trainer
    r = prog.rounds_per_chunk
    plan = np.stack([prog.batcher.next_round_indices() for _ in range(r)])
    pool = prog.batcher.device_pool()
    lrs = np.full((r,), prog.fsl.lr, np.float32)
    text = str(jax.make_jaxpr(tr.pool_chunk_fn)(prog.state, pool, plan, lrs))
    return text.count("name=quantize_2d")


def test_codec_skipped_fault_leaves_out_the_kernel(checkout, monkeypatch):
    """The fault keeps the codec's name and its billed bytes, so the
    uplink counter does not see it: the chunk program holds no quantize
    kernel, which on the chip leaves ``quantize_roofline`` absent and
    ``codec_missing`` at 1."""
    import faults
    cell = harness.load_cell(checkout, INT8_CELL)
    sound = harness.Program(cell).start(2)
    assert _quantize_calls(sound) >= 1
    monkeypatch.setattr(harness, "make_trainer",
                        faults.TRAINER_FAULTS["codec_skipped"])
    skipped = harness.Program(cell).start(2)
    assert skipped.trainer.transport.uplink.name == "int8"
    assert _quantize_calls(skipped) == 0


@pytest.mark.parametrize("fault", ["half_batch", "altered"])
def test_planted_fault_in_int8_cell_is_not_correct(checkout, monkeypatch,
                                                   fault):
    import faults
    monkeypatch.setattr(harness, "program_bundle",
                        faults.BUNDLE_FAULTS[fault])
    res = _run_cell(checkout, INT8_CELL, 7)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault,least", [("codec_skipped", 1.0),
                                         ("codec_nearest", 0.2)])
def test_codec_fault_in_int8_cell_is_not_correct(checkout, monkeypatch,
                                                 fault, least):
    """A skipped codec reads ``codec_gap`` 1 (the program's error is 0),
    a codec rounding to nearest ~0.29 (its error is sqrt(1/2) of the
    stochastic one's); the sound run reads under the limit."""
    import faults
    sound = _run_cell(checkout, INT8_CELL, 2 ** 31 + 5)
    assert sound["correct"], sound["checks"]
    assert sound["checks"]["codec_gap"]["value"] < 0.05
    assert "codec_missing" not in sound["checks"]     # no trace read
    monkeypatch.setattr(harness, "make_trainer",
                        faults.TRAINER_FAULTS[fault])
    res = _run_cell(checkout, INT8_CELL, 2 ** 31 + 5)
    assert not res["correct"], res["checks"]
    assert res["checks"]["codec_gap"]["value"] >= least


def test_codec_gap_of_the_identity_codec_is_zero(checkout):
    import jax.numpy as jnp
    from repro.transport import resolve_transport
    from repro.configs.base import FSLConfig
    cell = harness.load_cell(checkout, TINY_CELL)
    tp = resolve_transport(None, FSLConfig(num_clients=2, codec="none"))
    ups = [(0, 1, jnp.asarray(_payload(3)))]
    assert harness.codec_gap(cell, tp, ups, 3) == 0.0


def test_limits_only_on_numbers_a_run_reads(checkout):
    path = os.path.join(checkout, "bench", "workloads", f"{BIAS_CELL}.json")
    kept = open(path).read()
    try:
        _write(checkout, f"bench/workloads/{BIAS_CELL}.json",
               {"limits": {"delta_gap": 0.1, "loss_gapp": 0.1}})
        with pytest.raises(harness.BenchError, match="loss_gapp"):
            harness.load_cell(checkout, BIAS_CELL)
        # the identity codec runs no kernel that a trace could count
        _write(checkout, f"bench/workloads/{BIAS_CELL}.json",
               {"limits": {"delta_gap": 0.1, "codec_missing": 0.5}})
        with pytest.raises(harness.BenchError, match="no program kernel"):
            harness.load_cell(checkout, BIAS_CELL)
    finally:
        _write(checkout, f"bench/workloads/{BIAS_CELL}.json", kept)


# ---------------------------------------------------------------------------
# A trace recorded on a v5e: the tiny program of record_trace.py with the
# int8 uplink (``record_trace.py <out> --codec int8``), 2 chunks of 2 rounds
# ---------------------------------------------------------------------------

INT8_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "int8_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def int8_trace(tmp_path_factory):
    import gzip
    import shutil
    path = tmp_path_factory.mktemp("trace") / "int8_trace.xplane.pb"
    with gzip.open(INT8_TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.events(str(path))


def test_recorded_int8_trace_counts_each_kernel(int8_trace):
    """One ``quantize_2d`` call per upload: 2 clients x 2 rounds x 2
    chunks, each the custom fusion of the vmap's loop; the fused CE's
    two kernels once per client step (vmapped in one call over the 2
    clients, 2 steps a round) and once per server step (the server's
    64-wide LM head takes the fused CE too)."""
    s = devtrace.summarize_events(int8_trace)
    assert s.kernel("quantize_2d") == (8, pytest.approx(4.308e-06,
                                                        abs=1e-12))
    assert s.kernel("fused_ce_fwd") == (16, pytest.approx(1.1661e-05,
                                                          abs=1e-12))
    assert s.kernel("fused_ce_bwd") == (16, pytest.approx(8.86e-06,
                                                          abs=1e-12))
    assert len(s.kernels) == 5


def test_recorded_int8_trace_codec_missing(checkout, int8_trace):
    """Every upload of the recorded run (2 clients, 4 rounds) has its
    kernel call; a trace without them reads 1 and fails the limit."""
    import record_trace
    cell = harness.load_cell(checkout, INT8_CELL)
    cell = dataclasses.replace(cell, mix=dict(record_trace.MIX,
                                              codec="int8"))
    s = devtrace.summarize_events(int8_trace)
    assert harness.codec_missing(cell, s, 4) == 0.0
    ev = _kernel_trace()
    ev["paths"] = {d: [p.replace("quantize_2d", "other") for p in ps]
                   for d, ps in ev["paths"].items()}
    none = devtrace.summarize_events(ev)
    assert harness.codec_missing(cell, none, 4) == 1.0
    ok, checks = harness.checks_of(cell, 0.0, 0.0, codec_missing=1.0)
    assert not ok and checks["codec_missing"]["limit"] == 0.5


def test_recorded_int8_trace_quantize_roofline(int8_trace):
    """The reader on the recorded trace, for the recorded program's
    upload (B=1, S=32, d=64 at bfloat16: one 8 x 128 tile group padded
    to 64 x 128, 24,608 bytes a call)."""
    import types
    import record_trace
    mix = dict(record_trace.MIX, codec="int8")
    cell = harness.Cell(
        name="recorded", chips=1, config={"torch_dtype": "bfloat16"},
        mix=mix, limits={}, flops=None, end_to_end=[], per_layer=[],
        family=types.SimpleNamespace(numbers=lambda c: {"d": 64}),
        bench_dir=BENCH)
    got = _roofline(cell, devtrace.summarize_events(int8_trace))
    assert QUANTIZE_ROOFLINE.quantize_bytes(32, 64, 2) == 24608
    assert got["quantize_roofline"]["value"] == pytest.approx(
        100 * 8 * 24608 / 4.308e-06 / 819e9, rel=1e-6)
    assert 0 < got["quantize_roofline"]["value"] <= 100
