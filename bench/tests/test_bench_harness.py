"""The benchmark harness, driven end to end on the CPU at a tiny size.

A temporary checkout holds a copy of ``bench/``, the program's ``src``
and a ``BENCHMARK.json`` with one tiny cell added by files alone, as a
later change adds one.  The chip check is skipped by the caller
(``require_tpu=False``); everything else of a run happens: set-up, the
window, the reference, the comparison.
"""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

TINY_CELL = "tiny.tiny-mix"


def _tiny_checkout(dst):
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(BENCH, "configs", "qwen3-0.6b.json")))
    cfg.update(name="tiny", hidden_size=64, intermediate_size=128,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256)
    cfg["assumed"] = dict(cfg["assumed"], cut_layer=1, aux_rank=16)
    mix = dict(json.load(open(os.path.join(BENCH, "traffic",
                                           "client-h8.json"))),
               clients=2, h=2, seq=32, samples_per_client=8,
               rounds_per_chunk=2)
    files = {
        "bench/configs/tiny.json": cfg,
        "bench/traffic/tiny-mix.json": mix,
        # CPU readings at this size over seeds 1-8 and 2**31 + 12345: the
        # program's loss_gap at most 0.0016 and delta_gap 0.024; the fp8
        # control's loss_gap at least 0.0071; delta_gap of half_batch at
        # least 0.40, of altered at least 0.11, of unchanged 1
        f"bench/workloads/{TINY_CELL}.json": {
            "limits": {"loss_gap": 0.004, "delta_gap": 0.1}},
    }
    for path, obj in files.items():
        with open(os.path.join(dst, path), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(dst, "bench", "metrics", "probe.py"), "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "probe", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "tokens_per_s",
                               "workloads": [TINY_CELL]})
    bench["workloads"].append({"name": TINY_CELL + "-4chip", "config": "tiny",
                               "traffic": "tiny-mix", "chips": 4,
                               "why": "test"})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    # runs here leave JAX's process-wide compilation cache setting alone
    import repro.common
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.common, "enable_compile_cache", lambda: None)
        yield _tiny_checkout(str(tmp_path_factory.mktemp("checkout")))


def _run(root, seed=7):
    args = harness.parse(["--workload", TINY_CELL, "--seed", str(seed),
                          "--seconds", "0.2", "--trace", "0"])
    return harness.run(args, 0.0, root, require_tpu=False)


@pytest.fixture(scope="module")
def sound(tiny):
    return _run(tiny, seed=2 ** 31 + 12345)


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] >= 2


def test_last_line_keys(sound):
    assert list(sound) == ["correct", "attempted", "failed", "metrics",
                           "device", "checks"]
    assert set(sound["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                     "setup_s"}
    line = harness.result_line(True, 1, 0, {}, {}, {}, breakdown={})
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]


def test_wire_bytes_match_the_meter(tiny):
    """The wire metrics read CommMeter's bytes per round, which match the
    shapes: n uploads of B*S x d at bfloat16 (codec "none"), 4 label bytes
    per sequence (the program bills labels per sample, not per token), and
    FedAvg's up and down copy of every client's stage and aux head at
    bfloat16."""
    from repro.core.accounting import CommMeter
    cell = harness.load_cell(tiny, TINY_CELL)
    prog = harness.Program(cell).start(3)
    meter = CommMeter()
    rounds = len(prog.chunk(meter))
    ctx = harness.MetricContext(cell=cell, peaks={}, chips=1, summary=None,
                                rounds_traced=0, meter=meter.counts,
                                metered_rounds=rounds, state_bytes=0)
    wire = dataclasses.replace(cell, per_layer=[
        m for m in cell.per_layer
        if m["name"] in ("wire_mib_per_round", "uplink_mib_per_round")])
    got = harness.per_layer_metrics(wire, ctx)
    m, mix = cell.numbers, cell.mix
    n, rows, d = mix["clients"], mix["batch"] * mix["seq"], m["d"]
    upload = n * rows * d * 2
    labels = n * mix["batch"] * 4
    client = (m["vocab"] * d + m["cut"] * (
        2 * d + d * m["heads"] * m["head_dim"] * 2
        + 2 * d * m["kv_heads"] * m["head_dim"] + 2 * m["head_dim"]
        + 3 * d * m["ff"]))
    aux = d + d * m["aux_rank"] + m["aux_rank"] * m["vocab"]
    sync = 2 * n * (client + aux) * 2
    assert got["wire_mib_per_round"]["value"] == pytest.approx(
        (upload + labels + sync) / 2 ** 20, rel=1e-12)
    assert got["wire_mib_per_round"]["value"] == pytest.approx(
        meter.total / 2 ** 20 / rounds, rel=1e-12)
    assert got["uplink_mib_per_round"]["value"] == pytest.approx(
        upload / 2 ** 20, rel=1e-12)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_planted_fault_is_not_correct(tiny, monkeypatch, fault):
    import faults
    if fault in faults.TRAINER_FAULTS:
        monkeypatch.setattr(harness, "make_trainer",
                            faults.TRAINER_FAULTS[fault])
    else:
        monkeypatch.setattr(harness, "program_bundle",
                            faults.BUNDLE_FAULTS[fault])
    res = _run(tiny)
    assert not res["correct"], res["checks"]


def test_control_in_lower_precision_is_not_correct(tiny):
    cell = harness.load_cell(tiny, TINY_CELL)
    abstract = harness.Program(cell).abstract
    ref = harness.run_reference(cell, 7, abstract)
    ctl_losses, ctl_deltas = harness.run_reference(cell, 7, abstract,
                                                   precision="fp8")
    ok, checks = harness.checks_of(
        cell, *harness.compare(ctl_losses, ctl_deltas, *ref))
    assert not ok, checks


def test_new_cell_and_metric_found_by_name(tiny):
    cell = harness.load_cell(tiny, TINY_CELL)
    assert cell.config["name"] == "tiny" and cell.mix["clients"] == 2
    names = [m["name"] for m in cell.per_layer]
    assert "probe" in names
    other = harness.load_cell(tiny, "qwen3-0.6b.client-h8")
    assert "probe" not in [m["name"] for m in other.per_layer]
    ctx = harness.MetricContext(cell=cell, peaks={}, chips=1, summary=None,
                                rounds_traced=0, meter={},
                                metered_rounds=0, state_bytes=2 ** 30)
    only = dataclasses.replace(cell, per_layer=[
        m for m in cell.per_layer if m["name"] in ("probe", "state_gib")])
    got = harness.per_layer_metrics(only, ctx)
    assert got == {"probe": {"value": 42.0, "unit": "1"},
                   "state_gib": {"value": 1.0, "unit": "GiB"}}


def test_cell_across_chips_is_refused(tiny):
    with pytest.raises(harness.BenchError, match="one chip only"):
        harness.load_cell(tiny, TINY_CELL + "-4chip")


def _cli(cwd, workload="qwen3-0.6b.client-h8"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_off_the_chip_fails_without_a_result():
    r = _cli(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_fail_without_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _cli(str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
