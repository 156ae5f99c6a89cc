"""The stage reduction (bench/stages.py): self time of nested ops, the
innermost stage of a name path, and the per-layer values, on hand-made
events and on a small trace recorded on a TPU v5e
(``data/stage_trace.xplane.pb.gz``, made by ``record_trace.py``)."""
import gzip
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import stages  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "stage_trace.xplane.pb.gz")


def test_stage_of_takes_the_innermost_stage_by_component():
    assert stages.stage_of("jit(f)/while/body/client_step/vmap()/while/"
                           "body/transpose(jvp(aux_ce))/dot_general") == \
        "aux_ce"
    assert stages.stage_of("jit(f)/client_step/client_update/mul") == \
        "client_update"
    assert stages.stage_of("jit(f)/while/body/server_consume/while/body/"
                           "transpose(jvp())/dot_general") == \
        "server_consume"
    assert stages.stage_of("jit(f)/while/body/cond/branch_1_fun/fedavg/"
                           "div") == "fedavg"
    assert stages.stage_of("jit(f)/while/body/gather") == stages.UNSCOPED
    assert stages.stage_of("jit(f)/fedavg_like/aux_ce2/add") == \
        stages.UNSCOPED
    assert stages.stage_of("") == stages.UNSCOPED


def test_self_times_subtract_nested_ops():
    # an outer loop 0-10 holding a body loop 1-6 holding two ops, then an
    # op 7-9 directly in the outer loop; an op that outlasts its parent
    # (9.5-11) is charged only the overlap
    iv = [(0, 10), (1, 6), (1, 2), (3, 5), (7, 9), (9.5, 11), (12, 13)]
    assert stages.self_times(iv) == pytest.approx(
        [10 - 5 - 2 - 0.5, 5 - 1 - 2, 1, 2, 2, 1.5, 1])


def _hand_made():
    dev = "/device:TPU:0"
    ops = [("while.1", 1.0, 9.0), ("while.2", 1.0, 5.0),
           ("fusion.1", 1.0, 2.0), ("fusion.2", 2.0, 4.0),
           ("fusion.3", 5.0, 6.0), ("fusion.4", 6.5, 8.0),
           ("copy.1", 9.5, 10.5)]
    paths = ["jit(c)/while",
             "jit(c)/while/body/client_step/vmap()/while",
             "jit(c)/while/body/client_step/vmap()/while/body/"
             "jvp(aux_ce)/exp",
             "jit(c)/while/body/client_step/vmap()/while/body/"
             "client_update/mul",
             "jit(c)/while/body/server_consume/while/body/dot_general",
             "jit(c)/while/body/cond/branch_1_fun/fedavg/div",
             "jit(c)/copy"]
    mods = [("jit_c", 1.0, 9.0)]
    host = [("bench/chunk", 0.5, 10.0), ("chunk/dispatch", 0.5, 1.0)]
    return {"ops": {dev: ops}, "modules": {dev: mods}, "host": host,
            "paths": {dev: paths}}


def test_stage_seconds_add_up_to_busy_time():
    ev = _hand_made()
    s = stages.stage_seconds(ev)
    # window 0.5-10.0 clips copy.1 to 0.5 s
    assert s == pytest.approx({
        "client_step": 4 - 1 - 2, "aux_ce": 1, "client_update": 2,
        "server_consume": 1, "fedavg": 1.5,
        "unscoped": (8 - 4 - 1 - 1.5) + 0.5})
    busy = devtrace.summarize_events(ev).busy_s
    assert sum(s.values()) == pytest.approx(busy)


def test_metrics_per_round_and_compile_tallies():
    from repro.telemetry.compiles import Tally
    s = stages.stage_seconds(_hand_made())
    tallies = {"start": Tally((0, 0, 0), (0.0, 0.0, 0.0)),
               "setup": Tally((5, 4, 3), (1.0, 2.0, 30.0)),
               "window": Tally((5, 4, 3), (1.0, 2.0, 30.0))}
    m = stages.metrics(s, busy_s=8.5, rounds=2, tallies=tallies)
    assert set(m) == set(stages.UNITS)
    assert m["aux_ce_ms"] == pytest.approx(500.0)
    assert m["unscoped_share"] == pytest.approx(100 * 2.0 / 8.5)
    assert m["compiles_in_window"] == 0
    assert m["setup_lower_s"] == pytest.approx(3.0)
    assert m["setup_compile_s"] == pytest.approx(30.0)
    # nothing traced: the device values are left out, not zero
    assert set(stages.metrics({}, 0.0, 0, tallies)) == {
        "compiles_in_window", "setup_lower_s", "setup_compile_s"}


def test_stage_tool_reads_a_cell(tmp_path, monkeypatch, capsys):
    """``stages.main`` drives a tiny cell's ``--trace 1`` run through the
    harness on the CPU, whose trace has no device plane: the compile
    tallies around set-up and the window come back, and the stage values
    read zero."""
    import json
    import harness
    import repro.common
    from test_bench_harness import TINY_CELL, _tiny_checkout
    root = _tiny_checkout(str(tmp_path))
    monkeypatch.setattr(repro.common, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(devtrace, "summarize", lambda d, n: devtrace.Summary(
        window_s=1.0, busy_s=0.5, chunk_gaps_s=[], op_seconds={}, idle=[]))
    monkeypatch.setattr(harness, "peaks_of",
                        lambda kind, require: {"bf16_flops": 197e12})
    monkeypatch.setattr(stages, "BENCH_DIR", os.path.join(root, "bench"))
    rc = stages.main(["--workload", TINY_CELL, "--seed", "2147483659",
                      "--seconds", "0.2"], require_tpu=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(stages.UNITS) <= set(m)
    assert res["correct"] and res["attempted"] > 0
    assert m["compiles_in_window"] == 0
    assert m["setup_compile_s"] > 0 and m["setup_lower_s"] > 0
    assert m["aux_ce_ms"] == 0 and m["unscoped_share"] == 0
    assert set(res["stages"]) == set(stages.STAGES) | {stages.UNSCOPED}


# ---------------------------------------------------------------------------
# The recorded TPU trace: 2 chunks of a tiny cse_fsl program
# ---------------------------------------------------------------------------

CHUNK_SPANS = ("chunk/build", "chunk/dispatch", "chunk/fetch", "chunk/log")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "stage_trace.xplane.pb"
    with gzip.open(DATA, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return devtrace.events(str(path))


def test_recorded_trace_reads_the_name_path_of_each_op(recorded):
    d, = recorded["ops"]
    paths = recorded["paths"][d]
    assert len(paths) == len(recorded["ops"][d]) == 2950
    # ops outside any jitted name scope (the copies and buffer set-up the
    # compiler adds round the program) carry no path
    assert sum(1 for p in paths if p) == 2676
    assert all(p.startswith("jit(") for p in paths if p)
    # innermost stage wins: an op under both client_step and aux_ce is
    # aux_ce's, and the backward pass carries the scope
    both = [p for p in paths if "/client_step/" in p and "aux_ce" in p]
    assert both and {stages.stage_of(p) for p in both} == {"aux_ce"}
    assert any("transpose(jvp(aux_ce))" in p for p in paths)


def test_recorded_trace_stage_reduction(recorded):
    s = stages.stage_seconds(recorded)
    summary = devtrace.summarize_events(recorded)
    # every stage ran, and the stages and the unscoped rest are the busy
    # time, each op's time counted once (to the trace's ns rounding: one
    # op here ends 1 ns after the conditional that holds it)
    assert all(s[k] > 0 for k in stages.STAGES)
    assert sum(s.values()) == pytest.approx(summary.busy_s, abs=1e-8)
    # the nested scan loops: inclusive times add up to far more than the
    # busy time, self times do not
    assert sum(summary.op_seconds.values()) > 2.5 * summary.busy_s
    assert s == pytest.approx({
        "client_step": 0.000259216, "aux_ce": 0.000088,
        "client_update": 0.000025521, "server_consume": 0.000168093,
        "fedavg": 0.000029701, "unscoped": 0.000061218}, abs=1e-9)


def test_recorded_trace_summary_is_the_parents(recorded):
    """``devtrace.summarize_events`` reads this trace as the code before
    the stage reduction did (values taken with that code)."""
    s = devtrace.summarize_events(recorded)
    assert s.window_s == pytest.approx(0.00964497, abs=1e-12)
    assert s.busy_s == pytest.approx(0.000631748, abs=1e-12)
    assert s.chunk_gaps_s == pytest.approx([0.004035515], abs=1e-12)
    b = s.breakdown()
    assert [n.split(" = ")[0] for n, _ in b["device_ops"][:3]] == \
        ["%while.416", "%while.417", "%while.434"]
    assert [v for _, v in b["device_ops"][:3]] == pytest.approx(
        [0.000614059, 0.000348802, 0.000172164], abs=1e-12)
    # the longest idle gaps, named by the host event covering most of
    # each: the runner's enclosing span where it covers the whole gap,
    # the Python frame of run_compiled where the gap runs past the span
    frame = "$trainer.py:547 run_compiled"
    assert [n for n, _ in b["idle_gaps"][:5]] == \
        [frame, "chunk/run", "chunk/run", frame, frame]
    assert [v for _, v in b["idle_gaps"][:5]] == pytest.approx(
        [0.003189996, 0.00315536, 0.001044779, 0.000880694,
         0.000741426], abs=1e-12)


def test_recorded_chunk_spans_mark_the_runner_phases(recorded):
    """The chunk runner's spans are in the trace's host plane: per chunk
    (one ``run_compiled`` call each), build, dispatch, fetch and log in
    order inside ``chunk/run``, inside the benchmark's ``bench/chunk``
    annotation; the launch of a program runs inside ``chunk/dispatch``
    (or, for the lrs' conversion, ``chunk/build``), never in
    ``chunk/fetch``."""
    host = recorded["host"]
    spans = {n: sorted((a, b) for m, a, b in host if m == n)
             for n in CHUNK_SPANS + ("chunk/run",
                                     devtrace.CHUNK_ANNOTATION)}
    assert {n: len(v) for n, v in spans.items()} == \
        dict.fromkeys(spans, 2)
    for c, (lo, hi) in enumerate(spans[devtrace.CHUNK_ANNOTATION]):
        seq = [spans[n][c] for n in CHUNK_SPANS]
        run = spans["chunk/run"][c]
        assert lo <= run[0] <= seq[0][0] and seq[-1][1] <= run[1] <= hi
        assert all(a[1] <= b[0] for a, b in zip(seq, seq[1:]))
    launches = [(a, b) for n, a, b in host
                if n == "PJRT_LoadedExecutable_Execute"]
    inside = lambda ab, n: any(s <= ab[0] and ab[1] <= e
                               for s, e in spans[n])
    assert sum(inside(ab, "chunk/dispatch") for ab in launches) == 2
    assert not any(inside(ab, "chunk/fetch") for ab in launches)
