"""repro.telemetry: the unified observation layer.

The load-bearing contract (analysis rule T001) is that telemetry is
*observation-only*: every engine must produce bitwise-identical params
and history with the recorder enabled vs disabled, because emission only
ever happens host-side on values the engines already fetched.  On top of
that this file pins down:

  - the v1 round-record schema (validation, JSONL round-trip);
  - exporter determinism (Prometheus text) and Chrome-trace validity;
  - the async engine's simulated timeline *reconciling with its own
    accounting to the event*: wire-transfer spans sum to
    ``AsyncStats.comm_time``, retry-backoff spans to
    ``FaultStats.retry_seconds`` (non-blocking methods), compute spans
    to ``AsyncStats.compute_time``, serve spans to
    ``AsyncStats.server_busy`` — all exactly, not approximately;
  - the compiled path's real host spans (chunk build, dispatch, fetch,
    log), their profiler annotations and the compile counters;
  - the shared ``Recordable.to_record`` flattening and the zero-round
    summary guards;
  - the failure-aware analytic wall-clock estimate against the async
    engine's realized clock.
"""
import json
import math
import types

import jax
import numpy as np
import pytest

from repro.configs.base import FSLConfig
from repro.core.accounting import CommMeter, CostModel, flat_record
from repro.core.async_trainer import (AsyncStats, AsyncTrainer,
                                      ConstantLatency, LognormalLatency)
from repro.core.bundle import cnn_bundle
from repro.core.trainer import Trainer
from repro.data import FederatedBatcher, partition_iid, \
    synthetic_classification
from repro.faults import FaultStats, LossyWire
from repro.models.cnn import CNNConfig
from repro.network import UniformNetwork
from repro.population import FederatedPool, Population
from repro.sched import scheduler_from_flags
from repro.telemetry import (NULL_TELEMETRY, NullTelemetry, Telemetry,
                             make_round_record, resolve_telemetry,
                             validate_record)

SMOKE = CNNConfig("smoke_cnn", (8, 8, 1), 10, conv_channels=(2, 2), kernel=3,
                  server_widths=(8,), aux_channels=2, lrn=False)

# bitwise-neutrality must hold for every method; two is the acceptance
# floor (one non-blocking, one blocking — they exercise disjoint span
# emission sites in the async engine)
METHODS = ("cse_fsl", "fsl_mc")


def _setup(method, n=2, h=2):
    fsl = FSLConfig(num_clients=n, h=h, lr=0.05, method=method)
    bundle = cnn_bundle(SMOKE)
    x, y = synthetic_classification(24 * n, (8, 8, 1), 10, seed=0,
                                    signal=12.0)
    return bundle, fsl, partition_iid(x, y, n, seed=0)


def _cm(n):
    return CostModel(n=n, q=8, d_local=24, w_client=100, w_server=100,
                     aux=10)


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def _span_sum(tele, name):
    return sum(s.dur for s in tele.spans if s.name == name)


# ---------------------------------------------------------------------------
# Recorder basics
# ---------------------------------------------------------------------------


def test_null_recorder_is_shared_noop():
    assert resolve_telemetry(None) is NULL_TELEMETRY
    assert not NULL_TELEMETRY.enabled
    t = Telemetry()
    assert resolve_telemetry(t) is t and t.enabled
    with pytest.raises(TypeError, match="Telemetry or None"):
        resolve_telemetry(42)
    # every emission on the null recorder leaves no trace
    NULL_TELEMETRY.counter("x", 3, engine="loop")
    NULL_TELEMETRY.gauge("y", 1.0)
    NULL_TELEMETRY.sim_span("s", 0.0, 1.0, track="server")
    NULL_TELEMETRY.host_span("h", 0.0, 1.0)
    NULL_TELEMETRY.round_record("loop", 1, {"loss": 1.0}, True)
    NULL_TELEMETRY.run_summary("loop", comm=CommMeter())
    with NULL_TELEMETRY.timed("t"):
        pass
    assert not NULL_TELEMETRY.counters and not NULL_TELEMETRY.gauges
    assert not NULL_TELEMETRY.spans and not NULL_TELEMETRY.records
    assert isinstance(NULL_TELEMETRY, NullTelemetry)


def test_round_record_schema_validation():
    rec = make_round_record("loop", 3, {"loss": 1.5}, True, comm_bytes=10)
    assert validate_record(rec) is rec
    assert rec["v"] == 1 and rec["type"] == "round" and rec["round"] == 3
    bad = [dict(rec, v=99), dict(rec, engine="cuda"), dict(rec, round=0),
           dict(rec, aggregated="yes"), dict(rec, metrics={1: 2.0}),
           dict(rec, metrics={"loss": "nan?"}), dict(rec, comm_bytes=1.5),
           dict(rec, type="summary")]        # summary needs a summary dict
    for b in bad:
        with pytest.raises(ValueError):
            validate_record(b)


def test_counters_and_gauges_are_labelled():
    t = Telemetry()
    t.counter("ticks", 1, engine="loop")
    t.counter("ticks", 2, engine="loop")
    t.counter("ticks", 5, engine="async")
    t.gauge("depth", 3.0, engine="loop")
    t.gauge("depth", 7.0, engine="loop")          # latest-wins
    assert t.counters[("ticks", (("engine", "loop"),))] == 3
    assert t.counters[("ticks", (("engine", "async"),))] == 5
    assert t.gauges[("depth", (("engine", "loop"),))] == 7.0


# ---------------------------------------------------------------------------
# Bitwise neutrality: every engine, telemetry on vs off
# ---------------------------------------------------------------------------


def _loop_run(bundle, fsl, fed, tele, rounds=4):
    tr = Trainer(bundle, fsl, donate=False, telemetry=tele)
    meter = CommMeter()
    state, hist = tr.run(tr.init(0), FederatedBatcher(fed, 4, fsl.h, seed=0),
                         rounds, log_every=1, meter=meter,
                         cost_model=_cm(fsl.num_clients))
    return state, hist, meter


@pytest.mark.parametrize("method", METHODS)
def test_loop_bitwise_with_telemetry(method):
    bundle, fsl, fed = _setup(method)
    tele = Telemetry()
    s1, h1, m1 = _loop_run(bundle, fsl, fed, tele)
    s2, h2, m2 = _loop_run(bundle, fsl, fed, None)
    assert _leaves_equal(s1, s2)
    assert h1 == h2
    assert m1.as_dict() == m2.as_dict()
    rounds = [r for r in tele.records if r["type"] == "round"]
    assert [r["round"] for r in rounds] == [1, 2, 3, 4]
    assert all(r["engine"] == "loop" and r["metrics"]
               and all(isinstance(v, float) for v in r["metrics"].values())
               for r in rounds)
    # the record metrics ARE the history metrics, row for row
    hist_metrics = [{k: v for k, v in row.items()
                     if k not in ("round", "aggregated", "comm_bytes")}
                    for row in h1]
    assert [r["metrics"] for r in rounds] == hist_metrics
    assert tele.records[-1]["type"] == "summary"
    assert tele.gauges[("comm.total", (("engine", "loop"),))] == m1.total


@pytest.mark.parametrize("method", METHODS)
def test_compiled_bitwise_with_telemetry(method):
    bundle, fsl, fed = _setup(method)

    def go(tele):
        tr = Trainer(bundle, fsl, donate=False, telemetry=tele)
        return tr.run_compiled(tr.init(0),
                               FederatedBatcher(fed, 4, fsl.h, seed=0),
                               5, chunk=2, log_every=1)

    tele = Telemetry()
    s1, h1 = go(tele)
    s2, h2 = go(None)
    assert _leaves_equal(s1, s2)
    assert h1 == h2
    assert len([r for r in tele.records if r["type"] == "round"]) == 5


@pytest.mark.parametrize("method", METHODS)
def test_async_bitwise_with_telemetry(method):
    bundle, fsl, fed = _setup(method)

    def go(tele):
        tr = AsyncTrainer(bundle, fsl, latency=LognormalLatency(),
                          seed=7, telemetry=tele)
        state, hist = tr.run(tr.init(0),
                             FederatedBatcher(fed, 4, fsl.h, seed=0),
                             4, log_every=1)
        return state, hist, tr.stats

    tele = Telemetry()
    s1, h1, st1 = go(tele)
    s2, h2, st2 = go(None)
    assert _leaves_equal(s1, s2)
    assert h1 == h2
    assert st1.as_dict() == st2.as_dict()
    rounds = [r for r in tele.records if r["type"] == "round"]
    assert len(rounds) == 4
    # the async stream carries the simulated clock, monotone per round
    sims = [r["sim_time"] for r in rounds]
    assert all(b >= a for a, b in zip(sims, sims[1:])) and sims[0] > 0


@pytest.mark.parametrize("method", METHODS)
def test_population_bitwise_with_telemetry(method):
    bundle, fsl, fed = _setup(method)

    def go(tele):
        pop = Population(bundle, fsl, population=fsl.num_clients,
                         data=FederatedPool(fed, 4, fsl.h, seed=0),
                         donate=False, telemetry=tele)
        pop.init(seed=0)
        return pop.run(5, chunk=2, log_every=1)

    tele = Telemetry()
    s1, h1 = go(tele)
    s2, h2 = go(None)
    assert _leaves_equal(s1, s2)
    assert h1 == h2
    rounds = [r for r in tele.records if r["type"] == "round"]
    assert len(rounds) == 5 and all(r["engine"] == "population"
                                    for r in rounds)
    assert any(s.name == "chunk/build" for s in tele.spans)
    summary = tele.records[-1]
    assert summary["type"] == "summary"
    assert "population.windows" in summary["summary"]


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------


def test_jsonl_export_roundtrip(tmp_path):
    bundle, fsl, fed = _setup("cse_fsl")
    tele = Telemetry()
    _loop_run(bundle, fsl, fed, tele)
    path = tmp_path / "out.jsonl"
    tele.export_jsonl(str(path))
    lines = path.read_text().splitlines()
    assert len(lines) == len(tele.records)
    parsed = [validate_record(json.loads(ln)) for ln in lines]
    assert parsed == tele.records
    # deterministic serialization: keys sorted within each line
    for ln in lines:
        assert ln == json.dumps(json.loads(ln), sort_keys=True)


def test_prometheus_text_deterministic():
    bundle, fsl, fed = _setup("cse_fsl")
    tele = Telemetry()
    _loop_run(bundle, fsl, fed, tele)
    text = tele.prometheus_text()
    assert text == tele.prometheus_text()          # pure function of state
    assert '# TYPE repro_rounds_total counter' in text
    assert 'repro_rounds_total{engine="loop"} 4' in text
    # flattened summary gauges are sanitized into the metric charset
    assert "repro_comm_total" in text
    for line in text.splitlines():
        if line and not line.startswith("#"):
            name = line.split("{")[0].split(" ")[0]
            assert all(c.isalnum() or c in "_:" for c in name), line


def test_chrome_trace_shape():
    bundle, fsl, fed = _setup("cse_fsl")
    tele = Telemetry()
    tr = AsyncTrainer(bundle, fsl, latency=LognormalLatency(), seed=1,
                      telemetry=tele)
    tr.run(tr.init(0), FederatedBatcher(fed, 4, fsl.h, seed=0), 3)
    trace = tele.chrome_trace()
    assert set(trace) == {"traceEvents", "displayTimeUnit"}
    evs = trace["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == len(tele.spans)
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # process/thread metadata names the simulated timeline tracks
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta
             if e["name"] == "thread_name"}
    assert "server" in names and any(n.startswith("client/")
                                     for n in names)
    json.dumps(trace)                               # serializable as-is


# ---------------------------------------------------------------------------
# The async timeline reconciles with the engine's accounting — exactly
# ---------------------------------------------------------------------------


def _async_faulty(method, rounds=5, n=3, h=2):
    bundle, fsl, fed = _setup(method, n=n, h=h)
    tele = Telemetry()
    tr = AsyncTrainer(bundle, fsl, latency=LognormalLatency().compute_only(),
                      network=UniformNetwork(), faults="lossy", seed=3,
                      telemetry=tele)
    tr.run(tr.init(0), FederatedBatcher(fed, 4, h, seed=0), rounds)
    return tele, tr


def test_async_spans_reconcile_with_stats_exactly():
    """Non-blocking method: every accounting total the engine reports is
    the sum of the spans on the timeline, to float equality — the trace
    is the accounting, just with positions."""
    tele, tr = _async_faulty("cse_fsl")
    st = tr.stats
    fs = tr.participation_summary()["faults"]
    assert fs["retries"] > 0                     # the lossy wire did fire
    assert math.isclose(_span_sum(tele, "wire/up"), st.comm_time,
                        rel_tol=1e-9)
    assert math.isclose(_span_sum(tele, "retry_backoff"),
                        fs["retry_seconds"], rel_tol=1e-9)
    assert math.isclose(_span_sum(tele, "compute"), st.compute_time,
                        rel_tol=1e-9)
    assert math.isclose(_span_sum(tele, "serve"), st.server_busy,
                        rel_tol=1e-9)
    # spans never run past the realized simulated clock
    assert max(s.start + s.dur for s in tele.spans) <= st.async_time + 1e-9
    # per-attempt structure: delivered=True exactly once per consumed event
    delivered = [s for s in tele.spans if s.name == "wire/up"
                 and s.labels.get("delivered")]
    attempts = sum(s.labels["attempt"] == 1
                   for s in tele.spans if s.name == "wire/up")
    assert len(delivered) <= attempts


def test_async_blocking_method_trace():
    """Blocking methods add the gradient-download wire to the timeline;
    up+down transfer spans still sum to comm_time exactly.  (Backoff
    spans are the *realized* waits — FaultStats bills planned download
    backoffs for unserved clients too, so realized <= billed.)"""
    tele, tr = _async_faulty("fsl_mc")
    st = tr.stats
    fs = tr.participation_summary()["faults"]
    down = [s for s in tele.spans if s.name == "wire/down"]
    assert down and all(s.labels["channel"] == "downlink" for s in down)
    total_wire = _span_sum(tele, "wire/up") + _span_sum(tele, "wire/down")
    assert math.isclose(total_wire, st.comm_time, rel_tol=1e-9)
    assert _span_sum(tele, "retry_backoff") <= fs["retry_seconds"] + 1e-9
    assert math.isclose(_span_sum(tele, "compute"), st.compute_time,
                        rel_tol=1e-9)


# ---------------------------------------------------------------------------
# Compiled-path host spans
# ---------------------------------------------------------------------------


CHUNK_SPANS = ("chunk/build", "chunk/dispatch", "chunk/fetch", "chunk/log")


def test_compiled_chunk_spans():
    bundle, fsl, fed = _setup("cse_fsl")
    tele = Telemetry()
    tr = Trainer(bundle, fsl, donate=False, telemetry=tele)
    tr.run_compiled(tr.init(0), FederatedBatcher(fed, 4, fsl.h, seed=0),
                    5, chunk=2)
    by = {n: [s for s in tele.spans if s.name == n] for n in CHUNK_SPANS}
    assert [len(v) for v in by.values()] == [3] * 4      # ceil(5 / 2)
    spans = [s for v in by.values() for s in v]
    assert all(s.cat == "host" and s.dur >= 0 for s in spans)
    assert all([s.labels["chunk"] for s in v] == [0, 1, 2]
               for v in by.values())
    # each chunk's spans run in order, one after the other
    for c in range(3):
        seq = [by[n][c] for n in CHUNK_SPANS]
        assert all(a.start + a.dur <= b.start for a, b in zip(seq, seq[1:]))
    # the dispatch of each new chunk length compiles, measured: R=2
    # (chunk 0) and the trailing R=1 (chunk 2)
    assert [s.labels["compiles"] for s in by["chunk/dispatch"]] == [1, 0, 1]
    assert all(s.labels["compiles"] == 0 for n in ("chunk/fetch",
                                                   "chunk/log")
               for s in by[n])
    # one "chunk/run" span holds the whole call
    run, = [s for s in tele.spans if s.name == "chunk/run"]
    assert all(run.start <= s.start and s.start + s.dur <= run.start + run.dur
               for s in spans)
    assert run.labels == {"rounds": 5, "compiles": sum(
        s.labels["compiles"] for s in spans)}


def test_compile_counter_counts_a_new_chunk_length():
    bundle, fsl, fed = _setup("cse_fsl")
    tele = Telemetry()
    tr = Trainer(bundle, fsl, donate=False, telemetry=tele)
    batcher = FederatedBatcher(fed, 4, fsl.h, seed=0)
    key = ("xla_compiles_total", ())
    state, _ = tr.run_compiled(tr.init(0), batcher, 4, chunk=2)
    before = tele.counters[key]
    assert before >= 1
    state, _ = tr.run_compiled(state, batcher, 2, chunk=2)   # compiled R=2
    assert tele.counters[key] == before
    tr.run_compiled(state, batcher, 1, chunk=2)              # forced R=1
    assert tele.counters[key] == before + 1
    dispatch = [s for s in tele.spans if s.name == "chunk/dispatch"]
    assert dispatch[-1].labels == {"compiles": 1, "chunk": 0, "rounds": 1}
    assert tele.compile_tally().compiles == before + 1
    seconds = {p: tele.counters[("compile_seconds_total", (("phase", p),))]
               for p in ("trace", "lower", "compile")}
    assert all(v > 0 for v in seconds.values())
    text = tele.prometheus_text()
    assert "# TYPE repro_xla_compiles_total counter" in text
    assert f"repro_xla_compiles_total {before + 1}" in text
    assert 'repro_compile_seconds_total{phase="lower"}' in text


def test_compile_tally_counts_nested_traces_once():
    from repro.telemetry import compiles
    lis = compiles._Listener()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    lis(trace, 1.0, 2.0)            # an inner jit, traced inside ...
    lis(trace, 1.5, 1.75)           # (deeper still)
    lis(trace, 0.5, 3.0)            # ... its caller, which ends last
    lis(trace, 4.0, 4.5)            # a later, separate trace
    lis("/jax/core/compile/backend_compile_duration", 5.0, 7.0)
    lis("/some/other/event", 0.0, 9.0)
    t = lis.snapshot()
    assert t.counts == (4, 0, 1) and t.compiles == 1
    assert t.seconds == (3.0, 0.0, 2.0)
    d = t - compiles.Tally((1, 0, 0), (0.5, 0.0, 0.0))
    assert (d.count("trace"), d.secs("trace"), d.secs("compile")) == \
        (3, 2.5, 2.0)


def test_timed_spans_reach_the_profiler_trace(tmp_path):
    """With telemetry off, the chunk runner's spans are still profiler
    annotations: a jax.profiler trace's host plane holds each of them,
    once per chunk."""
    bundle, fsl, fed = _setup("cse_fsl")
    tr = Trainer(bundle, fsl, donate=False)
    state = tr.init(0)
    batcher = FederatedBatcher(fed, 4, fsl.h, seed=0)
    with jax.profiler.trace(str(tmp_path)):
        tr.run_compiled(state, batcher, 5, chunk=2)
    path, = tmp_path.glob("**/*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = [e.name for plane in pd.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events]
    assert {n: names.count(n) for n in CHUNK_SPANS + ("chunk/run",)} == \
        {**dict.fromkeys(CHUNK_SPANS, 3), "chunk/run": 1}
    assert not NULL_TELEMETRY.spans


# ---------------------------------------------------------------------------
# Recordable.to_record + zero-round guards
# ---------------------------------------------------------------------------


def test_to_record_flattening_and_prefix():
    meter = CommMeter()
    meter.log("uplink_smashed", 100)
    meter.log("model_sync", 40)
    rec = meter.to_record("comm.")
    assert rec == meter.to_record("comm.")         # deterministic
    assert all(k.startswith("comm.") for k in rec)
    assert list(rec) == sorted(rec)                # sorted at every level
    assert rec["comm.total"] == 140
    st = AsyncStats()
    r2 = st.to_record("async.")
    assert r2["async.rounds"] == 0 and "async.compute_time" in r2
    fs = FaultStats().to_record("faults.")
    assert fs["faults.retries"] == 0
    # nested dicts flatten depth-first with dotted keys
    flat = flat_record({"b": {"y": 1, "x": 2}, "a": 3}, "p.")
    assert list(flat) == ["p.a", "p.b.x", "p.b.y"]


def test_zero_round_summaries_are_well_defined():
    """Satellite of the telemetry schema: a zero-round run (resume at the
    horizon, degenerate sweep) must still produce a valid summary record
    — no NaN means, no empty-reduction crashes."""
    pol = scheduler_from_flags("deadline", 5.0)
    ctx = types.SimpleNamespace(network=None)
    out = pol.summary(ctx, np.zeros((0, 3), dtype=bool))
    assert out["rounds"] == 0 and out["mean_cohort"] == 0.0
    assert out["min_cohort"] == 0
    assert out["participation_rate"] == [0.0, 0.0, 0.0]
    fd = FaultStats().as_dict()
    assert fd["windows"] == 0 and fd["mean_participants"] is None
    json.dumps(fd)                                  # JSON-clean
    # both fold into a summary record without tripping validation
    tele = Telemetry()
    tele.run_summary("loop", participation=out, faults=FaultStats())
    assert validate_record(tele.records[-1])["type"] == "summary"


# ---------------------------------------------------------------------------
# Analytic failure-aware wall-clock vs the realized simulated clock
# ---------------------------------------------------------------------------


def test_wallclock_estimate_tracks_realized_async_clock():
    """``Trainer.wallclock_estimate(faults=...)`` is the analytic twin of
    the event engine's realized clock: expected retransmission counts
    and backoff vs one concrete draw.  On a compute-dominant constant
    profile the two must agree within 25% relative — the slack covers
    (a) the stochastic gap between expected and realized retries and
    (b) barrier vs event-driven server pipelining."""
    n, h, rounds, compute, server_time = 2, 2, 6, 0.5, 0.05
    bundle, fsl, fed = _setup("cse_fsl", n=n, h=h)
    net = UniformNetwork(up_mbps=2.0, down_mbps=10.0, rtt=0.03)
    faults = LossyWire(loss_rate=0.3, seed=1)
    asyn = AsyncTrainer(bundle, fsl,
                        latency=ConstantLatency(compute, 0.0, 0.0),
                        network=net, faults=faults,
                        server_time=server_time)
    asyn.run(asyn.init(0), FederatedBatcher(fed, 4, h, seed=0), rounds)
    tr = Trainer(bundle, fsl, donate=False, network=net, faults=faults)
    batch = FederatedBatcher(fed, 4, h, seed=0).next_round()
    est = tr.wallclock_estimate(_cm(n), 4, rounds, net, batch=batch,
                                compute=compute, server_time=server_time)
    clean = tr.wallclock_estimate(_cm(n), 4, rounds, net, batch=batch,
                                  compute=compute,
                                  server_time=server_time, faults="none")
    assert est.total > clean.total                  # failure-aware
    realized = asyn.stats.async_time
    assert realized > 0
    assert abs(est.total - realized) / realized < 0.25, \
        (est.total, realized)
