"""The host-side telemetry recorder — counters, gauges, spans, records.

One `Telemetry` instance observes a whole run: engines append round
records (the v1 schema in :mod:`repro.telemetry.record`), bump labelled
counters/gauges, and emit *spans* — either **sim** spans placed on the
async engine's simulated clock (per-client compute, per-attempt wire
transfers, retry backoffs, server service, outages, model-sync
barriers), or **host** spans measured with ``time.perf_counter`` (the
compiled path's chunk build/dispatch/fetch/log phases).  Exporters render
the accumulated state as JSONL, Prometheus text exposition, or Chrome
trace-event JSON (:mod:`repro.telemetry.export`).

Every :meth:`Telemetry.timed` span, on the null recorder too, also opens a
``jax.profiler.TraceAnnotation`` of its name: whenever a ``jax.profiler``
session runs, the span lands in the trace's host plane on the device ops'
clock (one flag check when none runs).  An enabled recorder labels each
timed span with ``compiles``, the backend compiles JAX reported during it,
and keeps the counters ``xla_compiles_total`` and
``compile_seconds_total{phase=trace|lower|compile}`` since its creation
(:mod:`repro.telemetry.compiles`), and beside them
``attention_traced_total{path=kernel|xla}`` and the gauge
``attention_block_share`` (:mod:`repro.telemetry.attention`).

The hard contract (rule T001, ``tests/test_telemetry.py``): telemetry is
**observation-only**.  A disabled recorder is the `NullTelemetry`
singleton whose every method is a pass, its timed spans profiler
annotations alone — engines guard their emission sites with ``if
telemetry.enabled:`` so the off path costs one attribute read.  An
enabled recorder only ever runs on the host, AFTER device values have
already been fetched by the engines' existing post-chunk / post-step
mirrors — it never adds a host callback, never touches the
donated ``lax.scan`` body, and never changes a compiled program
(fingerprint-checked by ``repro.analysis.audit_telemetry``).  Params and
history are bitwise-identical with telemetry on vs. off in all four
engines.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Mapping, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.core.accounting import flat_record
from repro.telemetry import attention, compiles
from repro.telemetry.record import (make_round_record, make_summary_record,
                                    validate_record)

LabelKey = Tuple[str, Tuple[Tuple[str, Any], ...]]


def _key(name: str, labels: Mapping[str, Any]) -> LabelKey:
    return (name, tuple(sorted(labels.items())))


class Span:
    """One named interval on a named track.

    ``cat`` is ``"sim"`` (start/dur in *simulated* seconds on the async
    engine's clock) or ``"host"`` (``perf_counter`` seconds).  ``track``
    names the timeline row — ``client/3``, ``server``, ``host`` — which
    the Chrome exporter maps to a thread."""

    __slots__ = ("name", "start", "dur", "track", "cat", "labels")

    def __init__(self, name: str, start: float, dur: float, track: str,
                 cat: str, labels: Dict[str, Any]):
        self.name = name
        self.start = float(start)
        self.dur = float(dur)
        self.track = track
        self.cat = cat
        self.labels = labels

    def __repr__(self):
        return (f"<Span {self.name} @{self.start:.6f}+{self.dur:.6f}"
                f" {self.track} {self.labels}>")


class _HostTimer:
    """Context manager backing :meth:`Telemetry.timed`: a profiler
    annotation, plus the span and its compile count for the recorder."""

    __slots__ = ("_tele", "_name", "_track", "_labels", "_t0", "_c0",
                 "_annotation")

    def __init__(self, tele: "Telemetry", name: str, track: str,
                 labels: Dict[str, Any]):
        self._tele = tele
        self._name = name
        self._track = track
        self._labels = labels
        self._annotation = TraceAnnotation(name)

    def __enter__(self):
        self._annotation.__enter__()
        self._c0 = compiles.snapshot().compiles
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        tele = self._tele
        tele.host_span(self._name, self._t0, dur, track=self._track,
                       compiles=compiles.snapshot().compiles - self._c0,
                       **self._labels)
        tele.note_compiles()
        return False


class Telemetry:
    """The enabled recorder.  All state lives in plain host containers;
    every method is cheap dict/list work on already-fetched values."""

    enabled: bool = True

    def __init__(self):
        self.counters: Dict[LabelKey, float] = {}
        self.gauges: Dict[LabelKey, float] = {}
        self.spans: List[Span] = []
        self.records: List[Dict[str, Any]] = []
        compiles.install()
        attention.install()
        self._compiles0 = compiles.snapshot()
        self._attention0 = attention.snapshot()

    # -- scalars -------------------------------------------------------------
    def counter(self, name: str, value: float = 1, **labels):
        """Add ``value`` to the labelled monotonic counter ``name``."""
        k = _key(name, labels)
        self.counters[k] = self.counters.get(k, 0) + value

    def gauge(self, name: str, value: float, **labels):
        """Set the labelled gauge ``name`` to its latest ``value``."""
        self.gauges[_key(name, labels)] = value

    def compile_tally(self) -> "compiles.Tally":
        """What JAX traced, lowered and compiled since this recorder was
        made (process-wide: every thread's programs count)."""
        return compiles.snapshot() - self._compiles0

    def attention_tally(self) -> "attention.Tally":
        """The attention calls JAX traced since this recorder was made,
        by path (process-wide, as :meth:`compile_tally`)."""
        return attention.snapshot() - self._attention0

    def note_compiles(self):
        """Bring ``xla_compiles_total`` and ``compile_seconds_total``
        up to :meth:`compile_tally`, and ``attention_traced_total`` and
        ``attention_block_share`` up to :meth:`attention_tally`."""
        t = self.compile_tally()
        self.counters[_key("xla_compiles_total", {})] = t.compiles
        for phase in compiles.PHASES:
            self.counters[_key("compile_seconds_total",
                               {"phase": phase})] = t.secs(phase)
        a = self.attention_tally()
        for path in ("kernel", "xla"):
            self.counters[_key("attention_traced_total",
                               {"path": path})] = getattr(a, path)
        self.gauges[_key("attention_block_share", {})] = a.block_share

    # -- spans ---------------------------------------------------------------
    def sim_span(self, name: str, start: float, dur: float, track: str,
                 **labels):
        """An interval on the async engine's *simulated* clock."""
        self.spans.append(Span(name, start, dur, track, "sim", labels))

    def host_span(self, name: str, start: float, dur: float,
                  track: str = "host", **labels):
        """An interval measured in real ``perf_counter`` seconds."""
        self.spans.append(Span(name, start, dur, track, "host", labels))

    def timed(self, name: str, track: str = "host", **labels):
        """``with tele.timed("chunk/build"):`` — a real host-side span,
        labelled with the backend compiles during it, and a profiler
        annotation of ``name``."""
        return _HostTimer(self, name, track, labels)

    # -- records -------------------------------------------------------------
    def round_record(self, engine: str, rnd: int, metrics: Mapping[str, Any],
                     aggregated: bool, comm_bytes: Optional[int] = None,
                     sim_time: Optional[float] = None,
                     extra: Optional[Mapping[str, Any]] = None):
        """Fold one engine round into the stream (validated at emit)."""
        rec = make_round_record(engine, rnd, metrics, aggregated,
                                comm_bytes=comm_bytes, sim_time=sim_time,
                                extra=extra)
        self.records.append(validate_record(rec))
        self.counter("rounds_total", 1, engine=engine)
        if aggregated:
            self.counter("aggregations_total", 1, engine=engine)

    def run_summary(self, engine: str, **sections):
        """Fold end-of-run summaries into ONE flat summary record.

        Each keyword names a section (``comm=meter``,
        ``stats=trainer.stats``, ``faults=...``, ``participation=...``,
        ``population=...``); values may be plain dicts or any object
        with ``as_dict()`` (``None`` sections are skipped).  Keys are
        flattened ``section.sub.key`` in deterministic sorted order
        (:func:`repro.core.accounting.flat_record`); numeric leaves also
        land as gauges for the Prometheus exporter."""
        summary: Dict[str, Any] = {}
        for section, value in sorted(sections.items()):
            if value is None:
                continue
            if hasattr(value, "as_dict"):
                value = value.as_dict()
            summary.update(flat_record(value, f"{section}."))
        rec = make_summary_record(engine, summary)
        self.records.append(validate_record(rec))
        self.note_compiles()
        for k, v in summary.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                self.gauge(k, float(v), engine=engine)

    # -- exports (thin wrappers over repro.telemetry.export) -----------------
    def export_jsonl(self, path: str):
        from repro.telemetry.export import export_jsonl
        export_jsonl(self, path)

    def prometheus_text(self) -> str:
        from repro.telemetry.export import prometheus_text
        return prometheus_text(self)

    def export_prometheus(self, path: str):
        from repro.telemetry.export import export_prometheus
        export_prometheus(self, path)

    def chrome_trace(self) -> Dict[str, Any]:
        from repro.telemetry.export import chrome_trace
        return chrome_trace(self)

    def export_trace(self, path: str):
        from repro.telemetry.export import export_trace
        export_trace(self, path)


class NullTelemetry(Telemetry):
    """The disabled recorder: every method is a no-op (``timed`` opens only
    the profiler annotation), ``enabled`` is False so engines skip even
    argument construction on hot paths.  A single module-level instance
    (`NULL_TELEMETRY`) is shared by every trainer that didn't ask for
    telemetry."""

    enabled = False

    def __init__(self):
        self.counters, self.gauges, self.spans, self.records = {}, {}, [], []

    def counter(self, name, value=1, **labels):
        pass

    def gauge(self, name, value, **labels):
        pass

    def sim_span(self, name, start, dur, track, **labels):
        pass

    def host_span(self, name, start, dur, track="host", **labels):
        pass

    def compile_tally(self):
        return compiles.Tally()

    def attention_tally(self):
        return attention.Tally()

    def note_compiles(self):
        pass

    def timed(self, name, track="host", **labels):
        return TraceAnnotation(name)

    def round_record(self, *a, **k):
        pass

    def run_summary(self, engine, **sections):
        pass


NULL_TELEMETRY = NullTelemetry()


def resolve_telemetry(t: Optional[Telemetry]) -> Telemetry:
    """``None`` -> the shared `NullTelemetry`; recorders pass through."""
    if t is None:
        return NULL_TELEMETRY
    if isinstance(t, Telemetry):
        return t
    raise TypeError(f"telemetry must be a Telemetry or None, got {t!r}")
