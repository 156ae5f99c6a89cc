"""Process-wide tally of JAX's trace, lowering and backend-compile events.

JAX reports each phase of building a program through ``jax.monitoring``:

  trace     /jax/core/compile/jaxpr_trace_duration          Python -> jaxpr
  lower     /jax/core/compile/jaxpr_to_mlir_module_duration jaxpr -> MLIR
  compile   /jax/core/compile/backend_compile_duration      XLA compile, or
                                                            the load of a
                                                            persistent-cache
                                                            hit (both run
                                                            inside it)

:func:`install` registers one listener for the process, once; the tally is
process-wide, as JAX's listeners are.  :func:`snapshot` returns an
immutable :class:`Tally`; the difference of two snapshots is what ran
between them.  A jaxpr trace of an inner
``jax.jit`` runs inside its caller's trace, so trace seconds count the
union of the trace intervals, not their sum; counts count every event.

The listener runs on the host after JAX has finished the phase, so it never
touches a compiled program (rule T001).
"""
from __future__ import annotations

import dataclasses
import threading
from typing import List, Tuple

from jax import monitoring

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
PHASES = ("trace", "lower", "compile")


@dataclasses.dataclass(frozen=True)
class Tally:
    """Events and seconds per phase (``trace``, ``lower``, ``compile``)."""
    counts: Tuple[int, int, int] = (0, 0, 0)
    seconds: Tuple[float, float, float] = (0.0, 0.0, 0.0)

    def count(self, phase: str) -> int:
        return self.counts[PHASES.index(phase)]

    def secs(self, phase: str) -> float:
        return self.seconds[PHASES.index(phase)]

    @property
    def compiles(self) -> int:
        """Backend compiles (or persistent-cache loads)."""
        return self.count("compile")

    def __sub__(self, other: "Tally") -> "Tally":
        return Tally(tuple(a - b for a, b in zip(self.counts, other.counts)),
                     tuple(a - b for a, b in zip(self.seconds,
                                                 other.seconds)))


class _Listener:
    """The registered callback: per phase, a count and the union of the
    event intervals.  Events end inner-first, so an interval that starts
    at or after a newer one's start lies inside it and is folded in."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = [0, 0, 0]
        self._seconds = [0.0, 0.0, 0.0]
        self._open: List[List[Tuple[float, float]]] = [[], [], []]

    def __call__(self, event: str, start: float, end: float, **_):
        phase = EVENTS.get(event)
        if phase is None:
            return
        i = PHASES.index(phase)
        with self._lock:
            self._counts[i] += 1
            tops = self._open[i]
            while tops and tops[-1][0] >= start:
                s, e = tops.pop()
                self._seconds[i] -= e - s
            tops.append((start, end))
            self._seconds[i] += end - start
            if len(tops) > 1024:
                del tops[:-1024]

    def snapshot(self) -> Tally:
        with self._lock:
            return Tally(tuple(self._counts), tuple(self._seconds))


_LISTENER = _Listener()
_INSTALLED = False
_INSTALL_LOCK = threading.Lock()


def install() -> None:
    """Register the listener with ``jax.monitoring``; later calls are
    no-ops.  Only events after the first call are counted."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if not _INSTALLED:
            monitoring.register_event_time_span_listener(_LISTENER)
            _INSTALLED = True


def snapshot() -> Tally:
    """The tally so far (all zero before :func:`install`)."""
    return _LISTENER.snapshot()
