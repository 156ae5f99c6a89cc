"""Process-wide tally of the attention calls JAX traced, by path.

``models.layers.attention`` records one ``jax.monitoring`` event,
``kernels.ops.ATTENTION_EVENT``, for every call as it is traced: through
the flash kernel (``kernels.ops.flash_attention``, path ``kernel``) or
through the XLA path (``xla``).  A kernel call also gives the (q-block,
kv-block) pairs its mask's block map computes and the pairs of the whole
grid, so :attr:`Tally.block_share` is the share of score tiles the kernel
computes (a 1,024-key window at S 4,096 in 512-blocks: 21 of 64; causal:
36).  The traced code only records the event, as JAX records its compile
phases for :mod:`repro.telemetry.compiles`; this module listens.

Counts are of traces, not of executions: a layer scanned over depth, or
vmapped over clients, is one call, and a program traced again counts
again.  :func:`install` registers the listener once for the process;
:func:`snapshot` returns an immutable :class:`Tally`, and the difference
of two snapshots is what was traced between them.
"""
from __future__ import annotations

import dataclasses
import threading

from jax import monitoring

from repro.kernels.ops import ATTENTION_EVENT


@dataclasses.dataclass(frozen=True)
class Tally:
    """Attention calls traced through the kernel and through XLA, and the
    kernel calls' computed and total block pairs."""
    kernel: int = 0
    xla: int = 0
    pairs: int = 0
    grid_pairs: int = 0

    @property
    def block_share(self) -> float:
        """Computed block pairs over the kernel grids' pairs (0 with no
        kernel call)."""
        return self.pairs / self.grid_pairs if self.grid_pairs else 0.0

    def __sub__(self, other: "Tally") -> "Tally":
        return Tally(*(a - b for a, b in zip(dataclasses.astuple(self),
                                             dataclasses.astuple(other))))


class _Listener:
    """The registered callback: adds each attention event to the tally."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tally = Tally()

    def __call__(self, event: str, path: str = "", pairs: int = 0,
                 grid_pairs: int = 0, **_):
        if event != ATTENTION_EVENT:
            return
        with self._lock:
            t = self._tally
            self._tally = dataclasses.replace(
                t, kernel=t.kernel + (path == "kernel"),
                xla=t.xla + (path == "xla"), pairs=t.pairs + pairs,
                grid_pairs=t.grid_pairs + grid_pairs)

    def snapshot(self) -> Tally:
        with self._lock:
            return self._tally


_LISTENER = _Listener()
_INSTALLED = False
_INSTALL_LOCK = threading.Lock()


def install() -> None:
    """Register the listener with ``jax.monitoring``; later calls are
    no-ops.  Only calls traced after the first call are counted."""
    global _INSTALLED
    with _INSTALL_LOCK:
        if not _INSTALLED:
            monitoring.register_event_listener(_LISTENER)
            _INSTALLED = True


def snapshot() -> Tally:
    """The tally so far (all zero before :func:`install`)."""
    return _LISTENER.snapshot()
