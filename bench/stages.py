"""Device time per stage of a round, and compile counts, for one cell.

The chunk program names its stages with ``jax.named_scope``
(``repro.core.stages``): ``client_step``, ``aux_ce``, ``client_update``,
``server_consume``, ``fedavg``.  XLA keeps each op's name path, and on a
TPU the profiler writes it as the ``tf_op`` stat of the op's event
metadata in the device plane, which ``devtrace.events`` reads beside each
op.  This module reduces a trace to the self time of each stage in the
traced window: an
op's self time is its duration minus what the ops nested in it on the same
line cover (the scan loops of the chunk program hold their bodies' ops),
and it counts for the innermost stage of the op's path, matched by path
component (``transpose(jvp(aux_ce))`` is ``aux_ce``).  Ops under no stage
count as ``unscoped``.  The self times of one device add up to its busy
time.

Run one cell with these readers attached to the harness's ``--trace 1``
run::

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>

It prints the harness's result line with the per-layer metrics below added
under ``metrics`` and the stage seconds under ``stages``:

  client_step_ms .. fedavg_ms  self time of the stage per round traced
  unscoped_share               unscoped self time over busy time, in %
  compiles_in_window           backend compiles during the timed window
  setup_lower_s                jaxpr trace + MLIR lowering seconds in set-up
  setup_compile_s              backend compile or cache-load seconds in
                               set-up

The compile counts come from ``repro.telemetry.compiles``, installed
before the program is built.
"""
from __future__ import annotations

import json
import os
import sys
import time

T_START = time.perf_counter()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

import devtrace  # noqa: E402  (bench/ is on the path, as for run.py)

STAGES = ("client_step", "aux_ce", "client_update", "server_consume",
          "fedavg")
UNSCOPED = "unscoped"
def stage_of(path: str) -> str:
    """The innermost stage among the components of an op's name path."""
    stage = UNSCOPED
    for part in (path or "").split("/"):
        part = devtrace.component(part)
        if part in STAGES:
            stage = part
    return stage


def window_of(ev: dict):
    """The traced window as ``devtrace.summarize_events`` takes it."""
    chunks = [(a, b) for n, a, b in ev["host"]
              if n == devtrace.CHUNK_ANNOTATION]
    if chunks:
        return min(a for a, _ in chunks), max(b for _, b in chunks)
    spans = [(a, b) for d in ev["ops"] for _, a, b in ev["ops"][d]]
    return min(a for a, _ in spans), max(b for _, b in spans)


def self_times(intervals):
    """Self seconds of each ``(t0, t1)``: its length minus what the
    intervals nested in it cover.  An interval that starts inside another
    is nested in it; one that outlasts it is charged only the overlap."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    out = [b - a for a, b in intervals]
    stack = []
    for i in order:
        a, b = intervals[i]
        while stack and intervals[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            p = stack[-1]
            out[p] -= min(b, intervals[p][1]) - a
        stack.append(i)
    return out


def stage_seconds(ev: dict) -> dict:
    """``{stage: seconds}`` of self time in the traced window, with
    ``unscoped`` for ops under no stage, averaged over devices."""
    lo, hi = window_of(ev)
    devices = sorted(ev["ops"])
    out = dict.fromkeys(STAGES + (UNSCOPED,), 0.0)
    for d in devices:
        paths = ev["paths"].get(d) or [""] * len(ev["ops"][d])
        kept = [(max(a, lo), min(b, hi), p)
                for (_, a, b), p in zip(ev["ops"][d], paths)
                if b > lo and a < hi]
        own = self_times([(a, b) for a, b, _ in kept])
        for (_, _, p), s in zip(kept, own):
            out[stage_of(p)] += s / len(devices)
    return out


def metrics(stage_s: dict, busy_s: float, rounds: int, tallies: dict):
    """The nine per-layer values from the stage seconds, the busy seconds
    and rounds of the traced window, and the compile tallies taken at
    ``start`` (before the program is built), ``setup`` (end of set-up) and
    ``window`` (end of the timed window)."""
    out = {}
    if stage_s and rounds > 0 and busy_s > 0:
        for s in STAGES:
            out[f"{s}_ms"] = 1e3 * stage_s[s] / rounds
        out["unscoped_share"] = 100.0 * stage_s[UNSCOPED] / busy_s
    if {"start", "setup", "window"} <= set(tallies):
        setup = tallies["setup"] - tallies["start"]
        out["compiles_in_window"] = (tallies["window"]
                                     - tallies["setup"]).compiles
        out["setup_lower_s"] = setup.secs("trace") + setup.secs("lower")
        out["setup_compile_s"] = setup.secs("compile")
    return out


UNITS = {"client_step_ms": "ms/round", "aux_ce_ms": "ms/round",
         "client_update_ms": "ms/round", "server_consume_ms": "ms/round",
         "fedavg_ms": "ms/round", "unscoped_share": "%",
         "compiles_in_window": "count", "setup_lower_s": "s",
         "setup_compile_s": "s"}


def main(argv, require_tpu: bool = True) -> int:
    """One ``--trace 1`` run of a cell through ``harness.run``, with the
    compile tally taken around set-up and the window, and the trace
    reduced by stage before the harness removes it."""
    root = os.path.dirname(BENCH_DIR)
    sys.path.insert(0, os.path.join(root, "src"))
    import harness
    from repro.telemetry import compiles

    args = harness.parse(list(argv) + ["--trace", "1"])
    compiles.install()
    tallies = {"start": compiles.snapshot()}
    found = {}
    run_window, remove = harness.window, devtrace.remove

    def window(prog, seconds, annotate):
        tallies["setup"] = compiles.snapshot()
        out = run_window(prog, seconds, annotate)
        tallies["window"] = compiles.snapshot()
        return out

    def read_then_remove(trace_dir):
        t = time.perf_counter()
        found["stages"] = stage_seconds(devtrace.events(
            devtrace.find_xplane(trace_dir)))
        print(f"stages: trace read in {time.perf_counter() - t:.1f} s",
              file=sys.stderr)
        remove(trace_dir)

    harness.window, devtrace.remove = window, read_then_remove
    try:
        res = harness.run(args, T_START, root, require_tpu=require_tpu)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    finally:
        harness.window, devtrace.remove = run_window, remove
    stage_s = found.get("stages", {})
    values = metrics(stage_s, res["device"].get("busy_s", 0.0),
                     res["attempted"], tallies)
    res["metrics"].update({k: {"value": v, "unit": UNITS[k]}
                           for k, v in values.items()})
    res["stages"] = stage_s
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
