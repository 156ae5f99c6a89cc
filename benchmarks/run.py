"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only NAME] [--smoke]

Order matters for runtime: the analytic tables run in seconds, the
convergence benchmarks train the paper's CNNs for real on CPU.
``--smoke`` forwards ``smoke=True`` to every suite whose ``main`` takes
it (the perf suites) — the fast CI path that still exercises the
asserted acceptance bars and writes the ``BENCH_*.json`` artifacts.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

from benchmarks import fig6_async_order, fig9_codec_tradeoff, \
    fig45_convergence, fig78_aux_arch, fig_faults, fig_population, \
    fig_sched, fig_wallclock, perf_bench, roofline_report, \
    table2_comm_storage, table5_tradeoff, table34_aux_params

SUITES = [
    ("table2_comm_storage", table2_comm_storage.main),
    ("table34_aux_params", table34_aux_params.main),
    ("fig45_convergence", fig45_convergence.main),
    ("fig6_async_order", fig6_async_order.main),
    ("fig78_aux_arch", fig78_aux_arch.main),
    ("fig9_codec_tradeoff", fig9_codec_tradeoff.main),
    ("fig_wallclock", fig_wallclock.main),
    ("fig_sched", fig_sched.main),
    ("fig_faults", fig_faults.main),
    ("table5_tradeoff", table5_tradeoff.main),
    ("perf_bench", perf_bench.main),
    ("fig_population", fig_population.main),
    ("roofline_report", roofline_report.main),
]


def main():
    from repro.analysis.guards import assert_x64_disabled
    assert_x64_disabled(where="benchmarks/run.py")
    from repro.common import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI path: forwarded to suites that take it")
    args = ap.parse_args()

    failures = []
    for name, fn in SUITES:
        if args.only and args.only != name:
            continue
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(fn).parameters:
            kwargs["smoke"] = True
        t0 = time.time()
        try:
            fn(**kwargs)
            print(f"\n[{name}] OK in {time.time() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
            print(f"\n[{name}] FAILED after {time.time() - t0:.1f}s")
    print(f"\n{'=' * 72}\nbenchmarks: {len(SUITES) - len(failures)}/"
          f"{len(SUITES)} OK" + (f"; failed: {failures}" if failures else ""))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
