"""Readings behind a cell's correctness limits, on the chip at the cell's size.

    python bench/calibrate.py --workload <name> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--faults half_batch,altered,...] \\
        [--fault-seeds 1,2,3]

For every seed the program runs its first chunk from the seed (the same
call and feed as a benchmark run's set-up), and the plain reference
replays those rounds; each line of output gives the compared numbers of
one reading (``codec_gap`` on the reference's uploads through the
reading's own uplink codec; none for the control):

  program  the program as it is (the lower reading: the largest over seeds)
  control  the cell's reference (its family's, through the harness)
           computed in fp8 in the program's place
  <fault>  the program with a fault of bench/faults.py planted at the
           harness's ``program_bundle`` or ``make_trainer`` seam

The ``unchanged`` fault reads 1 on ``delta_gap`` by construction (no leaf
moves) and is not run.  A benchmark run never runs this script.
"""
import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def seeds_of(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_of, required=True)
    ap.add_argument("--control-seeds", type=seeds_of, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=seeds_of, default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    from repro.common import enable_compile_cache
    enable_compile_cache()
    import gc
    import harness
    import faults

    cell = harness.load_cell(ROOT, args.workload)
    readings = {}           # (kind, seed) -> (losses, deltas)
    transports = {}         # kind -> the program's uplink transport

    def drive(kind, prog, seeds):
        transports[kind] = prog.trainer.transport
        for seed in seeds:
            t = time.perf_counter()
            prog.start(seed)
            losses = harness.losses_of(prog.chunk(None))
            readings[(kind, seed)] = (losses, prog.param_deltas())
            prog.state = None
            print(f"# {kind} seed {seed}: {time.perf_counter() - t:.1f} s",
                  file=sys.stderr, flush=True)

    prog = harness.Program(cell)
    abstract = prog.abstract
    drive("program", prog, args.seeds)
    del prog
    for name in [f for f in args.faults.split(",") if f]:
        gc.collect()
        seam = "program_bundle" if name in faults.BUNDLE_FAULTS \
            else "make_trainer"
        sound = getattr(harness, seam)
        setattr(harness, seam, {**faults.BUNDLE_FAULTS,
                                **faults.TRAINER_FAULTS}[name])
        try:
            prog = harness.Program(cell)
        finally:
            setattr(harness, seam, sound)
        drive(name, prog, args.fault_seeds)
        del prog
    gc.collect()

    for seed in sorted(set(args.seeds) | set(args.fault_seeds)
                       | set(args.control_seeds)):
        t = time.perf_counter()
        uploads = []
        ref_l, ref_d = harness.run_reference(cell, seed, abstract,
                                             uploads=uploads)
        print(f"# reference seed {seed}: {time.perf_counter() - t:.1f} s",
              file=sys.stderr, flush=True)
        if seed in args.control_seeds:
            readings[("control", seed)] = harness.run_reference(
                cell, seed, abstract, precision="fp8")
        for (kind, s), (losses, d) in sorted(readings.items()):
            if s != seed:
                continue
            loss_gap, delta_gap = harness.compare(losses, d, ref_l, ref_d)
            gap = None if kind == "control" else harness.codec_gap(
                cell, transports[kind], uploads, seed)
            print(json.dumps({"workload": args.workload, "kind": kind,
                              "seed": seed, "loss_gap": loss_gap,
                              "delta_gap": delta_gap, "codec_gap": gap,
                              "losses": losses, "ref_losses": ref_l}),
                  flush=True)
        del uploads
    return 0


if __name__ == "__main__":
    sys.exit(main())
