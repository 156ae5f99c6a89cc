"""Smoke run of the CSE-FSL training path on a TPU.

    python chip_smoke.py             # one chip: phases "train" and "int8"
    python chip_smoke.py --chips 4   # four chips: sharded population phase

Every phase drives the user entry point, ``repro.launch.train.main``, in
this one process, at the full width of qwen3-0.6b (28 layers, d_model
1024, vocab 151,936, bf16, split after layer 4) with random weights from
a fixed seed:

  - "train": cse_fsl, 4 clients, h=2, B=8, S=128, 4 rounds in chunks of
    2, codec none.  Every per-round loss must be finite.
  - "int8": the same run with the int8 uplink codec.  The Pallas quantize
    kernel must be compiled natively into the chunk program (a Mosaic
    custom call named ``quantize_2d``, not interpret mode), and the
    metered uplink bytes must equal ``Int8Codec.wire_bytes`` of the
    payload times the uploads.
  - "population" (``--chips 4`` only): 10,000 virtual clients, cohorts of
    8, with the cohort state sharded over every chip (``--mesh host``),
    against the same run on one chip.  The state must span all four chips
    and the per-round losses must agree within ``POP_RTOL``.

The first failure ends the run with a non-zero exit; nothing falls back
to the CPU or to interpret mode, and without a TPU the script exits 1
before any phase.  The last line of stdout is one JSON object naming the
device.  Run outputs (``--out`` records, telemetry) go to
``chiprun_out/smoke/``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "smoke")

# one bf16 activation ulp is 2^-8 relative; the sharded and one-chip
# programs sum FedAvg and the server's client scan in different orders
POP_RTOL = 2e-2

BASE_ARGV = ["--arch", "qwen3-0.6b", "--method", "cse_fsl", "--h", "2",
             "--batch", "8", "--seq", "128", "--rounds", "4", "--chunk", "2",
             "--log-every", "1"]


def run_train(name, extra, size="full"):
    """One ``repro.launch.train`` run; returns its result with the
    per-round metric rows checked finite, and prints losses and host
    seconds of the first (compiling) and later chunks."""
    from repro.launch import train
    os.makedirs(OUT, exist_ok=True)
    argv = BASE_ARGV + ["--size", size, *extra,
                        "--out", os.path.join(OUT, f"{name}.json"),
                        "--telemetry", os.path.join(OUT, f"{name}.jsonl")]
    print(f"[{name}] repro.launch.train {' '.join(argv)}", flush=True)
    res = train.main(argv)
    rows = res.history
    if len(rows) != 4:
        raise AssertionError(f"[{name}] expected 4 history rows, got {rows}")
    for row in rows:
        bad = {k: v for k, v in row.items()
               if k.endswith("loss") and not math.isfinite(v)}
        if bad:
            raise AssertionError(f"[{name}] non-finite losses {bad} in {row}")
    spans = res.telemetry.spans
    execs = [d.dur + f.dur for d, f in zip(
        [s for s in spans if s.name == "chunk/dispatch"],
        [s for s in spans if s.name == "chunk/fetch"])]
    print(f"[{name}] losses: "
          + json.dumps([{k: v for k, v in r.items() if k.endswith("loss")}
                        for r in rows]))
    print(f"[{name}] host seconds: first chunk (compile + run) {execs[0]}, "
          f"later chunks {execs[1:]}", flush=True)
    return res


def check_kernel_native(res):
    """The lowered chunk program holds the quantize kernel as a Mosaic
    custom call, compiled for the chip and not interpreted; it is found
    by its kernel name, since the aux head's fused CE kernels are Mosaic
    custom calls in every codec's program."""
    import jax
    import jax.numpy as jnp
    tr, fsl = res.trainer, res.trainer.fsl
    pool = res.data.device_pool()
    idx = jax.ShapeDtypeStruct((2, fsl.num_clients, fsl.h, 8), jnp.int32)
    lrs = jax.ShapeDtypeStruct((2,), jnp.float32)
    text = tr.pool_chunk_fn.lower(res.state, pool, idx, lrs).as_text()
    n = text.count('kernel_name = "quantize_2d"')
    if n == 0:
        raise AssertionError("[int8] no quantize_2d Mosaic kernel in the "
                             "chunk program: the quantize kernel was "
                             "interpreted")
    fused = text.count('kernel_name = "fused_ce_fwd"')
    print(f"[int8] chunk program holds {n} quantize_2d and {fused} "
          "fused_ce_fwd Mosaic kernel sites")


def check_uplink_bytes(res, rounds=4):
    """CommMeter's uplink bytes == Int8Codec.wire_bytes(payload) x
    uploads."""
    import jax
    import jax.numpy as jnp
    from repro.transport import Int8Codec
    tr, fsl = res.trainer, res.trainer.fsl
    sample = tr.pool_round_spec(res.data.device_pool(),
                                (fsl.num_clients, fsl.h, 8))
    up_spec, _ = tr.method.payload_specs(tr.bundle, fsl, sample)
    per_upload = sum(Int8Codec().wire_bytes(leaf)
                     for leaf in jax.tree_util.tree_leaves(up_spec)
                     if jnp.issubdtype(leaf.dtype, jnp.floating))
    uploads = rounds * fsl.num_clients * tr._uploads_per_round()
    want = per_upload * uploads
    got = res.meter.counts["uplink_smashed"]
    if got != want:
        raise AssertionError(f"[int8] uplink bytes {got} != {per_upload} "
                             f"x {uploads} uploads = {want}")
    print(f"[int8] uplink bytes {got} == Int8Codec.wire_bytes "
          f"{per_upload} x {uploads} uploads")


def phase_train(size="full"):
    res = run_train("train", ["--clients", "4", "--codec", "none"], size)
    del res
    gc.collect()


def phase_int8(size="full", native=True):
    res = run_train("int8", ["--clients", "4", "--codec", "int8"], size)
    if native:
        check_kernel_native(res)
    check_uplink_bytes(res)
    del res
    gc.collect()


def phase_population(size="full"):
    """Cohort state sharded over every device vs the same run on one."""
    import jax
    pop = ["--population", "10000", "--cohort", "8"]
    res = run_train("population_sharded", pop + ["--mesh", "host"], size)
    ndev = jax.device_count()
    for what, tree in (("cohort state", res.state["clients"]),
                       ("sample pool", res.population.device_pool())):
        spans = {len(leaf.sharding.device_set)
                 for leaf in jax.tree_util.tree_leaves(tree)}
        if spans != {ndev}:
            raise AssertionError(f"[population] {what} spans {spans} "
                                 f"devices, not all {ndev}")
    print(f"[population] cohort state and sample pool span all {ndev} "
          "devices")
    sharded = res.history
    del res
    gc.collect()
    res = run_train("population_one_chip", pop + ["--mesh", "none"], size)
    one = res.history
    del res
    gc.collect()
    worst = 0.0
    for a, b in zip(sharded, one):
        for k in a:
            if k.endswith("loss"):
                rel = abs(a[k] - b[k]) / max(abs(b[k]), 1e-6)
                worst = max(worst, rel)
                if rel > POP_RTOL:
                    raise AssertionError(
                        f"[population] round {a['round']} {k}: sharded "
                        f"{a[k]} vs one chip {b[k]} (rel {rel} > {POP_RTOL})")
    print(f"[population] sharded over {ndev} devices agrees with one chip: "
          f"worst relative loss difference {worst} <= {POP_RTOL}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded population phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py must run from a checkout of the repository "
              f"(no src/repro under {ROOT})", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.common import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}; compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r}; nothing runs off the "
              "chip", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    if args.chips == 4:
        phase_population()
    else:
        phase_train()
        phase_int8()
    if "repro.launch.dryrun" in sys.modules:
        raise AssertionError("repro.launch.dryrun was imported: it rewrites "
                             "XLA_FLAGS and must stay off the chip path")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
