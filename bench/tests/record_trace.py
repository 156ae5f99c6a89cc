"""Record the small TPU trace that ``test_bench_stages.py`` reads.

    python3 bench/tests/record_trace.py <out.xplane.pb.gz> [--codec int8]

A tiny dense cse_fsl program (2 clients, h=2, 2 layers cut after the
first, d 64, vocab 256, B=1, S=32, 2 rounds a chunk; uplink codec
"none" unless ``--codec`` names another) runs its compiled chunk once to
warm up, then two chunks under ``jax.profiler``, each inside the
benchmark's ``bench/chunk`` annotation, as a ``--trace 1`` run drives
them.  The xplane file is written gzipped.  Runs on the chip only.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

MIX = {"clients": 2, "h": 2, "batch": 1, "seq": 32, "rounds_per_chunk": 2,
       "samples_per_client": 16, "lr": 0.1, "codec": "none"}


def tiny_config():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny_dense", family="dense", num_layers=2,
                       d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
                       d_ff=128, vocab_size=256, qk_norm=True,
                       qkv_bias=False, rope_theta=1e6, cut_layer=1,
                       aux_rank=16, dtype="bfloat16", remat=True,
                       swa_window=0)


def record(out: str, codec: str = "none"):
    import jax
    from repro.configs.base import FSLConfig
    from repro.core.bundle import transformer_bundle
    from repro.core.trainer import Trainer
    import traffic as traffic_mod

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: no TPU; the recorded trace has to "
                         "come from the chip")
    mix = dict(MIX, codec=codec)
    fsl = FSLConfig(num_clients=mix["clients"], h=mix["h"], lr=mix["lr"],
                    method="cse_fsl", codec=mix["codec"])
    cfg = tiny_config()
    trainer = Trainer(transformer_bundle(cfg), fsl)
    state = trainer.init(0)
    batcher = traffic_mod.PlanBatcher(
        traffic_mod.Traffic(mix, cfg.vocab_size, seed=1))
    r = mix["rounds_per_chunk"]
    state, _ = trainer.run_compiled(state, batcher, r, chunk=r, log_every=1)
    trace_dir = tempfile.mkdtemp(prefix="stage-trace-")
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench/chunk"):
                state, _ = trainer.run_compiled(state, batcher, r, chunk=r,
                                                log_every=1)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        with open(path, "rb") as src, gzip.open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)
        print(f"record_trace: {os.path.getsize(path)} bytes, "
              f"{os.path.getsize(out)} gzipped, to {out}")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out")
    ap.add_argument("--codec", default="none")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    record(args.out, args.codec)


if __name__ == "__main__":
    main(sys.argv[1:])
