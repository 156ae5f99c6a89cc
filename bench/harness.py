"""One run of one benchmark cell: set-up, measured window, correctness.

Everything a cell needs is found by name from ``BENCHMARK.json``:

  bench/configs/<config>.json    model sizes, source, assumed split sizes
  bench/models/<family>.py       sizes -> the program's config, reference
  bench/flops/<family>.py        model FLOPs of one round
  bench/traffic/<traffic>.json   the training mix (see traffic.py)
  bench/codecs/<codec>.py        the reference of the mix's uplink codec,
                                 and the program kernel that runs it
  bench/workloads/<cell>.json    the limits of the correctness comparison
  bench/metrics/<metric>.py      one reader per per-layer metric
  bench/peaks.json               per-chip peaks keyed by device kind

A run: set-up builds the program's ``Trainer`` with the benchmark's seeded
weights and traffic, and drives its compiled chunk program once from the
seed (this compiles, or loads it from the cache, and is the program's
first steps, which the reference follows); the window then calls
``Trainer.run_compiled`` one chunk at a time for ``--seconds`` seconds;
after it the program is freed and the plain reference replays the first
chunk's rounds for the comparison that decides ``correct``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
GIB = 2 ** 30


class BenchError(Exception):
    """A run that cannot produce a result; the message goes to stderr."""


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"missing benchmark file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    if not os.path.isfile(path):
        raise BenchError(f"missing benchmark file {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with every file it names loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    family: object          # bench/models/<family>.py
    flops: object           # bench/flops/<family>.py
    end_to_end: list
    per_layer: list
    bench_dir: str

    @property
    def numbers(self) -> dict:
        return self.family.numbers(self.config)


def load_cell(root: str, workload: str) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    bdir = os.path.join(root, "bench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    if int(w["chips"]) != 1:
        # the window drives one Trainer on the default device; a cell
        # across chips needs a program placed on them (Population.run)
        raise BenchError(f"workload {workload!r} asks for {w['chips']} "
                         "chips; this harness drives one chip only")
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    family = config["family"]
    limits = load_json(os.path.join(bdir, "workloads",
                                    f"{workload}.json"))["limits"]
    if not limits:
        raise BenchError(f"workload {workload!r} compares no number")
    models = load_module(os.path.join(bdir, "models", f"{family}.py"),
                         f"bench_models_{family}")
    if not callable(getattr(models, "reference", None)):
        raise BenchError(f"bench/models/{family}.py has no reference(); "
                         "a cell needs its family's plain reference")
    mix = load_json(os.path.join(bdir, "traffic", f"{w['traffic']}.json"))
    import codec
    try:
        uplink = codec.module(mix["codec"])
    except ValueError as e:
        raise BenchError(str(e)) from e
    unknown = set(limits) - set(COMPARED)
    if unknown:
        raise BenchError(f"workload {workload!r} limits {sorted(unknown)}, "
                         f"which no run reads; compared: {list(COMPARED)}")
    if "codec_missing" in limits and not getattr(uplink, "KERNEL", None):
        raise BenchError(f"codec {mix['codec']!r} names no program kernel "
                         "to count on the trace (codec_missing)")
    wanted = lambda m: "workloads" not in m or workload in m["workloads"]
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, mix=mix,
        limits=limits, family=models,
        flops=load_module(os.path.join(bdir, "flops", f"{family}.py"),
                          f"bench_flops_{family}"),
        end_to_end=[m for m in bench["end_to_end"] if wanted(m)],
        per_layer=[m for m in bench["per_layer"] if wanted(m)],
        bench_dir=bdir)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def program_bundle(cfg):
    """The program's split model for ``cfg`` (a seam for fault tests)."""
    from repro.core.bundle import transformer_bundle
    return transformer_bundle(cfg)


def make_trainer(bundle, fsl):
    """The program's trainer (a seam for fault tests)."""
    from repro.core.trainer import Trainer
    return Trainer(bundle, fsl)


class Program:
    """The program built for a cell; ``start(seed)`` gives it the seed's
    weights, traffic and a fresh state (the compiled programs stay)."""

    def __init__(self, cell: Cell):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import FSLConfig

        import weights

        mix = cell.mix
        self.cell = cell
        self.cfg = cell.family.program_config(cell.config)
        self.fsl = FSLConfig(num_clients=mix["clients"], h=mix["h"],
                             lr=float(mix["lr"]), method="cse_fsl",
                             codec=mix["codec"])
        bundle = program_bundle(self.cfg)
        self.abstract = jax.eval_shape(
            bundle.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        # the program's own state layout, filled with the benchmark's
        # weights: init_state hands its key to bundle.init, which draws
        # every leaf from it, all in one jitted call
        self.bundle = dataclasses.replace(
            bundle, init=weights.param_fn(self.abstract))
        self.trainer = make_trainer(self.bundle, self.fsl)
        method = self.trainer.method
        self._init = jax.jit(lambda k: method.init_state(self.bundle,
                                                         self.fsl, k))
        self.rounds_per_chunk = int(mix["rounds_per_chunk"])
        self.state = None

    def start(self, seed: int):
        from repro.common import bytes_of
        from repro.core.accounting import CostModel

        import traffic as traffic_mod
        import weights

        mix = self.cell.mix
        self.seed = seed
        self.state = None           # frees the last seed's state first
        self.state = self._init(weights.seed_key(seed))
        self.traffic = traffic_mod.Traffic(mix, self.cfg.vocab_size, seed)
        self.batcher = traffic_mod.PlanBatcher(self.traffic)
        self.cost_model = CostModel(
            n=self.fsl.num_clients,
            q=self.bundle.smashed_bytes_per_sample * mix["seq"],
            d_local=mix["samples_per_client"],
            w_client=bytes_of(self.abstract["client"]),
            w_server=bytes_of(self.abstract["server"]),
            aux=bytes_of(self.abstract["aux"]))
        return self

    def chunk(self, meter):
        """One compiled chunk through ``Trainer.run_compiled``; returns its
        per-round history rows (fetched by the program itself)."""
        r = self.rounds_per_chunk
        self.state, history = self.trainer.run_compiled(
            self.state, self.batcher, r, chunk=r, log_every=1, meter=meter,
            cost_model=self.cost_model)
        return history

    def param_deltas(self):
        """Per-leaf L2 norms of each stored copy's change from the seeded
        weights: ``{leaf: [norm per client row]}`` for the client stage
        and aux head, ``{leaf: [norm]}`` for the server."""
        import jax
        import weights
        state = self.state
        now = {"client": state["clients"]["params"]["params"],
               "aux": state["clients"]["params"]["aux"],
               "server": jax.tree_util.tree_map(lambda a: a[None],
                                                state["server"]["params"])}
        return deltas(now, weights.make_params(self.abstract, self.seed))


def deltas(now, start):
    """``{leaf name: [L2 norm of (now[row] - start)] per row}`` over
    float32; ``now``'s leaves carry a leading row axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import weights

    @jax.jit
    def norms(now, start):
        return jax.tree_util.tree_map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)[None]),
                axis=tuple(range(1, a.ndim)))), now, start)

    out = norms(now, start)
    return {weights.leaf_name(p): np.asarray(v, np.float64).tolist()
            for p, v in jax.tree_util.tree_flatten_with_path(out)[0]}


def losses_of(history):
    return [(float(r["client_loss"]), float(r["server_loss"]))
            for r in history]


# ---------------------------------------------------------------------------
# The comparison that decides `correct`
# ---------------------------------------------------------------------------

# leaves whose reference change is below this share of the median leaf's
# move by round-off alone and are left out of the change comparison
STILL_LEAF = 1e-3


def compare(prog_losses, prog_deltas, ref_losses, ref_deltas):
    """The numbers compared: ``loss_gap``, the largest absolute gap of a
    round's client or server loss; ``delta_gap``, over leaves and stored
    copies, the largest gap between the program's and the reference's norm
    of a leaf's change, over the larger of the reference's norm of that
    leaf and of the median leaf."""
    import numpy as np
    loss_gap = max(abs(a - b) for pa, ra in zip(prog_losses, ref_losses)
                   for a, b in zip(pa, ra))
    if len(prog_losses) != len(ref_losses):
        loss_gap = math.inf
    ref = {k: float(v[0]) for k, v in ref_deltas.items()}
    med = float(np.median(list(ref.values())))
    delta_gap = 0.0
    for k, r in ref.items():
        if r < STILL_LEAF * med:
            continue
        rows = prog_deltas.get(k)
        if rows is None:
            return loss_gap, math.inf
        gap = max(abs(p - r) for p in rows) / max(r, med)
        delta_gap = max(delta_gap, gap)
    return loss_gap, delta_gap


def run_reference(cell: Cell, seed: int, abstract, precision="fp32",
                  uploads=None):
    """The reference's per-round losses and per-leaf change norms over the
    first chunk's rounds, from the seed's weights and traffic; the cell's
    family (``bench/models/<family>.py``) brings the reference.  A list
    given as ``uploads`` gets the reference's ``uploads``: ``(round,
    client, upload)`` of every upload as computed, before the codec."""
    import jax
    import traffic as traffic_mod
    import weights
    mix = cell.mix
    tr = traffic_mod.Traffic(mix, cell.numbers["vocab"], seed)
    ref = cell.family.reference(cell.numbers, mix, precision)
    params, losses = ref.run(weights.make_params(abstract, seed), tr,
                             int(mix["rounds_per_chunk"]))
    if uploads is not None:
        uploads.extend(ref.uploads)
    now = jax.tree_util.tree_map(lambda a: a[None], params)
    d = deltas(now, weights.make_params(abstract, seed))
    return losses, d


def codec_gap(cell: Cell, transport, uploads, seed: int):
    """Over the reference's uploads of the compared rounds, cast to the
    model's type: the widest gap between 1 and the ratio of two errors,
    the norm of (received - upload) through the program's uplink codec
    (``transport``, the trainer's own) over that through the reference
    codec (``bench/codecs/<codec>.py``), each decoded to the model's type.
    A skipped codec reads 1; a coarser or biased one far above 0.

    Each side's received upload leaves its own program in the model's
    type: a cast to it inside ``err`` may be elided where the compiler
    allows excess precision (it is on the TPU), which would compare the
    program's rounded upload with an unrounded reference."""
    import jax
    import jax.numpy as jnp
    import codec
    dtype = jnp.dtype(cell.config["torch_dtype"])
    ref_rt = codec.uplink(cell.mix["codec"], dtype)
    prog_rt = jax.jit(transport.code_uplink)

    @jax.jit
    def err(received, x):
        return jnp.linalg.norm(received.astype(jnp.float32)
                               - x.astype(jnp.float32))

    gap = 0.0
    for rnd, client, x in uploads:
        x = x.astype(dtype)
        e_prog = float(err(prog_rt(x, transport.unit_key(rnd, client)), x))
        e_ref = float(err(ref_rt(x.astype(jnp.float32),
                                 codec.upload_key(seed, rnd, client)), x))
        if e_ref > 0:
            gap = max(gap, abs(e_prog / e_ref - 1.0))
        elif e_prog > 0:
            return math.inf
    return gap


def codec_missing(cell: Cell, summary, rounds: int):
    """In a traced run: the share of the traced rounds' uploads (one a
    client a round) for which the trace shows no call of the kernel that
    the program's codec runs (``KERNEL`` of ``bench/codecs/<codec>.py``)."""
    import codec
    calls, _ = summary.kernel(codec.module(cell.mix["codec"]).KERNEL)
    return max(0.0, 1.0 - calls / (rounds * cell.mix["clients"]))


# the numbers a workload file may hold a limit for; one that is not read
# in a run (codec_missing without a trace) is not compared there
COMPARED = ("loss_gap", "delta_gap", "codec_gap", "codec_missing")


def checks_of(cell: Cell, loss_gap, delta_gap, **read):
    """The numbers that have a limit in the cell's workload file and are
    read in this run, each beside it, and whether every one is within
    it."""
    values = {"loss_gap": loss_gap, "delta_gap": delta_gap, **read}
    checks = {k: {"value": values[k], "limit": lim}
              for k, lim in cell.limits.items()
              if values.get(k) is not None}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


# ---------------------------------------------------------------------------
# Devices
# ---------------------------------------------------------------------------


def find_devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devs[0].platform!r}; the "
                         "benchmark runs on the chip only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def peaks_of(kind: str, require_tpu: bool):
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind in table:
        return table[kind]
    if require_tpu:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return None


def peak_memory(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return max(peaks)


def state_bytes(state, devs):
    """Bytes of the state's shards on each device; the largest."""
    import jax
    per = {d: 0 for d in devs}
    for leaf in jax.tree_util.tree_leaves(state):
        for s in leaf.addressable_shards:
            if s.device in per:
                per[s.device] += s.data.nbytes
    return max(per.values())


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None):
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def window(prog: Program, seconds: float, annotate):
    """Chunks until ``seconds`` have passed; returns (rounds, failed
    rounds, elapsed seconds).  The window closes after the fetch of the
    chunk that crosses ``seconds``."""
    rounds = failed = 0
    t0 = time.perf_counter()
    while True:
        with annotate():
            history = prog.chunk(None)
        rounds += len(history)
        failed += sum(1 for l in losses_of(history)
                      if not all(math.isfinite(v) for v in l))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return rounds, failed, elapsed


def run(args, t_start: float, root: str, require_tpu: bool = True):
    """One benchmark run; returns the result dict."""
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        raise BenchError(f"no program next to the benchmark (no src/repro "
                         f"under {root})")
    cell = load_cell(root, args.workload)
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, BENCH_DIR)
    from repro.common import enable_compile_cache
    enable_compile_cache()
    import jax
    # cache every program, small ones too, so a warm set-up compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = find_devices(cell.chips, require_tpu)
    kind = devs[0].device_kind
    peaks = peaks_of(kind, require_tpu)

    prog = Program(cell).start(args.seed)
    from repro.core.accounting import CommMeter
    # the first steps, metered: run_compiled rebuilds its CommProfile on
    # every call given a meter, which a caller running many chunks per call
    # pays once, so the window's one-chunk calls run without one and the
    # wire bytes per round come from these rounds
    meter = CommMeter()
    first = losses_of(prog.chunk(meter))
    metered_rounds = len(first)
    prog_deltas = prog.param_deltas()
    setup_s = time.perf_counter() - t_start

    trace_dir = None
    if args.trace:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir)
        annotate = lambda: jax.profiler.TraceAnnotation("bench/chunk")
    else:
        annotate = contextlib.nullcontext
    rounds, failed, elapsed = window(prog, args.seconds, annotate)
    if args.trace:
        jax.profiler.stop_trace()
    mem_peak = peak_memory(devs)
    st_bytes = state_bytes(prog.state, devs)
    abstract = prog.abstract
    transport = prog.trainer.transport
    del prog
    gc.collect()

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    mix = cell.mix
    tokens = rounds * mix["clients"] * mix["h"] * mix["batch"] * mix["seq"]
    breakdown = None
    read = {}
    if args.trace:
        import devtrace as trace_mod
        t = time.perf_counter()
        summary = trace_mod.summarize(trace_dir, len(devs))
        trace_mod.remove(trace_dir)
        print(f"bench: trace read in {time.perf_counter() - t:.1f} s",
              file=sys.stderr)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        ctx = MetricContext(cell=cell, peaks=peaks, chips=len(devs),
                            summary=summary, rounds_traced=rounds,
                            meter=meter.counts, metered_rounds=metered_rounds,
                            state_bytes=st_bytes)
        metrics = per_layer_metrics(cell, ctx)
        if "codec_missing" in cell.limits:
            read["codec_missing"] = codec_missing(cell, summary, rounds)
    else:
        values = {"tokens_per_s": tokens / elapsed,
                  "peak_hbm_gib": mem_peak / GIB,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    uploads = [] if "codec_gap" in cell.limits else None
    ref_losses, ref_deltas = run_reference(cell, args.seed, abstract,
                                           uploads=uploads)
    loss_gap, delta_gap = compare(first, prog_deltas, ref_losses, ref_deltas)
    if uploads is not None:
        read["codec_gap"] = codec_gap(cell, transport, uploads, args.seed)
    del uploads
    correct, checks = checks_of(cell, loss_gap, delta_gap, **read)
    return result_line(correct, rounds, failed, metrics, device, checks,
                       breakdown)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader may read."""
    cell: Cell
    peaks: dict
    chips: int
    summary: object         # trace.Summary of the traced window
    rounds_traced: int      # rounds completed in the traced window
    meter: dict             # CommMeter counts over the metered rounds
    metered_rounds: int
    state_bytes: int        # the state's bytes on the fullest device


def per_layer_metrics(cell: Cell, ctx: MetricContext) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = load_module(os.path.join(cell.bench_dir, "metrics",
                                          f"{m['name']}.py"),
                             f"bench_metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float, root: str, require_tpu: bool = True) -> int:
    args = parse(argv)
    try:
        res = run(args, t_start, root, require_tpu=require_tpu)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res))
    return 0
