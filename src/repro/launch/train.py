"""Distributed CSE-FSL training driver.

Two modes:
  - ``--mesh host``: run for real on however many devices exist (CPU here;
    the same code path runs on a TPU slice).  Reduced configs + synthetic
    federated data; this is the end-to-end driver used by the examples.
  - ``--mesh pod|multipod``: production mesh; requires real hardware with
    >=256 devices.  (Use ``repro.launch.dryrun`` to validate the program on
    this container.)

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --rounds 50 --clients 4 --h 5 [--size {reduced,full}] [--method cse_fsl]

Population mode (``--population N``) swaps the dense trainer for the
cohort engine (:mod:`repro.population`): N virtual clients sharding one
device-resident token pool, a cohort of ``--cohort`` (default
``--clients``) sampled per aggregation window by ``--sampler``, server
memory independent of N:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --population 10000 --cohort 8 --sampler stratified --network tiered
"""
from __future__ import annotations

import argparse
import json
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.configs.base import FSLConfig, SHAPES, ShapeConfig
from repro.configs.registry import get_config
from repro.core.accounting import CommMeter, CostModel
from repro.core.bundle import transformer_bundle
from repro.core.methods import available_methods
from repro.core.trainer import Trainer
from repro.faults import FAULT_MODELS, fault_from_flags
from repro.network import NETWORK_MODELS, network_from_flags
from repro.population import Population, VirtualPool
from repro.sched import COHORT_SAMPLERS, available_policies, \
    scheduler_from_flags
from repro.transport import available_codecs
from repro.common import bytes_of, count_params, enable_compile_cache
from repro.data import FederatedBatcher, partition_dirichlet, partition_iid, \
    synthetic_lm
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.serve import add_size_args


def build_data(cfg, fsl: FSLConfig, seq_len: int, samples_per_client: int,
               non_iid: bool, seed: int = 0):
    from repro.data import FederatedData
    n = fsl.num_clients
    x, y = synthetic_lm(n * samples_per_client, seq_len + 1, cfg.vocab_size,
                        seed=seed)
    if non_iid:
        # label-skew by leading-token bucket (the LM analogue of the paper's
        # per-writer F-EMNIST skew): Dirichlet over 16 token buckets.
        fed_idx = partition_dirichlet(np.arange(len(x))[:, None], x[:, 0] % 16,
                                      n, seed=seed)
        return FederatedData([x[ci[:, 0]] for ci in fed_idx.inputs],
                             [y[ci[:, 0]] for ci in fed_idx.inputs])
    shards = np.array_split(np.arange(len(x)), n)
    return FederatedData([x[s] for s in shards], [y[s] for s in shards])


class LMBatcher:
    """Adapts FederatedBatcher token pairs to the transformer input pytree."""

    def __init__(self, cfg, fed, batch_size: int, h: int, seed: int = 0):
        self.cfg = cfg
        self.inner = FederatedBatcher(fed, batch_size, h, seed=seed)
        # device-resident path: ``run_compiled`` probes for the pool
        # protocol with hasattr, so only expose it where it works —
        # token-only archs (a vlm pool would carry per-sample image
        # embeds; those fall back to host staging).
        if cfg.family != "vlm":
            self.device_pool = self._device_pool
            self.next_round_indices = self.inner.next_round_indices

    def next_round(self):
        x, y = self.inner.next_round()      # [n,h,B,S]
        inputs = {"tokens": jnp.asarray(x)}
        if self.cfg.family == "vlm":
            n, h, b, s = x.shape
            inputs["image_embeds"] = jnp.zeros(
                (n, h, b, self.cfg.num_image_tokens, self.cfg.d_model),
                jnp.float32)
        return inputs, jnp.asarray(y)

    def _device_pool(self):
        px, py = self.inner.device_pool()
        return {"tokens": px}, py


class LMPool:
    """Adapts a population data backend's token pool to the transformer
    input pytree (same leaf mapping as :class:`LMBatcher`)."""

    def __init__(self, cfg, inner):
        if cfg.family == "vlm":
            raise ValueError("population mode needs poolable (token-only) "
                             f"inputs; {cfg.name} is a vlm")
        self.cfg = cfg
        self.inner = inner
        self.stateless = inner.stateless

    def device_pool(self):
        px, py = self.inner.device_pool()
        return {"tokens": px}, py

    def round_indices(self, ids, rnd: int):
        return self.inner.round_indices(ids, rnd)


def main(argv=None):
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return the
    run: ``trainer``, ``population`` (None without ``--population``),
    ``data`` (the batcher or population pool), ``state``, ``history``,
    ``meter`` and ``telemetry`` (None unless a telemetry output was asked
    for)."""
    from repro.analysis.guards import assert_x64_disabled
    assert_x64_disabled(where="launch/train.py")
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--h", type=int, default=5)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--samples", type=int, default=512)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--method", default="cse_fsl",
                    choices=list(available_methods()))
    ap.add_argument("--codec", default="none",
                    choices=list(available_codecs()),
                    help="uplink wire codec (CommMeter reports the "
                         "compressed wire bytes)")
    ap.add_argument("--model-codec", default="none",
                    choices=list(available_codecs()),
                    help="model-sync (FedAvg up/download) wire codec")
    ap.add_argument("--network", default="ideal",
                    choices=sorted(NETWORK_MODELS),
                    help="per-client link model for the analytic "
                         "wall-clock estimate printed after training")
    ap.add_argument("--bandwidth-mbps", type=float, default=10.0,
                    help="mean uplink rate for --network uniform/lognormal/"
                         "trace (downlink 5x; tiered has per-tier rates)")
    ap.add_argument("--scheduler", default="wait_all",
                    choices=list(available_policies()),
                    help="aggregation-barrier scheduling policy (wait_all "
                         "= legacy everyone-participates barrier, bitwise)")
    ap.add_argument("--deadline-s", type=float, default=30.0,
                    help="wall-clock budget per round for "
                         "--scheduler deadline (arrivals past it are "
                         "dropped, FedAvg renormalizes over participants)")
    ap.add_argument("--faults", default="none",
                    choices=sorted(FAULT_MODELS),
                    help="deterministic fault model (repro.faults): lossy "
                         "wire with checksum-framed retransmission, "
                         "mid-round client crashes, server outages; 'none' "
                         "keeps the legacy bitwise path")
    ap.add_argument("--loss-rate", type=float, default=None,
                    help="per-transmission loss/corruption probability "
                         "(default: the --faults preset's)")
    ap.add_argument("--crash-rate", type=float, default=None,
                    help="per-client per-round crash probability "
                         "(default: the --faults preset's)")
    ap.add_argument("--max-retries", type=int, default=None,
                    help="retransmission budget per payload before the "
                         "sender gives up (wire drop)")
    ap.add_argument("--population", type=int, default=0,
                    help="fleet size N: run the cohort engine "
                         "(repro.population) instead of the dense trainer "
                         "— --clients becomes the per-window cohort size C")
    ap.add_argument("--cohort", type=int, default=0,
                    help="cohort size C for --population (default: "
                         "--clients)")
    ap.add_argument("--sampler", default="uniform",
                    choices=sorted(COHORT_SAMPLERS),
                    help="per-window cohort sampler (stratified draws "
                         "proportionally over --network tiered tiers)")
    ap.add_argument("--mesh", default="none", choices=["none", "host"],
                    help="shard the cohort state over a host mesh "
                         "(population mode; 'host' uses every local device)")
    add_size_args(ap)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--server-update", default="sequential")
    ap.add_argument("--chunk", type=int, default=10,
                    help="rounds fused per compiled dispatch "
                         "(run_compiled); 0 = per-round Python loop")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--out", default=None)
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write the unified round-record stream "
                         "(repro.telemetry JSONL, one validated record per "
                         "line) to PATH")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON timeline to PATH "
                         "(open in Perfetto / chrome://tracing)")
    ap.add_argument("--prom", default=None, metavar="PATH",
                    help="write Prometheus text exposition of telemetry "
                         "counters/gauges to PATH")
    ap.add_argument("--profile-dir", default=None, metavar="PATH",
                    help="bracket training with jax.profiler.start_trace/"
                         "stop_trace writing a TensorBoard/Perfetto XLA "
                         "profile under PATH")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.size == "reduced":
        cfg = cfg.reduced()
    # population mode: the compiled programs see a C-client fleet per
    # aggregation window; N only exists host-side (sampler + lazy state)
    cohort = (args.cohort or args.clients) if args.population \
        else args.clients
    fsl = FSLConfig(num_clients=cohort, h=args.h, lr=args.lr,
                    method=args.method, server_update=args.server_update,
                    codec=args.codec, model_codec=args.model_codec)
    bundle = transformer_bundle(cfg)
    d_local = args.samples
    if args.population:
        if args.scheduler != "wait_all":
            ap.error("--population replaces barrier scheduling with cohort "
                     "sampling; use --scheduler wait_all")
        # N virtual clients sharding one token pool, stateless draws
        x, y = synthetic_lm(args.samples, args.seq + 1, cfg.vocab_size)
        d_local = max(args.batch * args.h, args.samples // 8)
        pool_data = LMPool(cfg, VirtualPool(
            x, y, d_local=d_local, batch_size=args.batch, h=args.h))
        batcher = None
    else:
        fed = build_data(cfg, fsl, args.seq, args.samples, args.non_iid)
        batcher = LMBatcher(cfg, fed, args.batch, args.h)

    # Table II meter
    params_abs = jax.eval_shape(bundle.init,
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
    cm = CostModel(
        n=fsl.num_clients, q=bundle.smashed_bytes_per_sample * args.seq,
        d_local=d_local, w_client=bytes_of(params_abs["client"]),
        w_server=bytes_of(params_abs["server"]),
        aux=bytes_of(params_abs["aux"]))
    meter = CommMeter()

    # One Trainer drives every registered method: the CommProfile of the
    # selected method replaces the old per-method metering branches.
    # The scheduler plans against the selected network's links (wait_all
    # keeps the legacy barrier and builds no mask machinery at all).
    network = network_from_flags(args.network, args.bandwidth_mbps)
    faults = fault_from_flags(args.faults, args.loss_rate, args.crash_rate,
                              args.max_retries)
    # observation-only recorder (analysis rule T001): enabling it never
    # changes the compiled programs or the params/history bitwise
    tele = None
    if args.telemetry or args.trace or args.prom:
        from repro.telemetry import Telemetry
        tele = Telemetry()
    pop = None
    if args.population:
        mesh = None
        if args.mesh == "host":
            mesh = make_host_mesh(model=1, data=jax.device_count())
        pop = Population(bundle, fsl, population=args.population,
                         data=pool_data, sampler=args.sampler,
                         network=network, mesh=mesh, faults=faults,
                         telemetry=tele)
        trainer = pop.trainer
        pop.init()
    else:
        scheduler = scheduler_from_flags(args.scheduler, args.deadline_s)
        trainer = Trainer(bundle, fsl, scheduler=scheduler, network=network,
                          faults=faults, telemetry=tele)
        state = trainer.init()
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    t0 = time.time()

    def cb(rnd, metrics, _state):
        print(f"round {rnd:4d} lr={trainer.lr_at(rnd):.4f} "
              + " ".join(f"{k}={v:.4f}" for k, v in metrics.items()))

    # compiled chunk runner by default — bitwise-identical to the Python
    # loop, minus thousands of per-round dispatch round-trips (--chunk 0
    # falls back to the per-round reference loop)
    if pop is not None:
        state, history = pop.run(args.rounds, chunk=max(args.chunk, 1),
                                 log_every=args.log_every, callback=cb,
                                 meter=meter, cost_model=cm)
    elif args.chunk:
        state, history = trainer.run_compiled(state, batcher, args.rounds,
                                              chunk=args.chunk,
                                              log_every=args.log_every,
                                              callback=cb, meter=meter,
                                              cost_model=cm)
    else:
        state, history = trainer.run(state, batcher, args.rounds,
                                     log_every=args.log_every, callback=cb,
                                     meter=meter, cost_model=cm)
    dt = time.time() - t0
    if args.profile_dir:
        jax.profiler.stop_trace()
        print(f"XLA profile written under {args.profile_dir}")
    print(f"\n{args.rounds} rounds in {dt:.1f}s; "
          f"total comm = {meter.total/2**20:.1f} MiB "
          f"({json.dumps({k: round(v/2**20, 2) for k, v in meter.counts.items()})} MiB)")
    pop_summary = pop_memory = None
    if pop is not None:
        pop_summary = pop.population_summary(history)
        pop_memory = pop.memory_report()
        print(f"population {args.population:,} via {args.sampler!r} "
              f"cohorts of {fsl.num_clients}: "
              f"{pop_summary['unique_clients']} unique clients over "
              f"{pop_summary['windows']} windows"
              + (f", per tier { {k: v['participants'] for k, v in pop_summary['per_tier'].items()} }"
                 if pop_summary["per_tier"] else ""))
        if "straggler_seconds" in pop_summary:
            s = pop_summary["straggler_seconds"]
            print(f"cohort straggler seconds: p50={s['p50']:.1f} "
                  f"p90={s['p90']:.1f} p99={s['p99']:.1f} "
                  f"max={s['max']:.1f}")
        print(f"engine memory {pop_memory['engine_total']/2**20:.2f} MiB "
              f"(independent of N) vs dense per-client extrapolation "
              f"{pop_memory['dense_extrapolated']/2**20:.1f} MiB")
    wallclock = None
    if args.network != "ideal" and pop is None:
        # analytic barrier wall-clock under the selected links — the same
        # time model the AsyncTrainer measures event for event
        est = trainer.wallclock_estimate(cm, args.batch, args.rounds,
                                         network,
                                         batch=batcher.next_round())
        wallclock = est.as_dict()
        print(f"simulated sync wall-clock ({args.network}, "
              f"{args.bandwidth_mbps:g} Mbps up): {est.total:.1f}s "
              f"({est.comm_time:.1f}s transfer, "
              f"{est.model_sync_time:.1f}s model sync over "
              f"{est.agg_events} aggregations)")
    participation = trainer.participation_summary()
    if participation is not None and "mean_cohort" in participation:
        print(f"scheduler {args.scheduler!r} participation: "
              f"mean cohort {participation['mean_cohort']}/{fsl.num_clients}"
              + (f", per tier {participation['tier_participation']}"
                 if "tier_participation" in participation else ""))
    fault_summary = (participation or {}).get("faults")
    if fault_summary is not None:
        mean_p = fault_summary["mean_participants"]
        print(f"faults {args.faults!r}: {fault_summary['retries']} "
              f"retransmissions "
              f"({fault_summary['retransmit_bytes']/2**20:.2f} MiB burned, "
              f"{fault_summary['retry_seconds']:.1f}s backoff), "
              f"{fault_summary['crash_drops']} crashes, "
              f"{fault_summary['wire_drops']} wire drops, "
              f"{fault_summary['outages']} outages survived; "
              f"mean participants "
              + ("n/a" if mean_p is None else f"{mean_p:.2f}")
              + f"/{fsl.num_clients} over {fault_summary['windows']} windows"
              + (f" ({fault_summary['empty_windows']} empty)"
                 if fault_summary["empty_windows"] else ""))
    if args.out:
        # flat deterministic-key-order record (Recordable.to_record): the
        # same flattening the telemetry run summary uses, so downstream
        # consumers parse one shape regardless of which engine ran
        from repro.core.accounting import flat_record
        record = meter.to_record("comm.")
        for prefix, section in (("wallclock.", wallclock),
                                ("participation.", participation),
                                ("population.", pop_summary),
                                ("memory.", pop_memory)):
            if section:
                record.update(flat_record(section, prefix))
        with open(args.out, "w") as f:
            json.dump({"args": vars(args), "history": history,
                       "comm": meter.as_dict(), "wallclock": wallclock,
                       "participation": participation,
                       "faults": fault_summary,
                       "population": pop_summary,
                       "memory": pop_memory,
                       "record": record}, f, indent=1)
    if tele is not None:
        if args.telemetry:
            tele.export_jsonl(args.telemetry)
            print(f"telemetry: {len(tele.records)} records -> "
                  f"{args.telemetry}")
        if args.trace:
            tele.export_trace(args.trace)
            print(f"telemetry: {len(tele.spans)} spans -> {args.trace} "
                  f"(open in Perfetto)")
        if args.prom:
            tele.export_prometheus(args.prom)
            print(f"telemetry: {len(tele.counters) + len(tele.gauges)} "
                  f"series -> {args.prom}")
    return SimpleNamespace(trainer=trainer, population=pop,
                           data=batcher if pop is None else pool_data,
                           state=state, history=history, meter=meter,
                           telemetry=tele)


if __name__ == "__main__":
    main()
