"""Method-agnostic host-level trainer.

One training loop for every registered :class:`FSLMethod`: the Trainer owns
jit + donation, the lr schedule, the aggregation cadence (C), callbacks /
history, and — when given a :class:`CostModel` — integrated communication
metering driven by the method's declarative :class:`CommProfile` (no
per-method branching in the drivers).

  trainer = Trainer(bundle, fsl)            # method resolved from fsl.method
  state = trainer.init(seed=0)
  state, history = trainer.run(state, batcher, num_rounds=50,
                               log_every=10, meter=CommMeter(), cost_model=cm)

``run`` is the per-round reference loop (one jitted dispatch per round);
``run_compiled(..., chunk=R)`` fuses R rounds into one donated
``lax.scan`` program and is bitwise-identical to it — use it whenever the
host loop, not the math, is the bottleneck (see README "Performance").

``batcher.next_round()`` must yield ``(inputs, labels)`` pytrees with
leading dims ``[n_clients, h, B, ...]`` — the unified batch contract all
methods consume.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FSLConfig
from repro.core.accounting import CommMeter, CostModel
from repro.core.bundle import SplitModelBundle
from repro.core.methods import CommProfile, FSLMethod, get_method


def _stack_rounds(*xs):
    """Stack one leaf across a chunk of rounds.  Host arrays stack on the
    host first (one device transfer per leaf, not R), device arrays stack
    on device.

    LEGACY FALLBACK ONLY: batchers implementing the device-pool protocol
    (``device_pool()`` + ``next_round_indices()``, see
    :class:`repro.data.FederatedBatcher`) never hit this — the compiled
    path ships a tiny int32 index plan per chunk and gathers batches from
    the on-device pool in-scan instead of staging values host-side."""
    if all(isinstance(x, np.ndarray) for x in xs):
        return jnp.asarray(np.stack(xs))
    return jnp.stack([jnp.asarray(x) for x in xs])


class AggregationCadence:
    """The paper's every-C-batches aggregation schedule (Eq. 14 cadence).

    Aggregation fires whenever the cumulative per-client batch count
    crosses a multiple of C — *threshold crossing*, not ``count % C == 0``,
    so the schedule is correct also when C is not a multiple of the round
    granularity h (a round that crosses a threshold fires exactly one
    aggregation; ``% C`` would fire late or never, e.g. h=3, C=2).
    Shared by the synchronous :class:`Trainer` and the event-driven
    :class:`repro.core.async_trainer.AsyncTrainer` so both realize the
    identical schedule for the same (h, C) — zero-latency async runs are
    comparable to sync runs round for round.
    """

    def __init__(self, agg_every: int, batches_done: int = 0):
        self.agg_every = agg_every
        self.batches_done = batches_done

    def advance(self, num_batches: int) -> bool:
        """Account ``num_batches`` more per-client batches; True if an
        aggregation threshold was crossed."""
        prev = self.batches_done
        self.batches_done += num_batches
        return self.batches_done // self.agg_every > prev // self.agg_every


@dataclasses.dataclass
class Trainer:
    bundle: SplitModelBundle
    fsl: FSLConfig
    donate: bool = True
    method: Optional[Union[str, FSLMethod]] = None  # default: fsl.method
    server_constraint: Optional[Callable] = None
    # wire codecs: None resolves fsl.codec; a string names an uplink codec;
    # a repro.transport.Transport sets both directions explicitly.
    transport: Optional[Any] = None
    # scheduling: None/"wait_all" keeps the legacy everyone-participates
    # barrier (bitwise — no mask machinery is even built); a policy name
    # or repro.sched.SchedulerPolicy instance gates FedAvg participation
    # per round.  ``network`` is the NetworkModel the policy plans against
    # (default: the ideal network, i.e. scheduling on compute alone).
    scheduler: Optional[Any] = None
    network: Optional[Any] = None
    # fault injection: None/"none" keeps the lossless/immortal legacy path
    # (bitwise — no fault machinery is even built); a preset name or
    # repro.faults.FaultModel instance pre-draws a deterministic FaultTrace
    # that masks crashed/undelivered clients out of FedAvg and bills every
    # retransmission exactly.
    faults: Optional[Any] = None
    # observability: None resolves to the shared no-op NullTelemetry; a
    # repro.telemetry.Telemetry records per-round records, counters, and
    # host spans.  Observation-only by contract (rule T001): recording
    # happens on the host AFTER the existing post-step/post-chunk fetch —
    # params and history are bitwise-identical with telemetry on vs off.
    telemetry: Optional[Any] = None

    def __post_init__(self):
        from repro.faults import resolve_fault
        from repro.sched import resolve_policy
        from repro.telemetry import resolve_telemetry
        from repro.transport import resolve_transport
        m = self.method if self.method is not None else self.fsl.method
        if isinstance(m, str):
            m = get_method(m)
        self.method = m
        self.transport = resolve_transport(self.transport, self.fsl)
        self.scheduler = resolve_policy(self.scheduler)
        self.faults = resolve_fault(self.faults)
        self.telemetry = resolve_telemetry(self.telemetry)
        if self.network is None:
            from repro.network import IdealNetwork
            self.network = IdealNetwork()
        self._sched_ctx = self._sched_masks = None
        self._fault_stats = None
        donate = (0,) if self.donate else ()
        self.step_fn = jax.jit(
            m.make_round_step(self.bundle, self.fsl,
                              server_constraint=self.server_constraint,
                              transport=self.transport),
            donate_argnums=donate)
        # aggregation goes through the model-sync wire (identity model
        # codecs: make_wire_aggregate returns the plain aggregate, bitwise)
        self.agg_fn = jax.jit(
            m.make_wire_aggregate(self.fsl, transport=self.transport),
            donate_argnums=donate)
        # The compiled multi-round runner (run_compiled): R rounds fused
        # into one donated lax.scan program.  jit caches per chunk length,
        # so a trailing partial chunk costs one extra compile, not one per
        # call.
        self.chunk_fn = jax.jit(
            m.make_chunk_step(self.bundle, self.fsl,
                              server_constraint=self.server_constraint,
                              transport=self.transport),
            donate_argnums=donate)
        # Device-resident-data twin of chunk_fn: gathers each round's batch
        # from an on-device sample pool in-scan (state donated; the pool —
        # argument 1 — is NOT, it must survive across chunks).
        self.pool_chunk_fn = jax.jit(
            m.make_chunk_step(self.bundle, self.fsl,
                              server_constraint=self.server_constraint,
                              transport=self.transport, gather=True),
            donate_argnums=donate)
        # Scheduling/faults (non-wait_all or non-null faults only — the
        # default path above stays the untouched legacy code): renormalized
        # masked FedAvg plus the chunk variant that threads the
        # participation plan through the in-scan lax.cond.  Fault-dropped
        # clients ride the exact same machinery as scheduler-dropped ones.
        if not self.scheduler.is_wait_all or not self.faults.is_null:
            refresh = self.scheduler.refresh_dropped
            self.masked_agg_fn = jax.jit(
                m.make_wire_aggregate(self.fsl, transport=self.transport,
                                      participation=True, refresh=refresh),
                donate_argnums=donate)
            self.masked_chunk_fn = jax.jit(
                m.make_chunk_step(self.bundle, self.fsl,
                                  server_constraint=self.server_constraint,
                                  transport=self.transport,
                                  participation=True, refresh=refresh),
                donate_argnums=donate)
            self.masked_pool_chunk_fn = jax.jit(
                m.make_chunk_step(self.bundle, self.fsl,
                                  server_constraint=self.server_constraint,
                                  transport=self.transport,
                                  participation=True, refresh=refresh,
                                  gather=True),
                donate_argnums=donate)

    # -- public per-round API (custom loops, e.g. arrival-order studies) ----
    def init(self, seed: int = 0):
        return self.method.init_state(self.bundle, self.fsl,
                                      jax.random.PRNGKey(seed))

    def lr_at(self, rnd: int) -> float:
        steps = rnd // self.fsl.lr_decay_every
        return self.fsl.lr * self.fsl.lr_decay ** steps

    def step(self, state, batch, lr: Optional[float] = None, *,
             rnd: Optional[int] = None):
        """One global round.  Pass ``lr`` explicitly or ``rnd`` to use the
        schedule (``rnd=None`` and ``lr=None`` means lr_at(0))."""
        if lr is None:
            lr = self.lr_at(rnd or 0)
        return self.step_fn(state, batch, lr)

    def aggregate(self, state):
        return self.agg_fn(state)

    def merged_params(self, state):
        """Deployable {"client", ["aux",] "server"} params for evaluation."""
        return self.method.merged_params(state)

    def comm_profile(self, cost_model: CostModel, batch_size: int,
                     batch=None) -> CommProfile:
        """With a ``batch``, the profile's ``*_wire`` fields are exact for
        this trainer's transport (payload specs recovered via eval_shape);
        ``model_sync_wire`` needs no batch (model specs come from
        ``init_state`` shapes)."""
        specs = None
        if batch is not None and not self.transport.is_identity:
            specs = self.method.payload_specs(self.bundle, self.fsl, batch)
        mspecs = None
        if not self.transport.model_identity:
            mspecs = self.method.model_sync_specs(self.bundle, self.fsl)
        return self.method.comm_profile(cost_model, self.fsl, batch_size,
                                        transport=self.transport,
                                        payload_specs=specs,
                                        model_specs=mspecs)

    def chunk_fingerprint(self, batch, chunk: int) -> str:
        """Structural hash of this trainer's compiled chunk program over a
        sample round ``batch`` (``[n, h, B, ...]``), via the static
        checker's tracer.  Two Trainers of the same config must agree —
        a mismatch means nondeterministic construction forces a silent
        retrace+recompile per process (rule R001; perf_bench asserts this
        per run and ships the hash in BENCH_perf.json)."""
        from repro.analysis import trainer_chunk_fingerprint
        return trainer_chunk_fingerprint(self, batch, chunk)

    def wallclock_estimate(self, cost_model: CostModel, batch_size: int,
                           num_rounds: int, network, batch=None,
                           compute: float = 1.0, server_time: float = 0.05,
                           faults=None):
        """Analytic synchronous wall-clock for ``num_rounds`` rounds under
        ``network`` (a :class:`repro.network.NetworkModel`) — the same
        barrier time model the AsyncTrainer reports as its synchronous
        counterfactual (``AsyncStats.sync_time``), fed by the same
        codec-effective wire bytes.  With a ``batch`` the per-upload
        payload bytes are exact (payload specs via eval_shape); without
        one they derive from the analytic CommProfile.  ``compute`` is the
        per-upload-unit client compute seconds (the compute-only
        LatencyModel mean).  Returns a
        :class:`repro.network.WallClockEstimate`.

        With a non-null fault model (``faults=`` here, defaulting to the
        trainer's own) the estimate is failure-aware: transfer bytes are
        scaled by the expected transmission count under the capped retry
        budget (checksum frame included per attempt) and the expected
        backoff wait joins the per-unit compute time — the analytic twin
        of the event engine's realized retry seconds."""
        from repro.faults import FRAME_BYTES, resolve_fault
        from repro.network.wallclock import estimate_sync_wallclock
        fsl, m, tp = self.fsl, self.method, self.transport
        n = fsl.num_clients
        K = fsl.h if m.uploads_every_batch else 1
        profile = self.comm_profile(cost_model, batch_size, batch=batch)
        if batch is not None:
            up_spec, reply_spec = m.payload_specs(self.bundle, fsl, batch)
            up_bytes = tp.uplink_payload_bytes(up_spec)
            down_bytes = tp.downlink_payload_bytes(reply_spec) \
                if reply_spec is not None else 0
        else:
            if not tp.is_identity:
                raise ValueError(
                    "wallclock_estimate needs a `batch` to derive the "
                    "codec-effective payload bytes of a non-identity "
                    "transport (without one the estimate would silently "
                    "use uncompressed sizes)")
            up_bytes = (profile.wire_uplink_smashed
                        + profile.uplink_labels) // (n * K)
            down_bytes = profile.wire_downlink_grads // (n * K)
        fm = resolve_fault(faults if faults is not None else self.faults)
        if not fm.is_null:
            att = fm.expected_attempts()
            up_bytes = int(round((up_bytes + FRAME_BYTES) * att))
            if down_bytes:
                down_bytes = int(round((down_bytes + FRAME_BYTES) * att))
            compute = compute + fm.expected_backoff()
        mspecs = m.model_sync_specs(self.bundle, fsl)
        ms_up = tp.model_up_wire_bytes(mspecs)
        ms_down = tp.model_down_wire_bytes(mspecs)
        # rounds that cross a C-batch threshold — at most ONE aggregation
        # per round, exactly like AggregationCadence.advance(h)
        C = fsl.resolved_agg_every
        aggs = sum(1 for r in range(1, num_rounds + 1)
                   if (r * fsl.h) // C > ((r - 1) * fsl.h) // C)
        return estimate_sync_wallclock(
            network, n, num_rounds, uploads_per_round=K, up_bytes=up_bytes,
            down_bytes=down_bytes, blocking=m.downloads_gradients,
            compute=compute, server_time=server_time, agg_events=aggs,
            model_up_bytes=ms_up, model_down_bytes=ms_down)

    # -- scheduling plan ----------------------------------------------------
    def _plan_schedule(self, batch, horizon: int) -> np.ndarray:
        """Draw the scheduler's deterministic participation plan for global
        rounds ``0..horizon-1`` (indexed by the absolute round counter, so
        a resumed run realizes the same plan).  Payload bytes for the
        policy's SchedContext come from the method's payload specs through
        this trainer's transport — codec-effective, like the wall-clock
        estimate."""
        from repro.sched import SchedContext
        m, fsl, tp = self.method, self.fsl, self.transport
        up_spec, reply_spec = m.payload_specs(self.bundle, fsl, batch)
        ctx = SchedContext(
            fsl=fsl, network=self.network,
            up_bytes=tp.uplink_payload_bytes(up_spec),
            down_bytes=tp.downlink_payload_bytes(reply_spec)
            if reply_spec is not None else 0,
            blocking=m.downloads_gradients,
            uploads_per_round=fsl.h if m.uploads_every_batch else 1)
        masks = np.asarray(self.scheduler.plan(ctx, horizon), bool)
        if masks.shape != (horizon, fsl.num_clients):
            raise ValueError(f"scheduler plan shape {masks.shape} != "
                             f"{(horizon, fsl.num_clients)}")
        self._sched_ctx, self._sched_masks = ctx, masks
        return masks

    # -- fault plan ---------------------------------------------------------
    def _uploads_per_round(self) -> int:
        return self.fsl.h if self.method.uploads_every_batch else 1

    def _plan_faults(self, horizon: int):
        """Draw the fault trace for global rounds ``0..horizon-1``
        (absolute-round-indexed like the scheduler plan, so a
        checkpoint-resumed run replays the faults of the uninterrupted
        one) and reset the run's :class:`FaultStats`."""
        from repro.faults import FaultStats
        trace = self.faults.trace(horizon, self.fsl.num_clients,
                                  self._uploads_per_round())
        self._fault_stats = FaultStats()
        return trace

    def _effective_masks(self, batch, horizon: int,
                         fault_trace) -> np.ndarray:
        """Per-round participation = scheduler plan AND fault survival:
        a client aggregates only if the policy admitted it and its wire
        round completed (no crash, every unit delivered) in EVERY round
        of the window.  Both engines consume this one [horizon, n] plan,
        which is what keeps ``run`` ≡ ``run_compiled`` bitwise under
        faults."""
        sched_active = not self.scheduler.is_wait_all
        if sched_active:
            masks = np.array(self._plan_schedule(batch, horizon), copy=True)
        else:
            masks = np.ones((horizon, self.fsl.num_clients), bool)
        if fault_trace is not None:
            masks &= fault_trace.survives(self.method.downloads_gradients)
        return masks

    def participation_summary(self):
        """The scheduler policy's summary of the realized plan (None until
        a scheduled run has drawn one, and for wait_all), plus a
        ``"faults"`` entry with the run's :class:`FaultStats` whenever a
        non-null fault model was active."""
        base = None
        if self._sched_masks is not None:
            base = self.scheduler.summary(self._sched_ctx, self._sched_masks)
        if self.faults.is_null or self._fault_stats is None:
            return base
        out = dict(base or {})
        out["faults"] = self._fault_stats.as_dict()
        return out

    def _model_sync_wire_pair(self):
        """(up, down) wire bytes of ONE client's model-sync payload — the
        per-participant costs partial aggregation meters with."""
        mspecs = self.method.model_sync_specs(self.bundle, self.fsl)
        return (self.transport.model_up_wire_bytes(mspecs),
                self.transport.model_down_wire_bytes(mspecs))

    # -- shared per-round bookkeeping (run and run_compiled MUST log
    # identically — the bitwise-history contract in tests/test_compiled.py
    # rides on this being one code path) -----------------------------------
    def _log_round(self, rnd, rnd0, aggregated, metrics_fn, profile, meter,
                   log_every, callback, history, state, extra=None,
                   model_sync_bytes=None, wire_bytes=None, engine="loop"):
        """Meter + history row for one finished (post-aggregation) round.
        ``metrics_fn`` lazily yields the float-cast metrics dict so the
        per-round loop only fetches device scalars on logged rounds.
        Scheduling passes participation ``extra`` row fields and the
        cohort's actual ``model_sync_bytes`` (None: the full-fleet profile
        value — the wait_all path, byte for byte the legacy meter).
        Fault runs pass ``wire_bytes`` — the trace-exact per-kind byte
        dict (retransmissions and checksum frames included) that replaces
        the static per-round profile charges.

        An enabled telemetry recorder additionally folds EVERY round into
        its record stream under ``engine`` — pure host bookkeeping on the
        values this method already handles, after any device fetch, so
        history/meter/params stay bitwise-identical (rule T001)."""
        if profile is not None:
            if wire_bytes is None:
                meter.log("uplink_smashed", profile.wire_uplink_smashed)
                meter.log("uplink_labels", profile.uplink_labels)
                meter.log("downlink_grads", profile.wire_downlink_grads)
            else:
                for kind, nb in wire_bytes.items():
                    meter.log(kind, nb)
            if aggregated:
                meter.log("model_sync", profile.wire_model_sync
                          if model_sync_bytes is None else model_sync_bytes)
        tele = self.telemetry
        logged = log_every and (rnd + 1 - rnd0) % log_every == 0
        m = metrics_fn() if (logged or tele.enabled) else None
        if tele.enabled:
            tele.round_record(engine, rnd + 1, m, aggregated,
                              comm_bytes=meter.total if meter is not None
                              else None, extra=extra)
        if logged:
            row: dict = {"round": rnd + 1, **m, "aggregated": aggregated}
            if extra:
                row.update(extra)
            if meter is not None:
                row["comm_bytes"] = meter.total
            history.append(row)
            if callback:
                callback(rnd + 1, m, state)

    # -- the loop -----------------------------------------------------------
    def run(self, state, batcher, num_rounds: int, log_every: int = 0,
            callback=None, meter: Optional[CommMeter] = None,
            cost_model: Optional[CostModel] = None):
        """Run ``num_rounds`` global rounds.

        - aggregation fires every C batches (``fsl.resolved_agg_every``) on
          threshold crossing, resumed from ``state["round"]`` — a restarted
          run keeps the paper's C-batch schedule (and its lr schedule)
          instead of recounting from the start of the call;
        - ``callback(rnd, metrics, state)`` fires on the ``log_every``
          cadence, after aggregation, with float-cast metrics (``rnd`` is
          the global round index, resume-aware);
        - with ``meter`` + ``cost_model``, per-round and per-aggregation
          bytes from the method's CommProfile are logged and a
          ``comm_bytes`` running total is added to the history rows; each
          row also records whether that round ``aggregated``;
        - with a non-wait_all ``scheduler``, FedAvg runs masked and
          renormalized over the policy's plan — a client participates in
          an aggregation only if the plan admitted it in every round since
          the previous one; an empty cohort is a warned no-op.  Rows on
          aggregated rounds gain ``participants`` / ``dropped_updates``
          fields and the model-sync meter charges only the actual cohort.
        """
        from repro.faults import FRAME_BYTES, accumulate_round
        start_batches = self.method.batches_trained(self.fsl, state)
        cadence = AggregationCadence(self.fsl.resolved_agg_every,
                                     start_batches)
        rnd0 = start_batches // self.fsl.h
        n = self.fsl.num_clients
        history = []
        profile = None
        sched_active = not self.scheduler.is_wait_all
        fault_active = not self.faults.is_null
        use_masks = sched_active or fault_active
        horizon = rnd0 + num_rounds
        ftrace = self._plan_faults(horizon) if fault_active else None
        fstats = self._fault_stats
        unit_bytes = None
        blocking = self.method.downloads_gradients
        masks = ms_pair = None
        part = np.ones(n, bool) if use_masks else None
        # scheduler-only mirror: attributes window drops to the policy vs
        # the faults in FaultStats.deadline_drops
        part_s = np.ones(n, bool) if (sched_active and fault_active) else None
        dropped_updates = 0
        for rnd in range(rnd0, horizon):
            batch = batcher.next_round()
            if meter is not None and cost_model is not None and profile is None:
                batch_size = jax.tree_util.tree_leaves(batch[1])[0].shape[2]
                profile = self.comm_profile(cost_model, batch_size,
                                            batch=batch)
            if use_masks and masks is None:
                masks = self._effective_masks(batch, horizon, ftrace)
            state, metrics = self.step_fn(state, batch, self.lr_at(rnd))
            aggregated = cadence.advance(self.fsl.h)
            extra = ms_bytes = wire = None
            if use_masks:
                part &= masks[rnd]
                if part_s is not None:
                    part_s &= self._sched_masks[rnd]
            if fault_active and profile is not None:
                if unit_bytes is None:
                    unit_bytes = profile.unit_wire_bytes(
                        n, self._uploads_per_round())
                wire = accumulate_round(fstats, self.faults, ftrace, rnd,
                                        *unit_bytes, blocking, FRAME_BYTES)
            if aggregated:
                if not use_masks:
                    state = self.agg_fn(state)
                else:
                    k = int(part.sum())
                    if k == 0:
                        who = (f"scheduler {self.scheduler.name!r}"
                               if sched_active else
                               f"fault model {self.faults.name!r}")
                        warnings.warn(
                            f"{who} admitted no clients at the "
                            f"round-{rnd + 1} aggregation; FedAvg skipped "
                            "(no-op)")
                    else:
                        state = self.masked_agg_fn(
                            state, jnp.asarray(part, jnp.float32))
                    dropped_updates += n - k
                    extra = {"participants": k,
                             "dropped_updates": dropped_updates}
                    if fault_active:
                        fstats.windows += 1
                        fstats.participants.append(k)
                        if k == 0:
                            fstats.empty_windows += 1
                        if part_s is not None:
                            fstats.deadline_drops += n - int(part_s.sum())
                            part_s[:] = True
                        extra.update(
                            fault_retries=fstats.retries,
                            fault_drops=fstats.crash_drops + fstats.wire_drops)
                    if profile is not None:
                        if ms_pair is None:
                            ms_pair = self._model_sync_wire_pair()
                        recv = n if self.scheduler.refresh_dropped else k
                        ms_bytes = 0 if k == 0 \
                            else k * ms_pair[0] + recv * ms_pair[1]
                    part[:] = True
            self._log_round(rnd, rnd0, aggregated,
                            lambda: {k: float(v) for k, v in metrics.items()},
                            profile, meter, log_every, callback, history,
                            state, extra=extra, model_sync_bytes=ms_bytes,
                            wire_bytes=wire)
        if self.telemetry.enabled:
            self.telemetry.run_summary("loop", comm=meter,
                                       participation=self.participation_summary())
        return state, history

    # -- the compiled loop --------------------------------------------------
    @staticmethod
    def pool_round_spec(pool, idx_shape):
        """Abstract ``(inputs, labels)`` round batch implied by a device
        pool and an ``[n, h, B]`` index plan — shape-compatible with a
        staged batch everywhere only specs matter (CommProfile payload
        specs, scheduler plans)."""
        lead = tuple(idx_shape)
        return jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(lead + tuple(p.shape[1:]),
                                           p.dtype), pool)

    def run_compiled(self, state, batcher, num_rounds: int, chunk: int = 16,
                     log_every: int = 0, callback=None,
                     meter: Optional[CommMeter] = None,
                     cost_model: Optional[CostModel] = None,
                     device_data: bool = True):
        """Run ``num_rounds`` global rounds, ``chunk`` rounds per XLA
        dispatch — bitwise-identical to :meth:`run` (state AND history),
        as fast as the hardware allows.

        Each chunk stages ``R = min(chunk, remaining)`` rounds of batches
        on a new leading axis and hands them to one jitted
        ``lax.scan``-driven program with buffer donation (see
        :func:`repro.core.methods.base.make_chunk_step`): the aggregation
        cadence runs in the scan carry, the lr schedule is staged per
        chunk, and per-round metrics + ``aggregated`` flags come back as
        stacked device arrays fetched once.  ``CommMeter`` totals and
        history rows are reconstructed host-side from the static
        CommProfile and the returned aggregation mask — no per-round
        ``meter.log`` sync.

        Differences from :meth:`run` worth knowing:
        - donation: with ``donate=True`` (the default) the previous
          chunk's state buffers are consumed — keep no references to
          intermediate states across calls;
        - ``callback(rnd, metrics, state)`` fires on the ``log_every``
          cadence with that round's metrics but the *chunk-final* state
          (mid-chunk states are never materialized on the host).  Pass
          ``chunk=log_every`` when the callback inspects state (e.g.
          accuracy eval) — then every callback sees its exact round state;
        - resume: like :meth:`run`, both the cadence and the lr schedule
          restart from ``state["round"]``, so a checkpoint taken at ANY
          round — chunk-aligned or not — continues the paper's schedule.

        Data path: with ``device_data=True`` (the default) and a batcher
        implementing the device-pool protocol (``device_pool()`` +
        ``next_round_indices()``), the sample pool lives on device and
        each chunk ships only an ``[R, n, h, B]`` int32 index plan — the
        chunk program gathers batches in-scan and ``_stack_rounds`` never
        runs.  Identical RNG stream, identical gathered values: the path
        is bitwise-equal to staging.  Legacy batchers (no pool protocol)
        or ``device_data=False`` fall back to host staging.
        """
        from repro.faults import FRAME_BYTES, accumulate_round
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk} "
                             "(use Trainer.run for the per-round loop)")
        # "chunk/run" spans the whole call, so a device-idle gap anywhere
        # in it (the resume read of the round counter included) is named
        # by a chunk/* span on a profiler trace, not by a Python frame
        with self.telemetry.timed("chunk/run", rounds=num_rounds):
            start_batches = self.method.batches_trained(self.fsl, state)
            rnd0 = start_batches // self.fsl.h
            n = self.fsl.num_clients
            history = []
            profile = None
            done = 0
            sched_active = not self.scheduler.is_wait_all
            fault_active = not self.faults.is_null
            use_masks = sched_active or fault_active
            horizon = rnd0 + num_rounds
            ftrace = self._plan_faults(horizon) if fault_active else None
            fstats = self._fault_stats
            unit_bytes = None
            blocking = self.method.downloads_gradients
            masks = ms_pair = part_dev = None
            # host mirror of the in-scan participation carry — same math, so
            # rows/meter/warnings match Trainer.run exactly
            part = np.ones(n, bool) if use_masks else None
            part_s = np.ones(n, bool) if (sched_active and fault_active) \
                else None
            dropped_updates = 0
            pooled = (device_data and hasattr(batcher, "device_pool")
                      and hasattr(batcher, "next_round_indices"))
            pool = batcher.device_pool() if pooled else None
            tele = self.telemetry
            chunk_idx = 0
            while done < num_rounds:
                r = min(chunk, num_rounds - done)
                # host spans, observation-only: "chunk/build" stages the
                # plan, "chunk/dispatch" calls the jitted chunk (a compile,
                # counted in the span's ``compiles`` label, happens here),
                # "chunk/fetch" waits for its metrics, "chunk/log" is the
                # per-round host loop; each is a jax.profiler annotation
                with tele.timed("chunk/build", chunk=chunk_idx, rounds=r):
                    if pooled:
                        idx = np.stack([batcher.next_round_indices()
                                        for _ in range(r)])      # [R, n, h, B]
                        sample = self.pool_round_spec(pool, idx.shape[1:])
                        batches = None
                    else:
                        rounds = [batcher.next_round() for _ in range(r)]
                        sample = rounds[0]
                        batches = jax.tree_util.tree_map(_stack_rounds,
                                                         *rounds)
                    if meter is not None and cost_model is not None \
                            and profile is None:
                        batch_size = jax.tree_util.tree_leaves(
                            sample[1])[0].shape[2]
                        profile = self.comm_profile(cost_model, batch_size,
                                                    batch=sample)
                    if use_masks and masks is None:
                        masks = self._effective_masks(sample, horizon, ftrace)
                    lrs = jnp.asarray([self.lr_at(rnd0 + done + i)
                                       for i in range(r)], jnp.float32)
                with tele.timed("chunk/dispatch", chunk=chunk_idx, rounds=r):
                    if use_masks:
                        if part_dev is None:
                            part_dev = jnp.ones(n, jnp.float32)
                        mk = jnp.asarray(masks[rnd0 + done:rnd0 + done + r],
                                         jnp.float32)
                        if pooled:
                            state, metrics, agg_mask, part_dev = \
                                self.masked_pool_chunk_fn(
                                    state, pool, jnp.asarray(idx), lrs, mk,
                                    part_dev)
                        else:
                            state, metrics, agg_mask, part_dev = \
                                self.masked_chunk_fn(state, batches, lrs, mk,
                                                     part_dev)
                    elif pooled:
                        state, metrics, agg_mask = self.pool_chunk_fn(
                            state, pool, jnp.asarray(idx), lrs)
                    else:
                        state, metrics, agg_mask = self.chunk_fn(
                            state, batches, lrs)
                with tele.timed("chunk/fetch", chunk=chunk_idx, rounds=r):
                    # ONE host fetch per chunk: the stacked metrics + agg mask
                    agg_mask = np.asarray(agg_mask)
                    metrics = {k: np.asarray(v) for k, v in metrics.items()}
                with tele.timed("chunk/log", chunk=chunk_idx, rounds=r):
                    for i in range(r):
                        rnd = rnd0 + done + i
                        aggregated = bool(agg_mask[i])
                        extra = ms_bytes = wire = None
                        if use_masks:
                            part &= masks[rnd]
                            if part_s is not None:
                                part_s &= self._sched_masks[rnd]
                        if fault_active and profile is not None:
                            if unit_bytes is None:
                                unit_bytes = profile.unit_wire_bytes(
                                    n, self._uploads_per_round())
                            wire = accumulate_round(
                                fstats, self.faults, ftrace, rnd,
                                *unit_bytes, blocking, FRAME_BYTES)
                        if use_masks and aggregated:
                            k = int(part.sum())
                            if k == 0:
                                who = (f"scheduler {self.scheduler.name!r}"
                                       if sched_active else
                                       f"fault model {self.faults.name!r}")
                                warnings.warn(
                                    f"{who} admitted no clients at the "
                                    f"round-{rnd + 1} aggregation; FedAvg "
                                    "skipped (no-op)")
                            dropped_updates += n - k
                            extra = {"participants": k,
                                     "dropped_updates": dropped_updates}
                            if fault_active:
                                fstats.windows += 1
                                fstats.participants.append(k)
                                if k == 0:
                                    fstats.empty_windows += 1
                                if part_s is not None:
                                    fstats.deadline_drops += \
                                        n - int(part_s.sum())
                                    part_s[:] = True
                                extra.update(
                                    fault_retries=fstats.retries,
                                    fault_drops=(fstats.crash_drops
                                                 + fstats.wire_drops))
                            if profile is not None:
                                if ms_pair is None:
                                    ms_pair = self._model_sync_wire_pair()
                                recv = n if self.scheduler.refresh_dropped \
                                    else k
                                ms_bytes = 0 if k == 0 \
                                    else k * ms_pair[0] + recv * ms_pair[1]
                            part[:] = True
                        self._log_round(
                            rnd, rnd0, aggregated,
                            lambda: {k: float(v[i])
                                     for k, v in metrics.items()},
                            profile, meter, log_every, callback, history,
                            state,
                            extra=extra, model_sync_bytes=ms_bytes,
                            wire_bytes=wire, engine="compiled")
                chunk_idx += 1
                done += r
            if tele.enabled:
                tele.run_summary("compiled", comm=meter,
                                 participation=self.participation_summary())
            return state, history
