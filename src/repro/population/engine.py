"""The population engine: cohorts of C from fleets of N >= 10^6.

CSE-FSL's storage headline is that the server holds ONE model no matter
how many clients exist; this engine makes the simulation honor the same
scaling.  Instead of materializing dense per-client state for N clients
(the dense :class:`~repro.core.trainer.Trainer`, O(N) memory), a
:class:`Population` keeps:

  - the *cohort* state — the C sampled clients of the current aggregation
    window, stacked exactly like a dense ``fsl.num_clients = C`` trainer
    state, run through the Trainer's compiled pool-chunk program
    (``lax.scan`` over rounds, batches gathered in-scan from the
    device-resident pool);
  - ONE *default row* — the lazily-materialized state of every untouched
    client.  Methods FedAvg their whole stacked subtrees (params AND opt
    state, :meth:`FSLMethod.agg_keys`), so post-aggregation every cohort
    row is identical: an untouched client's state is a pure function of
    the global model, and with ``refresh=True`` (the CSE-FSL
    global-model semantics) the sparse cache below stays empty forever;
  - a sparse host-side *cache* for ``refresh=False`` (stateful-baseline
    semantics: non-cohort clients keep their last state): the post-window
    row — one shared pytree per window, since all cohort rows are equal —
    keyed by the touched client ids.  Memory is O(windows), not O(N).

Engine memory is therefore independent of N (:meth:`memory_report`
asserts this against the dense extrapolation in
``benchmarks/fig_population.py``), and for C == N with a
:class:`~repro.population.data.FederatedPool` the engine is
bitwise-identical to ``Trainer.run`` / ``run_compiled``
(tests/test_population.py).

Cohorts are drawn per aggregation *window* (the span between C-batch
threshold crossings) by a :class:`~repro.sched.CohortSampler` keyed on
``(seed, window)`` — the window index is a pure function of the round
counter, so checkpoint resume re-derives cohorts with no sampler state.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import bytes_of, tree_stack
from repro.configs.base import FSLConfig
from repro.core.accounting import CommMeter, CostModel
from repro.core.bundle import SplitModelBundle
from repro.core.trainer import Trainer
from repro.network.model import IDEAL_LINK, TIERS, ClientLink
from repro.sched import CohortSampler, resolve_cohort


@dataclasses.dataclass
class Population:
    """Cohort-sampled training over a fleet of ``population`` clients.

    ``fsl.num_clients`` is the COHORT size C — the compiled programs, the
    CommProfile, and the wire accounting all see a C-client fleet per
    window, which is exactly how cohort-scaled federated accounting is
    defined (bytes scale with who actually trains, not with N).
    """

    bundle: SplitModelBundle
    fsl: FSLConfig
    population: int
    data: Any                                   # FederatedPool / VirtualPool
    sampler: Optional[Union[str, CohortSampler]] = None
    transport: Optional[Any] = None
    network: Optional[Any] = None
    refresh: bool = True
    donate: bool = True
    mesh: Optional[Any] = None
    seed: int = 0
    compute_s: float = 1.0          # per-upload-unit client compute seconds
    server_time: float = 0.05       # per-reply server seconds (blocking)
    # fault injection (repro.faults): faults are drawn per COHORT SLOT —
    # slot c of window w is the sampled client occupying it — and crashed/
    # undelivered slots drop out of the window's FedAvg through the same
    # masked machinery the dense trainer uses.  Requires refresh=True (the
    # CSE-FSL global-model semantics): a crashed client's lost local
    # update is exactly the refresh overwrite.
    faults: Optional[Any] = None
    # observability (repro.telemetry): forwarded to the inner Trainer;
    # the cohort engine emits per-round records under engine="population"
    # plus chunk build/dispatch/fetch/log host spans.  Observation-only
    # (rule T001).
    telemetry: Optional[Any] = None

    def __post_init__(self):
        C = self.fsl.num_clients
        if self.population < C:
            raise ValueError(f"population {self.population} < cohort {C}")
        self.trainer = Trainer(self.bundle, self.fsl, donate=self.donate,
                               transport=self.transport,
                               network=self.network, faults=self.faults,
                               telemetry=self.telemetry)
        self.telemetry = self.trainer.telemetry
        self.faults = self.trainer.faults
        if not self.faults.is_null and not self.refresh:
            raise ValueError(
                "fault injection needs refresh=True cohort semantics: with "
                "refresh=False a crashed slot's locally-trained rows would "
                "enter the sparse cache as if aggregated")
        self.network = self.trainer.network
        self.sampler = resolve_cohort(self.sampler, seed=self.seed)
        self._unit = self.trainer.method.unit_batches(self.fsl)
        self._agg_every = self.fsl.resolved_agg_every
        self._state = None
        self._mesh_pool = None
        self._default: Dict[str, Any] = {}
        self._cache: Dict[int, Dict[str, Any]] = {}
        self._cohorts: Dict[int, np.ndarray] = {}
        self._window: Optional[int] = None
        self._stacked: tuple = ()
        self._windows_seen: set = set()
        self._records: List[Dict[str, Any]] = []
        self._payload_bytes = None
        self._tier_spans = None
        # fault runs: the global row at the current window's entry, kept so
        # a zero-participant window (in-scan FedAvg no-op) can be unwound —
        # the next cohort must inherit the last aggregated model, not the
        # dirty locally-trained rows the no-op left behind
        self._entry_row: Optional[Dict[str, Any]] = None
        self._window_empty = False

    # -- lazy per-client state ---------------------------------------------
    @property
    def cohort_size(self) -> int:
        return self.fsl.num_clients

    def window_of(self, rnd: int) -> int:
        """Aggregation-window index of global round ``rnd`` — the number
        of C-batch thresholds crossed before it (pure in ``rnd``)."""
        return (rnd * self._unit) // self._agg_every

    def cohort_for(self, window: int) -> np.ndarray:
        ids = self._cohorts.get(window)
        if ids is None:
            ids = self.sampler.sample(window, self.population,
                                      self.cohort_size, network=self.network)
            self._cohorts[window] = ids
        return ids

    def _row(self, cid: int) -> Dict[str, Any]:
        cached = self._cache.get(int(cid))
        return cached if cached is not None else self._default

    def _restack(self, ids: np.ndarray):
        """Materialize the cohort's stacked rows from cache/default."""
        rows = [self._row(i) for i in ids]
        stacked = {k: tree_stack([r[k] for r in rows])
                   for k in self._stacked}
        self._state = {**self._state, **stacked}
        self._place()

    def _advance_window(self, window: int):
        """Finish the current window, enter ``window``.

        With ``refresh=True`` nothing moves: post-aggregation rows are
        identical and ARE the global model — the incoming cohort's rows
        bitwise.  With ``refresh=False`` the outgoing cohort's (shared)
        post-window row enters the sparse cache and the incoming cohort
        restacks from cache/default."""
        if not self.refresh and self._window is not None:
            row = {k: jax.tree_util.tree_map(lambda x: x[0], self._state[k])
                   for k in self._stacked}
            for cid in self._cohorts[self._window]:
                self._cache[int(cid)] = row
            self._restack(self.cohort_for(window))
        self._window = window

    def _close_window(self, state):
        """Fault runs only: repair a zero-participant window and snapshot
        the entry row of the next one.  Called at every window boundary —
        if the finished window aggregated nobody, every row is restacked
        from the window-entry global row; otherwise the rows already ARE
        the new global model, and row 0 becomes the next entry snapshot."""
        if self._window_empty:
            stacked = {k: tree_stack([self._entry_row[k]] * self.cohort_size)
                       for k in self._stacked}
            state = {**state, **stacked}
            self._window_empty = False
        self._entry_row = {k: jax.tree_util.tree_map(lambda x: x[0], state[k])
                           for k in self._stacked}
        return state

    def _place(self):
        """Shard the cohort state over the mesh (no-op without one)."""
        if self.mesh is None:
            return
        from jax.sharding import NamedSharding
        from repro.sharding import state_specs
        abs_state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self._state)
        specs = state_specs(abs_state, mesh=self.mesh, fsdp_server=False)
        self._state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            self._state, specs)

    def device_pool(self):
        """The data backend's sample pool, replicated over the mesh when
        there is one: every device gathers its clients' batches locally,
        and the pool is not left on the first device alone."""
        pool = self.data.device_pool()
        if self.mesh is None:
            return pool
        if self._mesh_pool is None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._mesh_pool = jax.device_put(
                pool, NamedSharding(self.mesh, PartitionSpec()))
        return self._mesh_pool

    # -- lifecycle ----------------------------------------------------------
    def init(self, seed: int = 0):
        state = self.trainer.init(seed)
        self._stacked = tuple(k for k in ("clients", "servers") if k in state)
        # stack_clients broadcasts one init row to all C clients, so row 0
        # IS the global model every untouched client lazily shares
        self._default = {k: jax.tree_util.tree_map(lambda x: x[0], state[k])
                         for k in self._stacked}
        self._cache = {}
        self._state = state
        rnd = self.trainer.method.batches_trained(self.fsl, state) \
            // self.fsl.h
        self._window = self.window_of(rnd)
        self.cohort_for(self._window)
        if not self.faults.is_null:
            self._entry_row = dict(self._default)
            self._window_empty = False
        self._place()
        return self

    # -- stats ---------------------------------------------------------------
    def _client_link(self, cid: int) -> ClientLink:
        net = self.network
        if getattr(net, "is_ideal", False):
            return IDEAL_LINK
        if self._tier_spans is not None:
            for name, lo, hi in self._tier_spans:
                if lo <= cid < hi:
                    return TIERS[name]
            return TIERS[self._tier_spans[-1][0]]
        return net.expected_links(1)[0]

    def _client_seconds(self, link: ClientLink) -> float:
        """Analytic per-round seconds of one cohort client — the same
        blocking/streaming decomposition the deadline scheduler and the
        sync wall-clock estimator use."""
        up, down = self._payload_bytes
        m = self.trainer.method
        K = self.fsl.h if m.uploads_every_batch else 1
        if m.downloads_gradients:
            return (K * (self.compute_s + link.up_seconds(up))
                    + (K - 1) * (self.server_time + link.down_seconds(down)))
        return K * self.compute_s + link.up_seconds(up)

    def _record_window(self, window: int, ids: np.ndarray, rnd: int):
        if window in self._windows_seen:
            return
        self._windows_seen.add(window)
        tiers: Dict[str, int] = {}
        spans = getattr(self.network, "tier_ranges", None)
        if spans is not None and self._tier_spans is None:
            self._tier_spans = spans(self.population)
        seconds = []
        for cid in ids:
            link = self._client_link(int(cid))
            if self._tier_spans is not None:
                name = next(nm for nm, lo, hi in self._tier_spans
                            if lo <= int(cid) < hi)
                tiers[name] = tiers.get(name, 0) + 1
            if self._payload_bytes is not None:
                seconds.append(self._client_seconds(link))
        self._records.append({"window": window, "round": rnd,
                              "cohort": len(ids), "tiers": tiers,
                              "seconds": seconds})

    def population_summary(self, history=None) -> Dict[str, Any]:
        """Population-level streamed stats: per-tier participation, the
        straggler-seconds quantiles across every window's cohort, and the
        coverage of the fleet — per-client rows never exist, so this is
        the per-tier replacement for them."""
        tiers: Dict[str, int] = {}
        seconds: List[float] = []
        for rec in self._records:
            for name, k in rec["tiers"].items():
                tiers[name] = tiers.get(name, 0) + k
            seconds.extend(rec["seconds"])
        total = sum(tiers.values())
        out: Dict[str, Any] = {
            "population": self.population,
            "cohort": self.cohort_size,
            "windows": len(self._records),
            "sampler": self.sampler.name,
            "unique_clients": len({int(c) for w in self._windows_seen
                                   for c in self._cohorts.get(w, [])}),
            "per_tier": {name: {"participants": k,
                                "share": k / max(total, 1)}
                         for name, k in sorted(tiers.items())},
        }
        if seconds:
            q = np.quantile(np.asarray(seconds), [0.5, 0.9, 0.99])
            out["straggler_seconds"] = {"p50": float(q[0]),
                                        "p90": float(q[1]),
                                        "p99": float(q[2]),
                                        "max": float(max(seconds))}
        if history:
            accs = [row["accuracy"] for row in history
                    if "accuracy" in row]
            if accs:
                out["final_accuracy"] = float(accs[-1])
        return out

    def memory_report(self) -> Dict[str, Any]:
        """Engine-held bytes vs what a dense N-client fleet would cost.
        ``engine_total`` must not depend on ``population`` — the assertion
        ``fig_population.py`` makes by comparing N=10^4 and N=10^6 runs
        of the same cohort config."""
        row_bytes = bytes_of(self._default)
        shared = {k: v for k, v in self._state.items()
                  if k not in self._stacked}
        unique_rows = {id(r): r for r in self._cache.values()}
        engine = {
            "cohort_state": bytes_of({k: self._state[k]
                                      for k in self._stacked}),
            "server_state": bytes_of(shared),
            "default_row": row_bytes,
            "cache_rows": sum(bytes_of(r) for r in unique_rows.values()),
            "cache_entries": len(self._cache),
            "pool": bytes_of(self.data.device_pool()),
        }
        engine_total = (engine["cohort_state"] + engine["server_state"]
                        + engine["default_row"] + engine["cache_rows"])
        dense = self.population * row_bytes + engine["server_state"]
        return {"population": self.population, "cohort": self.cohort_size,
                "engine": engine, "engine_total": engine_total,
                "dense_extrapolated": dense}

    # -- checkpoint ----------------------------------------------------------
    def save(self, path: str):
        """Persist the cohort stack + the sparse cache via
        ``repro.checkpoint``.  Cohorts and data plans are pure functions
        of the round counter (sampler keyed on (seed, window), stateless
        data backends keyed on (seed, client, round)), so nothing else
        needs saving for a bitwise resume."""
        from repro import checkpoint as ckpt
        cache_ids = sorted(self._cache)
        tree = {"state": self._state, "default": self._default}
        if cache_ids:
            tree["cache"] = tree_stack([self._cache[i] for i in cache_ids])
        if self._entry_row is not None:
            # fault runs: the current window's entry row must survive a
            # mid-window restart for the empty-window recovery to replay
            # bitwise against the uninterrupted run
            tree["entry"] = self._entry_row
        step = int(np.asarray(self._state["round"]))
        ckpt.save(path, tree, step=step,
                  extra={"population": self.population,
                         "cohort": self.cohort_size,
                         "refresh": self.refresh,
                         "sampler": self.sampler.name,
                         "has_entry": self._entry_row is not None,
                         "cache_ids": [int(i) for i in cache_ids]})

    def restore(self, path: str):
        """Rebuild cohort stack, default row, and sparse cache; re-derive
        the window and its cohort from the restored round counter."""
        from repro import checkpoint as ckpt
        man = ckpt.manifest(path)
        extra = man["extra"]
        if extra["population"] != self.population \
                or extra["cohort"] != self.cohort_size:
            raise ValueError(
                f"checkpoint is for population={extra['population']} "
                f"cohort={extra['cohort']}, engine has "
                f"{self.population}/{self.cohort_size}")
        state_abs = jax.eval_shape(
            lambda k: self.trainer.method.init_state(self.bundle, self.fsl,
                                                     k),
            jax.ShapeDtypeStruct((2,), jnp.uint32))
        self._stacked = tuple(k for k in ("clients", "servers")
                              if k in state_abs)
        row_abs = {k: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
            state_abs[k]) for k in self._stacked}
        like = {"state": state_abs, "default": row_abs}
        cache_ids = [int(i) for i in extra["cache_ids"]]
        if cache_ids:
            like["cache"] = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((len(cache_ids),) + x.shape,
                                               x.dtype), row_abs)
        has_entry = bool(extra.get("has_entry", False))
        if has_entry:
            like["entry"] = row_abs
        tree = ckpt.restore(path, like)
        dev = lambda t: jax.tree_util.tree_map(jnp.asarray, t)
        self._state = dev(tree["state"])
        self._default = dev(tree["default"])
        if has_entry:
            self._entry_row = dev(tree["entry"])
        elif not self.faults.is_null:
            # pre-fault checkpoint resumed into a fault run: best effort —
            # valid whenever the checkpoint sits on a window boundary
            self._entry_row = {k: jax.tree_util.tree_map(
                lambda x: x[0], self._state[k]) for k in self._stacked}
        self._window_empty = False
        self._cache = {}
        if cache_ids:
            cache = dev(tree["cache"])
            for j, cid in enumerate(cache_ids):
                self._cache[cid] = jax.tree_util.tree_map(
                    lambda x: x[j], cache)
        rnd = self.trainer.method.batches_trained(self.fsl, self._state) \
            // self.fsl.h
        self._window = self.window_of(rnd)
        self.cohort_for(self._window)
        self._place()
        return self

    # -- the loop ------------------------------------------------------------
    def run(self, num_rounds: int, chunk: int = 16, log_every: int = 0,
            callback: Optional[Callable] = None,
            meter: Optional[CommMeter] = None,
            cost_model: Optional[CostModel] = None):
        """Run ``num_rounds`` global rounds of cohort training.

        Each dispatch covers a *segment* of rounds through the Trainer's
        device-resident ``pool_chunk_fn`` — only the per-round int32 index
        plans of the sampled cohorts cross to the device.  With
        ``refresh=True`` segments freely span window boundaries (the
        in-scan FedAvg leaves every row equal to the new global model, the
        exact init of the next cohort); with ``refresh=False`` segments
        cut at boundaries so the sparse cache can absorb the outgoing
        cohort host-side.  History rows, metering, and the lr/cadence
        schedule match ``Trainer.run_compiled`` row for row — for C == N
        with a FederatedPool, bitwise.
        """
        if self._state is None:
            raise RuntimeError("call init() or restore() before run()")
        from repro.faults import FRAME_BYTES, accumulate_round
        t = self.trainer
        state = self._state
        with self.telemetry.timed("chunk/run", rounds=num_rounds):
            rnd0 = t.method.batches_trained(self.fsl, state) // self.fsl.h
            pool = self.device_pool()
            history: List[dict] = []
            profile = None
            C = self.cohort_size
            fault_active = not self.faults.is_null
            blocking = t.method.downloads_gradients
            ftrace = fstats = surv = part = part_dev = None
            unit_bytes = ms_pair = None
            dropped_updates = 0
            if fault_active:
                ftrace = t._plan_faults(rnd0 + num_rounds)
                fstats = t._fault_stats
                surv = ftrace.survives(blocking)
                part = np.ones(C, bool)
                part_dev = jnp.ones(C, jnp.float32)
            done = 0
            while done < num_rounds:
                r0 = rnd0 + done
                w0 = self.window_of(r0)
                if w0 != self._window:
                    if fault_active:
                        state = self._close_window(state)
                    self._state = state
                    self._advance_window(w0)
                    state = self._state
                seg = min(chunk, num_rounds - done)
                if not self.refresh or fault_active:
                    # faults also cut segments at window boundaries, so an
                    # empty window can be repaired host-side before the next
                    # cohort trains on its rows
                    s = 1
                    while s < seg and self.window_of(r0 + s) == w0:
                        s += 1
                    seg = s
                with self.telemetry.timed("chunk/build", window=w0,
                                          rounds=seg):
                    plans = []
                    for i in range(seg):
                        w = self.window_of(r0 + i)
                        ids = self.cohort_for(w)
                        plans.append(self.data.round_indices(ids, r0 + i))
                    sample = t.pool_round_spec(pool, plans[0].shape)
                    if self._payload_bytes is None:
                        up_spec, reply_spec = t.method.payload_specs(
                            self.bundle, self.fsl, sample)
                        self._payload_bytes = (
                            t.transport.uplink_payload_bytes(up_spec),
                            t.transport.downlink_payload_bytes(reply_spec)
                            if reply_spec is not None else 0)
                    for i in range(seg):
                        w = self.window_of(r0 + i)
                        self._record_window(w, self.cohort_for(w), r0 + i)
                    if meter is not None and cost_model is not None \
                            and profile is None:
                        batch_size = jax.tree_util.tree_leaves(
                            sample[1])[0].shape[2]
                        profile = t.comm_profile(cost_model, batch_size,
                                                 batch=sample)
                    idx = jnp.asarray(np.stack(plans))
                    lrs = jnp.asarray([t.lr_at(r0 + i) for i in range(seg)],
                                      jnp.float32)
                with self.telemetry.timed("chunk/dispatch", window=w0,
                                          rounds=seg):
                    if fault_active:
                        mk = jnp.asarray(surv[r0:r0 + seg], jnp.float32)
                        state, metrics, agg_mask, part_dev = \
                            t.masked_pool_chunk_fn(state, pool, idx, lrs, mk,
                                                   part_dev)
                    else:
                        state, metrics, agg_mask = t.pool_chunk_fn(state, pool,
                                                                   idx, lrs)
                with self.telemetry.timed("chunk/fetch", window=w0,
                                          rounds=seg):
                    agg_mask = np.asarray(agg_mask)
                    metrics = {k: np.asarray(v) for k, v in metrics.items()}
                with self.telemetry.timed("chunk/log", window=w0, rounds=seg):
                    for i in range(seg):
                        rnd = r0 + i
                        aggregated = bool(agg_mask[i])
                        extra = ms_bytes = wire = None
                        if fault_active:
                            part &= surv[rnd]
                            if profile is not None:
                                if unit_bytes is None:
                                    unit_bytes = profile.unit_wire_bytes(
                                        C, t._uploads_per_round())
                                wire = accumulate_round(
                                    fstats, self.faults, ftrace, rnd,
                                    *unit_bytes, blocking, FRAME_BYTES)
                            if aggregated:
                                k = int(part.sum())
                                if k == 0:
                                    self._window_empty = True
                                    warnings.warn(
                                        f"fault model {self.faults.name!r} "
                                        f"admitted no clients at the round-"
                                        f"{rnd + 1} aggregation; FedAvg "
                                        "skipped (no-op)")
                                dropped_updates += C - k
                                fstats.windows += 1
                                fstats.participants.append(k)
                                if k == 0:
                                    fstats.empty_windows += 1
                                extra = {"participants": k,
                                         "dropped_updates": dropped_updates,
                                         "fault_retries": fstats.retries,
                                         "fault_drops": (fstats.crash_drops
                                                         + fstats.wire_drops)}
                                if profile is not None:
                                    if ms_pair is None:
                                        ms_pair = t._model_sync_wire_pair()
                                    ms_bytes = 0 if k == 0 \
                                        else k * ms_pair[0] + C * ms_pair[1]
                                part[:] = True
                        t._log_round(
                            rnd, rnd0, aggregated,
                            lambda: {k: float(v[i])
                                     for k, v in metrics.items()},
                            profile, meter, log_every, callback, history,
                            state,
                            extra=extra, model_sync_bytes=ms_bytes,
                            wire_bytes=wire, engine="population")
                done += seg
            self._state = state
            # a segment can END exactly on a window boundary — enter the new
            # window now so caches/cohorts are current for save()/stats
            w_next = self.window_of(rnd0 + num_rounds)
            if w_next != self._window:
                if fault_active:
                    self._state = self._close_window(self._state)
                self._advance_window(w_next)
            if self.telemetry.enabled:
                self.telemetry.run_summary(
                    "population", comm=meter,
                    population=self.population_summary(history),
                    participation=t.participation_summary())
            return self._state, history
