"""The `FSLMethod` interface: one API for CSE-FSL and every baseline.

A *method* is a stateless strategy object describing one federated split
learning algorithm end to end:

  - ``init_state(bundle, fsl, key)``      -> state pytree (stacked clients)
  - ``make_round_step(bundle, fsl, server_constraint=None)``
        -> jittable ``round_step(state, batch, lr) -> (state, metrics)``
  - ``make_aggregate()``                  -> jittable ``aggregate(state)``
  - ``merged_params(state)``              -> deployable ``{"client", ["aux",]
                                             "server"}`` params
  - ``comm_profile(cm, fsl, batch_size)`` -> declarative :class:`CommProfile`

All methods share one batch contract: ``batch = (inputs, labels)`` with
leading dims ``[n_clients, h, B, ...]``.  CSE-FSL consumes the ``h`` axis
as its local-update period (paper Alg. 1); the per-batch baselines run the
``h`` inner batches through a ``lax.scan`` (``h=1`` — one mini-batch per
round — remains the faithful-to-paper default for them).

Implementations register themselves with :func:`register`; the Trainer and
the launchers resolve them by name via :func:`get_method`, so adding a
fifth method is a one-file change (see README "Add your own method").
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import FSLConfig
from repro.core.accounting import CostModel
from repro.core.bundle import SplitModelBundle
from repro.core.stages import CLIENT_STEP, FEDAVG, SERVER_CONSUME

# ---------------------------------------------------------------------------
# Declarative communication / storage profile (paper Table II per method)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CommProfile:
    """Bytes moved / held by one method at a given (cost model, fsl, B).

    Per-*round* fields are totals across all ``n`` clients for one global
    round (= ``h`` mini-batches per client); ``model_sync`` is the total for
    one aggregation event (up + down for every client).  Storage fields are
    static byte counts (Table II last column and §VI-E).

    The ``*_wire`` fields are the codec-aware *effective* bytes: what the
    transport layer actually puts on the link (compressed payload + side
    channels like per-tile scales, exact per ``Codec.wire_bytes``).  They
    default to the raw analytic values, so an identity transport meters
    exactly what it always did; ``CommMeter`` is driven from the wire
    values so compressed runs report compressed bytes, not fp32 fiction.
    """
    uplink_smashed: int         # per round, at the model dtype (analytic)
    uplink_labels: int          # per round
    downlink_grads: int         # per round, at the model dtype (analytic)
    model_sync: int             # per aggregation event
    server_storage: int         # persistent server-side model bytes
    total_storage: int          # aggregation-time storage (server + clients)
    uplink_smashed_wire: int = -1   # codec-effective; -1 -> uplink_smashed
    downlink_grads_wire: int = -1   # codec-effective; -1 -> downlink_grads
    model_sync_wire: int = -1       # codec-effective; -1 -> model_sync

    @property
    def wire_uplink_smashed(self) -> int:
        w = self.uplink_smashed_wire
        return w if w >= 0 else self.uplink_smashed

    @property
    def wire_downlink_grads(self) -> int:
        w = self.downlink_grads_wire
        return w if w >= 0 else self.downlink_grads

    @property
    def wire_model_sync(self) -> int:
        w = self.model_sync_wire
        return w if w >= 0 else self.model_sync

    def unit_wire_bytes(self, n: int, k: int):
        """Per-upload-unit ``(smashed, labels, grads)`` wire bytes — the
        per-round totals split over the ``n * k`` identical upload units
        of a round (k = uploads per client per round).  The granularity
        fault billing charges at: each transmission *attempt* of a unit
        pays these bytes again, so retransmitted traffic is metered
        exactly, per attempt, never averaged."""
        per = n * k
        return (self.wire_uplink_smashed // per, self.uplink_labels // per,
                self.wire_downlink_grads // per)

    @property
    def per_round_total(self) -> int:
        return self.uplink_smashed + self.uplink_labels + self.downlink_grads

    @property
    def per_round_wire_total(self) -> int:
        return (self.wire_uplink_smashed + self.uplink_labels
                + self.wire_downlink_grads)


# ---------------------------------------------------------------------------
# Async / event-driven decomposition (AsyncTrainer)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AsyncHooks:
    """A method's decomposition of one global round into wall-clock events.

    The event engine (:class:`repro.core.async_trainer.AsyncTrainer`) runs
    ``uploads_per_round`` *transactions* per client per round; transaction k
    of client c covers ``batches_per_upload`` local mini-batches:

    1. ``client_compute(cslice, cbatch, lr)
       -> (cslice', upload, pending, metrics)`` — the client's local work
       for one upload unit.  ``cslice`` is that client's slice of the
       stacked state (its server replica included when the method is
       server-replicated); ``upload`` is the pytree that crosses the
       uplink; ``pending`` is client-side context held until the server's
       reply (None for non-blocking methods).
    2. ``server_consume(sstate, upload, lr) -> (sstate', reply, metrics)``
       — applied event-triggered in ARRIVAL order (paper Eq. 11-13).
       ``sstate`` is the shared server state when ``server_shared``, else
       the client's own replica slice.  ``reply`` is the downlink payload
       (cut-layer gradients) or None.
    3. ``client_receive(cslice, pending, reply, lr) -> cslice'`` — only
       for blocking methods (gradient download); the client cannot start
       transaction k+1 before it runs.
    """
    client_compute: Callable
    server_consume: Callable
    client_receive: Optional[Callable] = None
    uploads_per_round: int = 1
    batches_per_upload: int = 1
    server_key: str = "server"
    server_shared: bool = True
    # The shape contract of client_compute's ``cbatch``: True — a stacked
    # [batches_per_upload, B, ...] local phase (CSE-style h-step rounds,
    # kept even when h == 1); False — a single [B, ...] mini-batch.
    # ``batches_per_upload`` alone cannot distinguish the two at h == 1.
    unit_has_h_axis: bool = False


# ---------------------------------------------------------------------------
# One decomposition, two engines: the sync round step assembled from hooks
# ---------------------------------------------------------------------------


def _stacked_keys(hooks: AsyncHooks) -> tuple:
    return ("clients",) if hooks.server_shared \
        else ("clients", hooks.server_key)


def assemble_round_step(hooks: AsyncHooks, fsl: FSLConfig,
                        server_constraint: Optional[Callable] = None,
                        transport=None):
    """Build the synchronous ``round_step`` from a method's AsyncHooks.

    This is the tentpole of the wire-level refactor: the *same*
    client_compute / server_consume / client_receive decomposition the
    event engine runs drives the SPMD path, so the client->server wire is
    an explicit boundary in both.  Per upload unit:

      1. ``vmap(client_compute)`` over the stacked client axis;
      2. the transport codes each client's upload (uplink codec on float
         leaves — labels pass through);
      3. the server consumes: a ``lax.scan`` in client-index order when
         the server is shared (the zero-latency arrival order, Eq. 11-13;
         ``server_constraint`` rebalances each consumed batch, see
         EXPERIMENTS.md §Perf), or a ``vmap`` over per-client replicas;
      4. blocking methods code the gradient reply (downlink codec) and
         run ``vmap(client_receive)``.

    ``uploads_per_round`` units are driven by an outer ``lax.scan`` over
    the ``h`` axis.  With the identity transport no codec ops are inserted
    at all, so the assembled step is bitwise-identical to the pre-refactor
    fused per-method steps (asserted in tests/test_methods.py).
    """
    from repro.transport import resolve_transport
    tp = resolve_transport(transport, fsl)
    K, bpu = hooks.uploads_per_round, hooks.batches_per_upload
    if K * bpu != fsl.h:
        raise ValueError(f"hooks decompose {K}x{bpu} batches per round, "
                         f"but fsl.h={fsl.h}")
    if hooks.unit_has_h_axis:
        if K != 1:
            raise ValueError("unit_has_h_axis hooks must use a single "
                             "upload unit per round")
    elif bpu != 1:
        raise ValueError("unsupported decomposition: per-mini-batch hooks "
                         "require batches_per_upload == 1")
    blocking = hooks.client_receive is not None
    skey, shared = hooks.server_key, hooks.server_shared
    stacked = _stacked_keys(hooks)
    unroll = fsl.unroll or 1
    n = fsl.num_clients
    code_up = not tp.uplink.is_identity
    code_down = blocking and not tp.downlink.is_identity

    def _client_keys(state, channel: str):
        """One key per client, unique per (seed, unit counter, channel) —
        the fold salts come from ``repro.transport.CHANNEL_SALTS``, the
        single stream-discipline contract rule P001 audits."""
        from repro.transport import CHANNEL_SALTS
        base = tp.unit_key(state["round"], salt=CHANNEL_SALTS[channel])
        return jax.vmap(jax.random.fold_in, (None, 0))(base, jnp.arange(n))

    def unit_step(state, ubatch, lr):
        cstack = {k: state[k] for k in stacked}
        with jax.named_scope(CLIENT_STEP):
            cstack, uploads, pendings, cmetrics = jax.vmap(
                lambda cs, b: hooks.client_compute(cs, b, lr))(cstack,
                                                               ubatch)
        if code_up:
            uploads = jax.vmap(tp.code_uplink)(uploads,
                                               _client_keys(state, "uplink"))
        with jax.named_scope(SERVER_CONSUME):
            if shared:
                def consume(sstate, up):
                    if server_constraint is not None:
                        up = jax.tree_util.tree_map(server_constraint, up)
                    sstate, reply, m = hooks.server_consume(sstate, up, lr)
                    return sstate, (reply, m)

                sstate, (replies, smetrics) = lax.scan(
                    consume, state[skey], uploads, unroll=unroll)
            else:
                sstates, replies, smetrics = jax.vmap(
                    lambda s, up: hooks.server_consume(s, up, lr))(
                        cstack[skey], uploads)
                cstack = {**cstack, skey: sstates}
        if blocking:
            if code_down:
                replies = jax.vmap(tp.code_downlink)(
                    replies, _client_keys(state, "downlink"))
            cstack = jax.vmap(
                lambda cs, p, r: hooks.client_receive(cs, p, r, lr))(
                    cstack, pendings, replies)
        new_state = {**state, **cstack, "round": state["round"] + 1}
        if shared:
            new_state[skey] = sstate
        metrics = jax.tree_util.tree_map(jnp.mean, {**cmetrics, **smetrics})
        return new_state, metrics

    def round_step(state, batch, lr):
        if hooks.unit_has_h_axis:
            # one unit covering the whole [n, h, B, ...] round (CSE-style)
            return unit_step(state, batch, lr)
        # per-mini-batch hooks: scan the h axis, one unit per mini-batch
        per_k = jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 1, 0),
                                       batch)
        state, metrics = lax.scan(lambda s, b: unit_step(s, b, lr),
                                  state, per_k)
        return state, jax.tree_util.tree_map(jnp.mean, metrics)

    return round_step


# ---------------------------------------------------------------------------
# Compiled multi-round execution: R rounds fused into one scanned program
# ---------------------------------------------------------------------------


def _in_scope(name: str, fn):
    """``fn`` traced under ``jax.named_scope(name)``."""
    def scoped(*args):
        with jax.named_scope(name):
            return fn(*args)
    return scoped


def make_chunk_step(round_step, aggregate, fsl: FSLConfig,
                    unit_batches: int, masked_aggregate=None,
                    gather: bool = False):
    """Fuse a whole chunk of global rounds into one scannable program.

    ``Trainer.run`` dispatches one jitted ``round_step`` per round from the
    host, syncing metrics and the aggregation cadence every round — at
    paper scale the dispatch round-trips dwarf the per-round compute.  This
    assembler lowers the host loop itself into XLA: a ``lax.scan`` over a
    stacked ``[R, n, h, B, ...]`` batch chunk whose carry is the state, with
    the :class:`repro.core.trainer.AggregationCadence` threshold math
    computed in-carry from the ``state["round"]`` counter — ``lax.cond`` on
    the crossing picks ``aggregate`` per step, so non-divisible schedules
    (h=3, C=2) stay exact — and per-round metrics plus the ``aggregated``
    flags stacked into device arrays the host fetches once per chunk.

    ``unit_batches`` maps the round counter to per-client mini-batches
    (``fsl.h`` for h-periodic methods whose counter advances once per
    round, 1 for per-batch methods whose counter advances per inner unit)
    — the same inversion :meth:`FSLMethod.batches_trained` applies, so a
    chunk resumed from any checkpointed round keeps the paper's C-batch
    schedule.  The lr schedule is staged as a per-round ``lrs`` operand
    (computed host-side in double precision exactly like ``Trainer.lr_at``,
    then scanned over) so the compiled chunk is *bitwise* identical to the
    Python loop, not merely close.

    ``aggregate`` must be structure-preserving (both ``lax.cond`` branches
    return the same state pytree) — true of every registered method's
    FedAvg.  Returns ``chunk_step(state, batches, lrs) -> (state,
    stacked_metrics, agg_mask)``.

    With ``masked_aggregate`` (a scheduling ``aggregate(state, mask)``,
    see :meth:`FSLMethod.make_masked_aggregate`) the chunk instead takes a
    per-round participation plan: ``chunk_step(state, batches, lrs, masks,
    part) -> (state, stacked_metrics, agg_mask, part)``.  ``masks`` is the
    float ``[R, n]`` plan slice for this chunk and ``part`` the running
    participation carry — a client participates in an aggregation only if
    its plan admitted it in EVERY round since the previous aggregation
    (the intersection a multi-round C-batch window implies), and ``part``
    threads across chunk boundaries so non-aligned (chunk, C) schedules
    stay exact.  The ``lax.cond`` fires only when the accumulated cohort
    is non-empty — an empty cohort is a no-op round (the Trainer warns
    host-side); ``agg_mask`` still reports the cadence truth so history
    rows match the per-round loop.

    ``gather=True`` builds the *device-resident data* variant: instead of
    a stacked value chunk, the program takes ``(state, pool, idx, lrs[,
    masks, part])`` where ``pool`` is the whole sample pool (every leaf
    ``[S, ...]``, uploaded to the device once per run, never donated) and
    ``idx`` a ``[R, n, h, B]`` int32 index plan — the scan body gathers
    each round's batch from the pool (``pool_leaf[idx_r]``) before running
    the identical round step.  Since the gather output equals the staged
    host batch element for element, the pool chunk is bitwise-identical to
    the staged one; what it removes is the per-chunk host batch transfer
    (only the tiny index plan crosses per chunk).
    """
    agg_every = fsl.resolved_agg_every
    aggregate = _in_scope(FEDAVG, aggregate)
    if masked_aggregate is not None:
        masked_aggregate = _in_scope(FEDAVG, masked_aggregate)

    def advance(st, batch, lr):
        """One round + the in-carry C-batch threshold crossing."""
        prev = st["round"] * unit_batches
        st, metrics = round_step(st, batch, lr)
        done = st["round"] * unit_batches
        aggregated = (done // agg_every) > (prev // agg_every)
        return st, metrics, aggregated

    def fire_masked(st, acc, aggregated):
        fire = jnp.logical_and(aggregated, jnp.sum(acc) > 0)
        st = lax.cond(fire, masked_aggregate, lambda s, _: s, st, acc)
        return st, jnp.where(aggregated, jnp.ones_like(acc), acc)

    if masked_aggregate is not None and gather:
        def masked_pool_chunk_step(state, pool, idx, lrs, masks, part):
            def body(carry, xs):
                st, acc = carry
                ix, lr, mask = xs
                batch = jax.tree_util.tree_map(lambda p: p[ix], pool)
                st, metrics, aggregated = advance(st, batch, lr)
                st, acc = fire_masked(st, acc * mask, aggregated)
                return (st, acc), (metrics, aggregated)

            (state, part), (metrics, agg_mask) = lax.scan(
                body, (state, part), (idx, lrs, masks))
            return state, metrics, agg_mask, part

        return masked_pool_chunk_step

    if masked_aggregate is not None:
        def masked_chunk_step(state, batches, lrs, masks, part):
            def body(carry, xs):
                st, acc = carry
                batch, lr, mask = xs
                st, metrics, aggregated = advance(st, batch, lr)
                st, acc = fire_masked(st, acc * mask, aggregated)
                return (st, acc), (metrics, aggregated)

            (state, part), (metrics, agg_mask) = lax.scan(
                body, (state, part), (batches, lrs, masks))
            return state, metrics, agg_mask, part

        return masked_chunk_step

    if gather:
        def pool_chunk_step(state, pool, idx, lrs):
            def body(st, xs):
                ix, lr = xs
                batch = jax.tree_util.tree_map(lambda p: p[ix], pool)
                st, metrics, aggregated = advance(st, batch, lr)
                st = lax.cond(aggregated, aggregate, lambda s: s, st)
                return st, (metrics, aggregated)

            state, (metrics, agg_mask) = lax.scan(body, state, (idx, lrs))
            return state, metrics, agg_mask

        return pool_chunk_step

    def chunk_step(state, batches, lrs):
        def body(st, xs):
            batch, lr = xs
            st, metrics, aggregated = advance(st, batch, lr)
            st = lax.cond(aggregated, aggregate, lambda s: s, st)
            return st, (metrics, aggregated)

        state, (metrics, agg_mask) = lax.scan(body, state, (batches, lrs))
        return state, metrics, agg_mask

    return chunk_step


# ---------------------------------------------------------------------------
# The method interface
# ---------------------------------------------------------------------------


class FSLMethod:
    """Base class: subclasses set the four declarative traits and implement
    the state/step/aggregate factories."""

    name: str = ""
    # Declarative traits — these four booleans fully determine Table II.
    uploads_every_batch: bool = True    # False: once per h batches (CSE-FSL)
    downloads_gradients: bool = True    # True: cut-layer grads per batch
    server_replicated: bool = False     # True: one server copy per client
    has_aux: bool = False               # True: auxiliary head on clients
    # The stacked state subtrees make_aggregate FedAvgs (server-replicated
    # methods average their replicas too); make_masked_aggregate mirrors
    # exactly this set, so masked and plain aggregation touch the same
    # state.
    agg_keys: tuple = ("clients",)
    # Declared wire contract: the per-round transport channels this
    # method's round step crosses.  ``repro.analysis`` rule W003 checks the
    # declaration against the channels an abstract trace actually touches,
    # and A003 checks it against ``downloads_gradients`` — so the
    # declaration can never silently drift from the program.  Blocking
    # methods that ship cut-layer gradients back declare
    # ``("uplink", "downlink")``.
    wire_channels: tuple = ("uplink",)

    # -- training ----------------------------------------------------------
    def init_state(self, bundle: SplitModelBundle, fsl: FSLConfig,
                   key) -> Dict[str, Any]:
        raise NotImplementedError

    def make_round_step(self, bundle: SplitModelBundle, fsl: FSLConfig,
                        server_constraint: Optional[Callable] = None,
                        transport=None):
        """Returns ``round_step(state, batch, lr) -> (state, metrics)`` over
        the unified ``[n, h, B, ...]`` batch contract.

        The default assembles the step from :meth:`make_async_hooks` via
        :func:`assemble_round_step` — one decomposition, two engines.  A
        method only overrides this for sync-only execution modes the hook
        decomposition cannot express (e.g. CSE-FSL's fused batched server
        update)."""
        return assemble_round_step(self.make_async_hooks(bundle, fsl), fsl,
                                   server_constraint=server_constraint,
                                   transport=transport)

    def make_chunk_step(self, bundle: SplitModelBundle, fsl: FSLConfig,
                        server_constraint: Optional[Callable] = None,
                        transport=None, participation: bool = False,
                        refresh: bool = True, gather: bool = False):
        """Returns ``chunk_step(state, batches, lrs) -> (state, metrics,
        agg_mask)`` fusing a whole chunk of rounds (stacked on a new
        leading axis) into one scanned program — see :func:`make_chunk_step`.
        Composes with per-method ``make_round_step`` overrides (e.g.
        CSE-FSL's fused batched server update) automatically, since the
        scanned body IS the method's round step.

        ``participation=True`` builds the scheduling variant instead:
        ``chunk_step(state, batches, lrs, masks, part)`` threading a
        per-round participation plan into the in-scan FedAvg ``lax.cond``
        (masked, renormalized, empty-cohort no-op).

        ``gather=True`` builds the device-resident-data variant
        ``chunk_step(state, pool, idx, lrs[, masks, part])`` gathering
        each round's batch from an on-device sample pool in-scan —
        bitwise-identical math, no per-chunk host batch staging (jit it
        with ``donate_argnums=(0,)`` ONLY: the pool must survive the
        call)."""
        round_step = self.make_round_step(bundle, fsl,
                                          server_constraint=server_constraint,
                                          transport=transport)
        magg = self.make_wire_aggregate(fsl, transport=transport,
                                        participation=True,
                                        refresh=refresh) \
            if participation else None
        return make_chunk_step(round_step,
                               self.make_wire_aggregate(fsl,
                                                        transport=transport),
                               fsl, self.unit_batches(fsl),
                               masked_aggregate=magg, gather=gather)

    def make_aggregate(self):
        raise NotImplementedError

    def make_masked_aggregate(self, refresh: bool = True):
        """Participation-aware FedAvg: ``aggregate(state, mask)`` averages
        the :attr:`agg_keys` subtrees over the clients a float ``[n]``
        participation mask admits, weights renormalized over the
        participants (:func:`fedavg_masked`).  ``refresh`` decides whether
        non-participants receive the cohort average or keep their local
        state.  Callers guard the empty mask (host-side warning + no-op in
        the trainers, an in-graph predicate in the compiled chunk)."""
        keys = self.agg_keys

        def aggregate(state, mask):
            return {**state, **{k: fedavg_masked(state[k], mask,
                                                 refresh=refresh)
                                for k in keys}}

        return aggregate

    def make_wire_aggregate(self, fsl: FSLConfig, transport=None,
                            participation: bool = False,
                            refresh: bool = True):
        """Aggregation with the model-sync wire made explicit: before
        FedAvg each client's model subtree (``state["clients"]["params"]``
        — what :meth:`merged_params` deploys and what Table II's
        ``2 n alpha |w|`` counts) crosses the uplink through the
        transport's ``model_up`` codec; after FedAvg the averaged model is
        coded ONCE through ``model_down`` and broadcast, exactly like a
        server shipping one compressed checkpoint to every client.  Server
        replicas (``state["servers"]``) never cross the client link, so
        they aggregate uncoded.

        With the identity model codecs (the default) this returns
        :meth:`make_aggregate` unchanged — zero added ops, bitwise-legacy
        aggregation.  Both engines and the compiled chunk runner route
        aggregation through this wrapper, so quantized model sync shows up
        identically in all three execution paths (key salts 2/3 of
        ``Transport.unit_key``).

        ``participation=True`` returns the scheduling variant
        ``aggregate(state, mask)`` instead (:meth:`make_masked_aggregate`
        behind the same model-sync wire): only the mask's participants
        upload their coded model and enter the renormalized average, and
        ``refresh`` decides whether non-participants download the coded
        average or keep their local params."""
        from repro.transport import CHANNEL_SALTS, resolve_transport
        tp = resolve_transport(transport, fsl)
        agg = self.make_masked_aggregate(refresh=refresh) if participation \
            else self.make_aggregate()
        if tp.model_identity:
            return agg
        n = fsl.num_clients
        up_salt = CHANNEL_SALTS["model_up"]
        down_salt = CHANNEL_SALTS["model_down"]

        def _with_params(state, params):
            return {**state, "clients": {**state["clients"],
                                         "params": params}}

        def _coded_up(state):
            params = state["clients"]["params"]
            keys = jax.vmap(jax.random.fold_in, (None, 0))(
                tp.unit_key(state["round"], salt=up_salt), jnp.arange(n))
            return jax.vmap(tp.code_model_up)(params, keys)

        if participation:
            def aggregate(state, mask):
                coded = _coded_up(state)
                st = agg(_with_params(state, coded), mask)
                # the renormalized average of the participants' CODED
                # params, computed explicitly (with refresh=False the
                # stacked rows are no longer identical, so the
                # code-row-0-and-broadcast trick below does not apply)
                w = (mask / jnp.maximum(jnp.sum(mask), 1.0)).astype(
                    jnp.float32)
                avg = jax.tree_util.tree_map(
                    lambda x: jnp.tensordot(w, x.astype(jnp.float32),
                                            axes=1), coded)
                avg = tp.code_model_down(avg,
                                         tp.unit_key(state["round"], salt=3))
                sel = mask > 0

                def place(d, x, orig):
                    b = jnp.broadcast_to(d, x.shape).astype(x.dtype)
                    if refresh:
                        return b
                    s = sel.reshape((-1,) + (1,) * (x.ndim - 1))
                    return jnp.where(s, b, orig)

                params = jax.tree_util.tree_map(
                    place, avg, st["clients"]["params"],
                    state["clients"]["params"])
                return _with_params(st, params)

            return aggregate

        def aggregate(state):
            params = _coded_up(state)
            state = agg(_with_params(state, params))
            # post-FedAvg the stacked clients are identical: code the
            # average once and broadcast the same coded copy to all n
            avg = jax.tree_util.tree_map(lambda x: x[0],
                                         state["clients"]["params"])
            avg = tp.code_model_down(avg,
                                     tp.unit_key(state["round"], salt=3))
            params = jax.tree_util.tree_map(
                lambda d, x: jnp.broadcast_to(d, x.shape).astype(x.dtype),
                avg, state["clients"]["params"])
            return _with_params(state, params)

        return aggregate

    def merged_params(self, state) -> Dict[str, Any]:
        raise NotImplementedError

    # -- async / event-driven execution ------------------------------------
    def make_async_hooks(self, bundle: SplitModelBundle,
                         fsl: FSLConfig) -> AsyncHooks:
        """Decompose one global round into event-engine hooks (see
        :class:`AsyncHooks`).  All four paper methods implement this; a new
        method may leave it unimplemented and remain sync-only."""
        raise NotImplementedError(
            f"method {self.name!r} defines no async decomposition")

    def unit_batches(self, fsl: FSLConfig) -> int:
        """Per-client mini-batches covered by ONE increment of the
        ``state["round"]`` counter.  Per-batch methods advance the counter
        once per inner upload unit (1), CSE-FSL once per global round of
        ``h`` batches (h).  Both :meth:`batches_trained` and the compiled
        chunk cadence derive from this single multiplier."""
        return 1 if self.uploads_every_batch else fsl.h

    def batches_trained(self, fsl: FSLConfig, state) -> int:
        """Local mini-batches each client has trained so far, recovered
        from ``state["round"]`` via :meth:`unit_batches` — so a resumed
        ``Trainer.run``/``run_compiled`` keeps the paper's C-batch
        aggregation schedule (and its lr schedule)."""
        return int(state["round"]) * self.unit_batches(fsl)

    # -- accounting --------------------------------------------------------
    def hook_arg_specs(self, bundle: SplitModelBundle, fsl: FSLConfig,
                       batch):
        """Abstract argument specs for tracing the async hooks standalone.

        Returns ``(hooks, state_spec, cslice_spec, unit_spec, lr_spec)``:
        the hooks themselves, the full stacked state, ONE client's slice of
        the stacked subtrees, ONE upload unit of ``batch`` (``[n,(h,)B,
        ...]`` with the leading axes dropped per ``unit_has_h_axis``), and
        the scalar lr.  Shared by :meth:`payload_specs` and the static
        checker (``repro.analysis``), which traces ``client_compute`` /
        ``server_consume`` abstractly against exactly these specs."""
        hooks = self.make_async_hooks(bundle, fsl)
        state = jax.eval_shape(lambda k: self.init_state(bundle, fsl, k),
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        cslice = {k: jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), state[k])
            for k in _stacked_keys(hooks)}
        drop = 1 if hooks.unit_has_h_axis else 2            # [n,(h,)B,...]
        unit = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(tuple(x.shape[drop:]), x.dtype),
            batch)
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        return hooks, state, cslice, unit, lr

    def payload_specs(self, bundle: SplitModelBundle, fsl: FSLConfig,
                      batch):
        """Abstract (ShapeDtypeStruct) pytrees of ONE client's ONE upload
        unit and the server's reply, recovered from the async hooks via
        ``jax.eval_shape`` — the exact shapes the transport codecs see, so
        ``Codec.wire_bytes`` accounting is exact, not approximate.
        Returns ``(upload_spec, reply_spec)`` (``reply_spec`` is None for
        non-blocking methods)."""
        hooks, state, cslice, unit, lr = self.hook_arg_specs(bundle, fsl,
                                                             batch)
        _, upload, _, _ = jax.eval_shape(hooks.client_compute, cslice, unit,
                                         lr)
        reply = None
        if hooks.client_receive is not None:
            sstate = state[hooks.server_key] if hooks.server_shared \
                else cslice[hooks.server_key]
            _, reply, _ = jax.eval_shape(hooks.server_consume, sstate,
                                         upload, lr)
        return upload, reply

    def model_sync_specs(self, bundle: SplitModelBundle, fsl: FSLConfig):
        """Abstract pytree of ONE client's model-sync payload — the
        ``state["clients"]["params"]`` subtree that crosses the FedAvg
        wire at aggregation (client model + aux head; opt state stays
        local, matching Table II's ``2 n alpha |w|``)."""
        state = jax.eval_shape(lambda k: self.init_state(bundle, fsl, k),
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
            state["clients"]["params"])

    def comm_profile(self, cm: CostModel, fsl: FSLConfig, batch_size: int,
                     transport=None, payload_specs=None,
                     model_specs=None) -> CommProfile:
        n, q, lb = cm.n, cm.q, cm.label_bytes
        uploads = fsl.h if self.uploads_every_batch else 1
        smashed = n * uploads * q * batch_size
        labels = n * uploads * lb * batch_size
        grads = smashed if self.downloads_gradients else 0
        aux = cm.aux if self.has_aux else 0
        sync = 2 * n * (cm.w_client + aux)
        server = (n if self.server_replicated else 1) * (cm.w_server + aux)
        total = n * (cm.w_client + aux) + server
        wire_up = wire_down = wire_sync = -1
        if (transport is not None and payload_specs is not None
                and not transport.is_identity):
            up_spec, reply_spec = payload_specs
            wire_up = n * uploads * transport.uplink_wire_bytes(up_spec)
            if self.downloads_gradients and reply_spec is not None:
                wire_down = n * uploads * transport.downlink_wire_bytes(
                    reply_spec)
        if (transport is not None and model_specs is not None
                and not transport.model_identity):
            wire_sync = n * (transport.model_up_wire_bytes(model_specs)
                             + transport.model_down_wire_bytes(model_specs))
        return CommProfile(uplink_smashed=smashed, uplink_labels=labels,
                           downlink_grads=grads, model_sync=sync,
                           server_storage=server, total_storage=total,
                           uplink_smashed_wire=wire_up,
                           downlink_grads_wire=wire_down,
                           model_sync_wire=wire_sync)

    def __repr__(self):
        return f"<FSLMethod {self.name}>"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, FSLMethod] = {}


def register(cls):
    """Class decorator: ``@register`` on an FSLMethod subclass makes it
    resolvable by ``get_method(cls.name)``."""
    if not cls.name:
        raise ValueError(f"{cls.__name__} must set a non-empty .name")
    if cls.name in _REGISTRY:
        raise ValueError(
            f"duplicate FSL method name {cls.name!r}: already registered "
            f"by {type(_REGISTRY[cls.name]).__name__}; pick a distinct "
            f".name (registered: {available_methods()})")
    _REGISTRY[cls.name] = cls()
    return cls


def get_method(name: str) -> FSLMethod:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown FSL method {name!r}; registered: "
                       f"{available_methods()}") from None


def available_methods() -> tuple:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Shared helpers for implementations
# ---------------------------------------------------------------------------


def stack_clients(tree, n: int):
    """Replicate a param/opt pytree onto a leading ``num_clients`` axis."""
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy()
        if hasattr(x, "shape") else x, tree)


def fedavg(tree):
    """Mean over the stacked client axis, broadcast back (Eq. 14)."""
    def avg(x):
        m = jnp.mean(x.astype(jnp.float32), axis=0, keepdims=True)
        return jnp.broadcast_to(m, x.shape).astype(x.dtype)
    return jax.tree_util.tree_map(avg, tree)


def fedavg_masked(tree, mask, refresh: bool = True):
    """Partial-aggregation FedAvg: average over the clients ``mask`` admits,
    with the weights renormalized to sum to 1 over the participants (the
    FedLite partial-participation rule, arXiv 2201.11865).

    ``mask`` is a float ``[n]`` vector of 0/1 participation flags.  With
    ``refresh=True`` the participants' average is broadcast to every client
    (dropped clients are refreshed with the new global model); with
    ``refresh=False`` non-participants keep their own state bitwise and
    fold in at their next participating round.  Callers must guard the
    all-zero mask (an empty cohort is a scheduling no-op, not a division
    hazard — the denominator is clamped, but the "average" would be zeros).
    """
    def avg(x):
        w = (mask / jnp.maximum(jnp.sum(mask), 1.0)).astype(jnp.float32)
        m = jnp.tensordot(w, x.astype(jnp.float32), axes=1)
        b = jnp.broadcast_to(m, x.shape).astype(x.dtype)
        if refresh:
            return b
        sel = mask.reshape((-1,) + (1,) * (x.ndim - 1)) > 0
        return jnp.where(sel, b, x)
    return jax.tree_util.tree_map(avg, tree)


def client_mean(tree):
    """Mean over the stacked client axis without re-broadcasting."""
    return jax.tree_util.tree_map(
        lambda x: jnp.mean(x.astype(jnp.float32), 0).astype(x.dtype), tree)
