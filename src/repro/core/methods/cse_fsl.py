"""CSE-FSL: the paper's protocol as jittable JAX step functions.

One *global round* t (paper Fig. 2, Algorithms 1 & 2):

  1. clients run ``h`` local mini-batch steps on (x_c, a_c) via the
     auxiliary-head local loss (Eq. 8-10) — **no server gradients**;
  2. each client recomputes and uploads the smashed data of its last
     batch with the *updated* client model g_{x_c^{t,h}} (Alg. 1 line 9)
     — the upload crosses the transport layer, where the configured
     codec (``--codec int8`` etc.) compresses it;
  3. the server consumes the smashed batches **sequentially** in arrival
     order, updating its *single* model per batch (Eq. 11-13) — or, as a
     beyond-paper optimization, in one fused batched update;
  4. every C batches, FedAvg aggregation of (x_c, a_c) (Eq. 14), realized
     as a mean over the stacked client axis.

The synchronous ``round_step`` is assembled from the same
client_compute/server_consume hooks the event engine runs
(:func:`repro.core.methods.base.assemble_round_step`); only the fused
``server_update="batched"`` mode keeps a dedicated sync-only path (one
batched gradient cannot be expressed as event-triggered consumption).

Clients are *stacked* on a leading ``num_clients`` axis (sharded over the
("pod","data") mesh axes in the distributed launcher); between aggregations
the stacked slices genuinely diverge, exactly like real clients.

Chunked execution (``Trainer.run_compiled``): the state layout is
donation-safe (every leaf is a device array — the ``round`` counter is a
traced int32, never a Python int) and ``make_aggregate`` is
structure-preserving, so rounds scan under ``lax.scan`` with the cadence's
``lax.cond`` picking FedAvg in-carry.  The fused ``server_update="batched"``
override composes automatically: the chunk assembler scans whatever
``make_round_step`` returns.  The counter advances once per h-batch round
(``unit_batches = h``).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import FSLConfig
from repro.core.bundle import SplitModelBundle
from repro.core.stages import CLIENT_STEP, CLIENT_UPDATE, SERVER_CONSUME
from repro.core.methods.base import (AsyncHooks, FSLMethod,
                                     assemble_round_step, client_mean,
                                     fedavg, register, stack_clients)
from repro.optim import make_optimizer

# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------


def init_state(bundle: SplitModelBundle, fsl: FSLConfig, key) -> Dict[str, Any]:
    """clients: stacked replicas of (x_c, a_c) + opt state; server: single."""
    params = bundle.init(key)
    opt_init, _ = make_optimizer(fsl.optimizer)
    n = fsl.num_clients
    client = {"params": params["client"], "aux": params["aux"]}
    return {
        "clients": {"params": stack_clients(client, n),
                    "opt": stack_clients(opt_init(client), n)},
        "server": {"params": params["server"], "opt": opt_init(params["server"])},
        "round": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Client phase (shared by both engines)
# ---------------------------------------------------------------------------


def make_client_round(bundle: SplitModelBundle, fsl: FSLConfig):
    """One client's local phase (Alg. 1): ``client_round(cstate, cbatch, lr)
    -> (cstate', smashed, last_labels, mean_loss)`` over ``[h, B, ...]``.
    Vmapped by the sync round step; called per client slice by the async
    engine — same numerics either way."""
    _, opt_update = make_optimizer(fsl.optimizer)

    def client_round(cstate, cbatch, lr):
        """One client: h local steps, then recompute smashed of last batch."""
        inputs, labels = cbatch

        def one_step(carry, b):
            params, opt = carry
            binputs, blabels = b
            (loss, _), grads = jax.value_and_grad(
                lambda pr: bundle.client_loss(pr["params"], pr["aux"],
                                              binputs, blabels),
                has_aux=True)(params)
            with jax.named_scope(CLIENT_UPDATE):
                new_params, new_opt = opt_update(grads, opt, params, lr)
            return (new_params, new_opt), loss

        (params, opt), losses = lax.scan(
            one_step, (cstate["params"], cstate["opt"]), (inputs, labels),
            unroll=fsl.unroll or 1)
        # Alg.1 line 9: smashed data of the last batch with *updated* weights
        last_inputs = jax.tree_util.tree_map(lambda x: x[-1], inputs)
        last_labels = labels[-1]
        smashed = bundle.client_smashed(params["params"], last_inputs)
        return ({"params": params, "opt": opt}, smashed, last_labels,
                jnp.mean(losses))

    return client_round


# ---------------------------------------------------------------------------
# Round step
# ---------------------------------------------------------------------------


def _make_batched_round_step(bundle: SplitModelBundle, fsl: FSLConfig,
                             transport=None):
    """Beyond-paper sync-only mode: one fused server update over the
    concatenated client batch (gradient = mean over clients; lr scaled by
    n so the total step magnitude matches n sequential steps to first
    order).  The uplink codec still applies per client before the merge —
    the wire is crossed before the server fuses anything."""
    from repro.transport import resolve_transport
    tp = resolve_transport(transport, fsl)
    _, opt_update = make_optimizer(fsl.optimizer)
    client_round = make_client_round(bundle, fsl)
    n = fsl.num_clients

    def round_step(state, batch, lr):
        inputs, labels = batch
        with jax.named_scope(CLIENT_STEP):
            cstates, smashed, slabels, closs = jax.vmap(
                client_round, in_axes=(0, 0, None))(state["clients"],
                                                    (inputs, labels), lr)
        if not tp.uplink.is_identity:
            base = tp.unit_key(state["round"])
            keys = jax.vmap(jax.random.fold_in, (None, 0))(base,
                                                           jnp.arange(n))
            smashed = jax.vmap(lambda x, k: tp.code_uplink(x, k))(smashed,
                                                                  keys)
        smashed = lax.stop_gradient(smashed)
        merged_sm = smashed.reshape((-1,) + smashed.shape[2:])
        merged_lb = slabels.reshape((-1,) + slabels.shape[2:])
        with jax.named_scope(SERVER_CONSUME):
            loss, grads = jax.value_and_grad(bundle.server_loss)(
                state["server"]["params"], merged_sm, merged_lb)
            params, opt = opt_update(grads, state["server"]["opt"],
                                     state["server"]["params"], lr * n)
        new_state = {"clients": cstates,
                     "server": {"params": params, "opt": opt},
                     "round": state["round"] + 1}
        metrics = {"client_loss": jnp.mean(closs), "server_loss": loss}
        return new_state, metrics

    return round_step


def make_round_step(bundle: SplitModelBundle, fsl: FSLConfig,
                    server_constraint=None, transport=None):
    """Returns ``round_step(state, batch, lr) -> (state, metrics)``.

    batch: (inputs, labels) pytrees with leading dims [n_clients, h, B, ...].
    ``server_constraint``: optional fn(tree) -> tree applying a sharding
    constraint to each per-client (smashed, labels) the sequential server
    scan consumes — the §Perf fix for the data-axis sitting idle during
    the faithful event-triggered update (see EXPERIMENTS.md §Perf).
    ``transport``: the wire (None resolves ``fsl.codec``).

    The faithful sequential mode is assembled from the async hooks; the
    fused ``server_update="batched"`` mode keeps its own builder.
    """
    if fsl.server_update == "batched":
        return _make_batched_round_step(bundle, fsl, transport=transport)
    return assemble_round_step(make_async_hooks(bundle, fsl), fsl,
                               server_constraint=server_constraint,
                               transport=transport)


def make_aggregate():
    """FedAvg over the stacked client axis (Eq. 14), opt state included."""
    def aggregate(state):
        return {**state, "clients": fedavg(state["clients"])}
    return aggregate


def merged_params(state) -> Dict[str, Any]:
    """Final model = aggregated client stage + server stage (paper Step 4)."""
    cp = client_mean(state["clients"]["params"])
    return {"client": cp["params"], "aux": cp["aux"],
            "server": state["server"]["params"]}


def make_async_hooks(bundle: SplitModelBundle, fsl: FSLConfig) -> AsyncHooks:
    """Event decomposition (paper Fig. 3): one upload per client per round
    — h local steps, then the smashed batch crosses the uplink; the single
    server consumes arrivals event-triggered in arrival order (Eq. 11-13).
    Non-blocking: clients never wait for gradients."""
    _, opt_update = make_optimizer(fsl.optimizer)
    client_round = make_client_round(bundle, fsl)

    def client_compute(cslice, cbatch, lr):
        cstate, smashed, labels, loss = client_round(cslice["clients"],
                                                     cbatch, lr)
        return ({"clients": cstate}, (smashed, labels), None,
                {"client_loss": loss})

    def server_consume(sstate, upload, lr):
        smashed, labels = upload
        smashed = lax.stop_gradient(smashed)
        loss, grads = jax.value_and_grad(bundle.server_loss)(
            sstate["params"], smashed, labels)
        params, opt = opt_update(grads, sstate["opt"], sstate["params"], lr)
        return {"params": params, "opt": opt}, None, {"server_loss": loss}

    return AsyncHooks(client_compute, server_consume,
                      uploads_per_round=1, batches_per_upload=fsl.h,
                      server_key="server", server_shared=True,
                      unit_has_h_axis=True)


# ---------------------------------------------------------------------------
# Registered method
# ---------------------------------------------------------------------------


@register
class CSEFSL(FSLMethod):
    """The paper's method: h-periodic upload, aux head, single server."""
    name = "cse_fsl"
    uploads_every_batch = False
    downloads_gradients = False
    server_replicated = False
    has_aux = True
    wire_channels = ("uplink",)         # non-blocking: no gradient downlink

    def init_state(self, bundle, fsl, key):
        return init_state(bundle, fsl, key)

    def make_round_step(self, bundle, fsl, server_constraint=None,
                        transport=None):
        return make_round_step(bundle, fsl,
                               server_constraint=server_constraint,
                               transport=transport)

    def make_aggregate(self):
        return make_aggregate()

    def merged_params(self, state):
        return merged_params(state)

    def make_async_hooks(self, bundle, fsl):
        return make_async_hooks(bundle, fsl)
