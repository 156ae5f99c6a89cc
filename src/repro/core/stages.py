"""Stable names for the stages of a round, as scopes on the device trace.

The chunk program opens each stage under ``jax.named_scope(<name>)``.  The
name lands in every operation's name path (the ``op_name`` metadata XLA
keeps and the profiler reports), forward and backward alike: a backward op
carries it wrapped as ``transpose(jvp(<name>))``.  A trace reduction
therefore matches a stage by path component, and the innermost stage of a
path wins (``aux_ce`` and ``client_update`` are carved out of
``client_step``).  Scopes are metadata: the compiled program's work is the
same with or without them.

  client_step     the clients' local phase: forward and backward over their
                  ``h`` batches and the recompute of the upload
  aux_ce          the aux head and its full-vocabulary cross-entropy
  client_update   the optimizer step on the client stage and aux head
  server_consume  the server's forward, backward, LM-head CE and update
  fedavg          FedAvg of the stacked client stages

Methods and models import these names; ``repro.telemetry`` (host-only,
rule T001) does not belong in traced code.
"""
from __future__ import annotations

CLIENT_STEP = "client_step"
AUX_CE = "aux_ce"
CLIENT_UPDATE = "client_update"
SERVER_CONSUME = "server_consume"
FEDAVG = "fedavg"

STAGES = (CLIENT_STEP, AUX_CE, CLIENT_UPDATE, SERVER_CONSUME, FEDAVG)
