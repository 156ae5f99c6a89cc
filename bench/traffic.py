"""Training traffic made from the seed: token rows and the per-round batch
plan of every client.

A traffic mix is a JSON file under ``bench/traffic/`` holding only
numbers; this module is the one generator that reads any of them:

  clients          n, the clients that train each round
  h                local mini-batches per client per round (upload period)
  batch, seq       B and S of one client mini-batch
  rounds_per_chunk rounds fused into one compiled dispatch
  samples_per_client  rows each client holds (its local dataset)
  codec            uplink codec name ("none", "int8")
  lr               learning rate of every local and server step

The rows come from the benchmark's own copy of the program's synthetic LM
task (a sparse random Markov chain), so the program receives only
generated inputs and the reference reads the very same rows.
"""
from __future__ import annotations

import numpy as np

_PLAN_SALT = 0x5EED


def synthetic_lm(num_samples, seq_len, vocab, rng):
    """Token sequences from a sparse random Markov chain: with probability
    0.8 the next token is a fixed permutation of the previous one, else
    uniform.  Returns ``(x, y)`` with ``y`` the next-token labels of
    ``x``."""
    perm = rng.permutation(vocab)
    toks = rng.integers(0, vocab, size=(num_samples, seq_len)).astype(np.int32)
    for t in range(1, seq_len):
        follow = rng.random(size=num_samples) < 0.8
        toks[follow, t] = perm[toks[follow, t - 1]]
    return toks[:, :-1], toks[:, 1:]


class Traffic:
    """Rows and batch plans of one mix for one seed.

    Client ``c`` owns rows ``c * samples_per_client ...`` of the pool and
    walks a seeded permutation of them, one round at a time; the plan of
    round ``r`` is a pure function of ``(seed, r)``, so the reference
    rebuilds any round's batches without state.  Every row a client sees
    in its first ``samples_per_client // (h * batch)`` rounds is distinct.
    """

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.seed = int(seed)
        self.n = int(mix["clients"])
        self.h = int(mix["h"])
        self.batch = int(mix["batch"])
        self.seq = int(mix["seq"])
        self.per_client = int(mix["samples_per_client"])
        rng = np.random.default_rng([self.seed, 0])
        self.x, self.y = synthetic_lm(self.n * self.per_client,
                                      self.seq + 1, vocab, rng)
        self._orders = np.stack([
            np.random.default_rng([self.seed, _PLAN_SALT, c]).permutation(
                self.per_client) for c in range(self.n)])

    def round_plan(self, rnd: int) -> np.ndarray:
        """``[n, h, B]`` int32 pool rows of global round ``rnd``."""
        per = self.h * self.batch
        out = np.empty((self.n, self.h, self.batch), np.int32)
        for c in range(self.n):
            pos = (rnd * per + np.arange(per)) % self.per_client
            out[c] = (c * self.per_client
                      + self._orders[c][pos]).reshape(self.h, self.batch)
        return out


class PlanBatcher:
    """Feeds ``Trainer.run_compiled`` through its device-pool protocol:
    the pool goes to the device once, and each round ships its index
    plan.  ``next_round_indices`` walks the rounds in order."""

    def __init__(self, traffic: Traffic):
        self.traffic = traffic
        self.round = 0
        self._pool = None

    def device_pool(self):
        if self._pool is None:
            import jax.numpy as jnp
            self._pool = ({"tokens": jnp.asarray(self.traffic.x)},
                          jnp.asarray(self.traffic.y))
        return self._pool

    def next_round_indices(self):
        plan = self.traffic.round_plan(self.round)
        self.round += 1
        return plan
