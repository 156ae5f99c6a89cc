"""Faults planted under the timed path, to show that the comparison which
decides ``correct`` catches them.  Each is a drop-in for the harness's
``program_bundle`` or ``make_trainer`` seam; none is used by a benchmark
run itself.

  unchanged   the chunk program returns its state as it came in
  half_batch  client and server losses see the first half of each
              mini-batch only, the mean taken over the rest
  altered     every upload's first token activation is negated where the
              client produces it
  codec_skipped  in a coded mix, the uplink codec bills its coded bytes
              but hands the server the upload as computed, un-quantized
  codec_nearest  in a coded mix, the uplink codec rounds to the nearest
              step instead of stochastically (biased, and cheaper)
"""
from __future__ import annotations

import dataclasses


def half_batch(cfg):
    from repro.core.bundle import transformer_bundle
    b = transformer_bundle(cfg)

    def half(x):
        return x[: x.shape[0] // 2]

    def client_loss(cp, ap, inputs, labels):
        import jax
        return b.client_loss(cp, ap, jax.tree_util.tree_map(half, inputs),
                             half(labels))

    def server_loss(sp, smashed, labels):
        return b.server_loss(sp, half(smashed), half(labels))

    return dataclasses.replace(b, client_loss=client_loss,
                               server_loss=server_loss)


def altered(cfg):
    from repro.core.bundle import transformer_bundle
    b = transformer_bundle(cfg)

    def client_smashed(cp, inputs):
        sm = b.client_smashed(cp, inputs)
        return sm.at[:, 0, :].multiply(-1)

    return dataclasses.replace(b, client_smashed=client_smashed)


def unchanged(bundle, fsl):
    """A trainer whose chunk program computes its rounds' metrics but
    hands back the state it was given."""
    import jax
    from repro.core.trainer import Trainer
    tr = Trainer(bundle, fsl)
    step = tr.method.make_chunk_step(bundle, fsl, transport=tr.transport,
                                     gather=True)

    def chunk(state, pool, idx, lrs):
        _, metrics, agg = step(state, pool, idx, lrs)
        return state, metrics, agg

    tr.pool_chunk_fn = jax.jit(chunk)
    return tr


def codec_skipped(bundle, fsl):
    """A trainer whose uplink codec keeps its name and wire bytes but
    passes every upload through unchanged."""
    from repro.core.trainer import Trainer
    from repro.transport import resolve_transport
    tp = resolve_transport(None, fsl)
    coded = tp.uplink

    class Skipped(type(coded)):
        def roundtrip(self, payload, *, key=None):
            return payload

    return Trainer(bundle, fsl, transport=dataclasses.replace(
        tp, uplink=Skipped(**dataclasses.asdict(coded))))


def codec_nearest(bundle, fsl):
    """A trainer whose quantizing uplink codec rounds to nearest."""
    from repro.core.trainer import Trainer
    from repro.transport import resolve_transport
    tp = resolve_transport(None, fsl)
    return Trainer(bundle, fsl, transport=dataclasses.replace(
        tp, uplink=dataclasses.replace(tp.uplink, stochastic=False)))


BUNDLE_FAULTS = {"half_batch": half_batch, "altered": altered}
TRAINER_FAULTS = {"unchanged": unchanged, "codec_skipped": codec_skipped,
                  "codec_nearest": codec_nearest}
