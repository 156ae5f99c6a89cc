"""Bytes of smashed data on the uplink per round (CommMeter
``uplink_smashed``, codec-effective), in MiB."""


def read(ctx):
    if ctx.metered_rounds <= 0:
        return None
    return ctx.meter["uplink_smashed"] / 2 ** 20 / ctx.metered_rounds
