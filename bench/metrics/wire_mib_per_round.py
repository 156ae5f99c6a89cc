"""Bytes on the client-server wire per round (CommMeter total:
codec-effective uplink smashed data and labels, plus FedAvg's model
sync up and down), in MiB."""


def read(ctx):
    if ctx.metered_rounds <= 0:
        return None
    return sum(ctx.meter.values()) / 2 ** 20 / ctx.metered_rounds
