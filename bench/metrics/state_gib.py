"""Bytes of the training state (stacked client stages, aux heads and one
server model) on the fullest device, from its arrays' shards, in GiB."""


def read(ctx):
    return ctx.state_bytes / 2 ** 30
