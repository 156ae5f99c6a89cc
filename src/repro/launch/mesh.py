"""Production mesh builders.

A function (not a module-level constant) so importing never touches jax
device state.  Shapes: single pod = (16, 16) over ("data", "model") = 256
chips (TPU v5e pod slice); multi-pod = (2, 16, 16) over ("pod", "data",
"model") = 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes: the rules in ``repro.sharding``
    are partitioner hints, while JAX's default Explicit axes would make
    every sharding part of the array's type (and refuse, e.g., a vmap
    over a client axis sharded differently from its peers)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """``shape``: optional (data, model) override for the single-pod mesh —
    e.g. (32, 8) so the model axis divides 8 kv heads when serving."""
    if shape is not None and not multi_pod:
        axes = ("data", "model")
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) > need:        # 512 placeholder devices, single-pod mesh
        devs = devs[:need]
    return _mesh(shape, axes, devices=devs)


def make_host_mesh(model: int = 2, data: int = 2, pod: int = 0):
    """Small mesh over however many (host) devices exist — for tests."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
