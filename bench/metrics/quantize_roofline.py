"""Share of its roofline that the uplink's quantize kernel (``quantize_2d``,
the int8 codec's encode) reaches in the traced window: the HBM bytes its
calls move (:func:`quantize_bytes` of one upload of B*S rows by d at the
model's type, from the kernel's operands) over their device time, over
the chip's HBM bandwidth.  Bytes bound the kernel: it does a few vector
operations an element and no matrix product.  Absent when the kernel
never ran."""
import numpy as np

import codec

KERNEL = "quantize_2d"
# row tiles ``quantize_2d`` takes per grid step: its scale output has
# this many rows per group, and the payload's rows pad to whole groups
GROUP = 8


def quantize_bytes(rows: int, cols: int, itemsize: int) -> int:
    """HBM bytes one ``quantize_2d`` call moves for an ``[rows, cols]``
    payload of ``itemsize`` bytes an element, from the kernel's operands:
    the payload read and the one-byte values written, both padded to
    whole tiles and the rows to whole groups of ``GROUP`` row tiles, and
    the float32 scales written, one per tile of the padded rows."""
    nr, nc = codec.tiles(rows, cols)
    groups = -(-nr // GROUP)
    padded = groups * GROUP * codec.TILE_ROWS * nc * codec.TILE_COLS
    return padded * itemsize + padded + groups * GROUP * nc * 4


def read(ctx):
    calls, seconds = ctx.summary.kernel(KERNEL)
    if calls <= 0 or seconds <= 0:
        return None
    mix = ctx.cell.mix
    itemsize = np.dtype(ctx.cell.config["torch_dtype"]).itemsize
    per_call = quantize_bytes(mix["batch"] * mix["seq"],
                              ctx.cell.numbers["d"], itemsize)
    return 100.0 * calls * per_call / seconds / ctx.peaks["hbm_bw"]
