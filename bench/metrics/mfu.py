"""Model FLOP utilisation of the traced window: the useful FLOPs of the
rounds completed (bench/flops/<family>.py, no recomputation) over the
window's length times the chips times their bf16 peak."""


def read(ctx):
    s = ctx.summary
    if s.window_s <= 0 or ctx.rounds_traced <= 0:
        return None
    flops = ctx.rounds_traced * ctx.cell.flops.round_flops(
        ctx.cell.numbers, ctx.cell.mix)["total"]
    return 100.0 * flops / (s.window_s * ctx.chips * ctx.peaks["bf16_flops"])
