"""Mean device-idle gap between the end of one chunk program and the start
of the next, from the profiler trace, averaged over the cell's chips."""


def read(ctx):
    gaps = ctx.summary.chunk_gaps_s
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
