"""The decode program's share of its roofline: the least time for the
bytes a step needs (the weights plus the cache of each active slot at its
real length), at the peak table's rates, over the program's device
time."""

from bench import work
from bench.readings import DECODE, roofline

LAYER = "kernels: decode program"
MOVES = "itl_p95_ms"
MATCH = DECODE


def read(ctx):
    c = ctx.config

    def least(stats):
        n, ctx_sum = int(stats["n"]), int(stats["ctx_sum"])
        return work.least_time(work.decode_flops(c, n, ctx_sum),
                               work.decode_bytes(c, n, ctx_sum), ctx.peak)

    return roofline(ctx, MATCH, "decode", least)
