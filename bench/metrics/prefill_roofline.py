"""The prefill program's share of its roofline: the least time for the
work each prompt needs (causal attention over the real prompt, the last
position's logits), at the peak table's rates, over the program's device
time."""

from bench import work
from bench.readings import PREFILL, roofline

LAYER = "kernels: prefill program"
MOVES = "itl_p95_ms"
MATCH = PREFILL


def read(ctx):
    c = ctx.config

    def least(stats):
        s = int(stats["len"])
        return work.least_time(work.prefill_flops(c, s),
                               work.prefill_bytes(c, s), ctx.peak)

    return roofline(ctx, MATCH, "prefill", least)
