"""Mean device-idle time between consecutive decode programs while the
replica had work pending: the host's share of a decode round (the Python
round, the round timer, result publishing)."""

from bench import trace as T
from bench.readings import DECODE, pending_intervals

LAYER = "server round loop"
MOVES = "itl_p95_ms"
MATCH = DECODE


def read(ctx):
    def one(tr, w0, w1):
        dec = sorted((s, s + d) for name, s, d in T.in_window(tr["modules"], w0, w1)
                     if T.module_matches(name, MATCH))
        busy = T.busy_intervals(tr, w0, w1)
        pend = pending_intervals(tr, w0, w1)
        gaps = [T.idle_within(busy, a_end, b_start)
                for (_, a_end), (b_start, _) in zip(dec, dec[1:])
                if any(ps <= a_end and b_start <= pe for ps, pe in pend)]
        return sum(gaps) / len(gaps) / 1e6 if gaps else None

    return ctx.per_replica(one)
