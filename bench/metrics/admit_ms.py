"""Device time per admitted request: the prefill program plus the eager
operations outside the two jitted programs (the cache splice, the argmax,
the length updates), i.e. every module but decode, over the prefill
count."""

from bench import trace as T
from bench.readings import DECODE, PREFILL

LAYER = "server admission"
MOVES = "itl_p95_ms"
MATCH = PREFILL + DECODE


def read(ctx):
    def one(tr, w0, w1):
        n, _ = T.module_time(tr, PREFILL, w0, w1)
        if n == 0:
            return None
        other = sum(d for name, _, d in T.in_window(tr["modules"], w0, w1)
                    if not T.module_matches(name, DECODE))
        return other / n / 1e6

    return ctx.per_replica(one)
