"""Mean share of the reserved cache (``slots`` x ``max_seq`` positions)
that the active requests' real lengths fill at each decode step of the
traced span."""

from bench import trace as T

LAYER = "server round loop"
MOVES = "itl_p95_ms"
MATCH = []


def read(ctx):
    reserved = int(ctx.serving["slots"]) * int(ctx.serving["max_seq"])

    def one(tr, w0, w1):
        steps = T.spans(tr, "decode", w0, w1)
        if not steps:
            return None
        return 100.0 * sum(int(s[3]["ctx_sum"]) for s in steps) / (
            len(steps) * reserved)

    return ctx.per_replica(one)
