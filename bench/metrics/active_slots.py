"""Mean number of slots a decode step serves (the active requests at each
decode program call in the traced span).  At a fixed offered load, a
faster server holds each request for less time, so fewer are active."""

from bench import trace as T

LAYER = "server round loop"
MOVES = "itl_p95_ms"
MATCH = []


def read(ctx):
    def one(tr, w0, w1):
        steps = T.spans(tr, "decode", w0, w1)
        return sum(int(s[3]["n"]) for s in steps) / len(steps) if steps else None

    return ctx.per_replica(one)
