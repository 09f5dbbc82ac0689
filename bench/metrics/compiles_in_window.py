"""Compilations in the replicas between the window's opening and the last
window request's completion (the replica's ``replica.compiles`` counter,
read through ``ReplicaPool.device_report``); 0 once set-up warms every
shape."""

LAYER = "replica pool"
MOVES = "itl_p95_ms"
MATCH = []


def read(ctx):
    return ctx.compiles_in_window
