"""1 - the union of the device's busy intervals over the traced window."""

from bench.readings import device_idle

LAYER = "device"
MOVES = "itl_p95_ms"
MATCH = []


def read(ctx):
    return device_idle(ctx)
