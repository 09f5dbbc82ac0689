"""Model FLOPs of the prompts and tokens served in the window, over the
chip's bf16 peak times the time the replica had work pending."""

from bench.readings import DECODE, PREFILL, mfu

LAYER = "model step"
MOVES = "itl_p95_ms"
MATCH = PREFILL + DECODE


def read(ctx):
    return mfu(ctx)
