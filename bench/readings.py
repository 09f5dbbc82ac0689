"""What a per-layer metric reads, and the reducers several metrics share.

Each ``bench/metrics/<name>.py`` defines ``LAYER``, ``MOVES``, ``MATCH``
(the XLA module names it reads, where it reads any) and ``read(ctx)``,
which returns a number or ``None`` when the run holds nothing to read; the
harness then leaves the metric out of the line.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from bench import trace as T
from bench import work

PREFILL = ["jit_prefill"]   # the server's two jitted programs (make_steps)
DECODE = ["jit_decode"]


@dataclass
class Context:
    config: dict                       # the cell's model shape
    peak: dict | None                  # bench/peaks.json row (None off TPU)
    serving: dict = field(default_factory=dict)  # slots, max_seq
    traces: list = field(default_factory=list)   # normalised, per replica
    compiles_in_window: int | None = None

    def per_replica(self, fn):
        """Mean over traced replicas of ``fn(trace, w0, w1)``, skipping
        replicas where it reads nothing; ``None`` when none reads."""
        vals = []
        for tr in self.traces:
            if not tr["modules"]:
                continue  # no device plane: nothing ran on an accelerator
            v = fn(tr, *T.window(tr))
            if v is not None:
                vals.append(v)
        return sum(vals) / len(vals) if vals else None


def pending_intervals(tr: dict, w0: int, w1: int):
    """Where the replica had requests queued or in flight: from the
    window's opening state and the ``busy``/``idle`` marks."""
    win = [s for s in tr["spans"] if s[0] == T.WINDOW][0]
    pending = bool(win[3].get("pending", 0))
    marks = sorted((s[1], s[0]) for s in tr["spans"]
                   if s[0] in (T.BUSY, T.IDLE))
    out, start = [], (w0 if pending else None)
    for t, name in marks:
        if not w0 <= t < w1:
            continue
        if name == T.BUSY and start is None:
            start = t
        elif name == T.IDLE and start is not None:
            out.append((start, t))
            start = None
    if start is not None:
        out.append((start, w1))
    return out


def served_flops(c: dict, tr: dict, w0: int, w1: int) -> float:
    """Model FLOPs of the prompts admitted and tokens decoded in the
    window, as the two rooflines count them."""
    pre = sum(work.prefill_flops(c, int(s[3]["len"]))
              for s in T.spans(tr, "prefill", w0, w1))
    dec = sum(work.decode_flops(c, int(s[3]["n"]), int(s[3]["ctx_sum"]))
              for s in T.spans(tr, "decode", w0, w1))
    return pre + dec


def mfu(ctx: Context):
    """Served model FLOPs over peak times the time work was pending."""
    if ctx.peak is None:
        return None

    def one(tr, w0, w1):
        pend = sum(e - s for s, e in pending_intervals(tr, w0, w1))
        if pend <= 0:
            return None
        return 100.0 * served_flops(ctx.config, tr, w0, w1) / (
            ctx.peak["bf16_flops"] * pend / 1e9)

    return ctx.per_replica(one)


def device_idle(ctx: Context):
    """1 - the union of device-busy intervals over the window, in %."""
    def one(tr, w0, w1):
        return 100.0 * (1.0 - T.busy_ns(tr, w0, w1) / (w1 - w0))

    return ctx.per_replica(one)


def roofline(ctx: Context, program: list, span: str, least):
    """Least time for the work the program's calls needed, over the
    program's device time in the window.  ``least(span_stats)`` gives one
    call's least time; the mean over the window's host calls is scaled by
    the device's count of the program's runs, so a call cut by the window
    edge on one side only does not skew the share."""
    if ctx.peak is None:
        return None

    def one(tr, w0, w1):
        n, dev_ns = T.module_time(tr, program, w0, w1)
        calls = T.spans(tr, span, w0, w1)
        if n == 0 or not calls or dev_ns <= 0:
            return None
        mean_least = sum(least(s[3]) for s in calls) / len(calls)
        return 100.0 * mean_least * n / (dev_ns / 1e9)

    return ctx.per_replica(one)

