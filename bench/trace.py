"""From a profiler trace to the numbers the per-layer metrics read.

``normalize`` turns one replica's ``.xplane.pb`` into a small JSON-able
dict: the device's XLA module events, the union of its op events (the
busy intervals), and the host spans whose names start with ``perfbench_``
(written by ``bench/replica.py``), all in nanoseconds on the trace's one
clock.  Everything after that is plain
Python over that dict, so the reduction is checked on a recorded fixture
without a chip.
"""

from __future__ import annotations

import re

SPAN_PREFIX = "perfbench_"
WINDOW = "perfbench_window"
BUSY, IDLE = "perfbench_busy", "perfbench_idle"  # backlog starts, drains
# XLA module names of the jitted programs carry no suffix on the module
# line, or a "(<program id>)" one
_MODULE_LINES = ("XLA Modules",)
_OP_LINES = ("XLA Ops",)


def normalize(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    out = {"modules": [], "busy": [], "spans": [], "planes": {}}
    ops = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out["planes"][plane.name] = [ln.name for ln in lines]
        if plane.name.startswith("/device:TPU:"):
            for ln in lines:
                if ln.name in _MODULE_LINES:
                    out["modules"].extend(
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in ln.events)
                elif ln.name in _OP_LINES:
                    ops.extend((int(e.start_ns), int(e.start_ns + e.duration_ns))
                               for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["spans"].append(
                            [e.name, int(e.start_ns), int(e.duration_ns),
                             {k: v for k, v in e.stats}])
    out["busy"] = [list(iv) for iv in merge(ops)]
    return out


def window(tr: dict) -> tuple[int, int]:
    """The measured window: the replica's ``perfbench_window`` span."""
    spans = [s for s in tr["spans"] if s[0] == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"trace has {len(spans)} window spans, want 1")
    _, t0, dur, _ = spans[0]
    return t0, t0 + dur


def module_matches(name: str, patterns) -> bool:
    """An XLA module name against the metric's names: ``jit_decode``
    matches ``jit_decode`` and ``jit_decode(12)``."""
    return any(re.fullmatch(re.escape(p) + r"(\(\d+\))?", name)
               for p in patterns)


def in_window(events, w0: int, w1: int):
    """Events that start inside ``[w0, w1)``."""
    return [e for e in events if w0 <= e[1] < w1]


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, w0: int, w1: int) -> list[tuple[int, int]]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if e > w0 and s < w1]


def busy_intervals(tr: dict, w0: int, w1: int) -> list[tuple[int, int]]:
    """Device-busy intervals inside the window: the union of op events
    (of module events where the trace has no op line)."""
    ivs = tr["busy"] or [(s, s + d) for _, s, d in tr["modules"]]
    return merge(clip(ivs, w0, w1))


def busy_ns(tr: dict, w0: int, w1: int) -> int:
    return sum(e - s for s, e in busy_intervals(tr, w0, w1))


def idle_gaps(busy, w0: int, w1: int) -> list[tuple[int, int]]:
    """The complement of ``busy`` inside the window."""
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    return gaps


def idle_within(busy, s: int, e: int) -> int:
    """Idle nanoseconds inside ``[s, e)`` given merged busy intervals."""
    covered = sum(min(be, e) - max(bs, s) for bs, be in busy
                  if be > s and bs < e)
    return max(0, (e - s) - covered)


def module_time(tr: dict, patterns, w0: int, w1: int) -> tuple[int, int]:
    """(count, device ns) of the matching module events in the window."""
    ev = [m for m in in_window(tr["modules"], w0, w1)
          if module_matches(m[0], patterns)]
    return len(ev), sum(d for _, _, d in ev)


def spans(tr: dict, name: str, w0: int, w1: int):
    """Host spans ``perfbench_<name>`` that start in the window."""
    full = SPAN_PREFIX + name
    return [s for s in in_window(tr["spans"], w0, w1) if s[0] == full]


def host_state_at(tr: dict, t: int) -> str:
    """What the replica's host thread was doing at ``t``: the innermost
    (latest-starting) ``perfbench_`` span that covers it, else
    ``outside_server`` (the round timer, result publishing, heartbeat)."""
    best = None
    for name, s, d, _ in tr["spans"]:
        if name == WINDOW:
            continue
        if s <= t < s + d and (best is None or s > best[1]):
            best = (name, s)
    return best[0][len(SPAN_PREFIX):] if best else "outside_server"


def breakdown(traces: list[dict], top: int = 10) -> dict:
    """The device modules that took most time and the idle time by host
    state, per chip (averaged over the traced replicas), in seconds."""
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    for tr in traces:
        w0, w1 = window(tr)
        for name, _, d in in_window(tr["modules"], w0, w1):
            key = re.sub(r"\(\d+\)$", "", name)
            ops[key] = ops.get(key, 0.0) + d / 1e9
        for s, e in idle_gaps(busy_intervals(tr, w0, w1), w0, w1):
            key = host_state_at(tr, (s + e) // 2)
            idle[key] = idle.get(key, 0.0) + (e - s) / 1e9
    n = max(1, len(traces))

    def top_n(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": top_n(ops), "idle_gaps": top_n(idle)}
