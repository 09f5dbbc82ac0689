"""Device-idle time between decode programs, split by host state.

A gap is ``decode_gap_ms``'s: between consecutive decode programs while
the replica had work pending.  Every device-idle instant inside a gap is
credited to the innermost (latest-starting) ``perfbench_`` span over it,
the rule of ``host_state_at`` in ``bench/trace.py``, else to
``outside_server``.  So a gap's states add up to its idle time, and their
means over the gaps to ``decode_gap_ms``.  A span nested in another takes
its own share: the ``slot_wait`` inside a ``publish`` is not the
``publish``'s.

The spans are ``bench/replica.py``'s around the server's calls and the
program's own (``repro.obs.spans``): the executor's poll and take, the
results publish and its slot waits, the decode sync, and the rest.

    python3 -m bench.host_states TRACE.json [...]

prints each normalised trace's split, in ms per gap, beside
``decode_gap_ms``.
"""

from __future__ import annotations

from bisect import bisect_left, bisect_right

from bench import trace as T
from bench.readings import DECODE, pending_intervals

OUTSIDE = "outside_server"


def decode_gaps(tr: dict, w0: int, w1: int) -> list[tuple[int, int]]:
    """``(end of one decode program, start of the next)`` inside the
    window, where work was pending across the whole gap."""
    dec = sorted((s, s + d) for name, s, d in T.in_window(tr["modules"], w0, w1)
                 if T.module_matches(name, DECODE))
    pend = pending_intervals(tr, w0, w1)
    return [(a_end, b_start) for (_, a_end), (b_start, _) in zip(dec, dec[1:])
            if any(ps <= a_end and b_start <= pe for ps, pe in pend)]


def _timeline(tr: dict) -> tuple[list[int], list[str]]:
    """The host state from each span boundary to the next: ``state[i]``
    holds over ``[cuts[i], cuts[i + 1])``; before the first cut and from
    the last one on, no span holds."""
    spans = [(s, s + d, -i, name[len(T.SPAN_PREFIX):])
             for i, (name, s, d, _) in enumerate(tr["spans"])
             if name != T.WINDOW and d > 0]
    by_start = sorted(spans)
    by_end = sorted(spans, key=lambda sp: sp[1])
    cuts = sorted({t for s, e, _, _ in spans for t in (s, e)})
    active: dict[int, tuple] = {}
    state, j, k = [], 0, 0
    for t in cuts:
        while k < len(by_end) and by_end[k][1] <= t:
            active.pop(by_end[k][2], None)
            k += 1
        while j < len(by_start) and by_start[j][0] <= t:
            active[by_start[j][2]] = by_start[j]
            j += 1
        # the latest start; of two with one start, the first written
        state.append(max(active.values(), key=lambda sp: (sp[0], sp[2]))[3]
                     if active else OUTSIDE)
    return cuts, state


def split(tr: dict, w0: int, w1: int) -> dict[str, float]:
    """Mean, over the window's decode gaps, of the device-idle ms inside
    each gap by host state; empty where the window holds no such gap."""
    gaps = decode_gaps(tr, w0, w1)
    if not gaps:
        return {}
    cuts, state = _timeline(tr)
    busy = T.busy_intervals(tr, w0, w1)
    busy_starts = [s for s, _ in busy]
    busy_ends = [e for _, e in busy]
    total: dict[str, int] = {}
    for a, b in gaps:
        near = busy[bisect_right(busy_ends, a):bisect_left(busy_starts, b)]
        for s, e in T.idle_gaps(near, a, b):
            i = bisect_right(cuts, s) - 1
            while s < e:
                hi = min(e, cuts[i + 1]) if i + 1 < len(cuts) else e
                key = state[i] if i >= 0 else OUTSIDE
                total[key] = total.get(key, 0) + hi - s
                s, i = hi, i + 1
    return {k: v / len(gaps) / 1e6 for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])}


def main(paths: list[str]) -> None:
    import json

    from bench import spec
    from bench.readings import Context

    for path in paths:
        with open(path) as f:
            tr = json.load(f)
        w0, w1 = T.window(tr)
        parts = split(tr, w0, w1)
        gap = spec.load_metric("decode_gap_ms").read(
            Context(config={}, serving={}, peak=None, traces=[tr]))
        print(json.dumps({"trace": path, "gaps": len(decode_gaps(tr, w0, w1)),
                          "decode_gap_ms": gap, "sum_ms": sum(parts.values()),
                          "split_ms": parts}))


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
