"""The benchmark harness's tests in the tier-1 run, and the split of each
decode gap by the program's own host spans.

Every test of ``bench/tests/test_harness.py`` is imported here.  The CPU
rehearsal, ``bench/tests/test_rehearsal.py``, is not: each of its cases
spawns a run of about a minute."""

from __future__ import annotations

import glob
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec, work  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.host_states import OUTSIDE, decode_gaps, split  # noqa: E402
from bench.readings import Context  # noqa: E402
from bench.tests.test_harness import *  # noqa: E402,F401,F403

# the host spans bench/replica.py writes around the server's calls, and its
# window and backlog marks
BENCH_SPANS = {"ingest", "round", "admit", "prefill", "decode_round",
               "decode", "busy", "idle", "window"}
# the program's own spans (repro.obs.spans)
PROGRAM_SPANS = {"poll", "take", "publish", "slot_wait", "heartbeat",
                 "splice", "sync", "retire"}

# what each reader reads on the recorded trace
RECORDED_READINGS = {
    "admit_ms": 44.04212575,
    "decode_gap_ms": 6.197254200000001,
    "active_slots": 1.380952380952381,
    "kv_fill_pct": 0.5461556570870536,
    "prefill_roofline": 49.41323712404492,
    "decode_roofline": 11.161669968374088,
    "mfu_pct.chat": 5.852112550024324,
    "device_idle_pct.serve": 14.105733600000004,
    "compiles_in_window": 0,
}


def _ctx(traces, peak=None):
    c = spec.load_json(spec.ROOT / "bench/configs/qwen2-1.5b.json")
    return Context(config=c, serving=c["serving"], peak=peak, traces=traces,
                   compiles_in_window=0)


def _read(name, ctx):
    return spec.load_metric(name).read(ctx)


def _split(tr):
    return split(tr, *T.window(tr))


def test_recorded_trace_reads_as_before(recorded):
    """On the recorded v5e chat trace, which predates the program's spans,
    the nine readers read exactly what they read before, and its decode
    gaps split into the benchmark's own states alone."""
    ctx = _ctx([recorded], peak=work.peaks("TPU v5 lite"))
    chat = [m["name"] for m in spec.load_benchmark()["per_layer"]
            if "qwen2-1.5b.chat" in m["workloads"]]
    assert sorted(chat) == sorted(RECORDED_READINGS)
    for name, want in RECORDED_READINGS.items():
        assert _read(name, ctx) == want, name
    parts = _split(recorded)
    assert set(parts) <= BENCH_SPANS | {OUTSIDE}
    assert sum(parts.values()) == pytest.approx(RECORDED_READINGS[
        "decode_gap_ms"])


# -- the split on a hand-built trace --------------------------------------------

MS = 1_000_000  # ns


def _trace(modules, spans, busy=()):
    return {"modules": [[n, s * MS, d * MS] for n, s, d in modules],
            "busy": [[s * MS, e * MS] for s, e in busy],
            "spans": [["perfbench_" + n, s * MS, d * MS, st]
                      for n, s, d, st in spans],
            "planes": {}}


def _hand_built():
    """Decode programs at 0, 200, 500 and 900 ms; work pending from the
    window's opening until an ``idle`` mark at 700 ms, so the gaps read
    are 100-200 and 300-500 (600-900 is not pending).  A prefill program
    keeps the device busy over 350-400.

    * gap 1 (idle 100): poll 80-150 (50 of it in the gap), publish
      150-200 with slot_wait 160-190 nested inside it (20 and 30);
    * gap 2 (idle 150): publish 300-330 (30), poll 330-380 (idle 20,
      busy 350-380), under no span 400-420 (20), splice 420-500
      (80)."""
    return _trace(
        modules=[("jit_decode", 0, 100), ("jit_decode", 200, 100),
                 ("jit_prefill", 350, 50), ("jit_decode", 500, 100),
                 ("jit_decode", 900, 50)],
        spans=[("window", 0, 1000, {"pending": 1}),
               ("poll", 80, 70, {"timeout_us": 2000}),
               ("publish", 150, 50, {"rows": 3, "ok": 1}),
               ("slot_wait", 160, 30, {"step_us": 50000}),
               ("publish", 300, 30, {"rows": 2, "ok": 1}),
               ("poll", 330, 50, {"timeout_us": 2000}),
               ("splice", 420, 80, {"len": 64, "queued_us": 10}),
               ("idle", 700, 0, {}),
               ("poll", 600, 300, {"timeout_us": 100000})])


def test_split_on_a_hand_built_trace():
    tr = _hand_built()
    assert decode_gaps(tr, *T.window(tr)) == [(100 * MS, 200 * MS),
                                               (300 * MS, 500 * MS)]
    assert _split(tr) == pytest.approx({
        "poll": (50 + 20) / 2, "publish": (20 + 30) / 2, "slot_wait": 30 / 2,
        "splice": 80 / 2, OUTSIDE: 20 / 2})


def test_split_adds_up_to_the_decode_gap():
    """Every idle instant of every gap is some state's, so the states add
    up to ``decode_gap_ms``, with or without the program's spans."""
    tr = _hand_built()
    gap = _read("decode_gap_ms", _ctx([tr]))
    assert gap == pytest.approx(125.0)
    assert sum(_split(tr).values()) == pytest.approx(gap)
    bare = dict(tr, spans=[s for s in tr["spans"]
                           if s[0][len(T.SPAN_PREFIX):] not in PROGRAM_SPANS])
    assert _split(bare) == pytest.approx({OUTSIDE: gap})


def test_split_reads_nothing_without_a_decode_gap():
    tr = _hand_built()
    tr["modules"] = [m for m in tr["modules"] if m[1] != 200 * MS]
    tr["spans"].insert(1, ["perfbench_idle", 150 * MS, 0, {}])
    assert decode_gaps(tr, *T.window(tr)) == []
    assert _split(tr) == {}


# -- the program's spans through the profiler, on the CPU ----------------------

def test_program_spans_reach_the_profiler_trace(tmp_path, monkeypatch):
    """A JAX replica at tiny widths, served and stalled under a profiler
    session at host tracer level 1 (as ``bench/replica.py`` traces it):
    every program span reaches the normalised trace, none under a name
    the benchmark's own wrapper writes."""
    import jax

    import repro.device
    import repro.serving.replica as rep
    from repro.core import Domain
    from repro.obs import spans as S
    from repro.obs.metrics import unlink_export
    from repro.serving import TINY_MODEL_KWARGS, ShardRouter
    from repro.serving.messages import SERVE_RES

    monkeypatch.setattr(repro.device, "enable_compile_cache", lambda: "")
    env = dict(os.environ)  # the replica pins its platform in the env
    dom = Domain.create(arena_capacity=16 << 20)
    router = ShardRouter(dom, [0], max_new=6)
    stalled = dom.create_subscription(SERVE_RES, "spans/res")
    held = []
    stop, ready = threading.Event(), threading.Event()
    th = threading.Thread(target=rep.replica_main, args=(
        dom.name, 0, router.topic(0), "spans/res"), kwargs=dict(
        model="jax", model_kwargs=TINY_MODEL_KWARGS, slots=2, max_seq=128,
        max_new=6, depth=2, arena_mb=8, lease_period_s=0.02,
        stop_event=stop, ready_event=ready))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        th.start()
        while not ready.wait(0.1):
            assert th.is_alive(), "the replica failed to start"
        jax.profiler.start_trace(str(tmp_path / "xplane"),
                                 profiler_options=opts)
        try:
            time.sleep(0.1)  # idle heartbeats
            for i in range(2):
                router.submit(np.arange(8) + i)
            router.flush()
            deadline = time.monotonic() + 60
            while len(held) < 2 and time.monotonic() < deadline:
                held.extend(stalled.take())  # taken, never released
                time.sleep(0.01)
            time.sleep(0.2)  # the next round's publish waits for a slot
            stop.set()
            th.join(60)
        finally:
            jax.profiler.stop_trace()
    finally:
        stop.set()
        th.join(60)
        for ptr in held:
            ptr.release()
        router.close()
        stalled.close()
        unlink_export(dom.name, os.getpid())  # the replica's metrics export
        dom.close()
        S.use_sink(None)
        os.environ.clear()
        os.environ.update(env)
    assert not th.is_alive()
    (path,) = glob.glob(str(tmp_path / "xplane" / "**" / "*.xplane.pb"),
                        recursive=True)
    tr = T.normalize(path)
    names = {s[0][len(T.SPAN_PREFIX):] for s in tr["spans"]}
    assert PROGRAM_SPANS <= names
    assert not names & BENCH_SPANS
    stats = {s[0][len(T.SPAN_PREFIX):]: s[3] for s in tr["spans"]}
    assert {"timeout_us"} <= set(stats["poll"])
    assert {"rows", "ok"} <= set(stats["publish"])
    assert {"len", "queued_us"} <= set(stats["splice"])
