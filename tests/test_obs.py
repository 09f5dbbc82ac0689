"""Observability plane (repro.obs): shm trace rings, cross-process flow
reconstruction, the unified metrics registry + exporter, the agno_top
snapshot CLI — and the churn contract: SIGKILL a replica mid-flow and the
superseded attempt's flow must read as *truncated* (no phantom terminal
record from its late chunks) while the replayed attempt, under a fresh
trace id, is the rid's exactly-one *complete* flow."""

import gc
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import POINT_CLOUD2, Domain, EventExecutor
from repro.obs import flows as F
from repro.obs import metrics as M
from repro.obs import spans as S
from repro.obs import trace as T
from repro.serving import (
    FleetController,
    ReplicaPool,
    ResultsCollector,
    ShardRouter,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def dom():
    d = Domain.create(arena_capacity=32 << 20)
    yield d
    d.close()


def _drop_tracer(name):
    """Detach + unlink everything a test's tracing left behind (the cached
    writer ring must close before purge unlinks the segment)."""
    tr = T._tracers.pop(name, None)
    if tr is not None:
        tr.close()
    T.purge(name)


# ---------------------------------------------------------------------------
# trace ring: roundtrip, wrap, gating
# ---------------------------------------------------------------------------


def test_ring_roundtrip_records():
    name = f"obs-ring-{os.getpid()}"
    ring = T.TraceRing(name, cap=64)
    try:
        for i in range(10):
            ring.emit(i + 1, i, T.Stage.PUBLISH, arg=i * 3, flags=i & 1)
        rd = T.TraceReader(ring.name)
        recs = rd.records()
        rd.close()
        assert len(recs) == 10
        for i, (tid, t_ns, hop, stage, flags, arg, pid) in enumerate(recs):
            assert tid == i + 1 and hop == i
            assert stage == T.Stage.PUBLISH
            assert arg == i * 3 and flags == (i & 1)
            assert pid == os.getpid()
        ts = [r[1] for r in recs]
        assert ts == sorted(ts)
    finally:
        ring.close(unlink=True)


def test_ring_wrap_keeps_newest():
    name = f"obs-wrap-{os.getpid()}"
    ring = T.TraceRing(name, cap=64)
    try:
        for i in range(1, 201):
            ring.emit(i, 0, T.Stage.TAKE, arg=i)
        rd = T.TraceReader(ring.name)
        recs = rd.records()
        rd.close()
        # overwritten history is gone; the newest cap records survive, in
        # emit order
        assert [r[0] for r in recs] == list(range(137, 201))
    finally:
        ring.close(unlink=True)


def test_tracing_disabled_by_default(monkeypatch):
    monkeypatch.delenv("AGNOCAST_TRACE", raising=False)
    name = f"obs-off-{os.getpid()}"
    assert not T.enabled()
    assert T.tracer_for(name) is None       # no ring segment is created
    assert T.ring_names(name) == []


def test_tracer_for_is_per_process_singleton(monkeypatch):
    monkeypatch.setenv("AGNOCAST_TRACE", "1")
    name = f"obs-single-{os.getpid()}"
    try:
        tr = T.tracer_for(name)
        assert tr is not None and T.tracer_for(name) is tr
        tr.emit(T.next_trace_id(), 0, T.Stage.PUBLISH)
        assert len(T.ring_names(name)) == 1  # one writer ring per process
    finally:
        _drop_tracer(name)


def test_trace_ids_unique_nonzero_pid_salted():
    ids = {T.next_trace_id() for _ in range(1000)}
    assert len(ids) == 1000 and 0 not in ids
    assert all(i >> 40 == (os.getpid() & 0x3F_FFFF) for i in ids)


# ---------------------------------------------------------------------------
# unified metrics: registry, weakref lifetime, export, shims
# ---------------------------------------------------------------------------


def test_metrics_counter_gauge_snapshot():
    reg = M.MetricsRegistry()
    c = reg.counter("bus.dropped", topic="cam")
    assert c.name == "bus.dropped{topic=cam}"
    c.inc()
    c.inc(2)
    assert c.value == 3 and int(c) == 3
    g = reg.gauge("bus.depth")
    g.set(5)
    gf = reg.gauge("bus.load", fn=lambda: 7)
    snap = reg.snapshot()
    assert snap["bus.dropped{topic=cam}"] == 3
    assert snap["bus.depth"] == 5 and snap["bus.load"] == 7
    # same-named sibling (two bridges on one topic) dedups, not clobbers
    c2 = reg.counter("bus.dropped", topic="cam")
    c2.inc(9)
    snap = reg.snapshot()
    assert snap["bus.dropped{topic=cam}"] == 3
    assert snap["bus.dropped{topic=cam}#2"] == 9
    assert gf.value == 7


def test_metrics_weakref_dies_with_owner():
    reg = M.MetricsRegistry()
    c = reg.counter("tmp.leaky")
    c.inc()
    assert "tmp.leaky" in reg.snapshot()
    del c
    gc.collect()
    # a dead bridge's counts must not haunt later snapshots
    assert "tmp.leaky" not in reg.snapshot()


def test_metrics_export_roundtrip():
    reg = M.MetricsRegistry()
    c = reg.counter("x.drops")
    c.inc(5)
    domain = f"obs-mx-{os.getpid()}"
    exp = M.MetricsExporter(domain, reg=reg)
    try:
        exp.publish()
        snaps = M.read_exports(domain)
        assert snaps[os.getpid()]["x.drops"] == 5
    finally:
        exp.close(unlink=True)


def test_migrated_counter_shims_still_read(dom):
    """The scattered per-object counters moved into repro.obs.metrics;
    the old attribute names stay readable (back-compat shims)."""
    router = ShardRouter(dom, range(2))
    assert router.shed == 0 and router.shed_bytes == 0
    router._shed.inc(2)
    router._shed_bytes.inc(100)
    assert (router.shed, router.shed_bytes) == (2, 100)
    coll = ResultsCollector(dom, shards=range(1))
    assert coll.superseded == 0 and coll.dropped_window == 0
    coll._superseded.inc()
    assert coll.superseded == 1 and coll.stats()["superseded"] == 1
    router.close()
    coll.close()


# ---------------------------------------------------------------------------
# flow reconstruction: synthetic rings, then a live traced domain
# ---------------------------------------------------------------------------


def test_synthetic_flow_reconstruction():
    name = f"obs-synth-{os.getpid()}"
    ring = T.TraceRing(name, cap=256)
    try:
        msg, cut, srv = 1001, 1002, 1003
        for st in (T.Stage.PUBLISH, T.Stage.NOTIFY, T.Stage.TAKE,
                   T.Stage.CB_START, T.Stage.CB_END, T.Stage.RELEASE):
            ring.emit(msg, 0, st)
        ring.emit(cut, 0, T.Stage.PUBLISH)       # truncated: no release
        ring.emit(cut, 0, T.Stage.NOTIFY)
        ring.emit(srv, 0, T.Stage.SERVE_ENQ, arg=7)
        ring.emit(srv, 0, T.Stage.SERVE_FLUSH, arg=7)
        ring.emit(srv, 1, T.Stage.SERVE_ENQ, arg=7)
        ring.emit(srv, 2, T.Stage.SERVE_REASM, arg=0)
        ring.emit(srv, 2, T.Stage.SERVE_REASM, arg=1, flags=T.FLAG_EOS)

        agg = F.FlowAggregator(name)
        by_tid = {f.trace_id: f for f in agg.collect()}
        agg.close()
        assert set(by_tid) == {msg, cut, srv}

        f = by_tid[msg]
        assert f.complete and not f.serving and f.monotonic()
        bd = f.breakdown()
        stages = [v for k, v in bd.items() if k != "e2e"]
        assert all(v >= 0 for v in stages)
        # the per-stage deltas telescope exactly to the e2e delta
        assert abs(sum(stages) - bd["e2e"]) < 1e-12

        assert by_tid[cut].truncated
        f = by_tid[srv]
        assert f.serving and f.complete
        bd = f.breakdown()
        for k in ("enqueue_to_flush", "flush_to_replica",
                  "replica_to_first_chunk", "stream", "e2e"):
            assert bd[k] >= 0, (k, bd)
    finally:
        ring.close(unlink=True)


def test_traced_pubsub_message_flows(monkeypatch):
    """Live single-domain loop with tracing on: every published message's
    flow is recovered complete, with non-negative stage deltas."""
    monkeypatch.setenv("AGNOCAST_TRACE", "1")
    dom = Domain.create(arena_capacity=4 << 20)
    N = 6
    try:
        pub = dom.create_publisher(POINT_CLOUD2, "obs/t", depth=8)
        sub = dom.create_subscription(POINT_CLOUD2, "obs/t")
        for i in range(N):
            m = pub.borrow_loaded_message()
            m.data.extend(np.full(64, i, np.uint8))
            pub.publish(m)
            for ptr in sub.take():
                ptr.release()
        agg = F.FlowAggregator(dom.name)
        done = [f for f in agg.message_flows() if f.complete]
        stats = agg.breakdown_stats(done)
        agg.close()
        assert len(done) == N
        for f in done:
            assert f.monotonic()
            bd = f.breakdown()
            assert bd["e2e"] >= 0
            assert all(v >= 0 for k, v in bd.items())
        assert stats["publish_to_wakeup"]["n"] == N
        assert stats["e2e"]["p50"] >= 0
    finally:
        name = dom.name
        dom.close()
        _drop_tracer(name)


# ---------------------------------------------------------------------------
# the churn contract: SIGKILL mid-flow -> truncated old attempt, fresh
# complete flow via replay; respawn -> new incarnation's records show up
# ---------------------------------------------------------------------------


def test_flow_reconstruction_under_churn(monkeypatch):
    monkeypatch.setenv("AGNOCAST_TRACE", "1")  # spawned replicas inherit it
    dom = Domain.create(arena_capacity=32 << 20)
    K, N, POST, MAX_NEW = 2, 16, 8, 4
    pool = ReplicaPool(dom, range(K), model="echo", slots=2,
                       round_period_s=0.005)
    try:
        pool.wait_ready(60)
        router = ShardRouter(dom, range(K), max_new=MAX_NEW)
        completions: dict[int, int] = {}

        def on_complete(rid, toks):
            completions[rid] = completions.get(rid, 0) + 1
            router.complete(rid)

        collector = ResultsCollector(dom, shards=range(K),
                                     on_complete=on_complete,
                                     on_progress=router.touch)
        controller = FleetController(pool, router, collector,
                                     autoscale=False, respawn=True,
                                     respawn_backoff_s=0.0,
                                     stall_replay_s=5.0, flush_timeout_s=5.0)
        ex = EventExecutor(name="obs-churn-head")
        collector.attach_executor(ex)
        controller.attach_executor(ex, period_s=0.05)
        rng = np.random.default_rng(42)
        rids = [router.submit(rng.integers(0, 999, 8)) for _ in range(N)]
        router.flush()
        ex.spin(until=lambda: collector.n_completed >= N // 4, timeout=30)

        # kill the busiest shard mid-flow: its trace ring survives in shm
        # (writers never unlink) as the truncated-flow evidence
        per_shard: dict[int, int] = {}
        for rec in router.inflight.values():
            per_shard[rec.shard] = per_shard.get(rec.shard, 0) + 1
        victim = max(per_shard, key=per_shard.get)
        dead_pid = pool._procs[victim].pid
        pool.kill(victim)
        ex.spin(until=lambda: collector.n_completed >= N, timeout=120)
        ex.spin(until=lambda: (controller.respawns >= 1
                               and victim in router.ring), timeout=60)

        # post-respawn traffic: the fresh incarnation serves new flows
        post = [router.submit(rng.integers(0, 999, 8)) for _ in range(POST)]
        shards_post = {rid: router.inflight[rid].shard for rid in post}
        router.flush()
        ex.spin(until=lambda: collector.n_completed >= N + POST, timeout=60)
        ex.shutdown()

        assert completions == {r: 1 for r in rids + post}
        assert router.replays >= 1
        assert any(s == victim for s in shards_post.values())

        # reconstruction off the rings — including the dead incarnation's
        # ring — must return promptly (readers never block on a writer)
        agg = F.FlowAggregator(dom.name)
        sflows = agg.serving_flows()
        agg.close()
        by_rid: dict[int, list] = {}
        for f in sflows:
            enq = f.first(T.Stage.SERVE_ENQ, 0)
            if enq is not None:
                by_rid.setdefault(enq[5], []).append(f)

        for rid in rids + post:
            fs = by_rid.get(rid & 0xFFFF_FFFF)
            assert fs, f"rid {rid}: no flow recovered"
            comp = [f for f in fs if f.complete]
            # exactly ONE complete flow per rid: replay mints a fresh
            # trace id, and the dead generation's late chunks must not
            # stamp a phantom terminal record on the superseded attempt
            assert len(comp) == 1, rid
            assert comp[0].monotonic()
            assert all(v >= 0 for v in comp[0].breakdown().values())
        truncated = [f for fs in by_rid.values() for f in fs if f.truncated]
        assert len(truncated) >= 1          # the kill bit someone mid-flow

        # the respawned incarnation (a NEW pid) carried the post-kill
        # victim-shard flows end to end
        for rid in post:
            if shards_post[rid] != victim:
                continue
            (f,) = [f for f in by_rid[rid & 0xFFFF_FFFF] if f.complete]
            renq = f.first(T.Stage.SERVE_ENQ, 1)
            assert renq is not None and renq[6] != dead_pid
        router.close()
        collector.close()
    finally:
        pool.stop()
        name = dom.name
        dom.close()
        _drop_tracer(name)


# ---------------------------------------------------------------------------
# agno_top: one-shot snapshot CLI over a live domain
# ---------------------------------------------------------------------------


def test_agno_top_once_snapshot(dom):
    pub = dom.create_publisher(POINT_CLOUD2, "obs/topic", depth=4)
    m = pub.borrow_loaded_message()
    m.data.extend(np.ones(10, np.uint8))
    pub.publish(m)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scripts", "agno_top.py"),
         dom.name, "--once"],
        capture_output=True, text=True, timeout=60, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stderr
    assert "obs/topic" in out.stdout


# ---------------------------------------------------------------------------
# profiler spans: the no-op default, the executor's poll/take, the replica's
# publish path
# ---------------------------------------------------------------------------


class _RecordingSink:
    """A span sink that keeps ``{"name": ..., **stats}`` per span, from
    every thread."""

    def __init__(self):
        self.spans: list[dict] = []
        self._lock = threading.Lock()

    def __call__(self, name, **stats):
        rec = {"name": name, **stats}
        with self._lock:
            self.spans.append(rec)
        return _RecordedSpan(rec)

    def named(self, name):
        with self._lock:
            return [s for s in self.spans if s["name"] == S.PREFIX + name]


class _RecordedSpan:
    def __init__(self, rec):
        self.rec = rec

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.rec.update(stats)


@pytest.fixture()
def recorded_spans():
    sink = _RecordingSink()
    S.use_sink(sink)
    try:
        yield sink
    finally:
        S.use_sink(None)


def test_span_without_a_sink_is_the_shared_null():
    S.use_sink(None)
    sp = S.span("poll", timeout_us=5)
    assert sp is S.span("take") is S._NULL
    with sp as inner:
        inner.set_metadata(n=3)  # a no-op, like the span itself
    sink = _RecordingSink()
    S.use_sink(sink)
    S.use_sink(None)
    with S.span("take"):
        pass
    assert sink.spans == []  # unbound: nothing is built


def test_executor_emits_poll_and_take(dom, recorded_spans):
    pub = dom.create_publisher(POINT_CLOUD2, "obs/spans", depth=4)
    sub = dom.create_subscription(POINT_CLOUD2, "obs/spans")
    got = []
    with EventExecutor(name="spans") as ex:
        ex.add_subscription(sub, lambda ptr: got.append(len(ptr.data)))
        for _ in range(2):
            m = pub.borrow_loaded_message()
            m.data.extend(np.ones(16, np.uint8))
            pub.publish(m)
        deadline = time.monotonic() + 5
        while not got and time.monotonic() < deadline:
            ex.spin_once(0.25)
    assert got
    polls = recorded_spans.named("poll")
    assert polls and all(isinstance(p["timeout_us"], int) for p in polls)
    assert polls[0]["timeout_us"] == 250_000
    takes = recorded_spans.named("take")
    assert takes and sum(t["n"] for t in takes) == 2


def _wait(cond, timeout=20.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.01)


def test_echo_replica_publish_path_spans_and_timeouts(monkeypatch,
                                                      recorded_spans):
    """An echo replica whose collector takes its results but never
    releases them: the round publishes fill the results ring (an occupant
    that was never taken would be dropped, keep-last) and wait for a slot;
    stopping the replica returns the blocked publish unpublished, which
    ``replica.publish_timeouts`` counts."""
    import repro.serving.replica as rep
    from repro.serving.messages import SERVE_RES

    made = []

    def counter(name, **labels):
        c = M.registry.counter(name, **labels)
        made.append(c)
        return c

    monkeypatch.setattr(rep._metrics, "counter", counter)
    dom = Domain.create(arena_capacity=8 << 20)
    router = ShardRouter(dom, [0], max_new=8)
    stalled = dom.create_subscription(SERVE_RES, "obs/res")
    held = []  # taken and never released

    def stalled_slot_wait():
        held.extend(stalled.take())
        return recorded_spans.named("slot_wait")

    stop, ready = threading.Event(), threading.Event()
    th = threading.Thread(target=rep.replica_main, args=(
        dom.name, 0, router.topic(0), "obs/res"), kwargs=dict(
        model="echo", slots=2, max_new=8, depth=2, arena_mb=8,
        lease_period_s=0.02, stop_event=stop, ready_event=ready))
    th.start()
    try:
        assert ready.wait(20)
        _wait(lambda: recorded_spans.named("heartbeat"))
        for i in range(2):
            router.submit(np.arange(4) + i)
        router.flush()
        _wait(stalled_slot_wait)
    finally:
        stop.set()
        th.join(30)
        for ptr in held:
            ptr.release()
        router.close()
        stalled.close()
        dom.close()
    assert not th.is_alive()
    waits = recorded_spans.named("slot_wait")
    assert all(0 < w["step_us"] <= 50_000 for w in waits)
    pubs = recorded_spans.named("publish")
    # the idle heartbeat's empty beacon holds one of the ring's two slots
    assert pubs[0]["ok"] == 1 and {p["ok"] for p in pubs} == {0, 1}
    assert all(p["rows"] > 0 for p in pubs)
    kept = [p for p in pubs if p["ok"] == 0]
    (timeouts,) = [c for c in made if c.name == "replica.publish_timeouts"]
    assert kept and timeouts.value == len(kept)


def test_obs_and_core_import_without_jax():
    code = ("import sys\n"
            "import repro.obs, repro.obs.spans, repro.core\n"
            "from repro.obs import span\n"
            "with span('poll'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(_REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, env=env, cwd=_REPO)
    assert out.returncode == 0, out.stderr
