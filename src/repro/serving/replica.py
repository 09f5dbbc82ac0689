"""Replica process: one shard's server behind one EventExecutor.

``replica_main`` is the spawn-safe entrypoint the pool launches: join the
domain, subscribe to this shard's request topic, publish token chunks on
the shared results topic, and run one continuous-batching server on one
event loop.  Two server flavours behind the same wiring:

* ``model="echo"`` — :class:`EchoServer`, a jax-free stand-in that emits
  one deterministic token per rid per round (tests, fast demos: spawn
  cost is numpy + repro.core only);
* anything else — the real :class:`repro.runtime.InferenceServer`,
  built inside the child from ``configs.get_config`` at published widths
  (``model_kwargs`` may scale it down) on the chip the pool assigned, so
  the spawn args stay primitives.

Both implement the replica discipline:

* requests enter through ``ingest_serve_message`` — the generation gate
  makes replayed rids decode exactly once per generation;
* every decode round's new tokens flush as ONE ``SERVE_RES`` publish
  (``on_round_end``), with event-driven backpressure toward the
  collector;
* a heartbeat timer refreshes the subscriber lease while idle (busy
  replicas are stamped by every take), so the pool can tell wedged from
  quiet;
* shutdown is drain-then-exit: pending callbacks finish, in-flight
  requests run to completion (bounded), buffered chunks flush.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np

from repro.core.executor import EventExecutor
from repro.core.registry import AgnocastQueueFull
from repro.core.topic import Domain
from repro.obs import metrics as _metrics
from repro.obs import spans as _spans
from repro.obs import trace as _trace
from repro.obs.metrics import MetricsExporter

from .messages import (
    SERVE_REQ,
    SERVE_RES,
    GenerationGate,
    ResRow,
    iter_requests,
    pack_results,
)

__all__ = ["EchoServer", "TINY_MODEL_KWARGS", "replica_main"]

# qwen2-1.5b cut to a CPU-sized f32 toy: the ``model_kwargs`` the host-only
# callers (fig13, fig17, the sharded-serve demo) run ``model="jax"`` with
TINY_MODEL_KWARGS = dict(arch="qwen2-1.5b", num_layers=2, d_model=64,
                         d_ff=128, vocab_size=512, num_heads=2,
                         num_kv_heads=1, head_dim=32, param_dtype="float32",
                         compute_dtype="float32")


class EchoServer:
    """jax-free continuous-batching stand-in.

    One token per active rid per ``step_rounds`` call, deterministic in
    (prompt, position) — a replayed rid reproduces the identical stream on
    any replica, which is what lets the exactly-once tests compare replayed
    output bit-for-bit.  Mirrors the ``InferenceServer`` serving surface
    (``queue``/``_active``/``step_rounds``/``ingest_serve_message``/
    ``stream_sink``/``idle``) so the same executor wiring drives both.
    """

    def __init__(self, *, slots: int = 4, vocab: int = 50021):
        self.slots = slots
        self.vocab = vocab
        self.queue: deque[dict] = deque()
        self._active: dict[int, dict] = {}
        self.stream_sink = None           # callable(rid, gen, seq, tokens, eos)
        self.steps = 0
        self._gate = GenerationGate()

    # -- deterministic "decode" ------------------------------------------------

    def _token(self, st: dict, i: int) -> int:
        return int((st["base"] + 131 * i + 7) % self.vocab)

    # -- ingest (the shared SERVE_REQ generation gate) -------------------------

    def ingest_serve_message(self, ptr, *, max_new: int = 16) -> int:
        mnew = int(ptr.get("max_new")) or max_new
        admitted = 0
        for row in iter_requests(ptr):
            if not self._gate.admit(row.rid, row.gen, supersede=self.cancel):
                continue
            self.queue.append({
                "rid": row.rid, "gen": row.gen, "max_new": mnew,
                "base": int(np.asarray(row.tokens, np.int64).sum()),
                "emitted": 0,
            })
            admitted += 1
        return admitted

    def cancel(self, rid: int) -> bool:
        self._gate.drop(rid)
        if rid in self._active:
            del self._active[rid]
            return True
        n = len(self.queue)
        self.queue = deque(st for st in self.queue if st["rid"] != rid)
        return len(self.queue) != n

    # -- rounds ----------------------------------------------------------------

    def _emit(self, st: dict, eos: bool) -> None:
        i = st["emitted"]
        if self.stream_sink is not None:
            self.stream_sink(st["rid"], st["gen"], i, [self._token(st, i)],
                             eos)
        st["emitted"] = i + 1

    def _finish(self, rid: int) -> None:
        self._active.pop(rid, None)
        self._gate.finish(rid)

    def step_rounds(self) -> None:
        while self.queue and len(self._active) < self.slots:
            st = self.queue.popleft()
            self._emit(st, st["max_new"] <= 1)  # "prefill": first token
            if st["max_new"] <= 1:
                self._finish(st["rid"])
            else:
                self._active[st["rid"]] = st
        for rid in list(self._active):
            st = self._active[rid]
            eos = st["emitted"] + 1 >= st["max_new"]
            self._emit(st, eos)
            if eos:
                self._finish(rid)
        self.steps += 1

    @property
    def idle(self) -> bool:
        return not self.queue and not self._active

    def attach_executor(self, executor, sub, *, group=None, max_new: int = 16,
                        round_period_s: float = 0.002, on_round_end=None):
        """The shared arm-only-while-busy wiring
        (:func:`repro.serving.attach.attach_server_executor`), with the
        serve-row ingest bound in."""
        from .attach import attach_server_executor

        return attach_server_executor(
            self, executor, sub, group=group, max_new=max_new,
            round_period_s=round_period_s,
            ingest=lambda ptr: self.ingest_serve_message(ptr,
                                                         max_new=max_new),
            on_round_end=on_round_end)


def build_model(model: str, model_kwargs: dict | None):
    """The replica's :class:`repro.models.Model`: ``configs.get_config``
    at the architecture's published widths and dtype, with any
    ``model_kwargs`` (besides ``arch``) applied as ``.scaled`` reductions."""
    from repro.configs import get_config
    from repro.models import Model

    kw = dict(model_kwargs or {})
    arch = kw.pop("arch", model if model != "jax" else "qwen2-1.5b")
    cfg = get_config(arch)
    return Model(cfg.scaled(**kw) if kw else cfg)


def init_weights(m):
    """Random weights from seed 0, initialised under ``jit`` so the f32
    intermediates of a multi-GB init are never all resident at once.
    Every replica (and any reference) gets the same weights."""
    import jax

    return jax.jit(m.init)(jax.random.PRNGKey(0))


def _build_jax_server(model: str, model_kwargs: dict | None, *, slots: int,
                      max_seq: int, chip: int | None):
    """Real replica: the existing InferenceServer, built inside the child
    process on ``chip`` (``None``: the CPU).  Returns ``(server, report)``
    where ``report`` names the device the model was built on."""
    import os

    from repro.device import chip_env, enable_compile_cache, \
        held_accelerators

    # before the child's first jax import (the spawn start method
    # guarantees this function runs pre-import): libtpu and XLA read these
    # once, at start-up
    os.environ.update(chip_env(chip))

    import jax

    from repro.runtime.server import InferenceServer

    enable_compile_cache()
    # the round loop's host spans become TraceMe events, recorded only
    # while a profiler session runs
    _spans.use_profiler()
    compiles = _metrics.counter("replica.compiles")
    cache_hits = _metrics.counter("replica.compile_cache_hits")

    def on_duration(event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.inc()

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits.inc()

    # the listeners (held by jax.monitoring) keep the weakly registered
    # counters alive for the life of the process
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    m = build_model(model, model_kwargs)
    server = InferenceServer(m, slots=slots, max_seq=max_seq)
    params = init_weights(m)
    server.load(params)
    # jit prewarm BEFORE ready: the decode-step compile (~seconds under a
    # contended fleet spin-up) must not happen inside the first request's
    # callback, where it would starve the lease heartbeat long enough for
    # the pool to declare a perfectly healthy replica wedged
    from repro.runtime.server import Request

    server.submit(Request(rid="__prewarm__",
                          tokens=np.arange(8, dtype=np.int32), max_new=2))
    while not server.idle:
        server.step_rounds()
    server.results.pop("__prewarm__", None)
    (dev,) = jax.tree.leaves(params)[0].devices()
    report = {
        "device.platform": dev.platform,
        "device.kind": dev.device_kind,
        "device.id": dev.id,
        "device.held": held_accelerators(),
        "model.arch": m.cfg.name,
        "model.layers": m.cfg.num_layers,
        "model.d_model": m.cfg.d_model,
        "model.vocab": m.cfg.vocab_size,
        "model.dtype": m.cfg.param_dtype,
    }
    return server, report


def replica_main(dom_name: str, shard: int, req_topic: str, res_topic: str, *,
                 model: str = "echo", model_kwargs: dict | None = None,
                 slots: int = 4, max_seq: int = 256, max_new: int = 16,
                 depth: int = 16, arena_mb: int = 32,
                 round_period_s: float = 0.002, lease_period_s: float = 0.25,
                 flush_every: int = 1, chip: int | None = None,
                 stop_event=None, ready_event=None) -> None:
    """Entry point for one replica process (spawn-safe).

    A JAX replica runs on TPU chip ``chip`` (``None``: the CPU) and
    exports a device report (platform, kind, device id, held accelerator
    files, compile counts) through :class:`repro.obs.metrics.
    MetricsExporter`, which the pool reads back by pid.

    ``flush_every`` optionally batches result publishes across decode
    rounds.  It defaults to 1 (publish every round): the metadata plane is
    sharded per topic, so a replica's request takes contend on nobody and
    its result publishes bid only on the results topic's own lock — the
    domain-wide-flock era, when chunk batching was *required* for
    aggregate throughput to scale with K at all, is over.  Values > 1
    still trade completion latency for fewer metadata ops under extreme
    fan-in.  A round that produced an ``eos`` chunk flushes immediately
    (completion latency is never deferred)."""
    dom = Domain.join(dom_name, arena_capacity=arena_mb << 20)
    exporter = None
    if model == "echo":
        server = EchoServer(slots=slots)
    else:
        server, report = _build_jax_server(model, model_kwargs, slots=slots,
                                           max_seq=max_seq, chip=chip)
        server.keep_results = False  # we stream; never accumulate
        exporter = MetricsExporter(dom_name, extra=lambda: report)
        exporter.publish()
    # subscribe only once the server can actually consume: the subscriber
    # lease doubles as the liveness signal, and it must not start ticking
    # while a slow (fleet-contended) model build is still in progress
    sub = dom.create_subscription(SERVE_REQ, req_topic)
    res_pub = dom.create_publisher(SERVE_RES, res_topic, depth=depth)

    should_stop = stop_event.is_set if stop_event is not None else None
    rows: list[ResRow] = []
    eos_pending = [False]
    rounds_unflushed = [0]
    # publish_rows calls that returned with the rows kept (stopping, or the
    # collector stalled past the timeout)
    publish_timeouts = _metrics.counter("replica.publish_timeouts")

    # tracing (repro.obs): each SERVE_REQ row carries the head's trace id;
    # record rid -> tid at ingest (hop 1 = this replica) so every chunk the
    # sink emits travels back to the collector tagged with its flow.  The
    # map is bounded: an entry retires with its rid's eos chunk.
    tr = _trace.tracer_for(dom_name)
    rid_tid: dict[int, int] = {}

    def traced_ingest(ptr):
        if tr is not None:
            for row in iter_requests(ptr):
                if row.tid:
                    rid_tid[row.rid] = row.tid
                    tr.emit(row.tid, 1, _trace.Stage.SERVE_ENQ,
                            arg=row.rid & 0xFFFF_FFFF)
        return server.ingest_serve_message(ptr, max_new=max_new)

    def sink(rid, gen, seq, tokens, eos):
        rid = int(rid)
        tid = rid_tid.get(rid, 0)
        if eos:
            rid_tid.pop(rid, None)
        rows.append(ResRow(rid, gen, seq,
                           np.asarray(tokens, np.int32), eos, tid))
        eos_pending[0] |= eos

    server.stream_sink = sink

    def publish_rows():
        with _spans.span("publish", rows=len(rows)) as sp:
            loan = res_pub.borrow_loaded_message()
            pack_results(loan, rows, shard=shard,
                         depth=len(server.queue) + len(server._active),
                         stamp=time.monotonic())
            try:
                got = res_pub.publish_blocking(loan, timeout=30.0,
                                               should_stop=should_stop)
            except AgnocastQueueFull:
                got = None  # collector stalled past the timeout
            sp.set_metadata(ok=int(got is not None))
        if got is None:
            # stopping or saturated: return the loan, KEEP the rows — the
            # next round's flush retries, and backpressure toward a wedged
            # collector must never crash the replica (mirrors
            # ShardRouter.flush on the request side)
            loan.dealloc()
            publish_timeouts.inc()
            return
        rows.clear()
        eos_pending[0] = False
        rounds_unflushed[0] = 0

    def flush(force: bool = True):
        """Publish accumulated chunk rows as one unsized message (event-
        driven backpressure).  The per-round path (``force=False``) defers
        until ``flush_every`` rounds accumulated or a stream completed."""
        if not rows:
            rounds_unflushed[0] = 0
            return
        rounds_unflushed[0] += 1
        if force or eos_pending[0] or rounds_unflushed[0] >= flush_every:
            publish_rows()

    def round_flush():
        flush(force=False)

    ex = EventExecutor(name=f"replica-{shard}")
    if model == "echo":
        from .attach import attach_server_executor

        attach_server_executor(server, ex, sub, max_new=max_new,
                               round_period_s=round_period_s,
                               ingest=traced_ingest,
                               on_round_end=round_flush)
    else:
        from repro.runtime.server import attach_serving_executor

        attach_serving_executor(
            server, ex, sub, max_new=max_new, round_period_s=round_period_s,
            ingest=traced_ingest,
            on_round_end=round_flush)
    # idle heartbeat: take() stamps the lease while busy; this covers quiet.
    # It also beacons an empty SERVE_RES once per drain transition — the
    # collector's per-shard depth snapshot otherwise only updates on result
    # publishes, so a drained replica would look as deep as its last busy
    # round forever, and the controller's steal / scale-down decisions key
    # off depth reaching zero.
    last_depth = [-1]

    def heartbeat():
        with _spans.span("heartbeat"):
            dom.registry.refresh_lease(sub.tidx, sub.sidx)
            if exporter is not None:
                exporter.publish()  # live compile counts
            depth = len(server.queue) + len(server._active)
            if depth == 0 and not rows and last_depth[0] != 0:
                loan = res_pub.borrow_loaded_message()
                pack_results(loan, [], shard=shard, depth=0,
                             stamp=time.monotonic())
                try:
                    res_pub.publish(loan)
                except AgnocastQueueFull:
                    loan.dealloc()  # collector lagging: fresher problems
                    return
            last_depth[0] = depth

    ex.add_timer(lease_period_s, heartbeat)
    if ready_event is not None:
        ready_event.set()
    try:
        ex.spin(until=should_stop)
        # clean shutdown: finish queued callbacks, run in-flight requests to
        # completion (bounded), flush the last chunks
        ex.drain(2.0)
        deadline = time.monotonic() + 10.0
        while not server.idle and time.monotonic() < deadline:
            server.step_rounds()
            flush()
        flush()
    finally:
        ex.shutdown()
        res_pub.reclaim()
        if exporter is not None:
            exporter.publish()
            exporter.close()  # the segment outlives us: the pool unlinks it
        dom.close()
