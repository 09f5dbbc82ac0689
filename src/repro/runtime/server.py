"""Serving runtime: continuous batching with the device-arena KV hand-off.

The serving loop is the paper's pub/sub discipline applied twice:

* **host plane** — requests/results are agnocast messages (unsized: prompt
  lengths vary) when wired to topics; in-process queues otherwise;
* **device plane** — prefill "publishes" the KV pages it wrote for a
  request and the decode loop "subscribes"; pages return to the free list
  only when refcount == 0 AND unreceived == 0 (``DevicePagePool``), so
  cancelled requests, fan-out beams and prefix-shared prompts can all hold
  references without copies, and a vanished consumer is reclaimed by the
  janitor (``expire_consumer``) exactly like the registry sweep.

The decode cache is slot-contiguous ``(L, B_slots, S_max, KV, hd)``; pool
pages map 1:1 onto fixed-size token ranges of a slot. On TPU the same
metadata drives a paged Pallas decode kernel (the gather never
materializes); on CPU the contiguous layout is the fast path.

Request ingest is event-driven when wired to a topic: ``attach_executor``
registers a ``TOKEN_BATCH`` subscription's wakeup FIFO plus a decode-round
timer on an :class:`repro.core.executor.EventExecutor` (one mutually-
exclusive group, so ingest callbacks and decode rounds never interleave on
the server's mutable state), replacing any need to busy-poll the queue.

The sharded serving plane (:mod:`repro.serving`) runs this server as ONE
of K replicas: ``ingest_serve_message`` consumes rows that carry explicit
router-assigned ``(rid, generation)`` pairs — a higher generation
supersedes any queued/active copy (replay after replica loss), stale ones
are ignored, so a replayed rid decodes exactly once per generation — and
``stream_sink`` emits per-rid token chunks ``(rid, gen, seq, tokens,
eos)`` that the replica republished on the results topic for the
collector's windowed reassembly.  ``attach_serving_executor`` is the
shard-aware attach: the same arm-only-while-busy round timer, with a
pluggable ingest and an end-of-round flush hook, shared by the real
server and by jax-free test doubles (duck-typed on ``queue`` /
``_active`` / ``step_rounds``).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.device_arena import DevicePagePool
from repro.models import Model
from repro.obs.spans import span

__all__ = ["Request", "Result", "InferenceServer", "attach_serving_executor",
           "make_steps"]


def make_steps(model: Model, max_seq: int):
    """The server's two jitted programs: ``prefill(params, tokens)`` of one
    prompt into a ``max_seq`` cache, and ``decode(params, cache, tokens)``,
    one greedy step over every slot with the cache donated."""

    def prefill(params, tokens):
        return model.prefill(params, {"tokens": tokens}, max_seq=max_seq)

    def decode(params, cache, tokens):
        logits, new_cache = model.decode_step(params, cache, tokens)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return nxt, new_cache

    return jax.jit(prefill), jax.jit(decode, donate_argnums=(1,))


@dataclass
class Request:
    rid: str
    tokens: np.ndarray                  # prompt (unsized)
    max_new: int = 16
    stamp: float = field(default_factory=time.monotonic)
    submitted: float = 0.0              # set by InferenceServer.submit


@dataclass
class Result:
    rid: str
    tokens: list[int]
    prompt_len: int
    ttft: float                          # submission to first token
    latency: float


class InferenceServer:
    def __init__(self, model: Model, *, slots: int = 4, max_seq: int = 512,
                 page_tokens: int = 64, greedy: bool = True):
        self.model = model
        self.slots = slots
        self.max_seq = max_seq
        self.pool = DevicePagePool(
            num_pages=slots * (max_seq // page_tokens), page_tokens=page_tokens)
        self.queue: deque[Request] = deque()
        self.results: dict[str, Result] = {}
        self._active: dict[int, dict] = {}  # slot -> request state
        self._free_slots = list(range(slots - 1, -1, -1))
        self._cache = None
        self._params = None
        self._prefill = None
        self._decode = None
        self.steps = 0
        self._ingest_seq = 0  # server-wide: message seqs are per-publisher
        # -- sharded-serving surface (repro.serving) --------------------------
        from repro.serving.messages import GenerationGate

        self.stream_sink = None       # callable(rid, gen, seq, tokens, eos)
        self.keep_results = True      # replicas stream instead of accumulating
        self._gate = GenerationGate()  # the shared SERVE_REQ replay rule

    # -- setup ---------------------------------------------------------------

    def load(self, params) -> None:
        self._params = params
        self._prefill, self._decode = make_steps(self.model, self.max_seq)
        self._cache = self.model.init_cache(self.slots, self.max_seq)

    # -- request surface --------------------------------------------------------

    def submit(self, req: Request) -> None:
        req.submitted = time.monotonic()
        self.queue.append(req)

    def cancel(self, rid: str) -> bool:
        """Consumer vanishes mid-decode: the janitor path frees its pages."""
        self._gate.drop(rid)
        for slot, st in list(self._active.items()):
            if st["req"].rid == rid:
                self.pool.expire_consumer(f"decode/{rid}")
                self._retire(slot, finished=False)
                return True
        return False

    # -- the loop ---------------------------------------------------------------

    def _admit(self) -> None:
        while self.queue and self._free_slots:
            req = self.queue.popleft()
            queued_us = int((time.monotonic() - req.submitted) * 1e6)
            slot = self._free_slots.pop()
            prompt = jnp.asarray(req.tokens, jnp.int32)[None, :]
            logits, cache1 = self._prefill(self._params, prompt)
            first = int(jnp.argmax(logits[0, -1]))
            ttft = time.monotonic() - req.submitted
            with span("splice", len=len(req.tokens), queued_us=queued_us):
                # prefill publishes this request's pages; decode subscribes
                npages = self.pool.pages_for_tokens(len(req.tokens)
                                                    + req.max_new)
                pages = self.pool.alloc(npages)
                key = f"kv/{req.rid}"
                self.pool.publish(key, pages, consumers=[f"decode/{req.rid}"])
                self.pool.take(key, f"decode/{req.rid}")  # zero-copy receive
                # splice the request's KV into its slot of the batched cache
                self._cache = _splice_cache(self._cache, cache1, slot,
                                            len(req.tokens))
            st = {
                "req": req, "key": key, "generated": [first], "ttft": ttft,
                "gen": self._gate.current(req.rid), "chunk_seq": 0,
            }
            self._active[slot] = st
            self._emit(st, [first], False)

    def _emit(self, st: dict, tokens: list[int], eos: bool) -> None:
        """Stream one per-rid chunk to the sink (the replica's results
        publisher): monotone chunk seq per (rid, generation)."""
        if self.stream_sink is None:
            return
        self.stream_sink(st["req"].rid, st["gen"], st["chunk_seq"],
                         tokens, eos)
        st["chunk_seq"] += 1

    def _retire(self, slot: int, *, finished: bool = True) -> None:
        st = self._active.pop(slot)
        rid = st["req"].rid
        if finished:
            self.pool.release(st["key"], f"decode/{rid}")
            self._gate.finish(rid)  # late replays of <= gen ignored
            if self.keep_results:
                self.results[rid] = Result(
                    rid=rid, tokens=st["generated"],
                    prompt_len=len(st["req"].tokens), ttft=st["ttft"],
                    latency=time.monotonic() - st["req"].stamp)
        # zero the slot length so decode ignores it
        with span("retire"):
            self._cache["len"] = self._cache["len"].at[slot].set(0)
        self._free_slots.append(slot)

    def _decode_round(self) -> None:
        if not self._active:
            return
        toks = np.zeros((self.slots, 1), np.int32)
        for slot, st in self._active.items():
            toks[slot, 0] = st["generated"][-1]
        nxt, self._cache = self._decode(self._params, self._cache,
                                        jnp.asarray(toks))
        with span("sync"):  # the host waits here while the device decodes
            nxt = np.asarray(nxt)
        self.steps += 1
        for slot in list(self._active):
            st = self._active[slot]
            tok = int(nxt[slot])
            st["generated"].append(tok)
            done = (len(st["generated"]) >= st["req"].max_new
                    or len(st["req"].tokens) + len(st["generated"])
                    >= self.max_seq - 1)
            self._emit(st, [tok], done)
            if done:
                self._retire(slot)

    def serve(self, *, max_rounds: int = 10_000) -> dict[str, Result]:
        """Run until queue and slots drain; returns results by request id."""
        rounds = 0
        while (self.queue or self._active) and rounds < max_rounds:
            self._admit()
            self._decode_round()
            rounds += 1
        return self.results

    # -- event-driven ingest (the executor-layer wiring) -------------------------

    def ingest_message(self, ptr, *, max_new: int = 16) -> int:
        """Decode-side ingest of one ``TOKEN_BATCH`` message: each ragged row
        becomes one :class:`Request`.  The flat token field is read zero-copy
        out of the publisher's arena; only the per-request prompt slice is
        copied (it must outlive the released ``MessagePtr``)."""
        lens = np.asarray(ptr.row_lengths, np.int64)
        flat = np.asarray(ptr.tokens, np.int32)
        stamp = float(ptr.get("stamp"))
        off = 0
        for n in lens:
            n = int(n)
            # rid from a server-wide counter: registry seqs restart at 1 for
            # every publisher, so seq-derived rids collide across clients
            self._ingest_seq += 1
            req = Request(rid=f"ingest-{self._ingest_seq}",
                          tokens=flat[off:off + n].copy(), max_new=max_new)
            if stamp > 0:
                req.stamp = stamp
            self.submit(req)
            off += n
        return len(lens)

    def ingest_serve_message(self, ptr, *, max_new: int = 16) -> int:
        """Shard-plane ingest (:mod:`repro.serving`): each ragged row carries
        an explicit router-assigned ``(rid, generation)``.  A row whose
        generation supersedes a queued/active copy of the same rid replaces
        it (replay after replica loss or a lost result); a stale or
        duplicate generation — including one already *completed* — is
        dropped, so each rid decodes exactly once per generation."""
        from repro.serving.messages import iter_requests

        stamp = float(ptr.get("stamp"))
        mnew = int(ptr.get("max_new")) or max_new
        admitted = 0
        for row in iter_requests(ptr):  # copies each row's tokens out
            rid = str(row.rid)
            if not self._admit_generation(rid, row.gen):
                continue
            req = Request(rid=rid, tokens=row.tokens, max_new=mnew)
            if stamp > 0:
                req.stamp = stamp
            self.submit(req)
            admitted += 1
        return admitted

    def _admit_generation(self, rid: str, gen: int) -> bool:
        """The shared replay rule (:class:`repro.serving.messages.
        GenerationGate`): True iff this (rid, gen) should be admitted,
        superseding (cancelling) any older live copy."""

        def supersede(r):
            self.cancel(r)  # an active copy: the janitor frees its pages
            self.queue = deque(q for q in self.queue if q.rid != r)

        return self._gate.admit(rid, gen, supersede=supersede)

    def step_rounds(self) -> None:
        """One admission + decode round (the executor timer's callback)."""
        self._admit()
        self._decode_round()

    def attach_executor(self, executor, sub, *, group=None, max_new: int = 16,
                        round_period_s: float = 0.0005, ingest=None,
                        on_round_end=None):
        """Run this server on an :class:`~repro.core.executor.EventExecutor`
        (see :func:`attach_serving_executor` for the semantics)."""
        return attach_serving_executor(
            self, executor, sub, group=group, max_new=max_new,
            round_period_s=round_period_s, ingest=ingest,
            on_round_end=on_round_end)

    @property
    def idle(self) -> bool:
        """True when no request is queued or mid-decode."""
        return not self.queue and not self._active

    # -- introspection ------------------------------------------------------------

    def stats(self) -> dict:
        return {
            "free_pages": self.pool.free_pages,
            "live_publications": self.pool.live_publications,
            "active": len(self._active),
            "queued": len(self.queue),
            "decode_steps": self.steps,
        }


def attach_serving_executor(server, executor, sub, *, group=None,
                            max_new: int = 16, round_period_s: float = 0.0005,
                            ingest=None, on_round_end=None):
    """Wire a continuous-batching server onto an ``EventExecutor``.

    Request messages arriving on ``sub`` are admitted by the subscription
    callback; a oneshot round timer is armed only while work is pending (an
    idle server sleeps on epoll instead of ticking at 1/period).  Everything
    shares one mutually-exclusive callback group so server state is never
    mutated concurrently.

    The shard-aware knobs (used by :mod:`repro.serving` replicas):

    * ``ingest`` — alternative message decoder (e.g. the bound
      ``server.ingest_serve_message`` for rows with router-assigned rids);
      defaults to ``server.ingest_message``.
    * ``on_round_end`` — called after every decode round, in the same
      group: the replica's hook to flush its streamed token chunks as one
      results-topic publish per round.
    * ``round_period_s`` — the continuous-batching tick.  On an
      accelerator-bound replica the tick models the device's round latency
      (host sleeps while the device decodes), which is what lets K replicas
      on one box multiply slot-rounds per second.

    ``server`` is duck-typed (``queue`` / ``_active`` / ``step_rounds`` /
    ``ingest_message``) so jax-free doubles can ride the same wiring — the
    one implementation lives in :mod:`repro.serving.attach` (jax-free, so
    echo replicas share it).  Returns the subscription handle."""
    from repro.serving.attach import attach_server_executor

    return attach_server_executor(
        server, executor, sub, group=group, max_new=max_new,
        round_period_s=round_period_s, ingest=ingest,
        on_round_end=on_round_end)


def _splice_cache(batched, single, slot: int, length: int):
    """Write request ``single`` (batch=1) KV into slot ``slot``."""
    def leaf(b, s):
        if b.ndim >= 2 and s.shape[0] == b.shape[0] and s.shape[1] == 1:
            return b.at[:, slot].set(s[:, 0])
        return b
    out = jax.tree.map(leaf, batched, single)
    out["len"] = batched["len"].at[slot].set(length)
    return out
