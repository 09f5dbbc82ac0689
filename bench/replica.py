"""The benchmark's replica entry point: ``repro.serving.replica.replica_main``
with the benchmark's weights, tracing and read-outs around it.

The head points ``ReplicaPool``'s spawn target here.  The run's control
block (JSON in ``$PERFBENCH_CTL``) gives:

* ``weight_seed`` — the replica initialises its weights from this key
  (the program's own initialiser, on the device, in one jitted call);
* ``trace`` — when 1, the replica writes ``perfbench_*`` host spans
  around the server's calls (ingest, round, admission, prefill, decode
  round, decode step) and traces ``trace_s`` seconds of the measured
  window, from ``trace_at`` seconds after its opening, with the profiler,
  which only the process that holds the chip can do.  The head marks the
  window's opening and close with files in ``run_dir``;
* ``fault`` — a planted fault for the harness's own tests (``None`` in
  every benchmark run).

On exit the replica writes ``replica-<shard>.json`` into ``run_dir``: the
device's peak memory and, in a traced run, the normalised trace.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path

CTL_ENV = "PERFBENCH_CTL"
POLL_S = 0.005


def main(*args, **kwargs) -> None:
    ctl = json.loads(os.environ[CTL_ENV])
    import repro.serving.replica as rep

    shard = int(args[1])
    mark(ctl, shard, "spawned")
    run_dir = Path(ctl["run_dir"])
    held: dict = {}
    build_server = rep._build_jax_server

    def seeded_init(m):
        import jax

        mark(ctl, shard, f"on {jax.devices()[0].device_kind}, initialising")
        params = jax.jit(m.init)(jax.random.PRNGKey(ctl["weight_seed"]))
        jax.block_until_ready(params)
        mark(ctl, shard, "weights on the device")
        return params

    def build(model, model_kwargs, *, slots, max_seq, chip):
        rep.init_weights = seeded_init  # read by build_server at call time
        mark(ctl, shard, "building")
        server, report = build_server(model, model_kwargs, slots=slots,
                                      max_seq=max_seq, chip=chip)
        mark(ctl, shard, "built and prewarmed")
        if ctl.get("fault"):
            plant_fault(server, ctl["fault"], ctl.get("fault_at", 0))
        if ctl["trace"]:
            marks = instrument(server)
            threading.Thread(target=trace_window,
                             args=(ctl, shard, server, marks),
                             daemon=True).start()
        held["server"] = server
        return server, report

    rep._build_jax_server = build
    try:
        rep.replica_main(*args, **kwargs)
    finally:
        write_stats(run_dir, shard, held.get("server"))


def mark(ctl: dict, shard: int, what: str) -> None:
    """One set-up stage on stderr, in seconds since the head started (the
    monotonic clock is the host's, shared by both processes)."""
    import sys

    print(f"replica {shard}: {what} at "
          f"{time.monotonic() - ctl['t_start']:.1f} s",
          file=sys.stderr, flush=True)


def instrument(server) -> dict:
    """Host spans around the server's calls, on the profiler's clock, and
    zero-length ``perfbench_busy``/``perfbench_idle`` marks where the
    server's backlog starts and drains.  Returns the mark state."""
    from jax.profiler import TraceAnnotation as Span

    marks = {"pending": False}

    def wrap(name, fn, stats=None):
        def traced(*a, **k):
            with Span("perfbench_" + name, **(stats(*a) if stats else {})):
                return fn(*a, **k)
        return traced

    def decode_stats(params, cache, tokens):
        ctx = [len(st["req"].tokens) + len(st["generated"]) - 1
               for st in server._active.values()]
        return {"n": len(ctx), "ctx_sum": sum(ctx)}

    ingest = wrap("ingest", server.ingest_serve_message)
    step = wrap("round", server.step_rounds)

    def ingest_marked(*a, **k):
        out = ingest(*a, **k)
        if not marks["pending"] and not server.idle:
            marks["pending"] = True
            with Span("perfbench_busy"):
                pass
        return out

    def step_marked():
        step()
        if marks["pending"] and server.idle:
            marks["pending"] = False
            with Span("perfbench_idle"):
                pass

    server.ingest_serve_message = ingest_marked
    server.step_rounds = step_marked
    server._admit = wrap("admit", server._admit)
    server._decode_round = wrap("decode_round", server._decode_round)
    server._prefill = wrap("prefill", server._prefill,
                           lambda params, prompt: {"len": int(prompt.shape[1])})
    server._decode = wrap("decode", server._decode, decode_stats)
    return marks


def _wait_for(path: Path, until: float = float("inf")) -> None:
    while not path.exists() and time.monotonic() < until:
        time.sleep(POLL_S)


def trace_window(ctl: dict, shard: int, server, marks: dict) -> None:
    """Trace ``ctl["trace_s"]`` seconds, from ``ctl["trace_at"]`` seconds
    after the head's ``trace_start`` file (the window's opening) or until
    its ``trace_stop`` file (the window's close), whichever comes first;
    then write the normalised trace and delete the raw one."""
    import glob

    import jax
    from jax.profiler import ProfileOptions, TraceAnnotation

    from bench.trace import normalize

    run_dir = Path(ctl["run_dir"])
    opts = ProfileOptions()
    opts.python_tracer_level = 0  # host spans come from TraceAnnotation
    opts.host_tracer_level = 1    # TraceAnnotation spans are level 1
    raw = run_dir / f"xplane-{shard}"
    _wait_for(run_dir / "trace_start")
    stop = run_dir / "trace_stop"
    _wait_for(stop, time.monotonic() + ctl["trace_at"])
    jax.profiler.start_trace(str(raw), profiler_options=opts)
    win = TraceAnnotation("perfbench_window", pending=int(marks["pending"]))
    win.__enter__()
    mark(ctl, shard, "trace started")
    _wait_for(stop, time.monotonic() + ctl["trace_s"])
    win.__exit__(None, None, None)
    jax.profiler.stop_trace()
    mark(ctl, shard, "trace stopped")
    path = sorted(glob.glob(str(raw / "**" / "*.xplane.pb"), recursive=True))
    tr = normalize(path[-1]) if path else None
    with open(run_dir / f"trace-{shard}.json", "w") as f:
        json.dump(tr, f)
    shutil.rmtree(raw, ignore_errors=True)
    mark(ctl, shard, "trace read")
    (run_dir / f"trace_stopped-{shard}").touch()


def plant_fault(server, fault: str, at: int) -> None:
    """Break the timed path underneath, for the harness's own tests:
    ``alter_token`` changes every 5th decoded token of the first active
    slot where decode produces it; ``stale_cache`` leaves the batched cache
    unchanged on admission (the prompt's keys and values never reach the
    slot, only its length does); ``drop_request`` makes the replica
    ignore the ``at``-th request message it is sent, so that answer never
    comes."""
    import numpy as np

    if fault == "alter_token":
        decode, vocab = server._decode, server.model.cfg.vocab_size
        calls = [0]

        def altered(params, cache, tokens):
            nxt, cache = decode(params, cache, tokens)
            calls[0] += 1
            if calls[0] % 5 == 0 and server._active:
                nxt = np.array(nxt)
                slot = min(server._active)
                nxt[slot] = (nxt[slot] + 1) % vocab
            return nxt, cache

        server._decode = altered
    elif fault == "stale_cache":
        import repro.runtime.server as srv

        def unchanged(batched, single, slot, length):
            out = dict(batched)
            out["len"] = batched["len"].at[slot].set(length)
            return out

        srv._splice_cache = unchanged
    elif fault == "drop_request":
        ingest, seen = server.ingest_serve_message, [0]

        def dropping(ptr, **kw):
            seen[0] += 1  # one request per message; set-up sends `at - 3`
            return 0 if seen[0] == at else ingest(ptr, **kw)

        server.ingest_serve_message = dropping
    else:
        raise ValueError(f"unknown fault {fault!r}")


def write_stats(run_dir: Path, shard: int, server) -> None:
    stats = {"shard": shard, "memory_peak_bytes": None, "platform": None,
             "kind": None, "decode_steps": None}
    if server is not None:
        import jax

        dev = jax.devices()[0]
        mem = dev.memory_stats() or {}
        stats.update(memory_peak_bytes=mem.get("peak_bytes_in_use"),
                     platform=dev.platform, kind=dev.device_kind,
                     decode_steps=server.steps)
    with open(run_dir / f"replica-{shard}.json", "w") as f:
        json.dump(stats, f)
