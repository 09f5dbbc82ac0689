#!/usr/bin/env python3
"""Chip benchmark of the serving main path.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The head process (this one) runs the open-loop load generator,
``ShardRouter`` and ``ResultsCollector``; each replica is a
``ReplicaPool`` child pinned to its own chip, started through
``bench/replica.py``.  The head touches no JAX while a replica lives.
Set-up (spawn, JAX start-up, weights on the device from the seed, warm-up
of every prompt length the mix sends) ends when the window opens.
End-to-end numbers are taken on the client's side; with ``--trace 1`` the
replicas trace the middle ``TRACE_S`` seconds of the window and the line
carries the cell's per-layer metrics instead.  After the pool has stopped,
the head checks what was served against the float32 reference on the
chip.  The last line of stdout is one JSON object.

A run that finds no TPU exits non-zero and prints no result.
``--rehearse`` runs the cell at tiny widths on the CPU and skips the look
for a chip: ``correct`` is what the checks decide, the platform is
``cpu``, no metric is reported and the exit code is 1.  ``--control 1``
puts the float8 control in the program's place: the check that decides
``correct`` reads the control's gap instead of the served tokens'.
``--rates r1,r2`` sweeps open-loop rates on one pool (to find a mix's
knee) and prints one line per rate instead of a result.  ``--seeds s1,s2``
serves one window per traffic seed on one pool and prints one line per
seed with the program's checks and verdict and, with ``--control 1``, the
control's: the readings a limit is set from.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = ROOT / ".jax_cache"       # fixed: the path is part of the key
RUNS_DIR = ROOT / ".perfbench"
READY_TIMEOUT_S = 1200.0              # a cold first run compiles
WARM_TIMEOUT_S = 900.0
TRACE_S = 20.0                        # traced span, centred in the window
# how long the head waits, after the window's requests have finished, for
# each replica to stop its trace and write it: the profiler's stop_trace
# grows with the host events of the traced span (92-99 s for 20 s of the
# chat cell on a v5e host), so the wait grows with the span; the replicas
# stop theirs side by side, so it does not grow with their count
TRACE_WAIT_S = 60.0
TRACE_WAIT_PER_TRACED_S = 9.0
PUBLISH_TIMEOUT_S = 60.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Rec:
    prompt: object
    max_new: int
    sched: float
    arrivals: list = field(default_factory=list)
    tokens: list | None = None
    completions: int = 0


class Client:
    """The head's side of the serving path: submits through the router,
    pumps the collector, and stamps every token's arrival."""

    def __init__(self, router, collector, pool):
        self.router, self.collector, self.pool = router, collector, pool
        self.recs: dict[int, Rec] = {}
        self._janitor = 0.0
        collector.on_progress = self._progress
        collector.on_complete = self._complete

    def _progress(self, rid):
        rec = self.recs.get(rid)
        if rec is not None:
            rec.arrivals.append(time.monotonic())
        self.router.touch(rid)

    def _complete(self, rid, tokens):
        now = time.monotonic()
        rec = self.recs.get(rid)
        if rec is not None:
            rec.completions += 1
            rec.tokens = list(tokens)
            # one chunk per token: the eos chunk is the last arrival
            rec.arrivals.extend([now] * (len(tokens) - len(rec.arrivals)))
        self.router.complete(rid)

    def submit(self, prompt, max_new: int, sched: float,
               shard: int | None = None) -> int:
        # one request per message: the message carries its decode budget
        self.router.max_new = max_new
        sent = time.monotonic()
        rid = self.router.submit(prompt, shard=shard)
        if rid is None:
            raise RuntimeError("the router shed a request")
        # publish without blocking the head: a replica whose request queue
        # is full may itself be waiting for the head to take its results
        while self.router.flush(timeout=0.002) == 0:
            if time.monotonic() > sent + PUBLISH_TIMEOUT_S:
                raise RuntimeError("a request publish stalled")
            self.collector.pump(timeout=0.002)
        self.recs[rid] = Rec(prompt, max_new, sched)
        return rid

    def pump(self, timeout: float) -> None:
        self.collector.pump(timeout=timeout)
        now = time.monotonic()
        if now >= self._janitor:
            self._janitor = now + 0.5
            dead = self.pool.poll()
            if dead:
                raise RuntimeError(f"replicas {dead} died")

    def wait(self, rids, deadline: float) -> None:
        while (any(self.recs[r].tokens is None for r in rids)
               and time.monotonic() < deadline):
            self.pump(0.01)


def open_loop(client: Client, reqs, seconds: float, on_open=None):
    """Send each request at its due time; returns (t0, t1, rids, late)."""
    if on_open is not None:
        on_open()
    t0 = time.monotonic() + 0.001
    rids, late = [], []
    for r in reqs:
        due = t0 + r.due
        while (now := time.monotonic()) < due:
            client.pump(min(due - now, 0.005))
        late.append(time.monotonic() - due)
        rids.append(client.submit(r.prompt, r.max_new, due))
    t1 = t0 + seconds
    while (now := time.monotonic()) < t1:
        client.pump(min(t1 - now, 0.01))
    return t0, t1, rids, late


def e2e_metrics(client: Client, rids, end: float):
    from bench.traffic import percentile

    recs = [client.recs[r] for r in rids]
    # a request that never answered waited until the follow bound
    ttft = [(rec.arrivals[0] if rec.arrivals else end) - rec.sched
            for rec in recs]
    itl = [(b - a) * 1e3 for rec in recs
           for a, b in zip(rec.arrivals, rec.arrivals[1:])]
    return {"ttft_p95_s": percentile(ttft, 95),
            "itl_p95_ms": percentile(itl, 95)}


def served_checks(client: Client, rids, vocab: int, max_seq: int) -> dict:
    """Exact checks of reassembly: every rid once, whole, in the vocab."""
    missing = dup = wrong = 0
    for r in rids:
        rec = client.recs[r]
        if rec.tokens is None:
            missing += 1
            continue
        dup += rec.completions > 1
        want = min(rec.max_new, max_seq - 1 - len(rec.prompt))
        wrong += (len(rec.tokens) != want
                  or any(not 0 <= t < vocab for t in rec.tokens))
    return {"missing": (missing, 0), "duplicates": (dup, 0),
            "wrong_length": (wrong, 0)}


def logit_gaps(client, rids, ref, traffic, seed, control: bool) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over a seeded sample of finished requests that
    always holds the longest one (``served``); with ``control``, also the
    widest gap of the token the float8 pass puts first at the same
    positions (``control``)."""
    import numpy as np

    done = [r for r in rids if client.recs[r].tokens]
    if not done:
        return {"served": float("inf"), "control": float("inf")}
    longest = max(done, key=lambda r: len(client.recs[r].prompt)
                  + len(client.recs[r].tokens))
    rest = [r for r in done if r != longest]
    k = min(len(rest), int(traffic["ref_sample"]) - 1)
    pick = [longest] + list(np.random.default_rng([seed, 5]).choice(
        rest, size=k, replace=False))
    n_pos = int(traffic["output"]["max"])
    t = time.monotonic()
    out = {"served": 0.0, "control": 0.0} if control else {"served": 0.0}
    n_tok = 0
    for r in pick:
        rec = client.recs[r]
        for key, g in ref.gaps(rec.prompt, rec.tokens, n_pos=n_pos,
                               control=control).items():
            out[key] = max(out[key], float(g.max()))
        n_tok += len(rec.tokens)
    log(f"reference: {len(pick)} requests, {n_tok} served tokens, "
        f"{time.monotonic() - t:.1f} s")
    return out


def verdict(checks: dict) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(v <= lim for v, lim in checks.values())


def trace_wait(run_dir: Path, done: str, n: int, client: Client,
               trace_s: float) -> None:
    """Pump results until every replica has confirmed ``done``."""
    wait_s = TRACE_WAIT_S + TRACE_WAIT_PER_TRACED_S * trace_s
    t = time.monotonic()
    while not all((run_dir / f"trace_{done}-{s}").exists() for s in range(n)):
        if time.monotonic() > t + wait_s:
            raise RuntimeError(f"replicas did not confirm trace {done} in "
                               f"{wait_s} s")
        client.pump(0.01)
    log(f"trace {done} confirmed {time.monotonic() - t:.1f} s into the "
        f"{wait_s} s wait")


def compiles(pool, n: int) -> tuple[int, int]:
    """(programs compiled or loaded from the persistent cache, of them
    loaded) over the replicas so far."""
    time.sleep(0.3)  # the replicas export their counters every 0.25 s
    reps = [pool.device_report(s) or {} for s in range(n)]
    return (sum(int(r.get("replica.compiles", 0)) for r in reps),
            sum(int(r.get("replica.compile_cache_hits", 0)) for r in reps))


def sweep(client, traffic, rates, seed, seconds, vocab):
    """One open-loop window per rate, on one pool: completion rate, TTFT
    trend and drain time show where the backlog starts to grow."""
    from bench.traffic import generate, percentile

    for rate in rates:
        mix = dict(traffic, rate_rps=rate)
        reqs = generate(mix, seed, seconds, vocab)
        t0, t1, rids, late = open_loop(client, reqs, seconds)
        client.wait(rids, t1 + float(traffic["follow_s"]))
        end = time.monotonic()
        recs = [client.recs[r] for r in rids]
        ttft = [(rec.arrivals[0] if rec.arrivals else end) - rec.sched
                for rec in recs]
        q = max(1, len(ttft) // 4)
        itl = [(b - a) * 1e3 for rec in recs
               for a, b in zip(rec.arrivals, rec.arrivals[1:])]
        print(json.dumps({
            "rate_rps": rate, "requests": len(rids),
            "finished": sum(rec.tokens is not None for rec in recs),
            "drain_s": end - t1,
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p95_s": percentile(ttft, 95),
            "ttft_first_quarter_p50_s": percentile(ttft[:q], 50),
            "ttft_last_quarter_p50_s": percentile(ttft[-q:], 50),
            "itl_p50_ms": percentile(itl, 50),
            "itl_p95_ms": percentile(itl, 95),
            "gen_late_p99_ms": percentile(late, 99) * 1e3}), flush=True)


def run(args) -> dict | None:
    import numpy as np

    from bench import spec
    cell = spec.load_cell(args.workload)
    shape = spec.model_shape(cell.config, rehearse=args.rehearse)
    serving = dict(cell.config["serving"])
    limits = dict(cell.config["limits"])
    if args.rehearse:
        serving.update(cell.config["rehearse"]["serving"])
        limits.update(cell.config["rehearse"]["limits"])
    weight_seed = int(np.random.SeedSequence(args.seed).generate_state(1)[0]
                      & 0x7FFF_FFFF)

    run_dir = RUNS_DIR / f"{cell.name}-{args.seed}-{os.getpid()}"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    try:
        return measure(args, cell, shape, serving, limits, weight_seed,
                       run_dir)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)


def measure(args, cell, shape, serving, limits, weight_seed, run_dir):
    import numpy as np

    from bench import spec
    from bench.traffic import generate, warm_lengths

    traffic, n = cell.traffic, cell.chips
    vocab = int(shape["vocab_size"])
    n_warm = len(warm_lengths(traffic))
    trace_s = min(TRACE_S, args.seconds)
    os.environ["PERFBENCH_CTL"] = json.dumps({
        "run_dir": str(run_dir), "weight_seed": weight_seed, "t_start": T_START,
        "trace": int(args.trace), "trace_s": trace_s,
        "trace_at": (args.seconds - trace_s) / 2,
        "fault": args.fault, "fault_at": n_warm + 3})
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # cache every program, so that a warm run compiles nothing at all
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import repro.serving.pool as pool_mod
    from repro.core import Domain
    from repro.serving import ReplicaPool, ResultsCollector, ShardRouter

    import bench.replica

    pool_mod.replica_main = bench.replica.main  # the pool's spawn target
    dom = Domain.create(arena_capacity=64 << 20)
    pool = router = collector = None
    per_seed: list = []
    try:
        pool = ReplicaPool(
            dom, range(n), model="jax",
            model_kwargs=spec.program_kwargs(cell.config, cell.reduced,
                                             rehearse=args.rehearse),
            slots=int(serving["slots"]), max_seq=int(serving["max_seq"]),
            arena_mb=64, chips=None if args.rehearse else list(range(n)),
            lease_timeout_s=READY_TIMEOUT_S)
        pool.wait_ready(timeout=READY_TIMEOUT_S)
        log(f"replicas ready at {time.monotonic() - T_START:.1f} s")
        reports = [pool.device_report(s) for s in range(n)]
        device = check_devices(reports, shape, n, args.rehearse)
        router = ShardRouter(dom, range(n), depth=64)
        collector = ResultsCollector(dom, shards=range(n))
        client = Client(router, collector, pool)

        # warm-up through the served path: every prompt length on every
        # replica, so that no program compiles inside the window
        rng = np.random.default_rng([args.seed, 6])
        warm = [client.submit(rng.integers(0, vocab, length, dtype=np.int32),
                              2, time.monotonic(), shard=s)
                for s in range(n) for length in warm_lengths(traffic)]
        client.wait(warm, time.monotonic() + WARM_TIMEOUT_S)
        if any(client.recs[r].tokens is None for r in warm):
            raise RuntimeError("warm-up requests did not finish")
        log(f"warm-up done at {time.monotonic() - T_START:.1f} s")
        if args.rates:
            sweep(client, traffic, [float(r) for r in args.rates.split(",")],
                  args.seed, args.seconds, vocab)
            return None

        if args.seeds:
            # the readings that set a limit: one window per seed on this
            # pool, each checked against the reference once the pool stops
            for seed in args.seeds:
                reqs = generate(traffic, seed, args.seconds, vocab)
                _, t1, rids, _ = open_loop(client, reqs, args.seconds)
                client.wait(rids, t1 + float(traffic["follow_s"]))
                per_seed.append((seed, rids))
        else:
            reqs = generate(traffic, args.seed, args.seconds, vocab)
            c0, hits = compiles(pool, n)

            def on_open():
                # the replicas trace the window's middle ``trace_s`` seconds
                if args.trace:
                    (run_dir / "trace_start").touch()

            t0, t1, rids, late = open_loop(client, reqs, args.seconds, on_open)
            setup_s = t0 - T_START
            if args.trace:
                (run_dir / "trace_stop").touch()
            client.wait(rids, t1 + float(traffic["follow_s"]))
            end = time.monotonic()
            if args.trace:
                trace_wait(run_dir, "stopped", n, client, trace_s)
            c1, _ = compiles(pool, n)
            metrics = e2e_metrics(client, rids, end)
            log(f"end to end: {json.dumps(metrics)}")
            checks = served_checks(client, rids, vocab, int(serving["max_seq"]))
            attempted = len(rids)
            failed = sum(client.recs[r].tokens is None for r in rids)
            log(f"window {t1 - t0:.1f} s: {attempted} requests, {failed} failed, "
                f"compiles {c1 - c0} (before window {c0}, {hits} of them "
                f"from the persistent cache), generator late p99 "
                f"{1e3 * float(np.percentile(late, 99)):.2f} ms")
            router.close()
            collector.close()
            router = collector = None
    finally:
        if router is not None:
            router.close()
        if collector is not None:
            collector.close()
        if pool is not None:
            pool.stop()
        dom.close()
    limit = float(limits["logit_gap"])
    if args.seeds:
        seed_readings(client, per_seed, shape, weight_seed, traffic, vocab,
                      int(serving["max_seq"]), limit, args.control)
        return None

    stats = [load(run_dir / f"replica-{s}.json") for s in range(n)]
    traces = []
    if args.trace:
        traces = [load(run_dir / f"trace-{s}.json") for s in range(n)]
    peaks_bytes = [s["memory_peak_bytes"] for s in stats
                   if s["memory_peak_bytes"] is not None]
    device["memory_peak_bytes"] = max(peaks_bytes) if peaks_bytes else None

    from bench.reference import Reference

    gaps = logit_gaps(client, rids, Reference(shape, weight_seed), traffic,
                      args.seed, args.control)
    if args.control:
        # the control in the program's place: its tokens are what is judged
        log(f"control in the program's place: the program's own logit_gap "
            f"{gaps['served']!r}")
        checks["logit_gap"] = (gaps["control"], limit)
    else:
        checks["logit_gap"] = (gaps["served"], limit)
    result = {"correct": verdict(checks), "attempted": attempted,
              "failed": failed}
    on_tpu = device["platform"] == "tpu"
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        from bench import trace as T
        from bench import work
        from bench.readings import Context

        ctx = Context(config=shape, serving=serving,
                      peak=work.peaks(device["kind"]) if on_tpu else None,
                      traces=traces, compiles_in_window=c1 - c0)
        vals = {m["name"]: spec.load_metric(m["name"]).read(ctx)
                for m in cell.per_layer}
        with_device = [tr for tr in traces if tr["modules"]]
        if with_device:
            wins = [T.window(tr) for tr in with_device]
            device["busy_s"] = sum(T.busy_ns(tr, *w) for tr, w in
                                   zip(with_device, wins)) / 1e9 / len(wins)
            device["window_s"] = sum(w1 - w0 for w0, w1 in wins) / 1e9 / len(wins)
            result["breakdown"] = T.breakdown(with_device)
        else:
            log("the traces hold no device plane: "
                f"{traces[0]['planes'] if traces else None}")
    else:
        vals = dict(metrics, setup_s=setup_s)
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in vals.items()
                         if v is not None and k in units and on_tpu}
    result["device"] = device
    for key, (v, lim) in checks.items():
        log(f"check {key} {v!r} limit {lim!r}")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def seed_readings(client, per_seed, shape, weight_seed, traffic, vocab,
                  max_seq, limit, control) -> None:
    """One line per seed: the checks of its window's requests."""
    from bench.reference import Reference

    def shown(checks):
        return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}

    ref = Reference(shape, weight_seed)
    for seed, rids in per_seed:
        checks = served_checks(client, rids, vocab, max_seq)
        gaps = logit_gaps(client, rids, ref, traffic, seed, control)
        prog = dict(checks, logit_gap=(gaps["served"], limit))
        line = {"seed": seed, "requests": len(rids), "checks": shown(prog),
                "correct": verdict(prog)}
        if control:
            ctl = dict(checks, logit_gap=(gaps["control"], limit))
            line["control"] = {"checks": shown(ctl), "correct": verdict(ctl)}
        print(json.dumps(line), flush=True)


def load(path: Path):
    with open(path) as f:
        return json.load(f)


def check_devices(reports, shape, n: int, rehearse: bool) -> dict:
    """Every replica on a TPU (off it only in a rehearsal), all of one
    kind, on distinct chips, running the configuration's widths."""
    for s, rep in enumerate(reports):
        if rep is None:
            raise RuntimeError(f"replica {s} exported no device report")
        got = (rep["model.layers"], rep["model.d_model"], rep["model.vocab"],
               rep["model.dtype"])
        want = (shape["num_hidden_layers"], shape["hidden_size"],
                shape["vocab_size"], shape["torch_dtype"])
        if got != want:
            raise RuntimeError(f"replica {s} built {got}, the configuration "
                               f"says {want}")
    platforms = {r["device.platform"] for r in reports}
    kinds = {r["device.kind"] for r in reports}
    if len(platforms) != 1 or len(kinds) != 1:
        raise RuntimeError(f"replicas on mixed devices: {platforms} {kinds}")
    platform, kind = platforms.pop(), kinds.pop()
    if platform != "tpu" and not rehearse:
        raise RuntimeError(f"no TPU: the replicas run on {platform!r}")
    if platform == "tpu":
        held = {tuple(r["device.held"]) for r in reports}
        if len(held) != n:
            raise RuntimeError(f"{n} replicas hold {len(held)} distinct chips")
    return {"platform": platform, "kind": kind, "count": n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU; never reports metrics")
    ap.add_argument("--rates", default=None,
                    help="comma-separated open-loop rates to sweep")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seeds", type=lambda v: [int(x) for x in v.split(",")],
                    default=None,
                    help="comma-separated traffic seeds: one window each on "
                         "one pool, and one line of checks each")
    ap.add_argument("--fault", default=None,
                    help="plant a fault in the replicas (harness tests)")
    args = ap.parse_args(argv)
    result = run(args)
    if result is None:
        return 0
    print(json.dumps(result), flush=True)
    return 0 if result["device"]["platform"] == "tpu" else 1


if __name__ == "__main__":
    sys.exit(main())
