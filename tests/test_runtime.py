"""Runtime: fault tolerance, straggler policy, remesh planning, server."""

import time

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.launch.train import model_100m
from repro.models import Model
from repro.runtime import (
    FailureDetector,
    InferenceServer,
    Request,
    StragglerMonitor,
    Trainer,
    TrainerConfig,
    plan_remesh,
)


# -- failure detector ---------------------------------------------------------


def test_failure_detector_transitions():
    fd = FailureDetector([0, 1, 2], suspect_after=0.1, dead_after=0.3)
    t0 = time.monotonic()
    fd.beat(0, t0)
    fd.beat(1, t0 - 0.2)   # suspect
    fd.beat(2, t0 - 1.0)   # dead
    s = fd.state(t0)
    assert s == {0: "alive", 1: "suspect", 2: "dead"}
    assert fd.healthy(t0) == [0, 1]


# -- straggler monitor ----------------------------------------------------------


def test_straggler_flags_slow_host():
    sm = StragglerMonitor(list(range(4)), threshold=1.5, grace_steps=3)
    for step in range(6):
        for h in range(4):
            sm.record(h, 1.0 if h != 2 else 2.5)
    assert sm.stragglers() == [2]


def test_straggler_grace_period():
    sm = StragglerMonitor([0, 1], grace_steps=5)
    sm.record(0, 1.0)
    sm.record(1, 9.0)
    assert sm.stragglers() == []  # not enough evidence yet


# -- remesh planning --------------------------------------------------------------


def test_plan_remesh_keeps_model_axis():
    # 2x16x16 = 512 chips on 128 hosts (4 chips/host); lose 3 hosts
    healthy = list(range(125))
    plan = plan_remesh(healthy, 4, (2, 16, 16))
    assert plan.mesh_axes[-1] == "model"
    assert plan.mesh_shape[-1] == 16            # TP preserved
    used = np.prod(plan.mesh_shape)
    assert used <= 125 * 4
    assert plan.batch_scale <= 1.0


@settings(max_examples=30, deadline=None)
@given(st.integers(17, 300), st.sampled_from([1, 2, 4, 8]))
def test_plan_remesh_properties(n_hosts, chips):
    plan = plan_remesh(list(range(n_hosts)), chips, (2, 16, 16))
    used = int(np.prod(plan.mesh_shape))
    assert used <= n_hosts * chips              # never oversubscribe
    assert plan.mesh_shape[-1] == 16            # model extent invariant
    assert set(plan.hosts).isdisjoint(plan.dropped)
    # mesh axes match shape length
    assert len(plan.mesh_axes) == len(plan.mesh_shape)


def test_plan_remesh_too_small_raises():
    with pytest.raises(ValueError):
        plan_remesh([0], 4, (2, 16, 16))        # 4 chips < model=16


# -- trainer restart ---------------------------------------------------------------


@pytest.mark.slow
def test_trainer_checkpoint_restart(tmp_path):
    cfg = model_100m("qwen2-1.5b").scaled(num_layers=2, d_model=64, d_ff=128,
                                          vocab_size=512, num_heads=2,
                                          num_kv_heads=1, head_dim=32)
    tc = TrainerConfig(batch=2, seq_len=64, total_steps=4, ckpt_every=2,
                       ckpt_dir=str(tmp_path), zero_copy_data=False,
                       log_every=100)
    t1 = Trainer(Model(cfg), tc)
    s1 = t1.run()
    t1.close()
    assert s1["steps"] == 4
    # "crash" and restart: must resume from step 4, run to 6, data cursor kept
    tc2 = TrainerConfig(batch=2, seq_len=64, total_steps=6, ckpt_every=2,
                        ckpt_dir=str(tmp_path), zero_copy_data=False,
                        log_every=100)
    t2 = Trainer(Model(cfg), tc2)
    s2 = t2.run()
    t2.close()
    assert t2.step_num == 6
    assert t2.metrics_log[0]["step"] == 5       # continued, not restarted


# -- inference server ----------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_server():
    cfg = model_100m("qwen2-1.5b").scaled(num_layers=2, d_model=64, d_ff=128,
                                          vocab_size=512, num_heads=2,
                                          num_kv_heads=1, head_dim=32)
    model = Model(cfg)
    srv = InferenceServer(model, slots=2, max_seq=128, page_tokens=32)
    srv.load(model.init(jax.random.PRNGKey(0)))
    return srv, cfg


@pytest.mark.slow
def test_server_continuous_batching(tiny_server):
    srv, cfg = tiny_server
    rng = np.random.default_rng(1)
    for i in range(5):                          # 5 requests through 2 slots
        srv.submit(Request(rid=f"r{i}",
                           tokens=rng.integers(0, 512, int(rng.integers(4, 30))),
                           max_new=6))
    results = srv.serve()
    assert len(results) == 5
    for r in results.values():
        assert len(r.tokens) == 6
        assert all(0 <= t < cfg.vocab_size for t in r.tokens)
    st = srv.stats()
    assert st["live_publications"] == 0
    assert st["free_pages"] == srv.pool.num_pages  # two-counter rule held


@pytest.mark.slow
def test_server_cancel_janitor(tiny_server):
    srv, _ = tiny_server
    rng = np.random.default_rng(2)
    srv.submit(Request(rid="victim", tokens=rng.integers(0, 512, 8), max_new=30))
    srv.submit(Request(rid="survivor", tokens=rng.integers(0, 512, 8), max_new=4))
    srv._admit()
    srv._decode_round()
    assert srv.cancel("victim")
    results = srv.serve()
    assert "survivor" in results and "victim" not in results
    assert srv.pool.free_pages == srv.pool.num_pages


def test_server_ttft_counts_queue_wait(tiny_server):
    """``Result.ttft`` runs from submission, so time a request spent queued
    before admission is part of it."""
    srv, _ = tiny_server
    rng = np.random.default_rng(3)
    srv.submit(Request(rid="warm", tokens=rng.integers(0, 512, 8), max_new=2))
    srv.serve()  # compiles this prompt length outside the timed requests
    for i in range(3):
        srv.submit(Request(rid=f"q{i}", tokens=rng.integers(0, 512, 8),
                           max_new=3))
    time.sleep(0.2)
    results = srv.serve()
    for i in range(3):
        r = results[f"q{i}"]
        assert 0.2 <= r.ttft < r.latency
