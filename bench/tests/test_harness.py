"""The benchmark's yardstick, checked on the CPU: work counts, the peak
table, the traffic generator, the trace reduction on a recorded trace,
the reference against the served model's own definition, and the shape of
``BENCHMARK.json``.  Run with ``python -m pytest bench/tests``."""

from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import spec, work
from bench import trace as T
from bench.readings import Context
from bench.traffic import generate, n_requests, round_to

HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "fixtures" / "trace_chat_tpu.json"
BENCH = spec.load_benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def shape(name):
    return spec.load_json(spec.ROOT / f"bench/configs/{name}.json")


# -- work counts, against values worked out by hand ---------------------------

def test_qwen2_counts():
    c = shape("qwen2-1.5b")
    assert work.layer_params(c) == 46_792_704
    assert work.weight_bytes(c) == 3_087_138_816       # 3.09 GB
    assert work.kv_bytes_per_token(c) == 28_672        # 28 KiB
    assert work.prefill_flops(c, 1024) == 2_774_029_959_168
    assert work.prefill_bytes(c, 1024) == 3_119_644_672
    assert work.decode_flops(c, 32, 40_000) == 105_675_227_136
    assert work.decode_bytes(c, 32, 40_000) == 4_235_034_624


def test_qwen3_8b_9l_counts():
    c = shape("qwen3-8b-9l")
    assert work.layer_params(c) == 192_937_984
    # nine layers and the untied head; the embedding is gathered
    assert work.weight_bytes(c) == 4_717_543_424
    assert work.kv_bytes_per_token(c) == 36_864       # 36 KiB
    assert work.decode_bytes(c, 16, 30_000) == (
        4_717_543_424 + 16 * 4096 * 2 + 30_016 * 36_864)


COUNTS = ("linear_flops_per_token", "weight_bytes", "kv_bytes_per_token",
          "prefill_flops", "prefill_bytes", "decode_flops", "decode_bytes")

# a stand-in architecture module: its reference puts the logit 2.0 on the
# token after the one at each position, and it replaces two counts
STUB = """
import jax.numpy as jnp


def init_weights(c, seed):
    return {"scale": jnp.float32(2.0)}


def forward_logits(w, tokens, pos, c, control):
    nxt = (tokens[pos] + 1) % c["vocab_size"]
    return w["scale"] * (jnp.arange(c["vocab_size"])[None] == nxt[:, None])


def weight_bytes(c):
    return 1000


def decode_bytes(c, n_active, ctx_sum):
    return 7 * n_active + ctx_sum
"""


@pytest.fixture
def stub_arch(tmp_path, monkeypatch):
    """A configuration that names the stand-in module ``stub``."""
    (tmp_path / "stub.py").write_text(STUB)
    monkeypatch.setattr(spec, "ARCH_DIR", tmp_path)
    return dict(shape("qwen2-1.5b"), architecture="stub")


def test_architecture_module_replaces_the_counts_it_defines(stub_arch):
    dense = shape("qwen2-1.5b")
    assert work.decode_bytes(stub_arch, 3, 100) == 121
    assert work.weight_bytes(stub_arch) == 1000
    # the dense counts it leaves stay, and use the ones it replaced
    assert work.decode_flops(stub_arch, 3, 100) == work.decode_flops(dense, 3, 100)
    assert (work.prefill_bytes(stub_arch, 64) - work.prefill_bytes(dense, 64)
            == 1000 - work.weight_bytes(dense))


def test_architecture_module_is_the_reference(stub_arch):
    from bench.reference import Reference

    ref = Reference(stub_arch, 5)
    assert float(ref.w["scale"]) == 2.0
    prompt = np.array([10, 11, 12], np.int32)
    # the stub's best after 12 is 13, after 13 is 14; the third is off
    got = ref.gaps(prompt, [13, 14, 99], n_pos=8)
    assert list(got["served"]) == [0.0, 0.0, 2.0]


def test_configuration_without_architecture_is_the_dense_decoder():
    """Each count is the dense one to the last digit, and the reference
    is the dense decoder's."""
    from bench import reference

    for name in ("qwen2-1.5b", "qwen3-8b-9l"):
        c = shape(name)
        assert "architecture" not in c and spec.architecture(c) is None
        for count in COUNTS:
            fn = getattr(work, count)
            args = {"prefill_flops": (1536,), "prefill_bytes": (1536,),
                    "decode_flops": (16, 30_000),
                    "decode_bytes": (16, 30_000)}.get(count, ())
            assert fn(c, *args) == fn.dense(c, *args), (name, count)
    c = dict(shape("qwen2-1.5b"), **TINY)
    ref = reference.Reference(c, 1)
    assert ref.init_weights is reference.init_weights
    assert ref.forward_logits is reference.forward_logits


def test_least_time_takes_the_binding_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.least_time(197e12, 0, peak) == pytest.approx(1.0)
    assert work.least_time(0, 819e9, peak) == pytest.approx(1.0)
    assert work.least_time(197e12, 2 * 819e9, peak) == pytest.approx(2.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in peaks.json"):
        work.peaks("TPU v9 imaginary")


# -- traffic --------------------------------------------------------------------

@pytest.mark.parametrize(
    "mix", sorted(p.stem for p in (spec.HERE / "traffic").glob("*.json")))
def test_traffic_is_the_seeds_order_of_one_workload(mix):
    t = spec.load_json(spec.ROOT / f"bench/traffic/{mix}.json")
    a = generate(t, 2**33 + 7, 30, 151_936)
    b = generate(t, 2**33 + 7, 30, 151_936)
    c = generate(t, 5, 30, 151_936)
    assert len(a) == n_requests(t, 30)
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               and x.due == y.due for x, y in zip(a, b))
    # another seed: the same lengths and gaps, in another order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in c]
    allowed = set(t["prompt"]["lengths"])
    assert len(allowed) <= 8
    assert {len(x.prompt) for x in a} <= allowed
    assert all(t["output"]["min"] <= x.max_new <= t["output"]["max"] for x in a)
    # the gaps, the one from the last due time to the window's end
    # included, are one multiset for every seed
    gaps = sorted(np.diff([x.due for x in a] + [30]))
    gaps_c = sorted(np.diff([x.due for x in c] + [30]))
    assert a[0].due == 0 and a[-1].due < 30
    assert np.allclose(gaps, gaps_c)


def test_round_to_nearest_in_log_space():
    got = round_to(np.array([64, 100, 150, 3000]), [96, 192, 3072])
    assert list(got) == [96, 96, 192, 3072]


# -- trace reduction --------------------------------------------------------------

def _trace(modules, spans, busy=()):
    return {"modules": [list(m) for m in modules], "busy": [list(b) for b in busy],
            "spans": [list(s) for s in spans], "planes": {}}


def test_busy_union_idle_gaps_and_matching():
    tr = _trace(
        modules=[("jit_prefill(3)", 100, 50), ("jit_decode", 140, 20),
                 ("jit_decode(7)", 200, 30), ("jit_decoder", 400, 10)],
        spans=[("perfbench_window", 100, 400, {"pending": 1}),
               ("perfbench_round", 160, 50, {}),
               ("perfbench_idle", 460, 0, {})])
    w0, w1 = T.window(tr)
    assert (w0, w1) == (100, 500)
    busy = T.busy_intervals(tr, w0, w1)
    assert busy == [(100, 160), (200, 230), (400, 410)]
    assert T.busy_ns(tr, w0, w1) == 100
    assert T.idle_gaps(busy, w0, w1) == [(160, 200), (230, 400), (410, 500)]
    assert T.idle_within(busy, 150, 210) == 40
    assert T.module_time(tr, ["jit_decode"], w0, w1) == (2, 50)
    assert T.host_state_at(tr, 180) == "round"
    assert T.host_state_at(tr, 300) == "outside_server"
    from bench.readings import pending_intervals
    assert pending_intervals(tr, w0, w1) == [(100, 460)]


@pytest.fixture(scope="module")
def recorded():
    if not FIXTURE.exists():
        pytest.skip("no recorded trace fixture")
    with open(FIXTURE) as f:
        return json.load(f)


def test_recorded_trace_reduces(recorded):
    """A slice of a real traced window of ``qwen2-1.5b.chat`` on a TPU
    v5e: both jitted programs are found by module name, busy time lies
    inside the window, and every metric reads a value in its range."""
    tr = recorded
    w0, w1 = T.window(tr)
    assert T.module_time(tr, ["jit_decode"], w0, w1)[0] > 0
    assert T.module_time(tr, ["jit_prefill"], w0, w1)[0] > 0
    busy = T.busy_ns(tr, w0, w1)
    assert 0 < busy <= w1 - w0
    c = shape("qwen2-1.5b")
    ctx = Context(config=c, serving=c["serving"], peak=work.peaks("TPU v5 lite"),
                  traces=[tr], compiles_in_window=0)
    for m in BENCH["per_layer"]:
        if "qwen2-1.5b.chat" not in m["workloads"]:
            continue
        v = spec.load_metric(m["name"]).read(ctx)
        assert v is not None, m["name"]
        assert v >= 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
    bd = T.breakdown([tr])
    assert bd["device_ops"] and bd["idle_gaps"]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_readers_read_nothing_without_a_device_plane():
    tr = _trace(modules=[], spans=[("perfbench_window", 0, 100, {})])
    c = shape("qwen2-1.5b")
    ctx = Context(config=c, serving=c["serving"], peak=None, traces=[tr])
    for m in BENCH["per_layer"]:
        if m["name"] != "compiles_in_window":
            assert spec.load_metric(m["name"]).read(ctx) is None, m["name"]


def test_occupancy_readers():
    spans = [("perfbench_window", 0, 100, {}),
             ("perfbench_decode", 10, 5, {"n": 2, "ctx_sum": 4096}),
             ("perfbench_decode", 20, 5, {"n": 4, "ctx_sum": 12288}),
             ("perfbench_decode", 120, 5, {"n": 9, "ctx_sum": 9})]
    tr = _trace(modules=[("jit_decode", 10, 3)], spans=spans)
    ctx = Context(config={}, serving={"slots": 4, "max_seq": 4096}, peak=None,
                  traces=[tr])
    assert spec.load_metric("active_slots").read(ctx) == 3.0
    assert spec.load_metric("kv_fill_pct").read(ctx) == 50.0


# -- the reference ------------------------------------------------------------------

TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512}


# the Qwen3 decoder's flags over the qwen2-1.5b file: qk-norm, an untied
# head, no q/k/v bias
QWEN3 = {"program_arch": "qwen3-8b", "qk_norm": True,
         "tie_word_embeddings": False, "attention_bias": False}


@pytest.mark.parametrize("config, arch", [
    ("qwen2-1.5b", {}), ("qwen2-1.5b", QWEN3), ("qwen3-8b-9l", {})],
    ids=["qwen2", "qwen3", "qwen3-8b-9l"])
def test_reference_matches_the_served_models_definition(config, arch):
    """At tiny widths in float32: the reference's seeded weights are the
    program's, value for value, and its logits agree with the program's
    own forward pass."""
    import jax
    import jax.numpy as jnp

    from bench.reference import SEQ_BLOCK, forward_logits, init_weights
    from repro.serving.replica import build_model

    c = dict(shape(config), **arch, **TINY)
    kw = dict(spec.program_kwargs(c, [], rehearse=False),
              **{spec.PROGRAM_KEYS[k]: v for k, v in TINY.items()},
              param_dtype="float32", compute_dtype="float32")
    m = build_model("jax", kw)
    seed = 1234
    prog = jax.jit(m.init)(jax.random.PRNGKey(seed))
    ref = init_weights(c, seed)
    pl = prog["layers"]
    assert np.array_equal(np.asarray(ref["embed"], np.float32),
                          np.asarray(prog["tok_embed"].astype(jnp.bfloat16),
                                     np.float32))
    assert np.array_equal(
        np.asarray(ref["layers"]["w_down"], np.float32),
        np.asarray(pl["mlp"]["w_down"].astype(jnp.bfloat16), np.float32))
    # the program's float32 weights rounded to bfloat16 are the reference's
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                           prog)
    tokens = np.random.default_rng(0).integers(0, 512, SEQ_BLOCK, dtype=np.int32)
    pos = np.arange(0, SEQ_BLOCK, 97, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(m.forward(rounded, {"tokens": tokens[None]})[0][0])[pos]
        got = np.asarray(forward_logits(ref, tokens, pos, c, control=False))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_program_keys_reach_the_replica():
    """A ``program_keys`` entry outside the seven reaches ``model_kwargs``
    where the cell cuts it and in the rehearsal, and names a field of the
    program's ``ModelConfig``."""
    from repro.configs import get_config

    c = dict(shape("qwen2-1.5b"), num_experts=60,
             program_keys={"num_experts": "num_experts"})
    c["rehearse"] = dict(c["rehearse"], shape=dict(c["rehearse"]["shape"],
                                                   num_experts=4))
    kw = spec.program_kwargs(c, ["num_experts"])
    assert kw == {"arch": "qwen2-1.5b", "num_experts": 60}
    rehearsal = spec.program_kwargs(c, [], rehearse=True)
    assert rehearsal["num_experts"] == 4
    assert {rehearsal[spec.PROGRAM_KEYS[k]] for k in TINY} == set(TINY.values())
    for kw in (kw, rehearsal):
        kw = dict(kw)
        assert get_config(kw.pop("arch")).scaled(**kw).num_experts == kw[
            "num_experts"]


def test_unmapped_reduced_key_raises():
    c = shape("qwen2-1.5b")
    assert spec.program_kwargs(c, ["num_hidden_layers"]) == {
        "arch": "qwen2-1.5b", "num_layers": 28}
    with pytest.raises(KeyError, match="program_keys"):
        spec.program_kwargs(c, ["num_experts"])


def test_control_departs_from_the_reference():
    import jax

    from bench.reference import SEQ_BLOCK, forward_logits, init_weights

    c = dict(shape("qwen2-1.5b"), **TINY)
    w = init_weights(c, 3)
    tokens = np.random.default_rng(1).integers(0, 512, SEQ_BLOCK, dtype=np.int32)
    pos = np.arange(0, SEQ_BLOCK, 61, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(forward_logits(w, tokens, pos, c, control=False))
        fp8 = np.asarray(forward_logits(w, tokens, pos, c, control=True))
    err = np.abs(fp8 - exact).max()
    assert 1e-3 < err < 0.5 * np.abs(exact).max()


# -- BENCHMARK.json ------------------------------------------------------------------

def test_benchmark_json_shape():
    b = BENCH
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["command"][1] == "bench/run.py" and b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"]: w for w in b["workloads"]}
    confs = {c["name"] for c in b["configs"]}
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(cells) // 2)
    pairs = {(w["config"], w["traffic"]) for w in b["workloads"]}
    assert len(pairs) == len(cells)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]
        assert c["file"].startswith("bench/") and (spec.ROOT / c["file"]).exists()
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and w["config"] in confs
        assert (spec.HERE / "traffic" / f"{w['traffic']}.json").exists()
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.fullmatch(m["name"])
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for name in cells:
        reported = [m for m in b["end_to_end"]
                    if name in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(name in m["workloads"] for m in b["per_layer"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_metric_file_agrees_with_benchmark_json(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    mod = spec.load_metric(metric)
    assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]
    assert isinstance(mod.MATCH, list) and callable(mod.read)
    if metric.endswith("_roofline") or "mfu" in metric:
        assert m["unit"] == "%"
