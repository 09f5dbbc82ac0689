"""The whole harness at tiny widths on the CPU, with the look for a chip
left out (``--rehearse``): one replica is spawned, serves the mix, a trace
is reduced, and the reference decides ``correct``.  A run off a TPU
reports no metric.  With the timed path broken underneath (a token
altered where decode produces it, a slot's cache left unchanged on
admission, an answer that never comes) or with the float8 control in the
program's place, ``correct`` comes out false.

Each case is one subprocess of about a minute."""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]


def rehearse(*extra, workload="qwen2-1.5b.chat", seconds=2, trace=0):
    cmd = [sys.executable, "bench/run.py", "--workload", workload,
           "--seed", str(2**32 + 11), "--seconds", str(seconds),
           "--trace", str(trace), "--rehearse", *extra]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 1, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


WORKLOADS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_rehearsal_reports_no_device_metric(workload):
    line, err = rehearse(trace=1, workload=workload)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True
    assert line["metrics"] == {}
    assert "busy_s" not in line["device"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert "check logit_gap" in err


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault, check", [
    ("alter_token", "logit_gap"),      # a token altered where it is produced
    ("stale_cache", "logit_gap"),      # the slot's state left unchanged
    ("drop_request", "missing"),       # an answer that never comes
])
def test_broken_timed_path_fails_a_check(fault, check, workload):
    line, _ = rehearse("--fault", fault, workload=workload)
    got = line["checks"][check]
    assert got["value"] > got["limit"]
    assert line["correct"] is False
    if fault == "drop_request":
        assert got["value"] == 1 and line["failed"] == 1


@pytest.mark.parametrize("workload", WORKLOADS)
def test_float8_control_in_the_programs_place_is_not_correct(workload):
    """The float8 control's first choices are judged by the comparison
    that decides ``correct``, on the sample the program's tokens pass."""
    line, err = rehearse("--control", "1", workload=workload)
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    assert line["correct"] is False
    own = float(err.split("the program's own logit_gap ")[1].split()[0])
    assert own <= gap["limit"]


def test_benchmark_alone_prints_no_result(tmp_path):
    """A checkout with only BENCHMARK.json and bench/ has no program to
    measure: the run fails and prints no result line."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
