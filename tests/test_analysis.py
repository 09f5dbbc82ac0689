"""agnolint: lint rules (violating + clean fixture per rule), layout
drift detection (the v5->v6 magic-bump rule), the bounded interleaving
checker (clean pass + non-vacuity via injected bugs), and regression
tests for the protocol bugs this PR's audit/model run surfaced."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import repro.analysis.model as model
from repro.analysis import check_layout, lint_paths, lint_source
from repro.analysis.layout import extract_layout, write_lock
from repro.core import Registry
from repro.core.registry import _J_PENDING

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def _rules(report):
    return sorted({f.rule for f in report.findings})


# ---------------------------------------------------------------------------
# AGNO-LOCK-001: shm stores need a write-locked context
# ---------------------------------------------------------------------------

_LOCK1_BAD = """
import numpy as np

class Thing:
    def __init__(self, shm):
        self.rows = np.frombuffer(shm.buf, dtype="u8")

    def mutate(self, i):
        self.rows[i] = 7
"""

_LOCK1_GOOD = """
import numpy as np

class Thing:
    def __init__(self, shm):
        self.rows = np.frombuffer(shm.buf, dtype="u8")

    def mutate(self, i):
        with self._locked(i):
            self.rows[i] = 7
"""


def test_lock001_unlocked_store_flagged():
    rep = lint_source(_LOCK1_BAD, "repro/core/fake.py")
    assert _rules(rep) == ["AGNO-LOCK-001"]


def test_lock001_locked_store_clean():
    rep = lint_source(_LOCK1_GOOD, "repro/core/fake.py")
    assert rep.findings == []


def test_lock001_readonly_lock_gives_no_license():
    src = _LOCK1_GOOD.replace("self._locked(i)",
                              "self._locked(i, write=False)")
    rep = lint_source(src, "repro/core/fake.py")
    assert _rules(rep) == ["AGNO-LOCK-001"]


# ---------------------------------------------------------------------------
# AGNO-LOCK-002: domain -> topic order, never topic -> domain or nested topic
# ---------------------------------------------------------------------------

_LOCK2_BAD = """
class Thing:
    def wrong(self, t):
        with self._topic_flock(t):
            with self._lock:
                pass
"""

_LOCK2_GOOD = """
class Thing:
    def right(self, t):
        with self._lock:
            with self._topic_flock(t):
                pass
"""


def test_lock002_order():
    assert _rules(lint_source(_LOCK2_BAD,
                              "repro/core/fake.py")) == ["AGNO-LOCK-002"]
    assert lint_source(_LOCK2_GOOD, "repro/core/fake.py").findings == []


# ---------------------------------------------------------------------------
# AGNO-LOCK-003: no blocking calls while any lock is held
# ---------------------------------------------------------------------------

_LOCK3_BAD = """
import time

class Thing:
    def slow(self, t):
        with self._topic_flock(t):
            time.sleep(0.1)
"""


def test_lock003_blocking_under_lock():
    assert _rules(lint_source(_LOCK3_BAD,
                              "repro/core/fake.py")) == ["AGNO-LOCK-003"]
    ok = _LOCK3_BAD.replace("            time.sleep(0.1)",
                            "            pass\n        time.sleep(0.1)")
    assert lint_source(ok, "repro/core/fake.py").findings == []


# ---------------------------------------------------------------------------
# AGNO-HOT-001/002: publish-hot-path purity (subsumes the old grep test)
# ---------------------------------------------------------------------------

def test_hot001_sleep_on_hot_path_module():
    src = "import time\n\ndef f():\n    time.sleep(1)\n"
    assert _rules(lint_source(src,
                              "repro/core/topic.py")) == ["AGNO-HOT-001"]
    # same code on a non-hot-path module is fine
    assert lint_source(src, "repro/apps/replay.py").findings == []


def test_hot002_queuefull_coupling_in_apps():
    src = "def f(e):\n    return isinstance(e, AgnocastQueueFull)\n"
    assert _rules(lint_source(src,
                              "repro/data/pipeline.py")) == ["AGNO-HOT-002"]
    assert lint_source(src, "repro/core/fake.py").findings == []


# ---------------------------------------------------------------------------
# AGNO-HOT-003: trace emit bodies stay allocation/lock/syscall-free
# ---------------------------------------------------------------------------

_HOT3_BAD = """
class TraceRing:
    def emit(self, stage, seq):
        data = {"stage": stage}
        self._pack(seq, self._mono())
"""


def test_hot003_emit_purity():
    rep = lint_source(_HOT3_BAD, "repro/obs/trace.py")
    assert _rules(rep) == ["AGNO-HOT-003"]
    ok = _HOT3_BAD.replace('        data = {"stage": stage}\n', "")
    assert lint_source(ok, "repro/obs/trace.py").findings == []


# ---------------------------------------------------------------------------
# AGNO-CNT-001: bare counters in metrics-instrumented classes
# ---------------------------------------------------------------------------

_CNT_BAD = """
from repro.obs import metrics as _metrics

class Bridge:
    def __init__(self):
        self._relayed = _metrics.counter("bridge.relayed")
        self.dropped = 0

    def on_drop(self):
        self.dropped += 1
"""


def test_cnt001_bare_counter():
    rep = lint_source(_CNT_BAD, "repro/core/fake.py")
    assert _rules(rep) == ["AGNO-CNT-001"]
    ok = _CNT_BAD.replace("self.dropped = 0",
                          'self.dropped = _metrics.counter("bridge.dropped")'
                          ).replace("self.dropped += 1",
                                    "self.dropped.inc()")
    assert lint_source(ok, "repro/core/fake.py").findings == []


# ---------------------------------------------------------------------------
# suppressions: must carry a justification, and are counted
# ---------------------------------------------------------------------------

def test_suppression_with_justification():
    src = _LOCK1_BAD.replace(
        "self.rows[i] = 7",
        "self.rows[i] = 7  # agnolint: allow[AGNO-LOCK-001] -- "
        "single-writer byte, folded under the next lock holder")
    rep = lint_source(src, "repro/core/fake.py")
    assert rep.findings == []
    assert len(rep.suppressions) == 1
    assert rep.suppressions[0].rule == "AGNO-LOCK-001"


def test_suppression_without_justification_is_a_finding():
    src = _LOCK1_BAD.replace(
        "self.rows[i] = 7",
        "self.rows[i] = 7  # agnolint: allow[AGNO-LOCK-001]")
    rep = lint_source(src, "repro/core/fake.py")
    assert "AGNO-SUPP-001" in _rules(rep)


# ---------------------------------------------------------------------------
# the real tree is clean (this is the CI gate, run in-process)
# ---------------------------------------------------------------------------

def test_real_tree_lints_clean():
    rep = lint_paths([os.path.join(SRC, "repro")], root=ROOT)
    assert rep.findings == [], [str(f) for f in rep.findings]
    # every suppression in the tree carries a justification
    assert all(s.justification for s in rep.suppressions)


def test_real_tree_layout_clean():
    assert check_layout([SRC]) == []


# ---------------------------------------------------------------------------
# layout drift: the v5->v6 rule — constants changed, magic not bumped
# ---------------------------------------------------------------------------

def _scratch_registry(tmp_path, transform):
    src = os.path.join(SRC, "repro", "core", "registry.py")
    with open(src, "r", encoding="utf-8") as fh:
        text = fh.read()
    out = tmp_path / "registry_scratch.py"
    out.write_text(transform(text))
    return str(out)


def test_layout_drift_without_magic_bump_fails(tmp_path):
    scratch = _scratch_registry(
        tmp_path, lambda t: t.replace("MAX_PUBS = 8", "MAX_PUBS = 16", 1))
    findings = check_layout([SRC], overrides={"registry": scratch})
    assert any(f.rule == "AGNO-LAYOUT-001"
               and "did not" in f.msg for f in findings), \
        [str(f) for f in findings]


def test_layout_drift_with_magic_bump_requires_lock_regen(tmp_path):
    def bump(t):
        t = t.replace("MAX_PUBS = 8", "MAX_PUBS = 16", 1)
        return t.replace("_MAGIC = 0xA6_0C_0D_06", "_MAGIC = 0xA6_0C_0D_07", 1)
    scratch = _scratch_registry(tmp_path, bump)
    findings = check_layout([SRC], overrides={"registry": scratch})
    assert any(f.rule == "AGNO-LAYOUT-001" and "regenerate" in f.msg
               for f in findings), [str(f) for f in findings]


def test_layout_lock_roundtrip(tmp_path):
    lock = tmp_path / "lock.json"
    write_lock([SRC], lock_path=str(lock))
    assert check_layout([SRC], lock_path=str(lock)) == []
    data = json.loads(lock.read_text())
    assert set(data) >= {"registry", "trace", "transport", "metrics"}


def test_layout_extraction_sees_the_real_constants():
    ext = extract_layout([SRC])
    reg = ext["registry"]["consts"]
    assert reg["MAX_SUBS"] == 64 and reg["MAX_PUBS"] == 8
    assert ext["trace"]["consts"]["REC_SIZE"] == 24


# ---------------------------------------------------------------------------
# interleaving checker: clean protocol passes, injected bugs are caught
# ---------------------------------------------------------------------------

def test_model_two_process_exhaustive():
    stats = model.explore(model.SCENARIOS["pub_take_release"])
    assert stats["terminals"] > 0 and stats["states"] > 500


def test_model_waiter_scenario_passes():
    stats = model.explore(model.SCENARIOS["waiter_wakeup"])
    assert stats["terminals"] > 0


def test_model_catches_missing_dekker_recheck():
    with pytest.raises(model.Violation) as ei:
        model.explore(model.SCENARIOS["waiter_wakeup"],
                      bug="no_dekker_recheck")
    assert ei.value.kind == "lost-wakeup"
    # the counterexample names the fast-path byte store it lost the race on
    assert any(".f_store" in s for s in ei.value.trace)


def test_model_catches_rollback_waiter_clobber():
    with pytest.raises(model.Violation) as ei:
        model.explore(model.SCENARIOS["waiter_wakeup"],
                      bug="rollback_clobbers_waiters")
    assert ei.value.kind in ("waiter-flag-lost", "lost-wakeup")
    assert any("kill(" in s for s in ei.value.trace)


def test_model_catches_a_fold_that_zeroes_every_byte():
    """A fold that zeroes every release byte, not just those it read,
    loses a fast-path release stored in between; the publish that wraps
    onto the slot is then refused, with no kill needed."""
    with pytest.raises(model.Violation) as ei:
        model.explore(model.SCENARIOS["pub_take_release"],
                      bug="fold_zeroes_all")
    assert ei.value.kind == "lost-release"
    steps = [s.rsplit(".", 1)[-1] for s in ei.value.trace]
    fold = steps.index("fold", steps.index("f_gate"))
    assert steps[fold:].index("f_store") < steps[fold:].index("fold_zero")


def test_model_cli_fast_profile():
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.model",
         "--scenario", "pub_take_release", "--json"],
        capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": SRC})
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout)
    assert out["ok"] and out["results"][0]["scenario"] == "pub_take_release"


# ---------------------------------------------------------------------------
# regression: the two real registry bugs the audit + model run found
# ---------------------------------------------------------------------------

@pytest.fixture()
def reg():
    r = Registry.create()
    yield r
    r.close()
    r.unlink()


def _dead_pid() -> int:
    p = subprocess.Popen(["true"])
    p.wait()
    return p.pid


def test_rollback_preserves_concurrent_waiter_arm(reg):
    """A publisher dying mid-transaction must not wipe another
    publisher's concurrently-armed pub_waiters flag: the restored topic
    image predates the arm, and releasers skip the slot-freed FIFO
    write when the flag reads clear — the waiter would park forever."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "arena0", depth=2)
    j = reg._journal[t]
    # a dead writer's pending topic-image transaction, captured BEFORE
    # the waiter armed (flag = 0 in the image)
    j["topic_img"] = reg.topics[t].tobytes()
    j["pid"] = _dead_pid()
    j["tidx"], j["pidx"], j["slot"] = t, p, -1
    j["has_topic"], j["has_entry"] = 1, 0
    j["state"] = _J_PENDING
    reg.set_pub_waiter(t, p, True)          # lock-free arm, after the image
    with reg._topic_flock(t):
        reg._recover(t)
    assert reg.pub_waiter(t, p), \
        "rollback clobbered a concurrently-armed waiter flag"


def test_release_notify_uses_effective_held(reg):
    """release()'s freed decision must use the EFFECTIVE held mask: a
    sibling subscriber's lock-free release byte that lands after this
    release's fold still counts toward 'slot now publishable'.  Deciding
    on the raw mask skips the owner wakeup and strands a parked waiter
    (the sibling's fast path already returned — nobody retries)."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "arena0", depth=2)
    sa = reg.add_subscriber(t, os.getpid())
    sb = reg.add_subscriber(t, os.getpid())
    seq, _ = reg.publish(t, p, 0, 8)
    assert len(reg.take(t, sa)) == 1 and len(reg.take(t, sb)) == 1

    real_fold = reg._fold_releases
    state = {"armed": False}

    def fold_then_sibling_byte(tidx, pidx):
        real_fold(tidx, pidx)
        if state["armed"]:                  # B's byte lands after the fold
            reg.entries[tidx, pidx, seq % 2]["released"][sb] = 1
            state["armed"] = False

    notified = []
    reg._fold_releases = fold_then_sibling_byte
    reg._notify_owner = lambda tidx, pidx: notified.append((tidx, pidx))
    try:
        state["armed"] = True
        reg.set_pub_waiter(t, p, True)      # forces A onto the locked path
        reg.release(t, p, sa, seq)
    finally:
        reg._fold_releases = real_fold
    assert (t, p) in notified, \
        "held->0 transition hidden by an unfolded sibling release byte"


# ---------------------------------------------------------------------------
# the CLI end-to-end (strict mode over a tiny tree + JSON artifact)
# ---------------------------------------------------------------------------

def test_agnolint_cli_strict_and_json(tmp_path):
    bad = tmp_path / "repro_fake.py"
    bad.write_text(_LOCK1_BAD)
    report = tmp_path / "report.json"
    script = os.path.join(ROOT, "scripts", "agnolint.py")
    r = subprocess.run(
        [sys.executable, script, str(bad), "--strict",
         "--json", str(report)],
        capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    data = json.loads(report.read_text())
    assert data["lint"]["counts"].get("AGNO-LOCK-001") == 1
    assert data["layout"] == []     # the real tree's layout is clean
