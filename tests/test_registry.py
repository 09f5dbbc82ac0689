"""Registry transactionality: the kernel-module-analogue guarantees."""

import os
import stat

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import AgnocastQueueFull, Registry
from repro.core.registry import (
    _J_CLEAN,
    _J_PENDING,
    ST_FREE,
    ST_USED,
    domain_lock_path,
    fifo_dir,
    sub_fifo_path,
    topic_lock_path,
)


@pytest.fixture()
def reg():
    r = Registry.create()
    yield r
    r.close()
    r.unlink()


def test_topic_index_idempotent(reg):
    t1 = reg.topic_index("a")
    t2 = reg.topic_index("b")
    assert t1 != t2
    assert reg.topic_index("a") == t1


def test_publish_take_release_lifecycle(reg):
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "arena0", depth=4)
    s = reg.add_subscriber(t, os.getpid())
    seq, freeable = reg.publish(t, p, 100, 10)
    assert seq == 1 and freeable == []
    got = reg.take(t, s)
    assert len(got) == 1 and got[0].seq == 1 and got[0].desc_off == 100
    assert reg.take(t, s) == []  # delivered exactly once
    assert reg.reclaimable(t, p) == []  # still held
    reg.release(t, p, s, seq)
    assert reg.reclaimable(t, p) == [1]  # both counters zero -> owner may free


def test_late_subscriber_does_not_receive_old(reg):
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "arena0", depth=4)
    reg.publish(t, p, 1, 1)
    s = reg.add_subscriber(t, os.getpid())
    assert reg.take(t, s) == []  # unreceived mask snapshot at publish


def test_qos_keep_last_drops_unreceived(reg):
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=2)
    s = reg.add_subscriber(t, os.getpid())
    reg.publish(t, p, 1, 1)   # seq 1
    reg.publish(t, p, 2, 1)   # seq 2
    _, freeable = reg.publish(t, p, 3, 1)  # seq 3 evicts unreceived seq 1
    assert 1 in freeable
    got = reg.take(t, s)
    assert [e.seq for e in got] == [2, 3]
    assert reg.stats(t)["drops"][p] == 1


def test_queue_full_when_all_held(reg):
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=2)
    s = reg.add_subscriber(t, os.getpid())
    reg.publish(t, p, 1, 1)
    reg.publish(t, p, 2, 1)
    reg.take(t, s)  # subscriber now holds every ring slot
    with pytest.raises(AgnocastQueueFull):
        reg.publish(t, p, 3, 1)


def test_exclude_sub_skips_origin(reg):
    # the bridge publishes with exclude_sub=its own slot (loop prevention)
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    s_bridge = reg.add_subscriber(t, os.getpid())
    s_app = reg.add_subscriber(t, os.getpid())
    reg.publish(t, p, 1, 1, exclude_sub=s_bridge)
    assert reg.take(t, s_bridge) == []
    assert len(reg.take(t, s_app)) == 1


def test_journal_rollback_restores_before_image(reg):
    """Simulate a participant dying mid-mutation: PENDING journal from a
    dead pid must be rolled back by the next lock acquirer (§IV-B)."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    reg.publish(t, p, 123, 9)
    entry_before = reg.entries[t, p, 1 % 4].copy()
    # forge a dead writer's in-flight mutation
    j = reg._journal[0]
    j["pid"] = 2**22 + 12345  # certainly-dead pid
    j["tidx"], j["pidx"], j["slot"] = t, p, 1 % 4
    j["has_topic"], j["has_entry"] = 0, 1
    j["entry_img"] = entry_before.tobytes()
    j["state"] = _J_PENDING
    reg.entries[t, p, 1 % 4]["desc_off"] = 999  # the torn write
    # any LOCKED op triggers recovery (a v4 topic_index hit is lock-free
    # and deliberately does not recover — it never trusts torn rows)
    reg.add_subscriber(t, os.getpid())
    assert int(reg.entries[t, p, 1 % 4]["desc_off"]) == 123  # rolled back


def test_sweep_releases_dead_subscriber_refs(reg):
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    dead_pid = 2**22 + 54321
    with reg._lock:
        with reg._Txn(reg, t, topic=True):
            reg.topics[t]["sub_pids"][0] = dead_pid
            reg.topics[t]["sub_alive"] = np.uint64(1)
    reg.publish(t, p, 1, 1)
    assert reg.reclaimable(t, p) == []  # unreceived by "dead" sub
    rep = reg.sweep()
    assert rep["dead_subs"] == 1
    assert reg.reclaimable(t, p) == [1]


def test_sweep_marks_dead_publisher(reg):
    t = reg.topic_index("x")
    p = reg.add_publisher(t, 2**22 + 999, "ghost-arena", depth=4)
    rep = reg.sweep()
    assert rep["dead_pubs"] == 1
    assert "ghost-arena" in rep["orphan_arenas"]
    assert not reg.topics[t]["pub_alive"][p]


def test_attach_rejects_non_registry():
    r = Registry.create()
    try:
        import multiprocessing.shared_memory as sm

        seg = sm.SharedMemory(create=True, size=1 << 20)
        try:
            with pytest.raises(Exception):
                Registry.attach(seg.name)
        finally:
            seg.close()
            seg.unlink()
    finally:
        r.close()
        r.unlink()


# ---------------------------------------------------------------------------
# sharded metadata plane: per-topic locks + per-topic journal slots
# ---------------------------------------------------------------------------

_DEAD_PID = 2**22 + 31337  # beyond pid_max defaults: certainly not alive


def _forge_dead_writer(reg, tidx, pidx, slot):
    """Leave topic ``tidx`` looking like a writer died mid-mutation: a
    PENDING journal slot holding the before-image, plus the torn write."""
    before = reg.entries[tidx, pidx, slot].copy()
    j = reg._journal[tidx]
    j["pid"] = _DEAD_PID
    j["tidx"], j["pidx"], j["slot"] = tidx, pidx, slot
    j["has_topic"], j["has_entry"] = 0, 1
    j["entry_img"] = before.tobytes()
    j["state"] = _J_PENDING
    reg.entries[tidx, pidx, slot]["desc_off"] = 424242  # the torn write
    return before


@settings(max_examples=25, deadline=None)
@given(
    ops=st.lists(
        st.one_of(
            st.tuples(st.just("pub"), st.integers(1, 512)),
            st.tuples(st.just("take"), st.integers(0, 4)),
            st.tuples(st.just("release"), st.integers(0, 3)),
        ),
        max_size=25,
    ),
)
def test_distinct_topic_ops_never_roll_back_other_journals(ops):
    """Journal slots are per topic: any op sequence on topic B must leave a
    dead writer's PENDING journal on topic A exactly as it found it (B's
    acquirers are not A's recovery agents) — and the next op on A itself
    must then roll A back."""
    reg = Registry.create()
    try:
        ta = reg.topic_index("a")
        tb = reg.topic_index("b")
        pa = reg.add_publisher(ta, os.getpid(), "arena-a", depth=4)
        pb = reg.add_publisher(tb, os.getpid(), "arena-b", depth=4)
        sb = reg.add_subscriber(tb, os.getpid())
        reg.publish(ta, pa, 7, 1)                       # seq 1 -> slot 1
        before = _forge_dead_writer(reg, ta, pa, 1)
        journal_img = reg._journal[ta].tobytes()

        taken = []
        for kind, arg in ops:
            if kind == "pub":
                try:
                    reg.publish(tb, pb, arg, 1)
                except AgnocastQueueFull:
                    pass
            elif kind == "take":
                taken.extend(reg.take(tb, sb, limit=arg or None))
            elif kind == "release" and taken:
                e = taken.pop(arg % len(taken))
                reg.release(tb, pb, sb, e.seq)

        # topic A's pending journal and torn row are untouched by B traffic
        assert reg._journal[ta].tobytes() == journal_img
        assert int(reg.entries[ta, pa, 1]["desc_off"]) == 424242
        # ...until the next acquirer of A itself runs recovery
        reg.take(ta, reg.add_subscriber(ta, os.getpid()))
        assert int(reg._journal[ta]["state"]) == _J_CLEAN
        assert int(reg.entries[ta, pa, 1]["desc_off"]) == int(before["desc_off"])
    finally:
        reg.close()
        reg.unlink()


def test_topic_index_recovers_dead_creator(reg):
    """A creator that died mid-create leaves a torn topic row + PENDING
    journal; the next topic_index (domain lock) must roll it back before
    trusting the name scan."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    reg.publish(t, p, 55, 1)
    _forge_dead_writer(reg, t, p, 1)
    assert reg.topic_index("y") != t     # scan ran; never matched torn state
    assert int(reg._journal[t]["state"]) == _J_CLEAN   # rolled back
    assert int(reg.entries[t, p, 1]["desc_off"]) == 55


def test_lock_files_world_writable_despite_umask():
    """O_CREAT's mode is masked by umask: the chmod-after-create must leave
    both the domain and per-topic lock files attachable cross-user."""
    old = os.umask(0o077)
    try:
        reg = Registry.create()
        try:
            t = reg.topic_index("x")
            reg.add_publisher(t, os.getpid(), "a", depth=4)  # opens t-lock
            for path in (domain_lock_path(reg.name),
                         topic_lock_path(reg.name, t)):
                mode = stat.S_IMODE(os.stat(path).st_mode)
                assert mode == 0o666, f"{path}: {oct(mode)}"
        finally:
            reg.close()
            reg.unlink()
    finally:
        os.umask(old)


def test_unlink_removes_locks_and_fifo_dir(tmp_path):
    """Registry.unlink must leave nothing in /tmp: domain lock, per-topic
    locks, and the FIFO directory all go."""
    import glob

    reg = Registry.create()
    name = reg.name
    t = reg.topic_index("x")
    reg.add_publisher(t, os.getpid(), "a", depth=4)   # touches a topic lock
    os.makedirs(fifo_dir(name), exist_ok=True)
    fifo = sub_fifo_path(name, t, 0)
    os.mkfifo(fifo)
    reg.close()
    reg.unlink()
    leftovers = glob.glob(f"/tmp/.agnocast-{name}*")
    assert leftovers == [], leftovers


def test_sweep_unlinks_dead_subscriber_fifo(reg):
    """The janitor drops a dead subscriber's wakeup FIFO file along with
    its refs (no /tmp leak across runs)."""
    t = reg.topic_index("x")
    reg.add_publisher(t, os.getpid(), "a", depth=4)
    s = reg.add_subscriber(t, _DEAD_PID)   # creates the slot's FIFO file
    path = sub_fifo_path(reg.name, t, s)
    assert os.path.exists(path)
    rep = reg.sweep()
    assert rep["dead_subs"] == 1
    assert not os.path.exists(path)


# ---------------------------------------------------------------------------
# registry layout v4: seqlock reads, waiter-free release, hash lookup,
# topic generations
# ---------------------------------------------------------------------------

import threading
import time as _time

from repro.core.registry import (
    RegistryError,
    _open_and_wake,
    fifo_dir as _fifo_dir,
    pub_fifo_path,
)


def test_topic_flock_lazy_init_single_object_under_race(reg):
    """Regression (v3 bug): two threads racing the lazy per-topic lock
    open must converge on ONE _Flock — a split would leak an fd and hand
    each thread its own (useless) thread mutex."""
    results = []
    barrier = threading.Barrier(8)

    def grab():
        barrier.wait()
        results.append(reg._topic_flock(7))

    threads = [threading.Thread(target=grab) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(results) == 8
    assert len({id(r) for r in results}) == 1


def test_topic_flock_refuses_after_close():
    """close() vs a worker thread's lazy lock open: the loser must get an
    error, never a fresh fd into a closed registry (fd leak)."""
    r = Registry.create()
    try:
        r.topic_index("x")
        r.close()
        with pytest.raises(RegistryError):
            r._topic_flock(9)
    finally:
        r.unlink()


def test_open_and_wake_retries_while_reader_mid_open(tmp_path):
    """The lost-wakeup asymmetry fix: ENXIO with a live, still-interested
    peer means *mid-open*, not *gone* — the wakeup must be retried."""
    path = str(tmp_path / "f.fifo")
    os.mkfifo(path)
    fds = []

    def late_reader():
        _time.sleep(0.02)
        fds.append(os.open(path, os.O_RDONLY | os.O_NONBLOCK))

    th = threading.Thread(target=late_reader)
    th.start()
    fd = _open_and_wake(path, still_wanted=lambda: True, retry_s=1.0)
    th.join()
    assert fd is not None
    assert os.read(fds[0], 10) == b"\x01"
    os.close(fd)
    os.close(fds[0])
    # without a predicate the no-reader path still short-circuits
    path2 = str(tmp_path / "g.fifo")
    os.mkfifo(path2)
    assert _open_and_wake(path2) is None


def test_notify_owner_rechecks_armed_waiter_before_dropping(reg):
    """Owner-side mirror of the EPIPE retry: a blocked publisher mid-open
    of its slot-freed FIFO read end must still get the wakeup byte."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    os.makedirs(_fifo_dir(reg.name), exist_ok=True)
    path = pub_fifo_path(reg.name, t, p)
    try:
        os.mkfifo(path)
    except FileExistsError:
        pass
    reg.set_pub_waiter(t, p, True)
    got = []

    def late_reader():
        _time.sleep(0.02)
        got.append(os.open(path, os.O_RDONLY | os.O_NONBLOCK))

    th = threading.Thread(target=late_reader)
    th.start()
    reg._notify_owner(t, p)  # ENXIO at first: must retry, not drop
    th.join()
    assert got
    assert os.read(got[0], 10) == b"\x01"
    os.close(got[0])


def test_fast_release_is_deferred_byte_store(reg):
    """No waiter, no pending rollback: release records intent in its own
    released byte and leaves the held fold to the next lock holder."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    s = reg.add_subscriber(t, os.getpid())
    seq, _ = reg.publish(t, p, 100, 10)
    reg.take(t, s)
    reg.release(t, p, s, seq)
    e = reg.entries[t, p, seq % 4]
    assert int(e["released"][s]) == 1          # intent recorded...
    assert (int(e["held"]) >> s) & 1 == 1      # ...fold deferred
    assert reg.reclaimable(t, p) == [seq]      # lock holder folds
    assert int(e["held"]) == 0
    assert not e["released"].any()


def test_fold_keeps_a_release_stored_while_it_runs(reg, monkeypatch):
    """A fast-path release that lands while a lock holder folds is kept for
    the next fold.  Zeroed with the bytes the fold read, it was lost: the
    slot stayed held with no reference, and once the ring wrapped every
    publish into it was refused."""
    import repro.core.registry as R

    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    s = reg.add_subscriber(t, os.getpid())
    a, _ = reg.publish(t, p, 1, 1)
    b, _ = reg.publish(t, p, 2, 1)
    reg.take(t, s)
    reg.release(t, p, s, a)                    # fast path: byte store
    fold = R._rel_masks

    def racing(rel):
        out = fold(rel)
        monkeypatch.setattr(R, "_rel_masks", fold)
        reg.release(t, p, s, b)                # the subscriber, mid-fold
        return out

    monkeypatch.setattr(R, "_rel_masks", racing)
    assert reg.reclaimable(t, p) == [a]        # the fold that raced
    assert reg.reclaimable(t, p) == [b]        # b's release outlived it
    for _ in range(4):                         # the ring wraps past both
        reg.publish(t, p, 3, 1)


def test_can_publish_counts_unfolded_release_intent(reg):
    """The waiter-side re-check reads release bytes: a fast-path release
    that raced the flag arming is still visible to can_publish."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=1)
    s = reg.add_subscriber(t, os.getpid())
    seq, _ = reg.publish(t, p, 1, 1)
    reg.take(t, s)
    assert reg.can_publish(t, p) is False
    reg.release(t, p, s, seq)                  # fast path: byte store only
    assert int(reg.entries[t, p, 0]["released"][s]) == 1
    assert reg.can_publish(t, p) is True       # effective-held sees the byte


def test_release_with_armed_waiter_takes_locked_path_and_wakes(reg):
    """An armed waiter flag routes release onto the locked protocol: held
    cleared under the lock, no lingering byte, one FIFO wakeup."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=2)
    s = reg.add_subscriber(t, os.getpid())
    seq, _ = reg.publish(t, p, 1, 1)
    reg.take(t, s)
    os.makedirs(_fifo_dir(reg.name), exist_ok=True)
    path = pub_fifo_path(reg.name, t, p)
    try:
        os.mkfifo(path)
    except FileExistsError:
        pass
    rfd = os.open(path, os.O_RDONLY | os.O_NONBLOCK)
    try:
        reg.set_pub_waiter(t, p, True)
        reg.release(t, p, s, seq)
        e = reg.entries[t, p, seq % 2]
        assert int(e["held"]) == 0
        assert not e["released"].any()
        assert os.read(rfd, 10) == b"\x01"
    finally:
        os.close(rfd)


def test_seqlock_fallback_repairs_crashed_writer_parity(reg):
    """A writer that died inside its critical section leaves wseq odd.
    With its PENDING journal naming a dead pid, hint readers must take the
    locked path whose recovery repairs parity + rolls back; a bare odd
    counter (died before journaling) yields a dirty-but-bounded hint and
    is repaired by the topic's next locked op."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    reg.publish(t, p, 1, 1)
    # tier 3: wedged — PENDING journal from a dead writer
    _forge_dead_writer(reg, t, p, 1)
    reg.topics[t]["wseq"] = int(reg.topics[t]["wseq"]) + 1  # "crashed" odd
    assert reg.can_publish(t, p) is True        # locked repair, did not hang
    assert int(reg.topics[t]["wseq"]) % 2 == 0  # parity repaired
    assert int(reg._journal[t]["state"]) == _J_CLEAN
    assert int(reg.entries[t, p, 1]["desc_off"]) == 1  # torn write undone
    # tier 2: bare odd counter, clean journal — hint answers unvalidated,
    # the next locked op repairs the parity
    reg.topics[t]["wseq"] = int(reg.topics[t]["wseq"]) + 1
    assert reg.can_publish(t, p) in (True, False)   # bounded, no hang
    reg.publish(t, p, 2, 1)                         # locked op -> repair
    assert int(reg.topics[t]["wseq"]) % 2 == 0


def test_rollback_preserves_concurrent_release_intent(reg):
    """An entry before-image restore must OR-merge the current released
    bytes: a subscriber's lock-free release is never undone by somebody
    else's rollback."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    s = reg.add_subscriber(t, os.getpid())
    seq, _ = reg.publish(t, p, 123, 9)
    reg.take(t, s)                               # held by s
    slot = seq % 4
    _forge_dead_writer(reg, t, p, slot)          # before-image: held, no byte
    reg.entries[t, p, slot]["released"][s] = 1   # concurrent fast release
    # next lock holder: rollback (restores held + desc_off), merge byte, fold
    assert reg.reclaimable(t, p) == [seq]
    assert int(reg.entries[t, p, slot]["desc_off"]) == 123
    assert not reg.entries[t, p, slot]["released"].any()


def test_rollback_keeps_wseq_monotonic(reg):
    """Restoring a topic before-image must never rewind wseq (ABA: a
    reader that snapshotted the old value would validate a torn read)."""
    t = reg.topic_index("x")
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    reg.publish(t, p, 1, 1)
    w0 = int(reg.topics[t]["wseq"])              # even
    j = reg._journal[t]
    j["pid"] = _DEAD_PID
    j["tidx"], j["pidx"], j["slot"] = t, p, -1
    j["has_topic"], j["has_entry"] = 1, 0
    j["topic_img"] = reg.topics[t].tobytes()     # image carries wseq == w0
    j["state"] = _J_PENDING
    reg.topics[t]["wseq"] = w0 + 10              # later activity (even)
    reg.add_subscriber(t, os.getpid())           # locked op -> rollback
    w1 = int(reg.topics[t]["wseq"])
    assert w1 % 2 == 0
    assert w1 > w0 + 10                          # strictly advanced, never rewound


def test_seqlock_readers_never_observe_torn_rows(reg):
    """Property the whole fast plane stands on: hammer lock-free reads
    against a writer that deliberately parks the row in an inconsistent
    intermediate state inside every critical section — a validated
    snapshot must never contain it (retry/fallback instead)."""
    t = reg.topic_index("x")
    stop = threading.Event()
    errors = []

    def writer():
        try:
            while not stop.is_set():
                with reg._locked(t):
                    row = reg.topics[t]
                    row["name"] = b"TORN"        # never a valid state:
                    row["sub_alive"] = 0xDEAD    # fields mutated separately
                    _time.sleep(0)
                    row["name"] = b"x"
                    row["sub_alive"] = 0
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    def snap():
        row = reg.topics[t]
        return bytes(row["name"]).rstrip(b"\0"), int(row["sub_alive"])

    th = threading.Thread(target=writer)
    th.start()
    try:
        validated = 0
        for _ in range(3000):
            ok, val = reg._seqlock_read(t, snap)
            if ok:
                assert val == (b"x", 0), f"torn snapshot validated: {val}"
                validated += 1
    finally:
        stop.set()
        th.join()
    assert not errors
    assert validated > 0  # the fast path actually ran


def test_topic_index_hash_scales_and_is_stable(reg):
    """O(1) lookup at v4 scale: hundreds of topics resolve, stay stable
    across handles, and an unknown name still raises."""
    names = [f"scale/topic-{i}" for i in range(300)]
    idxs = [reg.topic_index(n) for n in names]
    assert len(set(idxs)) == len(names)
    assert [reg.topic_index(n) for n in names] == idxs   # fast-path hits
    other = Registry.attach(reg.name)
    try:
        assert [other.topic_index(n, create=False) for n in names] == idxs
    finally:
        other.close()
    with pytest.raises(RegistryError):
        reg.topic_index("scale/none-such", create=False)


def test_destroy_topic_recycles_with_fresh_generation(reg):
    """destroy -> recreate bumps the row generation: stale handles are
    fenced out of the recycled slot (publish raises, take empty, release
    no-op) and the dead incarnation's FIFO files are gone."""
    t = reg.topic_index("x")
    g = reg.topic_gen(t)
    p = reg.add_publisher(t, os.getpid(), "a", depth=4)
    s = reg.add_subscriber(t, os.getpid())
    seq, _ = reg.publish(t, p, 1, 1, gen=g)
    fifo = sub_fifo_path(reg.name, t, s)
    assert os.path.exists(fifo)
    assert reg.destroy_topic("x") is True
    assert not os.path.exists(fifo)              # recycled slot: fresh inodes
    with pytest.raises(RegistryError):
        reg.topic_index("x", create=False)       # tombstoned
    t2 = reg.topic_index("x")                    # recreate (lowest free row)
    assert t2 == t
    g2 = reg.topic_gen(t2)
    assert g2 == g + 1
    # the new tenant's plane, with a stale handle poking at it
    p2 = reg.add_publisher(t2, os.getpid(), "b", depth=4)
    s2 = reg.add_subscriber(t2, os.getpid())
    seq2, _ = reg.publish(t2, p2, 7, 1, gen=g2)
    got = reg.take(t2, s2, gen=g2)
    assert [e.seq for e in got] == [seq2]
    with pytest.raises(RegistryError):
        reg.publish(t, p, 9, 1, gen=g)           # stale gen: rejected
    assert reg.take(t, s, gen=g) == []           # stale gen: nothing
    reg.release(t, p2, s2, seq2, gen=g)          # stale gen: must not touch
    assert reg.reclaimable(t2, p2) == []         # s2's ref survived intact
    reg.release(t2, p2, s2, seq2, gen=g2)
    assert reg.reclaimable(t2, p2) == [seq2]
