"""Tests for the runtime lock-order / hold-time / blocking detector
(faabric_tpu/analysis/lockcheck.py, FAABRIC_LOCKCHECK=1).

The in-process tests drive CheckedLockFactory directly (creating
checked locks without patching the global factories — installation is
process-wide and irreversible, so the full install path runs in a
subprocess instead).
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
import threading

import pytest

from faabric_tpu.analysis import lockcheck


@pytest.fixture(autouse=True)
def _fresh_state():
    """Run each test on an empty graph, then restore the pre-test state
    EXACTLY: under FAABRIC_LOCKCHECK=1 the session-wide cycle gate must
    neither lose the evidence accumulated by earlier tests nor inherit
    the inversions these tests plant on purpose."""
    st = lockcheck._state
    with st.mx:
        saved = (dict(st.edges), dict(st.same_site), list(st.blocking))
    lockcheck.reset()
    yield
    with st.mx:
        st.edges.clear()
        st.edges.update(saved[0])
        st.same_site.clear()
        st.same_site.update(saved[1])
        st.blocking[:] = saved[2]


def _locks(n: int, reentrant: bool = False):
    factory = lockcheck.CheckedLockFactory(reentrant)
    return [factory() for _ in range(n)]


def test_factory_wraps_in_scope_creations():
    (lk,) = _locks(1)
    # This file lives under tests/ → in scope → wrapped
    assert type(lk).__name__ == "_CheckedLock"
    with lk:
        assert lk.locked()
    assert not lk.locked()


def test_planted_lock_order_inversion_is_reported():
    factory = lockcheck.CheckedLockFactory(False)
    a = factory()
    b = factory()

    def t1():
        with a:
            with b:
                pass

    def t2():
        with b:
            with a:
                pass

    t1()
    th = threading.Thread(target=t2)
    th.start()
    th.join()

    rep = lockcheck.report()
    assert len(rep["cycles"]) == 1, lockcheck.format_report(rep)
    cycle = rep["cycles"][0]
    # Both acquisition stacks present: each hop names where the holder
    # acquired and the full stack of the closing acquisition
    for hop in cycle:
        assert hop["holder_acquired_at"] != "?"
        assert hop["acquisition_stack"]


def test_consistent_order_is_not_a_cycle():
    factory = lockcheck.CheckedLockFactory(False)
    a = factory()
    b = factory()
    for _ in range(3):
        with a:
            with b:
                pass
    rep = lockcheck.report()
    assert rep["cycles"] == []
    assert len(rep["edges"]) == 1


def test_rlock_reentry_is_not_same_site_nesting():
    (r,) = _locks(1, reentrant=True)
    with r:
        with r:
            pass
    rep = lockcheck.report()
    assert rep["same_site_nesting"] == []
    assert rep["cycles"] == []


def test_two_instances_from_one_site_nested_is_reported():
    a, b = _locks(2)  # one creation line → one site, two instances
    with a:
        with b:
            pass
    rep = lockcheck.report()
    # Not a provable cycle (site-keyed graph cannot order instances),
    # but named for an ordering-discipline review
    assert len(rep["same_site_nesting"]) == 1
    assert rep["cycles"] == []


def test_hold_time_histogram_lands_in_telemetry():
    from faabric_tpu.telemetry import get_metrics

    (lk,) = _locks(1)
    with lk:
        pass
    snap = get_metrics().snapshot()
    fam = snap.get("faabric_lock_hold_seconds")
    assert fam is not None and fam["series"], list(snap)
    assert any("test_lockcheck.py" in row["labels"].get("site", "")
               for row in fam["series"])


def test_condition_protocol_over_checked_rlock():
    """Condition(wrapped RLock) must fully release the lock around
    wait() — both for correctness and so the held-tracking follows."""
    factory = lockcheck.CheckedLockFactory(True)
    cv = threading.Condition(factory())
    hits = []

    def waiter():
        with cv:
            cv.wait(5.0)
            hits.append(1)

    th = threading.Thread(target=waiter)
    th.start()
    # If wait() failed to release the inner lock this would deadlock
    for _ in range(100):
        with cv:
            cv.notify_all()
        th.join(timeout=0.05)
        if not th.is_alive():
            break
    assert not th.is_alive() and hits == [1]


def test_not_installed_leaves_threading_untouched():
    if lockcheck.installed():
        pytest.skip("running under FAABRIC_LOCKCHECK=1")
    assert threading.Lock is lockcheck._orig_lock
    assert threading.RLock is lockcheck._orig_rlock
    assert not lockcheck.enabled_by_env()


def test_checked_lock_overhead_is_bounded():
    """Sanity bound, not a benchmark: a checked acquire/release pair must
    stay within interpreter noise — microseconds, not milliseconds."""
    import time as _time

    (lk,) = _locks(1)
    n = 2000
    t0 = _time.perf_counter()
    for _ in range(n):
        with lk:
            pass
    per = (_time.perf_counter() - t0) / n
    assert per < 200e-6, f"checked lock cost {per * 1e6:.1f}µs"


def test_full_install_blocking_reports_subprocess():
    """End-to-end: install() patches the factories and the blocking
    syscalls; planted sleep-under-lock and indefinite-Event.wait-under-
    lock are reported, cv.wait on the lock's own Condition is exempt."""
    planted = textwrap.dedent('''
        import threading, time
        lk = threading.Lock()
        with lk:
            time.sleep(0.01)            # planted: blocking under lock
        ev = threading.Event()
        with lk:
            ev.wait(0.01)               # planted: Event.wait under lock
        cv = threading.Condition()
        def waiter():
            with cv:
                cv.wait(1.0)            # exempt: waits on its OWN lock
        t = threading.Thread(target=waiter); t.start()
        time.sleep(0.05)
        with cv:
            cv.notify_all()
        t.join()
    ''')
    script = "\n".join([
        "import json, os",
        'os.environ["FAABRIC_LOCKCHECK"] = "1"',
        "from faabric_tpu.analysis import lockcheck",
        "lockcheck.install()",
        f"code = compile({planted!r}, 'tests/planted_blocking.py', 'exec')",
        "exec(code, {})",
        "rep = lockcheck.report()",
        "print(json.dumps({"
        "  'calls': sorted({b['call'] for b in rep['blocking_under_lock']}),"
        "  'held': [b['held'] for b in rep['blocking_under_lock']],"
        "  'cycles': len(rep['cycles'])}))",
    ])
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["calls"] == ["Event.wait", "time.sleep"]
    assert rep["cycles"] == 0
    # Every report names the planted lock's creation site
    assert all(any("planted_blocking" in s for s in held)
               for held in rep["held"])
