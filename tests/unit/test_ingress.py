"""High-QPS invocation ingress tests (ISSUE 8).

Tick batching vs the immediate-path cutover, the decision-cache
admission fast path (signature mismatches must NOT hit), group-commit
journal replay idempotence + torn-group-tail atomicity, admission
shedding (429 + Retry-After on the REST surface), and the pipelined
wire shapes (EXECUTE_BATCHES, bulk SUBMIT_BATCH, batched mappings).

All in-process and mock-mode (dispatch/mappings record instead of
dialing); the full-QPS chaos test against real processes is
tests/dist/test_chaos.py.
"""

import json
import os
import threading
import time
import types

import pytest

from faabric_tpu.batch_scheduler import get_decision_cache
from faabric_tpu.batch_scheduler.decision import NOT_ENOUGH_SLOTS
from faabric_tpu.ingress import AdmissionController, IngressShedError
from faabric_tpu.planner.planner import Planner
from faabric_tpu.proto import (
    BatchExecuteType,
    ReturnValue,
    batch_exec_factory,
)
from faabric_tpu.util.config import get_system_config
from faabric_tpu.util.testing import set_mock_mode


@pytest.fixture(autouse=True)
def _mock_and_clean():
    set_mock_mode(True)
    from faabric_tpu.planner.client import clear_mock_planner_calls
    from faabric_tpu.scheduler.function_call import clear_mock_requests
    from faabric_tpu.transport.ptp_remote import clear_sent_ptp

    clear_mock_requests()
    clear_mock_planner_calls()
    clear_sent_ptp()
    yield
    get_decision_cache().clear()
    set_mock_mode(False)
    get_system_config().reset()


def _planner(slots=64, n_hosts=2) -> Planner:
    p = Planner()
    for i in range(n_hosts):
        p.register_host(f"ing-h{i}", slots, 0)
    return p


# ---------------------------------------------------------------------------
# Tick batching vs the immediate-path cutover
# ---------------------------------------------------------------------------
def test_idle_submission_takes_immediate_path():
    p = _planner()
    try:
        d = p.ingress.submit(batch_exec_factory("u", "fn", 1), source="s")
        assert d.n_messages == 1 and d.hosts[0].startswith("ing-h")
        st = p.ingress.stats()
        assert st["immediateTotal"] == 1
        assert st["batchedTotal"] == 0 and st["ticks"] == 0
        assert st["queueDepth"] == 0  # credits released
    finally:
        p.ingress.stop()


def test_concurrent_submissions_batch_into_ticks():
    p = _planner(slots=64)
    decisions = {}
    errs = []

    # Make the overlap deterministic: mock-mode call_batch finishes
    # inside one GIL slice, so 30 barrier-released threads can fully
    # SERIALIZE — each finds the ingress idle, takes the immediate
    # path, and batchedTotal reads 0 (the 1-core full-suite flake
    # recorded at PR 16). A sleep inside call_batch releases the GIL
    # while the immediate path is held (_inline > 0), guaranteeing the
    # remaining submissions observe a busy ingress and enqueue.
    real_call_batch = p.call_batch

    def slow_call_batch(req, *a, **k):
        time.sleep(0.02)
        return real_call_batch(req, *a, **k)

    p.call_batch = slow_call_batch

    barrier = threading.Barrier(30)

    def submit(i):
        try:
            barrier.wait(timeout=10)
            decisions[i] = p.ingress.submit(
                batch_exec_factory("u", "fn", 1), source=f"s{i % 3}")
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    try:
        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(30)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert len(decisions) == 30
        assert all(d.n_messages == 1 for d in decisions.values())
        st = p.ingress.stats()
        # Overlapping submissions MUST have batched: at most a few
        # raced the idle check onto the immediate path
        assert st["batchedTotal"] >= 15
        assert st["ticks"] >= 1
        assert st["batchedTotal"] / st["ticks"] > 1.0  # real batching
        assert st["queueDepth"] == 0 and st["queuedRequests"] == 0
    finally:
        p.ingress.stop()


def test_non_batchable_requests_bypass_the_queue():
    p = _planner()
    try:
        req = batch_exec_factory("u", "mpifn", 1)
        req.messages[0].is_mpi = True
        assert not p.is_batchable_shape(req)
        d = p.ingress.submit(req, source="s")
        assert d.n_messages == 1
        st = p.ingress.stats()
        # Went straight through: neither admitted nor ticked
        assert st["admittedTotal"] == 0 and st["immediateTotal"] == 0
    finally:
        p.ingress.stop()


# ---------------------------------------------------------------------------
# Decision-cache admission fast path
# ---------------------------------------------------------------------------
def test_group_pass_uses_decision_cache_fast_path():
    p = _planner(slots=64)
    try:
        cache = get_decision_cache()
        r1 = batch_exec_factory("u", "hot", 1)
        results, deferred = p.call_batch_group([r1])
        assert not deferred and results[0] is not None
        before = cache.stats()
        assert before["misses"] >= 1  # first sighting ran the policy

        r2 = batch_exec_factory("u", "hot", 1)
        results, _ = p.call_batch_group([r2])
        after = cache.stats()
        assert after["hits"] == before["hits"] + 1
        # The cached placement was reused verbatim
        assert results[0].hosts == [
            cache.get_cached_decision(r2).hosts[0]]
    finally:
        p.ingress.stop()


def test_cache_signature_mismatch_never_hits():
    p = _planner(slots=64)
    try:
        cache = get_decision_cache()
        p.call_batch_group([batch_exec_factory("u", "sig", 2)])
        assert cache.get_cached_decision(
            batch_exec_factory("u", "sig", 2)) is not None
        # Different width, different function, different user, and a
        # different batch TYPE of the same shape: all distinct keys
        assert cache.get_cached_decision(
            batch_exec_factory("u", "sig", 3)) is None
        assert cache.get_cached_decision(
            batch_exec_factory("u", "other", 2)) is None
        assert cache.get_cached_decision(
            batch_exec_factory("v", "sig", 2)) is None
        threads = batch_exec_factory("u", "sig", 2)
        threads.type = int(BatchExecuteType.THREADS)
        assert cache.get_cached_decision(threads) is None
    finally:
        p.ingress.stop()


def test_compact_tenant_never_shares_cached_placement():
    """Compact wedges a tenant id into req.subtype and filters hosts
    running other tenants' apps; the admission fast path must honor
    both the tenant-tagged cache key and the live filter."""
    from faabric_tpu.batch_scheduler import reset_batch_scheduler

    reset_batch_scheduler("compact")
    p = _planner(slots=4, n_hosts=2)
    try:
        a = batch_exec_factory("u", "fn", 1)
        a.subtype = 1
        results, _ = p.call_batch_group([a])
        host_a = results[0].hosts[0]

        # Same user/function/width, different tenant: must not reuse
        # tenant 1's cached row — the policy places it on the OTHER host
        b = batch_exec_factory("u", "fn", 1)
        b.subtype = 2
        results, _ = p.call_batch_group([b])
        assert results[0] is not None
        assert results[0].hosts[0] != host_a
    finally:
        p.ingress.stop()
        reset_batch_scheduler()


def test_compact_filter_invalidates_stale_cache_entry():
    """A cached placement whose host has SINCE acquired another
    tenant's app must fall out of the fast path: availability alone is
    not validity — the policy's filter_hosts is part of correctness."""
    from faabric_tpu.batch_scheduler import reset_batch_scheduler

    reset_batch_scheduler("compact")
    p = _planner(slots=4, n_hosts=1)
    try:
        cache = get_decision_cache()
        a = batch_exec_factory("u", "fn", 1)
        a.subtype = 1
        results, _ = p.call_batch_group([a])
        assert results[0] is not None  # tenant 1's row cached for h0
        m = a.messages[0]
        m.return_value = int(ReturnValue.SUCCESS)
        p.set_message_results([m])  # tenant 1 leaves the host

        c = batch_exec_factory("u", "other", 1)
        c.subtype = 2
        results, _ = p.call_batch_group([c])
        assert results[0] is not None  # tenant 2 now runs on h0

        # Tenant 1 returns: its cache entry names h0, h0 has free slots,
        # but tenant 2 is in flight there — the probe must reject the
        # cached row AND the policy must refuse the host (backlogged)
        misses = cache.stats()["misses"]
        a2 = batch_exec_factory("u", "fn", 1)
        a2.subtype = 1
        results, deferred = p.call_batch_group([a2])
        assert not deferred
        assert results[0] is None
        assert cache.stats()["misses"] == misses + 1
    finally:
        p.ingress.stop()
        reset_batch_scheduler()


def test_stale_cache_capacity_falls_back_to_policy():
    p = _planner(slots=2, n_hosts=1)
    try:
        cache = get_decision_cache()
        # Prime the cache with a placement on ing-h0...
        cache.add_cached_decision(batch_exec_factory("u", "big", 2),
                                  ["ing-h0", "ing-h0"], 0)
        p.register_host("ing-roomy", 8, 0)
        # ...then shrink ing-h0 (keep-alive slot update) so the cached
        # placement no longer fits
        p.register_host("ing-h0", 1, 0)

        req = batch_exec_factory("u", "big", 2)
        results, _ = p.call_batch_group([req])
        assert results[0] is not None
        assert "ing-roomy" in set(results[0].hosts)  # policy re-placed
        assert cache.stats()["misses"] >= 1  # capacity fail = miss
    finally:
        p.ingress.stop()


# ---------------------------------------------------------------------------
# Group-commit journal
# ---------------------------------------------------------------------------
def _journaled_planner(monkeypatch, tmp_path) -> Planner:
    monkeypatch.setenv("FAABRIC_PLANNER_JOURNAL_DIR", str(tmp_path))
    monkeypatch.setenv("FAABRIC_PLANNER_RECONCILE_GRACE", "30")
    get_system_config().reset()
    return Planner()


def _fingerprint(planner) -> str:
    with planner._lock:
        return json.dumps(planner._journal_snapshot_locked(),
                          sort_keys=True, default=str)


def test_group_commit_one_record_replay_idempotent(monkeypatch, tmp_path):
    p = _journaled_planner(monkeypatch, tmp_path)
    p.register_host("h1", 64, 0)
    reqs = [batch_exec_factory("u", "fn", 1) for _ in range(8)]
    results, deferred = p.call_batch_group(reqs)
    assert not deferred and all(r is not None for r in results)
    p.flush_journal()

    from faabric_tpu.planner.journal import load_journal_dir

    _, records, meta = load_journal_dir(str(tmp_path))
    assert not meta["torn"]
    groups = [r for r in records if r["k"] == "group"]
    # ONE group-commit record holds the whole tick's app_updates
    assert len(groups) == 1 and groups[0]["n"] == 8
    assert all(s["k"] == "app_update" for s in groups[0]["recs"])
    p.close_journal()

    # Restart replay restores every app; replaying the log TWICE lands
    # in identical state (idempotence)
    p2 = _journaled_planner(monkeypatch, tmp_path)
    assert len(p2.get_in_flight_apps()) == 8
    fp2 = _fingerprint(p2)
    p2.close_journal()

    p3 = _journaled_planner(monkeypatch, tmp_path)
    snapshot, records, _ = p3._journal.replay()
    with p3._lock:
        for rec in records:
            p3._apply_journal_record_locked(rec)
    assert _fingerprint(p3) == fp2
    p3.close_journal()


def test_torn_group_tail_drops_the_whole_tick(monkeypatch, tmp_path):
    from faabric_tpu.planner.journal import (
        JOURNAL_FILE,
        load_journal_dir,
    )

    p = _journaled_planner(monkeypatch, tmp_path)
    p.register_host("h1", 64, 0)
    first = [batch_exec_factory("u", "fn", 1) for _ in range(3)]
    p.call_batch_group(first)
    p.flush_journal()
    intact_size = os.path.getsize(os.path.join(str(tmp_path),
                                               JOURNAL_FILE))
    second = [batch_exec_factory("u", "fn", 1) for _ in range(3)]
    p.call_batch_group(second)
    p.flush_journal()
    p.close_journal()

    # Crash mid-append: cut the SECOND group record in half. The CRC
    # rejects it, so the whole second tick vanishes atomically — no
    # partial application of half a tick's decisions.
    path = os.path.join(str(tmp_path), JOURNAL_FILE)
    full = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(intact_size + (full - intact_size) // 2)

    _, records, meta = load_journal_dir(str(tmp_path))
    assert meta["torn"]
    groups = [r for r in records if r["k"] == "group"]
    assert len(groups) == 1 and groups[0]["n"] == 3

    p2 = _journaled_planner(monkeypatch, tmp_path)
    replayed = set(p2.get_in_flight_apps())
    assert replayed == {r.app_id for r in first}
    assert not replayed & {r.app_id for r in second}
    p2.close_journal()


def test_journaldump_renders_and_filters_group_records(monkeypatch,
                                                       tmp_path):
    from faabric_tpu.runner import journaldump

    p = _journaled_planner(monkeypatch, tmp_path)
    p.register_host("h1", 64, 0)
    p.call_batch_group([batch_exec_factory("u", "fn", 1)
                        for _ in range(4)])
    p.flush_journal()
    p.close_journal()

    _, records, _ = journaldump.load_journal_dir(str(tmp_path))
    text = journaldump.render(records)
    assert "group" in text and "app_update" in text and "└" in text
    # --kind matches the envelope kind AND the coalesced sub-kinds
    assert journaldump.filter_kind(records, "group")
    narrowed = journaldump.filter_kind(records, "app_update")
    assert narrowed and all(s["k"] == "app_update"
                            for g in narrowed for s in g["recs"])
    assert journaldump.filter_kind(records, "result") == []


# ---------------------------------------------------------------------------
# Admission control + shedding
# ---------------------------------------------------------------------------
def test_admission_queue_bound_sheds():
    a = AdmissionController(queue_max=5, source_credits=100)
    assert a.try_admit("s1", 3).admitted
    v = a.try_admit("s1", 3)  # 6 > 5
    assert not v.admitted and v.retry_after > 0
    a.release("s1", 3)
    assert a.try_admit("s1", 5).admitted
    st = a.stats()
    assert st["shedTotal"] == 3 and st["queueDepth"] == 5


def test_admission_per_source_credit_cap():
    a = AdmissionController(queue_max=100, source_credits=4)
    assert a.try_admit("greedy", 4).admitted
    assert not a.try_admit("greedy", 1).admitted  # over its cap...
    assert a.try_admit("modest", 4).admitted      # ...others unaffected
    a.release("greedy", 4)
    assert a.try_admit("greedy", 2).admitted


def test_http_endpoint_sheds_with_429_and_retry_after():
    from faabric_tpu.endpoint.http_server import (
        HttpMessageType,
        PlannerHttpEndpoint,
    )

    p = _planner()
    try:
        # A queue bound of 1 message: a 2-message batch must shed
        p.ingress.admission = AdmissionController(queue_max=1,
                                                  source_credits=100)
        ep = PlannerHttpEndpoint(port=0, planner=p)
        req = batch_exec_factory("tenant", "fn", 2)
        body = json.dumps({
            "http_type": int(HttpMessageType.EXECUTE_BATCH),
            "payload": json.dumps(req.to_dict()),
        }).encode()
        status, payload, headers = ep.handle(body)
        assert status == 429
        out = json.loads(payload)
        assert out["retryAfterSeconds"] > 0
        assert int(headers["Retry-After"]) >= 1
        # Shed is visible on the health surface
        assert p.health_summary()["ingress"]["shedTotal"] >= 2
    finally:
        p.ingress.stop()


def test_queue_deadline_fails_unscheduled_submissions(monkeypatch):
    monkeypatch.setenv("FAABRIC_INGRESS_QUEUE_TIMEOUT", "0.3")
    monkeypatch.setenv("FAABRIC_PLANNER_TICK_MS", "5")
    get_system_config().reset()
    p = Planner()  # NO hosts: nothing can ever be placed
    try:
        req = batch_exec_factory("u", "fn", 1)
        p.ingress.submit_many([req], source="s")
        deadline = time.time() + 10
        status = p.get_batch_results(req.app_id)
        while not status.finished and time.time() < deadline:
            time.sleep(0.05)
            status = p.get_batch_results(req.app_id)
        assert status.finished
        assert all(m.return_value == int(ReturnValue.FAILED)
                   for m in status.message_results)
        assert b"Shed" in status.message_results[0].output_data
        assert p.ingress.stats()["queueDepth"] == 0  # credits released
    finally:
        p.ingress.stop()


def test_sync_waiter_gets_not_enough_slots_at_deadline(monkeypatch):
    monkeypatch.setenv("FAABRIC_PLANNER_TICK_MS", "5")
    get_system_config().reset()
    p = Planner()  # no hosts
    try:
        # Occupy the immediate path so the waiter is forced to queue
        blocker = batch_exec_factory("u", "fn", 1)
        t = threading.Thread(
            target=lambda: p.ingress.submit(blocker, timeout=1.0))
        t.start()
        d = p.ingress.submit(batch_exec_factory("u", "fn", 1),
                             timeout=0.4)
        t.join()
        assert d.app_id == NOT_ENOUGH_SLOTS
    finally:
        p.ingress.stop()


def test_tick_firing_within_waiter_grace_still_schedules(monkeypatch):
    """A tick that fires after an entry's bare deadline but before its
    sync waiter's withdraw (deadline + grace) must SCHEDULE the entry:
    shedding there would return spurious NOT_ENOUGH_SLOTS from a busy
    (not full) cluster while the caller is still happily waiting."""
    monkeypatch.setenv("FAABRIC_PLANNER_TICK_MS", "5")
    get_system_config().reset()
    p = _planner()
    stall = threading.Event()
    release = threading.Event()
    orig = p.call_batch_group

    def stalled(reqs):
        stall.set()
        release.wait(timeout=30)
        return orig(reqs)

    p.call_batch_group = stalled
    try:
        p.ingress.submit_many([batch_exec_factory("u", "fn", 1)],
                              source="s")
        assert stall.wait(timeout=10)  # tick loop now held mid-"network"
        out = {}

        def waiter():
            out["d"] = p.ingress.submit(batch_exec_factory("u", "fn", 1),
                                        source="s", timeout=0.3)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.45)  # past the 0.3s deadline, inside the 0.5s grace
        p.call_batch_group = orig
        release.set()
        t.join(timeout=10)
        d = out["d"]
        assert d is not None and d.app_id != NOT_ENOUGH_SLOTS
        assert d.n_messages == 1
    finally:
        release.set()
        p.call_batch_group = orig
        p.ingress.stop()


def test_stop_with_stalled_tick_never_resurrects_zombie_thread():
    """stop()'s 5s join can expire while a tick is stalled in network;
    a later start() + submission spawns a NEW tick thread and must not
    resurrect the zombie — it exits when its stalled call returns."""
    p = _planner()
    stall = threading.Event()
    release = threading.Event()
    orig = p.call_batch_group

    def stalled(reqs):
        stall.set()
        release.wait(timeout=30)
        return orig(reqs)

    p.call_batch_group = stalled
    try:
        req = batch_exec_factory("u", "fn", 1)
        p.ingress.submit_many([req], source="s")
        assert stall.wait(timeout=10)
        t_old = p.ingress._thread
        p.ingress.stop()  # join expires: the tick is mid-"network"
        assert t_old.is_alive()

        p.ingress.start()
        p.call_batch_group = orig
        req2 = batch_exec_factory("u", "fn", 1)
        p.ingress.submit_many([req2], source="s")
        t_new = p.ingress._thread
        assert t_new is not t_old

        release.set()
        t_old.join(timeout=10)
        assert not t_old.is_alive()  # zombie saw it lost the loop
        deadline = time.time() + 10
        while time.time() < deadline:
            if all(p.get_scheduling_decision(r.app_id) is not None
                   for r in (req, req2)):
                break
            time.sleep(0.02)
        assert p.get_scheduling_decision(req2.app_id) is not None
        assert p.ingress.stats()["tickThreadAlive"]
        # Scoped to THIS coordinator's tick name (ingress/tick@<id>):
        # under full-suite load another test's coordinator may still be
        # draining its own tick thread, which must not count here.
        ticks = [t for t in threading.enumerate()
                 if t.name == p.ingress._tick_name and t.is_alive()]
        assert ticks == [t_new]
    finally:
        release.set()
        p.call_batch_group = orig
        p.ingress.stop()


def test_executor_idle_racing_flush_does_not_repark():
    """An executor whose last batch drains concurrently with flush()
    must not re-enter the idle free-list: a later claim would hand out
    a dead executor whose pool thread already exited."""
    from faabric_tpu.proto import func_to_string
    from faabric_tpu.scheduler.scheduler import Scheduler

    s = Scheduler("idle-h", None)
    req = batch_exec_factory("u", "fn", 1)
    msg = req.messages[0]

    class StubExec:
        bound_msg = msg

        def shutdown(self):
            pass

    e = StubExec()
    func = func_to_string(msg)
    with s._lock:  # register as claim_executor's create path does
        s._executors.setdefault(func, []).append(e)
        s._parkable.add(id(e))
    s.notify_executor_idle(e)
    assert s._idle[func] == [e]  # registered executors park

    s.flush()  # clears the registry and shuts the executor down
    s.notify_executor_idle(e)  # the racing epilogue arrives late
    assert func not in s._idle


# ---------------------------------------------------------------------------
# Pipelined wire shapes
# ---------------------------------------------------------------------------
def test_execute_batches_wire_slices_per_request():
    from faabric_tpu.proto import ber_to_wire
    from faabric_tpu.scheduler.function_call import (
        FunctionCalls,
        FunctionCallServer,
    )
    from faabric_tpu.transport.message import TransportMessage

    reqs = [batch_exec_factory("u", "fn", 1) for _ in range(3)]
    for i, r in enumerate(reqs):
        r.messages[0].input_data = bytes([i]) * (i + 1)
    headers, tails = [], []
    for r in reqs:
        h, t = ber_to_wire(r)
        headers.append(h)
        tails.append(t)

    seen = []
    stub = types.SimpleNamespace(
        scheduler=types.SimpleNamespace(execute_batch=seen.append))
    msg = TransportMessage(
        code=int(FunctionCalls.EXECUTE_BATCHES),
        header={"bers": headers, "tails": [len(t) for t in tails]},
        payload=b"".join(tails))
    FunctionCallServer.do_async_recv(stub, msg)
    assert [r.app_id for r in seen] == [r.app_id for r in reqs]
    assert [r.messages[0].input_data for r in seen] == \
        [r.messages[0].input_data for r in reqs]


def test_bers_from_wire_rejects_tail_length_mismatch():
    """A frame whose declared tail lengths do not consume exactly the
    payload is corrupt and must fail at the frame level, not silently
    drop trailing bytes or error confusingly inside the last request."""
    from faabric_tpu.proto import ber_to_wire, bers_from_wire

    reqs = [batch_exec_factory("u", "fn", 1) for _ in range(2)]
    for r in reqs:
        r.messages[0].input_data = b"xy"
    pairs = [ber_to_wire(r) for r in reqs]
    headers = [h for h, _ in pairs]
    tails = [t for _, t in pairs]
    payload = b"".join(tails)
    hdr = {"bers": headers, "tails": [len(t) for t in tails]}
    assert len(bers_from_wire(hdr, payload)) == 2
    with pytest.raises(ValueError, match="payload carries"):
        bers_from_wire(hdr, payload + b"extra")
    with pytest.raises(ValueError, match="payload carries"):
        bers_from_wire({"bers": headers,
                        "tails": [len(tails[0]), len(tails[1]) + 1]},
                       payload)


def test_bulk_submit_rpc_enqueues_every_app():
    from faabric_tpu.planner.server import PlannerCalls, PlannerServer
    from faabric_tpu.proto import ber_to_wire
    from faabric_tpu.scheduler.function_call import get_batch_requests
    from faabric_tpu.transport.message import TransportMessage

    p = _planner(slots=64)
    try:
        reqs = [batch_exec_factory("u", "fn", 1) for _ in range(5)]
        headers, tails = [], []
        for r in reqs:
            h, t = ber_to_wire(r)
            headers.append(h)
            tails.append(t)
        msg = TransportMessage(
            code=int(PlannerCalls.SUBMIT_BATCH),
            header={"bers": headers, "tails": [len(t) for t in tails],
                    "host": "client"},
            payload=b"".join(tails))
        stub = types.SimpleNamespace(planner=p)
        resp = PlannerServer.do_sync_recv(stub, msg)
        assert resp.header["accepted"]

        deadline = time.time() + 10
        while time.time() < deadline:
            dispatched = {r.app_id for _, r in get_batch_requests()}
            if {r.app_id for r in reqs} <= dispatched:
                break
            time.sleep(0.02)
        assert {r.app_id for r in reqs} <= dispatched
    finally:
        p.ingress.stop()


def test_tick_mappings_and_clear_groups_are_batched():
    from faabric_tpu.transport.ptp_remote import get_sent_mappings

    p = _planner(slots=64, n_hosts=1)
    try:
        reqs = [batch_exec_factory("u", "fn", 1) for _ in range(4)]
        results, _ = p.call_batch_group(reqs)
        assert all(r is not None for r in results)
        sent = get_sent_mappings()
        # One mapping set per decision reached the host (mock mode
        # records per set; the wire carries them as ONE RPC)
        assert len(sent) == 4
        assert {m.group_id for _, m in sent} == \
            {r.group_id for r in results}
        # Completing each app coalesces its group clear per host —
        # exercised end-to-end in the chaos/bench paths; here just
        # verify results complete cleanly through the batched form
        msgs = [r.messages[0] for r in reqs]
        for m in msgs:
            m.return_value = int(ReturnValue.SUCCESS)
        p.set_message_results(msgs)
        for r in reqs:
            assert p.get_batch_results(r.app_id).finished
    finally:
        p.ingress.stop()
