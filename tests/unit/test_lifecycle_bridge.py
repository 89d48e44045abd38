"""The lifecycle ledger's bridge into the JAX profiler (ISSUE 25): the one
boundary helper ``phase_span``, the two keys the ledger gained (``hin``,
``rcu``), and what derives from the helper's clock reads. Nothing here
asserts a timing: every check is an identity between numbers that must
come from the same clock read, or the presence of a span."""

import glob
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from faabric_tpu.executor import executor as executor_module
from faabric_tpu.executor.executor import ExecutorTask
from faabric_tpu.proto import (
    Message,
    ReturnValue,
    batch_exec_factory,
    message_factory,
    messages_from_wire,
    messages_to_wire,
)
from faabric_tpu.telemetry.lifecycle import (
    NULL_LIFECYCLE,
    PHASE_ADMIT,
    PHASE_DISPATCH,
    PHASE_EXEC_QUEUE_EXIT,
    PHASE_HTTP_IN,
    PHASE_QUEUE_EXIT,
    PHASE_RUN_CPU,
    PHASE_RUN_END,
    PHASE_RUN_START,
    PHASE_STATE_ACC,
    RUN_CPU_LABEL,
    Lifecycle,
    LifecycleStats,
    ledger_durations,
    ledger_run_cpu_s,
    ledger_span_s,
    ledger_stamps,
)
from tests.unit.test_execution_e2e import EchoExecutor, EchoFactory

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GET_AVAILABLE_HOSTS, EXECUTE_BATCH, EXECUTE_BATCH_STATUS = 5, 10, 11


def host_spans(trace_dir):
    """name → [(stats, start_ns, duration_ns)] of the ``faabric:*`` events
    in the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    found: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("faabric:"):
                    found.setdefault(e.name, []).append(
                        (dict(e.stats), e.start_ns, e.duration_ns))
    return found


# ---------------------------------------------------------------------------
# The helper alone
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("session", [False, True])
def test_phase_span_stamps_both_ends_from_its_own_two_reads(session,
                                                            tmp_path):
    import jax

    msg = message_factory("u", "f")
    msg.lc["adm"] = 5
    if session:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with Lifecycle.phase_span(msg, "aaa", "bbb", "unit",
                                  cpu_phase=PHASE_RUN_CPU) as ps:
            assert msg.lc["aaa"] == ps.start_ns and "bbb" not in msg.lc
    finally:
        if session:
            jax.profiler.stop_trace()
    assert msg.lc["bbb"] == ps.end_ns >= ps.start_ns > 0
    assert msg.lc[PHASE_RUN_CPU] >= 0
    if session:
        (stats, _start, _dur), = host_spans(str(tmp_path))["faabric:unit"]
        # the ledger as it stood at entry, and the clock tie
        assert stats == {"msg_id": f"m{msg.id}", "mono_ns": ps.start_ns,
                         "adm": 5, "aaa": ps.start_ns}


def test_a_boundary_left_to_the_neighbouring_span_is_not_stamped():
    msg = message_factory("u", "f")
    with Lifecycle.phase_span(msg, "aaa", None, "unit"):
        pass
    with Lifecycle.phase_span(msg, None, None, "unit"):
        pass
    assert set(msg.lc) == {"aaa"}


def test_a_jax_that_another_thread_is_still_importing_is_left_alone(
        monkeypatch):
    """A guest's first lazy ``import jax`` on one pool thread puts the
    modules into ``sys.modules`` before their bodies have run; a task on
    another thread must neither fail nor wait on that."""
    import types

    monkeypatch.setitem(sys.modules, "jax.profiler",
                        types.ModuleType("jax.profiler"))
    msg = message_factory("u", "f")
    with Lifecycle.phase_span(msg, "aaa", "bbb", "unit") as ps:
        pass
    assert (msg.lc["aaa"], msg.lc["bbb"]) == (ps.start_ns, ps.end_ns)


def test_with_the_plane_off_only_the_two_clock_reads_are_left():
    msg = message_factory("u", "f")
    with NULL_LIFECYCLE.phase_span(msg, "aaa", "bbb", "unit",
                                   cpu_phase=PHASE_RUN_CPU) as ps:
        pass
    assert msg.lc == {}
    assert ps.end_ns >= ps.start_ns > 0
    NULL_LIFECYCLE.backdate([msg], PHASE_HTTP_IN, 7)
    assert msg.lc == {}


# ---------------------------------------------------------------------------
# hin and rcu in the ledger
# ---------------------------------------------------------------------------

def full_ledger():
    return {PHASE_HTTP_IN: 1_000, PHASE_ADMIT: 1_400, PHASE_QUEUE_EXIT: 1_500,
            PHASE_DISPATCH: 2_000, PHASE_EXEC_QUEUE_EXIT: 2_600,
            PHASE_RUN_START: 2_700, PHASE_RUN_END: 9_700,
            PHASE_RUN_CPU: 3_000, PHASE_STATE_ACC: 1_000}


def test_hin_and_rcu_ride_the_wire_and_stay_out_of_the_stamp_walk():
    msg = message_factory("u", "f")
    msg.lc.update(full_ledger())
    dicts, tail = messages_to_wire([msg])
    assert dicts[0] == msg.to_wire_dict()
    back = messages_from_wire(dicts, tail)[0]
    assert back.lc == msg.lc
    assert Message.from_dict(msg.to_dict()).lc == msg.lc

    keys = [k for _t, k in ledger_stamps(back.lc)]
    assert keys == [PHASE_HTTP_IN, PHASE_ADMIT, PHASE_QUEUE_EXIT,
                    PHASE_DISPATCH, PHASE_EXEC_QUEUE_EXIT, PHASE_RUN_START,
                    PHASE_RUN_END]
    durations = ledger_durations(back.lc)
    assert durations["http_in"] == pytest.approx(400e-9)
    # the partition of the span: state carved out of run, rcu no part
    assert RUN_CPU_LABEL not in durations
    assert durations["state"] + durations["run"] == pytest.approx(7_000e-9)
    assert sum(durations.values()) == pytest.approx(ledger_span_s(back.lc))
    assert ledger_span_s(back.lc) == pytest.approx(8_700e-9)
    assert ledger_run_cpu_s(back.lc) == pytest.approx(3_000e-9)
    assert ledger_run_cpu_s({}) is None


def test_backdate_is_a_first_write():
    a, b = message_factory("u", "f"), message_factory("u", "f")
    a.lc[PHASE_HTTP_IN] = 3
    Lifecycle.backdate([a, b], PHASE_HTTP_IN, 9)
    assert (a.lc[PHASE_HTTP_IN], b.lc[PHASE_HTTP_IN]) == (3, 9)


def test_the_fold_reports_run_cpu_beside_run_and_never_as_dominant():
    stats = LifecycleStats(half_life=60.0)
    msg = message_factory("u", "f")
    msg.lc.update(full_ledger(), rec=10_000)
    msg.return_value = int(ReturnValue.SUCCESS)
    stats.fold([msg])
    snap = stats.snapshot()
    assert {"http_in", "run", "state", RUN_CPU_LABEL} <= set(snap["phases"])
    assert RUN_CPU_LABEL not in [d["phase"] for d in snap["dominant_p99"]]
    assert snap["dominant_p99"][0]["phase"] == "run"


def test_the_timeline_keeps_the_duration_keys_off_its_clock():
    from faabric_tpu.runner.timeline import _msg_rows, render_text

    rows = _msg_rows({"messageResults": [{"id": 1, "lc": full_ledger()}]})
    assert (rows[0]["t0"], rows[0]["t1"]) == (1_000, 9_700)
    text = render_text(1, rows)
    assert "http_in=" in text and "on the CPU: 0.003ms" in text


# ---------------------------------------------------------------------------
# The executor's boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plane_on", [True, False])
def test_histograms_and_exec_graph_come_from_the_helpers_reads(
        plane_on, monkeypatch):
    """queue_us / exec_us and the two histograms equal the ledger's gaps:
    no clock is read a second time for a boundary."""
    monkeypatch.setattr(executor_module, "_LC",
                        Lifecycle() if plane_on else NULL_LIFECYCLE)
    req = batch_exec_factory("demo", "echo", 1)
    msg = req.messages[0]
    msg.input_data = b"abc"
    ex = EchoExecutor(msg)
    assert ex.try_claim()
    ex._tasks_outstanding = 1
    task = ExecutorTask(0, req)
    run_h, queue_h = (executor_module._RUN_SECONDS,
                      executor_module._QUEUE_WAIT_SECONDS)
    before = (run_h.sum, run_h.count, queue_h.sum, queue_h.count)
    ex._run_task(0, task)
    assert msg.return_value == int(ReturnValue.SUCCESS)
    assert msg.output_data == b"cba"
    graph = msg.int_exec_graph_details
    assert (run_h.count, queue_h.count) == (before[1] + 1, before[3] + 1)
    if not plane_on:
        assert msg.lc == {}
        assert graph["queue_us"] >= 0 and graph["exec_us"] >= 0
        return
    lc = msg.lc
    run_ns = lc[PHASE_RUN_END] - lc[PHASE_RUN_START]
    queue_ns = lc[PHASE_EXEC_QUEUE_EXIT] - task.enqueue_ns
    assert graph == {"queue_us": queue_ns // 1000, "exec_us": run_ns // 1000}
    assert run_h.sum - before[0] == pytest.approx(run_ns / 1e9, abs=1e-12)
    assert queue_h.sum - before[2] == pytest.approx(queue_ns / 1e9,
                                                    abs=1e-12)
    assert 0 <= lc[PHASE_RUN_CPU]
    assert lc[PHASE_EXEC_QUEUE_EXIT] <= lc[PHASE_RUN_START]


@pytest.fixture
def rest_cluster():
    """Planner server, its REST endpoint object and one worker in this
    process, every RPC over real sockets."""
    from faabric_tpu.endpoint import PlannerHttpEndpoint
    from faabric_tpu.executor import set_executor_factory
    from faabric_tpu.planner import PlannerServer, get_planner
    from faabric_tpu.runner import WorkerRuntime
    from faabric_tpu.transport.common import register_host_alias
    from tests.conftest import next_port_base

    base = next_port_base()
    register_host_alias("planner", "127.0.0.1", base)
    register_host_alias("brA", "127.0.0.1", base + 1000)
    get_planner().reset()
    planner_server = PlannerServer(port_offset=base)
    planner_server.start()
    set_executor_factory(EchoFactory())
    w = WorkerRuntime(host="brA", slots=4, planner_host="planner")
    w.start()

    yield PlannerHttpEndpoint(port=0)

    w.shutdown()
    planner_server.stop()
    get_planner().reset()
    set_executor_factory(None)


def invoke_over_rest(endpoint, n: int = 1) -> list:
    """One EXECUTE_BATCH through the endpoint's handler, polled to its
    end; the messages' results as the REST status gives them."""
    req = batch_exec_factory("demo", "echo", n)
    for m in req.messages:
        m.input_data = b"abc"
    status, _out, _h = endpoint.handle(json.dumps({
        "http_type": EXECUTE_BATCH,
        "payload": json.dumps(req.to_dict())}).encode())
    assert status == 200, _out
    poll = json.dumps({"http_type": EXECUTE_BATCH_STATUS,
                       "payload": json.dumps({"app_id": req.app_id})})
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        status, out, _h = endpoint.handle(poll.encode())
        got = json.loads(out)
        if got["finished"] and len(got["messageResults"]) == n:
            return got["messageResults"]
        time.sleep(0.01)
    raise AssertionError("the invocation never finished")


def test_rest_invocation_carries_hin_first_and_rcu(rest_cluster):
    result, = invoke_over_rest(rest_cluster)
    lc = result["lc"]
    assert ledger_stamps(lc)[0][1] == PHASE_HTTP_IN
    assert lc[PHASE_HTTP_IN] <= lc[PHASE_ADMIT] <= lc[PHASE_QUEUE_EXIT]
    assert "http_in" in ledger_durations(lc)
    assert 0 <= lc[PHASE_RUN_CPU]
    assert lc[PHASE_RUN_CPU] / 1e9 <= ledger_durations(lc)["run"] + 1e-3
    # the planner folded both
    from faabric_tpu.planner import get_planner

    phases = get_planner().health_summary()["lifecycle"]["phases"]
    assert {"http_in", RUN_CPU_LABEL} <= set(phases)


def test_under_a_profiler_session_the_invocation_leaves_its_three_spans(
        rest_cluster, tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        result, = invoke_over_rest(rest_cluster)
    finally:
        jax.profiler.stop_trace()
    spans = host_spans(str(tmp_path))
    lc, msg_id = result["lc"], f"m{result['id']}"
    mine = {name: [s for s in found if s[0]["msg_id"] == msg_id]
            for name, found in spans.items()}
    assert {name: len(found) for name, found in mine.items()} == {
        "faabric:run_prep": 1, "faabric:run": 1, "faabric:result_push": 1}
    prep, run, push = (mine[f"faabric:{label}"][0]
                       for label in ("run_prep", "run", "result_push"))
    # the upstream stamps, the planner's among them, ride every span, and
    # each span has those the worker made before it opened
    upstream = (PHASE_HTTP_IN, PHASE_ADMIT, PHASE_QUEUE_EXIT, PHASE_DISPATCH)
    for stats, _start, _dur in (prep, run, push):
        assert all(stats[k] == lc[k] for k in upstream)
    assert prep[0]["mono_ns"] == prep[0][PHASE_EXEC_QUEUE_EXIT] \
        == lc[PHASE_EXEC_QUEUE_EXIT]
    assert PHASE_RUN_START not in prep[0]
    assert run[0]["mono_ns"] == run[0][PHASE_RUN_START] == lc[PHASE_RUN_START]
    assert push[0][PHASE_RUN_END] == lc[PHASE_RUN_END]
    assert push[0][PHASE_RUN_CPU] == lc[PHASE_RUN_CPU]
    # one clock tie serves all three: the spans lie on the profiler's
    # clock in the ledger's order
    assert prep[1] <= run[1] <= run[1] + run[2] <= push[1]


# ---------------------------------------------------------------------------
# The planner process stays JAX-free
# ---------------------------------------------------------------------------

def test_a_planner_process_has_loaded_no_jax_after_an_invocation():
    """``python -m faabric_tpu.runner planner`` and one worker process;
    one invocation over REST; then the planner's memory map holds nothing
    of jaxlib (importing jax maps its shared objects, as this process's
    own map shows)."""
    import jax  # noqa: F401 — this process: the positive control

    from faabric_tpu.util.network import get_free_port
    from tests.conftest import next_port_base

    with open("/proc/self/maps") as f:
        assert "jaxlib" in f.read()

    base = next_port_base()
    http_port = get_free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               FAABRIC_HOST_ALIASES=(f"brp=127.0.0.1+{base},"
                                     f"brw=127.0.0.1+{base + 1000}"))
    planner = subprocess.Popen(
        [sys.executable, "-m", "faabric_tpu.runner", "planner",
         "--port-offset", str(base), "--http-port", str(http_port)],
        cwd=REPO, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    worker = None

    def post(http_type, payload=""):
        body = json.dumps({"http_type": http_type,
                           "payload": payload}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{http_port}/", data=body,
                method="POST"), timeout=10) as resp:
            return json.loads(resp.read())

    def wait_for(what, ready):
        deadline = time.monotonic() + 30
        while True:
            try:
                if ready():
                    return
            except OSError:  # the REST endpoint is not listening yet
                pass
            assert time.monotonic() < deadline, f"never saw {what}"
            time.sleep(0.05)

    try:
        # The REST endpoint starts after the planner's RPC server, and a
        # worker that finds no planner to register with exits at once
        wait_for("the planner's REST endpoint",
                 lambda: post(GET_AVAILABLE_HOSTS)["hosts"] == [])
        worker = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "dist", "procs.py"),
             "worker", "brw", "brp", "2"], cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True)
        assert worker.stdout.readline().strip() == "READY"
        wait_for("the worker among the planner's hosts", lambda: [
            h["ip"] for h in post(GET_AVAILABLE_HOSTS)["hosts"]] == ["brw"])
        req = batch_exec_factory("dist", "noop", 1)
        post(EXECUTE_BATCH, json.dumps(req.to_dict()))
        status = {}

        def finished():
            status.update(post(EXECUTE_BATCH_STATUS,
                               json.dumps({"app_id": req.app_id})))
            return status["finished"]

        wait_for("the invocation's end", finished)
        result, = status["messageResults"]
        assert result["return_value"] == int(ReturnValue.SUCCESS)
        assert PHASE_HTTP_IN in result["lc"] and PHASE_RUN_CPU in result["lc"]
        with open(f"/proc/{planner.pid}/maps") as f:
            assert "jaxlib" not in f.read()
    finally:
        procs = [p for p in (worker, planner) if p is not None]
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        if worker is not None:
            worker.stdout.close()
