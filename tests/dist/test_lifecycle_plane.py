"""Distributed acceptance for the invocation lifecycle plane
(ISSUE 14): a real planner + two worker processes under concurrent
bulk-submitted load, with a planted ``executor.run=delay`` fault so one
phase demonstrably dominates.

Asserts that every SUCCESS invocation's phase ledger spans ≥90% of its
measured end-to-end wall (test-clock submit → client-stamped waiter
wake), that ``GET /timeseries`` shows a nonzero ingress-depth series,
that the declared ``FAABRIC_SLO`` burns (and surfaces on /healthz),
that the doctor's dominant-phase finding names the inflated ``run``
phase, that the timeline CLI renders one app's cross-host ledger, and
that the live ``GET /flight`` rings merge through ``flightdump --url``.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from faabric_tpu.proto import ReturnValue, batch_exec_factory
from faabric_tpu.telemetry.lifecycle import (
    PHASE_ADMIT,
    PHASE_DISPATCH,
    PHASE_EXEC_QUEUE_EXIT,
    PHASE_QUEUE_EXIT,
    PHASE_RECORDED,
    PHASE_RESULT_PUSH,
    PHASE_RUN_END,
    PHASE_RUN_START,
    PHASE_SCHED,
    PHASE_WAITER_WAKE,
    ledger_durations,
    ledger_span_s,
)

PROCS = os.path.join(os.path.dirname(__file__), "procs.py")

# The plant has to outweigh busy neighbours: the digest's quantiles are
# log-bucketed (edges at powers of √2), and at 0.2 s a loaded host's
# executor-queue p99 reached run's own bucket and tied with it for the
# dominant phase. 0.6 s sits two buckets higher, mid-bucket.
RUN_DELAY_S = 0.6
N_THREADS = 3
BULK = 10       # per submit RPC: the pre-admit client serialization of
BULKS = 4       # the frame is the one unledgerable head, kept small
PER_THREAD = BULK * BULKS
# Phase-A concurrency (120 messages) stays inside the 2×64 slot pool so
# the planted run delay — not the admission queue — dominates the p99;
# phase B then deliberately floods the queue for the trend assertions.
BURST = 400


@pytest.fixture(scope="module")
def lifecycle_cluster():
    """Planner + two 64-slot workers, every executor run inflated by a
    planted 600 ms delay fault; this process is a 0-slot client host."""
    from faabric_tpu.util.network import get_free_port
    from tests.conftest import next_port_base

    base = next_port_base()
    # Every port inside the one slot next_port_base() gave, the
    # planner's too (+1600 lies clear of the hosts' service and MPI
    # ranges): the default planner ports are test_multiprocess's, and
    # xdist runs modules side by side
    aliases = (f"lfw1=127.0.0.1+{base},lfw2=127.0.0.1+{base + 1000},"
               f"lfcli=127.0.0.1+{base + 2000},"
               f"lfpl=127.0.0.1+{base + 1600}")
    http_port = get_free_port()
    w1_http = get_free_port()
    common = dict(
        os.environ,
        FAABRIC_HOST_ALIASES=aliases,
        JAX_PLATFORMS="cpu",
        DIST_HTTP_PORT=str(http_port),
        # The planted dominant phase: every guest run pays RUN_DELAY_S
        FAABRIC_FAULTS=f"executor.run=delay:{int(RUN_DELAY_S * 1e3)}ms",
        # Fast sampling so the burst's queue depth is captured
        FAABRIC_TIMESERIES_INTERVAL_S="0.05",
        # An SLO the planted runs must burn (5 ms p99 target)
        FAABRIC_SLO="p99_e2e_ms=5,error_rate=0.01",
        FAABRIC_SLO_WINDOWS="10,30",
    )
    procs = []

    def spawn(env, *args):
        p = subprocess.Popen([sys.executable, PROCS, *args],
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True,
                             env=env)
        procs.append(p)
        return p

    def await_ready(p):
        for _ in range(100):
            line = p.stdout.readline()
            if not line:
                break
            if line.strip() == "READY":
                return
        raise AssertionError("child never printed READY")

    try:
        planner = spawn(common, "planner", str(base + 1600))
        await_ready(planner)
        w1 = spawn({**common, "WORKER_HTTP_PORT": str(w1_http)},
                   "worker", "lfw1", "lfpl", "64")
        w2 = spawn(common, "worker", "lfw2", "lfpl", "64")
        for p in (w1, w2):
            await_ready(p)
    except BaseException:
        for p in procs:
            p.kill()
            p.wait(timeout=5)
            if p.stdout is not None:
                p.stdout.close()
        raise
    from tests.dist.test_multiprocess import drain_stdout

    for p in procs:
        drain_stdout(p)

    from faabric_tpu.executor import ExecutorFactory
    from faabric_tpu.runner import WorkerRuntime
    from faabric_tpu.transport.common import clear_host_aliases

    os.environ["FAABRIC_HOST_ALIASES"] = aliases
    clear_host_aliases()

    class NullFactory(ExecutorFactory):
        def create_executor(self, msg):
            raise RuntimeError("client runs nothing")

    me = WorkerRuntime(host="lfcli", slots=0, factory=NullFactory(),
                       planner_host="lfpl")
    me.start()
    me.dist_http_port = http_port
    me.w1_http_port = w1_http

    yield me

    me.shutdown()
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()
        if p.stdout is not None:
            p.stdout.close()
    os.environ.pop("FAABRIC_HOST_ALIASES", None)
    clear_host_aliases()


def _get(base: str, path: str):
    with urllib.request.urlopen(f"{base}{path}", timeout=20) as resp:
        return json.loads(resp.read().decode())


def test_dist_lifecycle_ledger_timeseries_slo_and_doctor(
        lifecycle_cluster):
    me = lifecycle_cluster
    base = f"http://127.0.0.1:{me.dist_http_port}"
    client = me.planner_client

    # -- concurrent bulk-submitted load --------------------------------
    # N_THREADS × BULK single-message noop apps, fire-and-forget, then
    # every thread blocks on its own results — the waiter-wake stamp is
    # therefore the PUSH arrival, an honest end-of-life mark.
    per_thread: list[list] = [[] for _ in range(N_THREADS)]
    walls: list[list] = [[] for _ in range(N_THREADS)]
    errors: list[str] = []

    def submitter(ti: int) -> None:
        try:
            submitted = []
            for _ in range(BULKS):
                reqs = [batch_exec_factory("dist", "noop", 1)
                        for _ in range(BULK)]
                t0 = time.monotonic()
                accepted, retry = client.submit_functions_many(reqs)
                assert accepted, f"bulk shed (retry {retry})"
                submitted.append((t0, reqs))
            for t0, reqs in submitted:
                for req in reqs:
                    msg = client.get_message_result(
                        req.app_id, req.messages[0].id, timeout=90.0)
                    per_thread[ti].append(msg)
                    walls[ti].append(t0)
        except Exception as e:  # noqa: BLE001 — report to the test
            errors.append(f"{ti}: {e!r}")

    threads = [threading.Thread(target=submitter, args=(i,))
               for i in range(N_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors

    # -- acceptance: every SUCCESS ledger spans ≥90% of its wall -------
    required = (PHASE_ADMIT, PHASE_QUEUE_EXIT, PHASE_SCHED,
                PHASE_DISPATCH, PHASE_EXEC_QUEUE_EXIT, PHASE_RUN_START,
                PHASE_RUN_END, PHASE_RESULT_PUSH, PHASE_RECORDED,
                PHASE_WAITER_WAKE)
    low_coverage = []
    for ti in range(N_THREADS):
        for msg, t0 in zip(per_thread[ti], walls[ti]):
            assert msg.return_value == int(ReturnValue.SUCCESS), \
                msg.output_data
            lc = msg.lc
            missing = [p for p in required if p not in lc]
            assert not missing, (missing, sorted(lc))
            durations = ledger_durations(lc)
            # The planted fault sits inside the run phase
            assert durations["run"] >= RUN_DELAY_S * 0.9, durations
            # Measured e2e wall: test-clock submit → the client-side
            # waiter-wake stamp (same CLOCK_MONOTONIC)
            wall = lc[PHASE_WAITER_WAKE] / 1e9 - t0
            span = ledger_span_s(lc)
            assert wall > 0
            if span < 0.9 * wall:
                low_coverage.append((msg.id, span, wall))
    assert not low_coverage, (
        f"{len(low_coverage)} invocation(s) under 90% ledger coverage: "
        f"{low_coverage[:5]}")

    # -- healthz: lifecycle digest + burning SLO -----------------------
    health = _get(base, "/healthz")
    lifecycle = health["lifecycle"]
    assert lifecycle["count"] >= N_THREADS * PER_THREAD
    assert lifecycle["dominant_p99"][0]["phase"] == "run", \
        lifecycle["dominant_p99"][:3]
    slo = health["slo"]
    latency = [t for t in slo["targets"] if t["name"] == "p99_e2e_ms"][0]
    assert latency["burning"], latency
    error_t = [t for t in slo["targets"] if t["name"] == "error_rate"][0]
    assert not error_t["burning"], error_t

    # -- /metrics: lifecycle histograms + process gauges ---------------
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
        metrics_text = resp.read().decode()
    assert "faabric_lifecycle_phase_seconds" in metrics_text
    assert 'phase="run"' in metrics_text
    assert "faabric_process_rss_bytes" in metrics_text
    assert "faabric_slo_burn_rate" in metrics_text

    # -- doctor: the dominant-phase finding names 'run' ----------------
    from faabric_tpu.runner.doctor import diagnose, fetch_live

    findings = diagnose(fetch_live(base))
    dominant = [f for f in findings if f["kind"] == "dominant_phase"]
    assert dominant, [f["kind"] for f in findings]
    assert "'run'" in dominant[0]["subject"], dominant[0]
    assert any(f["kind"] == "slo_burn" for f in findings), \
        [f["kind"] for f in findings]

    # -- timeline CLI renders one app's cross-host ledger --------------
    from faabric_tpu.runner.timeline import (
        _msg_rows,
        fetch_status,
        render_text,
    )

    app_id = per_thread[0][-1].app_id
    rows = _msg_rows(fetch_status(base, app_id))
    assert rows, f"timeline found no ledgers for app {app_id}"
    text = render_text(app_id, rows)
    assert "run=" in text and "ingress_queue=" in text

    # -- phase B: flood the admission queue, then read the trend -------
    # 400 messages against 128 slots of 600 ms runs: the backlog holds
    # admission credits for ≥1 s, so the 50 ms sampler must catch a
    # nonzero ingress-depth series.
    base_results = health["resultsTotal"]
    reqs = [batch_exec_factory("dist", "noop", 1) for _ in range(BURST)]
    accepted, retry = client.submit_functions_many(reqs)
    assert accepted, f"burst shed (retry {retry})"
    deadline = time.time() + 120
    while time.time() < deadline:
        done = _get(base, "/healthz")["resultsTotal"] - base_results
        if done >= BURST:
            break
        time.sleep(0.2)
    assert done >= BURST, f"burst incomplete: {done}/{BURST}"

    ts = _get(base, "/timeseries")
    planner_series = (ts["hosts"].get("planner") or {}).get("series") or {}
    depth = planner_series.get("ingress_depth") or []
    assert depth, f"no ingress_depth series: {sorted(planner_series)}"
    assert max(v for _t, v in depth) > 0, depth[-10:]
    # worker rings merged too, with the process resource series
    for host in ("lfw1", "lfw2"):
        series = (ts["hosts"].get(host) or {}).get("series") or {}
        assert series.get("proc_rss_bytes"), (host, sorted(series))


def test_dist_flight_endpoints_and_flightdump_url(lifecycle_cluster):
    me = lifecycle_cluster
    base = f"http://127.0.0.1:{me.dist_http_port}"
    worker_base = f"http://127.0.0.1:{me.w1_http_port}"

    # Live rings served by planner AND worker HTTP endpoints
    planner_ring = _get(base, "/flight")
    assert planner_ring["ring_size"] > 0
    # The SLO burn from the load test left a flight record
    kinds = {e["kind"] for e in planner_ring["events"]}
    assert "slo_burn" in kinds, sorted(kinds)

    worker_ring = _get(worker_base, "/flight")
    assert worker_ring["process"].startswith("worker-")
    assert isinstance(worker_ring["events"], list)

    # Worker-local /metrics and /timeseries answer without the planner
    with urllib.request.urlopen(f"{worker_base}/metrics",
                                timeout=10) as resp:
        text = resp.read().decode()
    assert "faabric_process_rss_bytes" in text
    wts = _get(worker_base, "/timeseries")
    assert wts["series"].get("proc_rss_bytes")

    # flightdump --url merges the live rings onto one timeline
    from faabric_tpu.runner.flightdump import fetch_live_rings, merge_dumps

    dumps = fetch_live_rings([base, worker_base])
    assert len(dumps) == 2
    events = merge_dumps(dumps)
    assert any(e["kind"] == "slo_burn" for e in events)
    assert all(e.get("dump_reason") == "live" for e in events)
