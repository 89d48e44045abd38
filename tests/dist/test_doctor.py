"""Distributed acceptance for the performance introspection plane
(ISSUE 12): a real planner + two worker processes run an MPI workload
with TWO planted faults —

- a **slow link**: worker dw1 carries a ``transport.bulk=delay`` fault
  toward dw2, so every bulk frame dw1→dw2 pays a fixed extra latency
  (shm rings are disabled cluster-wide to force the timed TCP path, the
  cross-host stand-in, same as the wire-codec dist test);
- a **slow rank**: rank 5 sleeps before ENTERING each collective
  (MPI_PERF_SLOW_RANK, procs.py fn_mpi_perf) — every other rank waits
  on it, so totals inflate uniformly and only the entry-skew analysis
  can name the culprit.

Asserts that ``GET /perf`` profiles both links and flags the straggler,
that the profile-store bandwidth agrees with the comm-matrix-derived
GiB/s within 25%, and that the cluster doctor ranks BOTH planted faults
in its top findings.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from faabric_tpu.proto import ReturnValue, batch_exec_factory

PROCS = os.path.join(os.path.dirname(__file__), "procs.py")

SLOW_RANK = 5
ROUNDS = 6


@pytest.fixture(scope="module")
def doctor_cluster():
    """Planner + two workers with the planted faults; this process is a
    0-slot client host. Wire codec forced raw so every ring leg ships
    full-size measurable frames (the repeated np.full payload would
    otherwise delta down to headers)."""
    from faabric_tpu.util.network import get_free_port
    from tests.conftest import next_port_base

    base = next_port_base()
    # Every port inside the one slot next_port_base() gave, the
    # planner's too (+1600 lies clear of the hosts' service and MPI
    # ranges): the default planner ports are test_multiprocess's, and
    # xdist runs modules side by side
    aliases = (f"dw1=127.0.0.1+{base},dw2=127.0.0.1+{base + 1000},"
               f"dcli=127.0.0.1+{base + 2000},"
               f"dpl=127.0.0.1+{base + 1600}")
    http_port = get_free_port()
    common = dict(
        os.environ,
        FAABRIC_HOST_ALIASES=aliases,
        JAX_PLATFORMS="cpu",
        DIST_HTTP_PORT=str(http_port),
        SHM_RING_BYTES="0",
        FAABRIC_WIRE_CODEC="raw",
        MPI_PERF_SLOW_RANK=str(SLOW_RANK),
        MPI_PERF_SLOW_S="0.25",
        MPI_PERF_ROUNDS=str(ROUNDS),
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
        # The fault registry logs its armed spec before READY — skip
        # any log lines, fail only on EOF
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
        # The slow link: ONLY dw1's sends toward dw2 pay the delay —
        # the reverse direction stays fast, giving the doctor a healthy
        # link of the same plane to compare against
        w1 = spawn(
            {**common,
             "FAABRIC_FAULTS": "transport.bulk=delay:8ms@dest=dw2"},
            "worker", "dw1", "dpl")
        w2 = spawn(common, "worker", "dw2", "dpl")
        for p in (w1, w2):
            await_ready(p)
    except BaseException:
        # Setup failure skips teardown: reap the children NOW or their
        # fixed planner ports wedge every later dist module
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
    # This pytest process reports ITS link profiles and matrix as host
    # dcli: start from a clean slate, or a link that an earlier test of
    # this process left behind (xdist runs many files in one) outranks
    # the planted one among the doctor's top findings
    from faabric_tpu.telemetry import get_comm_matrix, reset_perf_profile

    reset_perf_profile()
    get_comm_matrix().reset()

    class NullFactory(ExecutorFactory):
        def create_executor(self, msg):
            raise RuntimeError("client runs nothing")

    me = WorkerRuntime(host="dcli", slots=0, factory=NullFactory(),
                       planner_host="dpl")
    me.start()
    me.dist_http_port = http_port

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
    with urllib.request.urlopen(f"{base}{path}", timeout=15) as resp:
        return json.loads(resp.read().decode())


def _bulk_link_gibs(perf_doc: dict) -> dict[tuple, dict]:
    """(src, dst) → bytes-weighted gibs_avg over the bulk-tcp rows."""
    links: dict[tuple, dict] = {}
    for row in perf_doc["links"]:
        if row.get("plane") != "bulk-tcp" or row.get("gibs_avg") is None:
            continue
        key = (row["src"], row["dst"])
        cur = links.setdefault(key, {"bytes": 0, "weighted": 0.0,
                                     "messages": 0})
        cur["bytes"] += row.get("bytes") or 0
        cur["weighted"] += (row["gibs_avg"] * (row.get("bytes") or 0))
        cur["messages"] += row.get("messages") or 0
    return {k: {"gibs": v["weighted"] / v["bytes"],
                "bytes": v["bytes"], "messages": v["messages"]}
            for k, v in links.items() if v["bytes"] > 0}


def test_dist_doctor_names_slow_link_and_straggler(doctor_cluster):
    me = doctor_cluster
    req = batch_exec_factory("dist", "mpi_perf", 1)
    req.messages[0].mpi_rank = 0
    me.planner_client.call_functions(req)
    r = me.planner_client.get_message_result(req.app_id,
                                             req.messages[0].id,
                                             timeout=180.0)
    assert r.return_value == int(ReturnValue.SUCCESS), r.output_data
    assert r.output_data == b"r0:ok"
    deadline = time.time() + 60
    status = me.planner_client.get_batch_results(req.app_id)
    while not status.finished and time.time() < deadline:
        time.sleep(0.3)
        status = me.planner_client.get_batch_results(req.app_id)
    assert status.finished
    for m in status.message_results:
        assert m.return_value == int(ReturnValue.SUCCESS), m.output_data

    base = f"http://127.0.0.1:{me.dist_http_port}"
    perf = _get(base, "/perf")

    # -- the profile store measured both directions of the wire --------
    links = _bulk_link_gibs(perf)
    assert ("dw1", "dw2") in links, sorted(links)
    assert ("dw2", "dw1") in links, sorted(links)
    slow = links[("dw1", "dw2")]["gibs"]
    fast = links[("dw2", "dw1")]["gibs"]
    assert slow < fast * 0.5, (
        f"planted delay invisible: dw1→dw2 {slow:.3f} GiB/s vs "
        f"dw2→dw1 {fast:.3f}")

    # -- acceptance: profile bandwidth ≈ comm-matrix bandwidth (≤25%) --
    matrix = _get(base, "/commmatrix")
    for host in ("dw1", "dw2"):
        cells = [c for c in matrix["hosts"].get(host, [])
                 if c["plane"] == "bulk-tcp"]
        m_bytes = sum(c["bytes"] for c in cells)  # wire bytes, like
        # the profile store's observe() feed
        m_lat = sum(c.get("lat_sum", 0.0) for c in cells)
        assert m_bytes > 0 and m_lat > 0, f"no matrix rows for {host}"
        matrix_gibs = (m_bytes / m_lat) / (1 << 30)
        rows = {k: v for k, v in links.items() if k[0] == host}
        tot = sum(v["bytes"] for v in rows.values())
        profile_gibs = sum(v["gibs"] * v["bytes"]
                           for v in rows.values()) / tot
        assert profile_gibs == pytest.approx(matrix_gibs, rel=0.25), (
            f"{host}: profile {profile_gibs:.3f} vs matrix "
            f"{matrix_gibs:.3f} GiB/s")

    # -- the merged series flags the planted straggler -----------------
    stragglers = perf["stragglers"]
    flagged = {(s["world"], s["rank"]) for s in stragglers}
    assert (7600, SLOW_RANK) in flagged, stragglers
    # and nobody else was blamed in that world
    others = [s for s in stragglers
              if s["world"] == 7600 and s["rank"] != SLOW_RANK]
    assert not others, f"false positives: {others}"

    # -- healthz grew the perf block (and saw the aggregation) ---------
    healthz = _get(base, "/healthz")
    perf_block = healthz.get("perf")
    assert perf_block is not None
    assert perf_block["lastAggregationAgeSeconds"] is not None
    assert perf_block["clusterLinks"] and perf_block["clusterLinks"] > 0
    assert perf_block["clusterStragglers"] >= 1

    # -- the doctor ranks BOTH planted faults in its top findings ------
    from faabric_tpu.runner.doctor import diagnose, fetch_live

    findings = diagnose(fetch_live(base))
    top5 = findings[:5]
    slow_links = [f for f in top5 if f["kind"] == "slow_link"]
    assert slow_links, f"no slow_link in top findings: {top5}"
    assert any("dw1→dw2" in f["subject"] for f in slow_links), slow_links
    straggler_f = [f for f in top5 if f["kind"] == "straggler"]
    assert straggler_f, f"no straggler in top findings: {top5}"
    assert any(f"rank {SLOW_RANK}" in f["subject"]
               for f in straggler_f), straggler_f
