"""Distributed acceptance for the state observability plane (ISSUE 16):
a real planner + two worker processes, with this (client) process
mastering a planted HOT key (2 MiB) plus three cold keys. Worker-side
invocations hammer the hot key — three full re-pulls (planted pull
amplification) and a two-chunk dirty push each — then the test asserts

- ``GET /statemap`` ranks the hot key first with the correct master and
  a per-origin byte split naming the worker host(s);
- the ``plane=state`` comm-matrix byte totals agree with BOTH the
  statemap's remote-origin ledger bytes and the workers' own
  hand-reported wire counts within 5%;
- the cluster doctor ranks the planted master hotspot (every key
  mastered on one host) and the pull amplification.
"""

import json
import os
import subprocess
import sys
import urllib.request

import pytest

from faabric_tpu.proto import ReturnValue, batch_exec_factory

PROCS = os.path.join(os.path.dirname(__file__), "procs.py")

HOT_SIZE = 2 << 20
COLD_SIZE = 64 << 10
CHUNK = 4096
HAMMERS = 2  # sequential worker invocations of fn_state_hot


@pytest.fixture(scope="module")
def statemap_cluster():
    """Planner + two workers; this process is a 0-slot client host that
    masters the planted keys (its runtime's StateServer serves them)."""
    from faabric_tpu.util.network import get_free_port
    from tests.conftest import next_port_base

    base = next_port_base()
    # Every port inside the one slot next_port_base() gave, the
    # planner's too (+1600 lies clear of the hosts' service and MPI
    # ranges): the default planner ports are test_multiprocess's, and
    # xdist runs modules side by side
    aliases = (f"sw1=127.0.0.1+{base},sw2=127.0.0.1+{base + 1000},"
               f"scli=127.0.0.1+{base + 2000},"
               f"spl=127.0.0.1+{base + 1600}")
    http_port = get_free_port()
    env = dict(os.environ, FAABRIC_HOST_ALIASES=aliases,
               JAX_PLATFORMS="cpu", FAABRIC_METRICS="1",
               DIST_HTTP_PORT=str(http_port))
    procs = []

    def spawn(*args):
        p = subprocess.Popen([sys.executable, PROCS, *args],
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True, env=env)
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
        planner = spawn("planner", str(base + 1600))
        await_ready(planner)
        w1 = spawn("worker", "sw1", "spl")
        w2 = spawn("worker", "sw2", "spl")
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
    from faabric_tpu.telemetry import get_comm_matrix
    from faabric_tpu.telemetry.statestats import (
        get_state_stats,
        reset_state_stats,
    )
    from faabric_tpu.transport.common import clear_host_aliases

    os.environ["FAABRIC_HOST_ALIASES"] = aliases
    clear_host_aliases()
    # This pytest process reports ITS ledger/matrix as host scli: start
    # the module from a clean slate or earlier in-process tests (unit
    # suite, other dist modules) pollute the byte accounting below
    reset_state_stats()
    get_state_stats().reset()
    get_comm_matrix().reset()

    class NullFactory(ExecutorFactory):
        def create_executor(self, msg):
            raise RuntimeError("client runs nothing")

    me = WorkerRuntime(host="scli", slots=0, factory=NullFactory(),
                       planner_host="spl")
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


def test_dist_statemap_attribution_and_doctor(statemap_cluster):
    me = statemap_cluster

    # -- plant: this host masters one hot + three cold keys ------------
    hot = me.state.get_kv("dist", "hot", HOT_SIZE)
    assert hot.is_master
    hot.set(b"\x07" * HOT_SIZE)
    for i in range(3):
        kv = me.state.get_kv("dist", f"cold{i}", COLD_SIZE)
        kv.set(bytes([i]) * COLD_SIZE)

    # -- hammer the hot key from the worker side (sequential, so the
    #    hand-computed wire bytes are exact) ---------------------------
    exec_hosts, wire_total = set(), 0
    for _ in range(HAMMERS):
        req = batch_exec_factory("dist", "state_hot", 1)
        me.planner_client.call_functions(req)
        r = me.planner_client.get_message_result(
            req.app_id, req.messages[0].id, timeout=30.0)
        assert r.return_value == int(ReturnValue.SUCCESS), r.output_data
        assert r.output_data.startswith(b"wire=")
        wire_total += int(r.output_data.split(b"=")[1])
        exec_hosts.add(r.executed_host)
    assert exec_hosts <= {"sw1", "sw2"}
    # 3 full pulls + a 2-chunk dirty push per invocation
    assert wire_total == HAMMERS * (3 * HOT_SIZE + 2 * CHUNK)

    base = f"http://127.0.0.1:{me.dist_http_port}"

    # -- /statemap: ranking, master, origin split ----------------------
    smap = _get(base, "/statemap")
    top = smap["keys"][0]
    assert top["key"] == "dist/hot", [r["key"] for r in smap["keys"]]
    assert top["rank"] == 1
    assert top["master"] == "scli"
    assert top["size"] == HOT_SIZE
    by_origin = top["by_origin"]
    assert "scli" in by_origin  # the master's own set() traffic
    for host in exec_hosts:
        assert by_origin[host]["bytes"] > 0, by_origin
    remote_bytes = sum(o["bytes"] for h, o in by_origin.items()
                       if h != "scli")
    assert remote_bytes > by_origin["scli"]["bytes"]
    # Planted amplification: 3 pulls per invocation, 1 first-time
    assert top["pull_amplification"] >= 3.0

    cold_keys = {r["key"]: r for r in smap["keys"]
                 if r["key"].startswith("dist/cold")}
    assert len(cold_keys) == 3
    assert all(r["master"] == "scli" for r in cold_keys.values())
    assert smap["hosts"]["scli"]["mastered_keys"] >= 4
    assert smap["hosts"]["scli"]["mastered_bytes"] >= \
        HOT_SIZE + 3 * COLD_SIZE

    # -- plane=state comm rows vs the ledger's pulled-byte counters ----
    matrix = _get(base, "/commmatrix")
    comm_state = sum(c["bytes"]
                     for cells in matrix["hosts"].values()
                     for c in cells if c.get("plane") == "state")
    # Against the workers' own hand-counted wire bytes…
    assert comm_state == pytest.approx(wire_total, rel=0.05), (
        f"comm {comm_state} vs reported wire {wire_total}")
    # …and against the statemap's remote-origin ledger bytes (which
    # additionally carry the local set_chunk staging writes, <5%)
    assert comm_state == pytest.approx(remote_bytes, rel=0.05), (
        f"comm {comm_state} vs statemap remote {remote_bytes}")

    # -- the doctor ranks the planted faults ---------------------------
    from faabric_tpu.runner.doctor import diagnose, fetch_live

    findings = diagnose(fetch_live(base))
    hotspot = [f for f in findings if f["kind"] == "master_hotspot"]
    assert hotspot, f"no master_hotspot finding: {findings[:5]}"
    assert any("scli" in f["subject"] for f in hotspot), hotspot
    amp = [f for f in findings if f["kind"] == "pull_amplification"]
    assert any("dist/hot" in f["subject"] for f in amp), (
        f"no pull_amplification on dist/hot: {findings[:8]}")
