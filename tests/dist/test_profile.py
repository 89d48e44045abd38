"""Distributed acceptance for the continuous profiling plane (ISSUE
18): a real planner + two worker processes with the always-on stack
sampler running at a 10 ms cadence. One worker executes a planted
busy-spin (distinctive frame) with a light lock convoy alongside it;
while it runs the test asserts

- the planner-merged ``GET /profile`` ranks the planted frame #1
  cluster-wide, attributed to the CORRECT host and the
  ``executor/pool`` thread class;
- that host's GIL-pressure gauge reads hot and the cluster doctor
  raises ``cpu_hotspot`` + ``gil_saturation`` findings naming it;
- the OTHER (idle) worker stays free of profile-plane findings — the
  attribution is per-host, not cluster-smeared.
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

SPIN_S = 24.0


@pytest.fixture(scope="module")
def profile_cluster():
    """Planner + two workers sampling at 10 ms; this process is a
    0-slot client host that only drives invocations."""
    from faabric_tpu.util.network import get_free_port
    from tests.conftest import next_port_base

    base = next_port_base()
    # Every port inside the one slot next_port_base() gave, the
    # planner's too (+1600 lies clear of the hosts' service and MPI
    # ranges): the default planner ports are test_multiprocess's, and
    # xdist runs modules side by side
    aliases = (f"pf1=127.0.0.1+{base},pf2=127.0.0.1+{base + 1000},"
               f"pfcli=127.0.0.1+{base + 2000},"
               f"pfpl=127.0.0.1+{base + 1600}")
    http_port = get_free_port()
    # 10 ms cadence (default 25): finer drift resolution so the planted
    # GIL saturation reads well above threshold within the spin window,
    # and the 50-sample evidence floor fills in half a second
    env = dict(os.environ, FAABRIC_HOST_ALIASES=aliases,
               JAX_PLATFORMS="cpu", FAABRIC_METRICS="1",
               FAABRIC_PROFILE_INTERVAL_MS="10",
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
        w1 = spawn("worker", "pf1", "pfpl")
        w2 = spawn("worker", "pf2", "pfpl")
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
    # This pytest process reports ITS stacks as host pfcli: drop what
    # the sampler gathered under earlier tests of this process (xdist
    # runs many files in one), or an old stack's CPU outranks the plant
    from faabric_tpu.telemetry.profiler import reset_profiler

    reset_profiler()

    class NullFactory(ExecutorFactory):
        def create_executor(self, msg):
            raise RuntimeError("client runs nothing")

    me = WorkerRuntime(host="pfcli", slots=0, factory=NullFactory(),
                       planner_host="pfpl")
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


def test_dist_profile_hotspot_attribution_and_doctor(profile_cluster):
    me = profile_cluster
    base = f"http://127.0.0.1:{me.dist_http_port}"

    # -- plant: busy-spin + lock convoy on whichever worker the planner
    #    picks, captured MID-SPIN (pressure is an EWMA — it decays) ----
    req = batch_exec_factory("dist", "profile_spin", 1)
    req.messages[0].input_data = str(SPIN_S).encode()
    me.planner_client.call_functions(req)
    # Wait on the aggregation's own counts, not on a fixed sleep: under
    # a pure-Python burn the burning host's sampler gets the GIL a few
    # times a second (each /proc read hands it back), fewer with busy
    # neighbours, so its 50-sample evidence floor fills slowly
    time.sleep(SPIN_S * 0.25)
    deadline = time.monotonic() + SPIN_S * 0.6
    while True:
        doc = _get(base, "/profile")
        if time.monotonic() > deadline or all(
                (doc["hosts"].get(h) or {}).get("samples", 0) >= 50
                for h in ("pf1", "pf2")):
            break
        time.sleep(0.25)
    from faabric_tpu.runner.doctor import diagnose, fetch_live

    findings = diagnose(fetch_live(base))

    r = me.planner_client.get_message_result(
        req.app_id, req.messages[0].id, timeout=SPIN_S + 30.0)
    assert r.return_value == int(ReturnValue.SUCCESS), r.output_data
    host = r.executed_host
    assert host in ("pf1", "pf2"), host
    idle = "pf2" if host == "pf1" else "pf1"

    # -- merged /profile: planted frame ranked #1, right host + class --
    assert doc["stacks"], doc
    top = doc["stacks"][0]
    assert top["rank"] == 1
    assert top["host"] == host, (top, host)
    assert top["class"] == "executor/pool", top
    assert any("_planted_profile_burn" in f for f in top["frames"]), top
    assert top["cpu_ms"] > 500.0, top
    for h in (host, idle):
        assert doc["hosts"][h]["samples"] >= 50, doc["hosts"]

    # -- GIL attribution: spin host hot, idle host calm ----------------
    assert doc["gil"][host]["pressure"] >= 0.25, doc["gil"]
    assert doc["gil"][host]["runnable_avg"] >= 0.5, doc["gil"]
    assert doc["gil"][idle]["runnable_avg"] < 0.5, doc["gil"]

    # -- the doctor ranks the planted faults on the right host ---------
    hot = [f for f in findings if f["kind"] == "cpu_hotspot"]
    assert any(host in f["subject"] and "executor/pool" in f["subject"]
               for f in hot), (hot, findings[:6])
    gil = [f for f in findings if f["kind"] == "gil_saturation"]
    assert any(host in f["subject"] for f in gil), (gil, findings[:6])

    # -- and NOTHING profile-shaped on the idle worker -----------------
    for f in findings:
        if f["kind"] in ("cpu_hotspot", "gil_saturation",
                         "sampler_starved"):
            assert idle not in f["subject"], f
