"""The readers of the program's own spans (``benchmarks/program_spans.py``
and the seven per-layer metrics that read it), on a small hand-written spans
dict in the form ``program_spans.extract`` gives, and once end to end on the
CPU. No timing is asserted: every number checked is arithmetic on stamps
written below."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import cells, program_spans

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
US = 1_000  # ns
# The profiler's clock starts with the session; the ledger's stamps are
# CLOCK_MONOTONIC. In the dict below: trace ns = monotonic ns - OFFSET.
OFFSET = 77_000_000_000_000
NEW = ["ingress_ms.serve", "planner_ms.serve", "executor_queue_ms.serve",
       "run_prep_ms.serve", "result_push_ms.serve", "run_host_cpu_ms.serve",
       "idle_outside_run_share.serve"]


def mono(trace_us):
    return OFFSET + trace_us * US


def invocation(msg_id, hin_us, *, run_us, request=None, rcu_us=300,
               push_us=150, rqu=False):
    """The host events of one invocation. Stamps, in trace µs after
    ``hin``: adm +40, qex +100 (ingress 100), sch +250, dsp +600 (planner
    500), eqx +1300 (executor queue 700), rns +1350 (run prep 50); with
    ``rqu`` the planner gave up a first attempt at +2000 and dispatched
    again at +2100, and the worker's stamps follow that."""
    late = 1500 if rqu else 0
    lc = {"hin": mono(hin_us), "adm": mono(hin_us + 40),
          "qex": mono(hin_us + 100), "sch": mono(hin_us + 250),
          "dsp": mono(hin_us + 600 + late), "eqx": mono(hin_us + 1300 + late)}
    if rqu:
        lc["rqu"] = mono(hin_us + 2000)
    eqx, rns = hin_us + 1300 + late, hin_us + 1350 + late
    rne = rns + run_us

    def span(label, start_us, dur_us, **more):
        return {"name": f"faabric:{label}", "start_ns": start_us * US,
                "dur_ns": dur_us * US,
                "stats": dict(lc, msg_id=msg_id, mono_ns=mono(start_us),
                              **more)}

    events = [span("run_prep", eqx, 45),
              span("run", rns, run_us, rns=mono(rns)),
              span("result_push", rne + 5, push_us, rns=mono(rns),
                   rne=mono(rne), rcu=rcu_us * US, stx=20 * US)]
    if request:
        events.append({"name": request, "start_ns": (rns + 20) * US,
                       "dur_ns": (run_us - 40) * US, "stats": {}})
    return events


def spans_of(*groups, modules=None):
    host = sorted((e for g in groups for e in g),
                  key=lambda e: e["start_ns"])
    return {"host": host, "modules": modules or {}}


def two_requests():
    """Request 4: hin at 0, run 1350 → 101350. Request 5: hin at 104000,
    run 105350 → 205350. Before them the invocation that started the
    profiler (only its push is inside the session), between nothing, after
    them the one that stops it (its run never closes)."""
    starter = [e for e in invocation("m1", -3000, run_us=1000)
               if e["name"] == "faabric:result_push"]
    stopper = [e for e in invocation("m9", 207000, run_us=1000)
               if e["name"] == "faabric:run_prep"]
    return spans_of(
        starter,
        invocation("m4", 0, run_us=100_000, request="bench:request#4"),
        invocation("m5", 104_000, run_us=100_000, request="bench:request#5",
                   rcu_us=500, push_us=250),
        stopper,
        modules={"/device:TPU:0": [
            # request 4: two programs with a gap inside the run
            [1500 * US, 40_000 * US], [50_000 * US, 51_000 * US],
            # a transfer after the run has closed, before the next opens
            [102_000 * US, 500 * US],
            # request 5
            [105_500 * US, 99_000 * US]]})


def test_phases_from_stamps_through_the_clock_tie():
    spans = two_requests()
    found = program_spans.requests(spans)
    assert [r["msg_id"] for r in found] == ["m4", "m5"]
    assert [r["request"] for r in found] == ["bench:request#4",
                                             "bench:request#5"]
    # the invocations that served no request are invocations still
    assert [i["msg_id"] for i in program_spans.invocations(spans)] == [
        "m4", "m5"]
    first = found[0]
    assert first["offset_ns"] == -OFFSET
    # every stamp, the planner's too, on the trace's clock
    on_trace = {k: t for t, k in program_spans.stamps_of(first)}
    assert on_trace["hin"] == 0 and on_trace["dsp"] == 600 * US
    assert on_trace["rne"] == 101_350 * US
    assert "rcu" not in on_trace and "stx" not in on_trace
    assert program_spans.phases_ms(first) == pytest.approx({
        "ingress": 0.1, "planner": 0.5, "executor_queue": 0.7,
        "run_prep": 0.05, "result_push": 0.15, "run_host_cpu": 0.3})
    assert program_spans.phase_ms(spans, "result_push") == \
        pytest.approx(0.2)
    assert program_spans.phase_ms(spans, "run_host_cpu",
                                  over=program_spans.mean) == \
        pytest.approx(0.4)
    assert program_spans.phase_ms(spans, "no_such_phase") is None
    assert program_spans.median([3, 1, 2]) == 2
    assert program_spans.median([4, 1, 2, 3]) == 2.5


def test_a_requeued_ledgers_gaps_fall_where_they_happened():
    spans = spans_of(invocation("m7", 0, run_us=10_000,
                                request="bench:request#0", rqu=True))
    request, = program_spans.requests(spans)
    phases = program_spans.phases_ms(request)
    # sch → rqu (the first attempt and the detection of its death, 1750
    # µs) is no phase's; planner is decision (150) + the second dispatch
    # (rqu → dsp, 100)
    assert phases["planner"] == pytest.approx(0.25)
    assert phases["ingress"] == pytest.approx(0.1)
    assert phases["executor_queue"] == pytest.approx(0.7)
    labels = [p for p, _s, _e in program_spans.phase_intervals(request)]
    assert labels == ["ingress", "ingress", "planner", "planner",
                      "executor_queue", "run_prep", "result_push"]


def test_idle_outside_run_with_two_requests_and_a_gap_inside_one():
    spans = two_requests()
    # window: request 4's span opens at 1370, request 5's closes at 205330
    assert program_spans.window_of(spans) == (1370 * US, 205_330 * US)
    window, gaps = program_spans.idle_outside_run(spans)
    assert window == 203_960 * US
    # run 4 closes at 101350, run 5 opens at 105350; the transfer at
    # 102000-102500 is the chip at work. The gap inside run 4
    # (41500-50000) is the guest's, not counted here.
    assert gaps == {"/device:TPU:0": [(101_350 * US, 102_000 * US),
                                      (102_500 * US, 105_350 * US)]}
    share = program_spans.idle_outside_run_share(spans)
    assert share == pytest.approx(100.0 * 3500 / 203_960)
    by_phase = program_spans.idle_by_phase(spans)
    # request 5's inbound phases (104000-105350) and request 4's push
    # (101355-101505) lie in those gaps; the rest is the way back and the
    # client
    assert by_phase == pytest.approx({
        "result_push": 150e-6, "ingress": 100e-6, "planner": 500e-6,
        "executor_queue": 700e-6, "run_prep": 50e-6,
        "outside the ledger": 2000e-6})
    assert sum(by_phase.values()) == pytest.approx(3500e-6)


def test_a_trace_without_the_programs_spans_reads_as_nothing():
    spans = two_requests()
    parent = {"host": [e for e in spans["host"]
                       if e["name"].startswith("bench:")],
              "modules": spans["modules"]}
    assert program_spans.requests(parent) == []
    assert program_spans.idle_outside_run(parent) is None
    assert program_spans.idle_by_phase(parent) is None
    for empty in (parent, {"host": [], "modules": {}}, None):
        assert program_spans.idle_outside_run_share(empty) is None
        assert program_spans.phase_ms(empty, "ingress") is None
    # spans, and no device plane (a rehearsal on the CPU)
    assert program_spans.idle_outside_run_share(
        dict(spans, modules={})) is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_entry_has_its_reader(name, monkeypatch):
    manifest = cells.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["serve_chat_1chip"]
    read = cells.load_module(manifest, "layer_metrics", name).read
    record = {"cell": {"name": "serve_chat_1chip"}}
    seen = []

    def load(out_dir):
        seen.append(out_dir)
        return two_requests()

    monkeypatch.setattr(program_spans, "load", load)
    want = {"ingress_ms.serve": 0.1, "planner_ms.serve": 0.5,
            "executor_queue_ms.serve": 0.7, "run_prep_ms.serve": 0.05,
            "result_push_ms.serve": 0.2, "run_host_cpu_ms.serve": 0.4,
            "idle_outside_run_share.serve": 100.0 * 3500 / 203_960}
    assert read(record) == pytest.approx(want[name])
    assert seen == [os.path.join(REPO, ".bench_out", "serve_chat_1chip")]
    # the parent commit's trace: nothing, and no error
    monkeypatch.setattr(program_spans, "load", lambda out_dir: None)
    assert read(record) is None


def test_a_run_without_a_trace_has_no_spans(tmp_path):
    assert program_spans.load(str(tmp_path)) is None
    # a file that is no trace: the child fails, the reader says nothing
    broken = tmp_path / "bad" / "trace" / "plugins" / "profile" / "x"
    broken.mkdir(parents=True)
    (broken / "host.xplane.pb").write_bytes(b"not a trace")
    assert not program_spans.requests(
        program_spans.load(str(tmp_path / "bad")))


def test_the_readers_find_the_spans_in_a_rehearsal_end_to_end(tmp_path):
    """``--rehearse --trace 1`` on the CPU, on a copy of the toy manifest
    with the new entries (and a cell of its own, so that its logs are no
    other test's): the spans travel worker → profiler → xplane → child
    → readers. Values are printed as null on a CPU; a metric is in the
    line only if its reader found something to read."""
    with open(os.path.join(REPO, "tests", "bench", "data",
                           "toy_manifest.json")) as f:
        toy = json.load(f)
    real = cells.load_manifest()
    cell = "toy_serve_spans"
    toy["workloads"].append({
        "name": cell, "config": "toy", "traffic": "toy_chat", "chips": 1,
        "why": "rehearsal of the span readers"})
    for m in toy["end_to_end"] + toy["per_layer"]:
        if "toy_serve" in m.get("workloads", []):
            m["workloads"].append(cell)
    toy["per_layer"] += [dict(m, workloads=[cell])
                         for m in real["per_layer"] if m["name"] in NEW]
    manifest = tmp_path / "toy_manifest.json"
    manifest.write_text(json.dumps(toy))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--manifest", str(manifest), "--rehearse", "--workload", cell,
         "--seed", "2147483999", "--seconds", "1", "--trace", "1"],
        env=env, cwd=REPO, timeout=300, capture_output=True, text=True)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    line = json.loads(p.stdout.strip().splitlines()[-1])
    # no device plane on a CPU, so no share of the device's window
    assert set(NEW) - {"idle_outside_run_share.serve"} <= set(
        line["metrics"])
    assert "launch_ms.serve" in line["metrics"]
    assert all(m["value"] is None for m in line["metrics"].values())

    out_dir = os.path.join(REPO, ".bench_out", cell)
    with open(os.path.join(out_dir, program_spans.CACHE_NAME)) as f:
        spans = json.load(f)
    found = program_spans.requests(spans)
    assert found and all(r["request"].startswith("bench:request#")
                         for r in found)
    for r in found:
        assert {"run_prep", "run", "result_push"} <= set(r["spans"])
        assert {"ingress", "planner", "executor_queue", "run_prep",
                "result_push", "run_host_cpu"} <= set(
                    program_spans.phases_ms(r))
        keys = [k for _t, k in program_spans.stamps_of(r)]
        assert keys[0] == "hin" and keys[-1] == "rne"
    summary = program_spans.summary(out_dir)
    assert len(summary["requests"]) == len(found)
    assert all("launch_less_phases" in row for row in summary["requests"])
