"""The harness end to end on the CPU, at toy widths on four virtual
devices: parent → planner + worker → REST → executor → guest → the
program's ``generate`` / ``make_train_step`` → the plain reference. The
cells are those of ``tests/bench/data/toy_manifest.json``, found as files
by name like the real ones; every value of a metric is printed as null,
because a CPU's number is never written under a device metric's name.

What is checked: the shape of the last line; that the lower-precision
control fails the comparison that the program passes; and that ``correct``
comes out false when the timed path is broken underneath (a token altered
where it is produced, a step that returns its state unchanged, half of the
batch left out). No timing is asserted."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import cells
from benchmarks.run import compare, verdicts

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(REPO, "tests", "bench", "data", "toy_manifest.json")
RUN = os.path.join(REPO, "benchmarks", "run.py")
SEED = 2147483999  # more than 32 signed bits hold


def run_cell(workload, *extra, trace=0, seed=SEED):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--rehearse",
         "--workload", workload, "--seed", str(seed), "--seconds", "1",
         "--trace", str(trace), *extra],
        env=env, cwd=REPO, timeout=300, capture_output=True, text=True)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    return json.loads(p.stdout.strip().splitlines()[-1]), p


def limits_of(workload):
    manifest = cells.load_manifest(MANIFEST)
    cell = cells.load_cell(manifest, workload)
    return cell["traffic_values"]["check"]["limits"]["toy"]


def check_shape(line, workload, kind):
    manifest = cells.load_manifest(MANIFEST)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["rehearsal"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    wanted = {m["name"]: m["unit"]
              for m in cells.metrics_of(manifest, kind, workload)}
    assert set(line["metrics"]) <= set(wanted) and line["metrics"]
    for name, m in line["metrics"].items():
        assert m == {"value": None, "unit": wanted[name]}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 4
    assert set(line["compared"]) == set(limits_of(workload))


def test_serve_rehearsal_and_its_lower_precision_control():
    line, p = run_cell("toy_serve", "--control", "fp8", trace=1)
    check_shape(line, "toy_serve", "per_layer")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"launch_ms.serve", "return_ms.serve",
                                    "request_p90_ms.serve"}
    # every number compared is printed beside its limit, last on stderr
    tail = p.stderr.strip().splitlines()[-3:]
    assert tail[0].startswith("compared served_logit_gap = ")
    assert tail[-1] == "correct = True"
    # the reference in fp8, put in the program's place, is held to the
    # same limits by the run itself and is not correct
    assert line["control_correct"] is False
    assert set(line["control"]) == set(line["compared"])
    gap = line["control"]["served_logit_gap"]
    assert gap["value"] > 1.4 * gap["limit"]
    assert line["control"]["malformed_answers"]["value"] == 0.0


def test_gang_train_rehearsal_its_control_and_a_planted_fault():
    line, _ = run_cell("toy_train_gang", "--control", "fp8", "--faults",
                       "half_batch", "state_unchanged")
    check_shape(line, "toy_train_gang", "end_to_end")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # control and faults are held to the limits by the run itself
    for planted in ("control", "fault_half_batch", "fault_state_unchanged"):
        assert line[f"{planted}_correct"] is False
        assert set(line[planted]) == set(line["compared"])
    gap = line["control"]["grad_norm_gap"]
    assert gap["value"] > 2 * gap["limit"]
    # the reference with half of the batch left out reads far above it
    gap = line["fault_half_batch"]["grad_norm_gap"]
    assert gap["value"] > 100 * gap["limit"]
    # planted in the reference, an unchanged state has the right gradient
    # and has moved nothing
    assert line["fault_state_unchanged"]["change_norm_gap"]["value"] == \
        pytest.approx(1.0, abs=1e-4)


def test_a_control_that_the_limits_let_pass_fails_the_run():
    """The harness's own rule, without a cluster: a control or a fault
    whose numbers hold every limit makes the verdict true, and ``run.py``
    exits non-zero on it."""
    limits = {"a_gap": 0.1, "exact": 0}
    record = {"numbers": {"a_gap": 0.01, "exact": 0.0},
              "control": {"a_gap": 0.05, "exact": 0.0},
              "fault_half_batch": {"a_gap": 0.4, "exact": 0.0},
              "fault_missing": {"exact": 0.0}}
    got = verdicts(record, limits)
    assert {k: ok for k, (ok, _) in got.items()} == {
        "control": True, "fault_half_batch": False, "fault_missing": False}
    assert got["control"][1]["a_gap"] == {"value": 0.05, "limit": 0.1}
    assert compare({"a_gap": float("nan"), "exact": 0.0}, limits)[0] is False


@pytest.mark.parametrize("workload, failing", [
    ("toy_serve_token_altered", {"served_logit_gap"}),
    ("toy_train_state_unchanged", {"grad_norm_gap", "change_norm_gap"}),
    ("toy_train_half_batch", {"grad_norm_gap", "change_norm_gap"}),
])
def test_a_timed_path_broken_underneath_is_not_correct(workload, failing):
    line, p = run_cell(workload)
    assert line["correct"] is False
    assert p.stderr.strip().splitlines()[-1] == "correct = False"
    over = {name for name, row in line["compared"].items()
            if row["value"] > row["limit"]}
    assert failing <= over, line["compared"]
    if workload == "toy_train_state_unchanged":
        # nothing moved: both norms read 1 by the measure (the change to
        # the rounding of the weights made a second time)
        assert line["compared"]["grad_norm_gap"]["value"] == \
            pytest.approx(1.0)
        assert line["compared"]["change_norm_gap"]["value"] == \
            pytest.approx(1.0, abs=1e-4)
