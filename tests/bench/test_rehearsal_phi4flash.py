"""The batched-solving cell end to end on the CPU at toy widths
(``tests/bench/data/toy_phi4flash_manifest.json``, found as files by name
like the real one): parent → planner + worker → REST → executor →
``guests/serve_phi4flash.py`` → the program's ``generate`` at batch 8
through Mamba-1 layers, rings, one shared cache and one memory, prefill in
two chunks with the cross-decoder at the last position alone →
``reference/phi4flash.py``. Every value of a metric is printed as null;
what is checked is the shape of the result, the counters that come back
with the replies, that the fp8 control and all four planted faults fail
the limit the program holds, and that a program which cannot express the
configuration fails at once and not at the deadline."""

import json
import os
import subprocess
import sys

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(REPO, "tests", "bench", "data",
                        "toy_phi4flash_manifest.json")
RUN = os.path.join(REPO, "benchmarks", "run.py")
SEED = 2147484041  # more than 32 signed bits hold
CELL = "toy_serve_phi4flash"
FAULTS = ("window_unbounded", "memory_stale", "lambda_dropped",
          "state_dropped")
NEW = ("step_mfu.serve_phi4flash", "decode_hbm_share.serve_phi4flash",
       "prefill_mfu.serve_phi4flash", "mixer_share.serve_phi4flash",
       "scan_share.serve_phi4flash", "attention_roofline.serve_phi4flash")


def run_cell(*extra, trace=0, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               **(env_extra or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--rehearse",
         "--workload", CELL, "--seed", str(SEED), "--seconds", "1",
         "--trace", str(trace), *extra],
        env=env, cwd=REPO, timeout=400, capture_output=True, text=True)


def test_solving_rehearsal_its_counters_its_control_and_its_faults():
    p = run_cell("--control", "fp8", "--faults", *FAULTS, trace=1)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    line = json.loads(p.stdout.strip().splitlines()[-1])
    manifest = cells.load_manifest(MANIFEST)
    wanted = {m["name"] for m in
              cells.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW) <= wanted
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    # a CPU trace holds no device plane: the new readers find nothing to
    # read and say nothing, the runtime's read the host's clock as ever
    assert {"launch_ms.serve", "return_ms.serve"} <= set(line["metrics"]) \
        <= wanted
    assert not [m for m in line["metrics"] if m.endswith("serve_phi4flash")]
    assert all(m["value"] is None for m in line["metrics"].values())
    # the fp8 control and the four planted faults, held to the same limit
    # by the run itself, fail it
    limit = line["compared"]["served_logit_gap"]["limit"]
    assert line["compared"]["served_logit_gap"]["value"] < limit
    for key in ("control",) + tuple(f"fault_{f}" for f in FAULTS):
        assert line[f"{key}_correct"] is False, key
        assert line[key]["served_logit_gap"]["value"] > limit, key
    assert line["compared"]["malformed_answers"] == {"value": 0.0,
                                                     "limit": 0}
    # every request of the window came back with the program's counters
    with open(os.path.join(REPO, ".bench_out", CELL, "record.json")) as f:
        record = json.load(f)
    from benchmarks import weights_phi4flash

    with open(os.path.join(REPO, "tests", "bench", "data", "configs",
                           "toy_phi4flash.json")) as f:
        sizes = weights_phi4flash.sizes_of(json.load(f))
    assert record["loaded"]["n_params"] \
        == weights_phi4flash.n_params(sizes)["total"]
    assert record["trace_loops"] is None
    assert record["new_tokens"] == 8 * 8
    assert record["tokens_compared"] == 4 * 8
    assert len(record["per_row_gap"]) == 4
    one = 8 * 4 * 64 * 4          # rows × kv heads × lanes × float32
    for r in record["requests"]:
        assert r["rows"] == 8 and r["cache_slots"] == 128
        assert (r["window_layers"], r["window_slots"]) == (3, 16)
        assert r["window_cache_bytes"] == 3 * 2 * 16 * one
        assert r["shared_cache_bytes"] == 2 * 128 * one
        assert (r["cross_layers"], r["memory_layers"], r["ssm_layers"],
                r["attention_layers"]) == (2, 2, 4, 1)
        assert r["state_bytes"] == 4 * 8 * (8 + 3) * 1024 * 4
        assert r["scan_chunks"] == 2 and r["prefill_skipped_layers"] == 4
        assert r["attention_streamed_layers"] == 6
        assert r["attention_streamed_bytes"] == 3 * 2 * (16 + 128) * one
        assert r["ut_passes"] == 9


def test_a_program_that_cannot_say_the_configuration_fails_at_once(tmp_path):
    """The parent commit's ``ModelConfig`` has no window, no lent state,
    no differential form: there the guest must raise at ``make_guest``,
    the worker exit before READY and the run exit non-zero, soon. Stood in
    for by a ``faabric_tpu.models`` whose ``ModelConfig`` is PR 38's."""
    shim = tmp_path / "sitecustomize.py"
    shim.write_text(
        "import dataclasses, sys\n"
        "if any(a.endswith('worker.py') for a in sys.argv):\n"
        "    import faabric_tpu.models as m\n"
        "    new = ('sliding_window', 'cache_source', 'memory_source',\n"
        "           'differential', 'norm', 'attention_bias', 'ssm_inner',\n"
        "           'ssm_dt_rank')\n"
        "    old = [(f.name, object, None)\n"
        "           for f in dataclasses.fields(m.ModelConfig)\n"
        "           if f.name not in new]\n"
        "    m.ModelConfig = dataclasses.make_dataclass('ModelConfig', old)\n")
    path = os.pathsep.join([str(tmp_path), REPO])
    p = run_cell(env_extra={"PYTHONPATH": path})
    assert p.returncode == 1, (p.returncode, p.stderr[-2000:])
    assert "before READY" in p.stderr
    assert p.stdout.strip() == ""
    with open(os.path.join(REPO, ".bench_out", CELL, "worker.log")) as f:
        assert "unexpected keyword argument" in f.read()
