"""The long-document cell end to end on the CPU at toy widths
(``tests/bench/data/toy_axk1_manifest.json``, found as files by name like
the real one): parent → planner + worker → REST → executor →
``guests/serve_axk1.py`` → the program's ``generate`` at batch 8 through
latent caches under YaRN, a dense layer and expert layers with a shared
expert, prefill in three chunks → ``reference/axk1.py``. Every value of a
metric is printed as null; what is checked is the shape of the result, the
counters that come back with the replies, that the fp8 control and the
four planted faults fail the limit the program holds, and that a program
which cannot express the configuration fails at once and not at the
deadline."""

import json
import os
import subprocess
import sys

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(REPO, "tests", "bench", "data",
                        "toy_axk1_manifest.json")
RUN = os.path.join(REPO, "benchmarks", "run.py")
SEED = 2147484041  # more than 32 signed bits hold
CELL = "toy_serve_axk1"
FAULTS = ("shared_dropped", "weights_unnormalised", "yarn_dropped",
          "chunk_carry_dropped")
NEW = ("step_mfu.serve_axk1", "prefill_mfu.serve_axk1",
       "decode_hbm_share.serve_axk1", "latent_prefill_share.serve_axk1",
       "expert_share.serve_axk1")


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


def test_longdoc_rehearsal_its_counters_its_control_and_its_faults():
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
    assert not [m for m in line["metrics"] if m.endswith("serve_axk1")]
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
    # every request of the window came back with the program's counters,
    # the static ones and those the call summed on the device
    with open(os.path.join(REPO, ".bench_out", CELL, "record.json")) as f:
        record = json.load(f)
    from benchmarks import weights_axk1

    with open(os.path.join(REPO, "tests", "bench", "data", "configs",
                           "toy_axk1.json")) as f:
        sizes = weights_axk1.sizes_of(json.load(f))
    assert record["loaded"]["n_params"] \
        == weights_axk1.n_params(sizes)["total"]
    assert record["trace_loops"] is None
    assert record["new_tokens"] == 8 * 8
    assert record["tokens_compared"] == 4 * 8
    assert len(record["per_row_gap"]) == 4
    # float32 on both sides: no pick differs
    assert record["routing_mismatch_share"] == 0.0
    for r in record["requests"]:
        assert r["rows"] == 8 and r["cache_slots"] == 128
        assert r["cache_bytes"] == 3 * 8 * 128 * 24 * 4
        assert (r["experts_held"], r["router_width"], r["shared_experts"],
                r["dense_layers"], r["expert_layers"]) == (4, 16, 1, 1, 2)
        assert (r["prefill_chunks"], r["score_blocks"],
                r["expanded_bytes"]) == (3, 3, 0)
        # the dense feed-forward and the two shared experts, by the
        # kernel's plans from their own widths
        assert r["ffn_streamed_layers"] == 3
        assert r["ffn_streamed_bytes"] == 3 * 64 * (96 + 2 * 48) * 4
        assert r["picks_held"] + r["picks_absent"] == 8 * (48 + 8) * 4 * 2
        assert r["picks_zero"] == 0
        assert 0 < r["experts_hit_decode"] <= 8 * 2 * 4
        assert r["tiles_decode"] >= r["experts_hit_decode"]


def test_a_program_that_cannot_say_the_configuration_fails_at_once(tmp_path):
    """The parent commit's ``ModelConfig`` has no feed-forward kind a
    layer, no shared expert, no sigmoid router, no rotary scaling: there
    the guest must raise at ``make_guest``, the worker exit before READY
    and the run exit non-zero, soon. Stood in for by a
    ``faabric_tpu.models`` whose ``ModelConfig`` is PR 40's."""
    shim = tmp_path / "sitecustomize.py"
    shim.write_text(
        "import dataclasses, sys\n"
        "if any(a.endswith('worker.py') for a in sys.argv):\n"
        "    import faabric_tpu.models as m\n"
        "    new = ('ffn_types', 'shared_experts', 'router_score',\n"
        "           'router_renormalise', 'router_bias', 'latent_scale',\n"
        "           'rope_scaling')\n"
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
