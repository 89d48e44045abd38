"""The batched-extraction cell end to end on the CPU at toy widths
(``tests/bench/data/toy_granite_manifest.json``, found as files by name
like the real one): parent → planner + worker → REST → executor →
``guests/serve_granite.py`` → the program's ``generate`` at batch 3
through state-space layers and a grouped-query attention layer, prefill in
two chunks → ``reference/granite.py``. Every value of a metric is printed
as null; what is checked is the shape of the result, the counters that
come back with the replies, that the fp8 control and both planted faults
fail the limit the program holds, and that a program which cannot express
the configuration fails at once and not at the deadline."""

import json
import os
import subprocess
import sys

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(REPO, "tests", "bench", "data",
                        "toy_granite_manifest.json")
RUN = os.path.join(REPO, "benchmarks", "run.py")
SEED = 2147484001  # more than 32 signed bits hold
NEW = ("step_mfu.serve_granite", "decode_hbm_share.serve_granite",
       "prefill_mfu.serve_granite", "ssm_share.serve_granite",
       "state_roofline.serve_granite", "scan_share.serve_granite")


def run_cell(*extra, trace=0, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               **(env_extra or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--rehearse",
         "--workload", "toy_serve_granite", "--seed", str(SEED),
         "--seconds", "1", "--trace", str(trace), *extra],
        env=env, cwd=REPO, timeout=300, capture_output=True, text=True)


def test_extraction_rehearsal_its_counters_its_control_and_its_faults():
    p = run_cell("--control", "fp8", "--faults", "state_dropped",
                 "window_dropped", trace=1)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    line = json.loads(p.stdout.strip().splitlines()[-1])
    manifest = cells.load_manifest(MANIFEST)
    wanted = {m["name"] for m in
              cells.metrics_of(manifest, "per_layer", "toy_serve_granite")}
    assert set(NEW) <= wanted
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    # a CPU trace holds no device plane: the new readers find nothing to
    # read and say nothing, the runtime's read the host's clock as ever
    assert {"launch_ms.serve", "return_ms.serve"} <= set(line["metrics"]) \
        <= wanted
    assert not [m for m in line["metrics"] if m.endswith("serve_granite")]
    assert all(m["value"] is None for m in line["metrics"].values())
    # the fp8 control and both planted faults, held to the same limit by
    # the run itself, fail it
    limit = line["compared"]["served_logit_gap"]["limit"]
    assert line["compared"]["served_logit_gap"]["value"] < limit
    for key in ("control", "fault_state_dropped", "fault_window_dropped"):
        assert line[f"{key}_correct"] is False, key
        assert line[key]["served_logit_gap"]["value"] > limit, key
    assert line["compared"]["malformed_answers"] == {"value": 0.0,
                                                     "limit": 0}
    # every request of the window came back with the program's counters
    with open(os.path.join(REPO, ".bench_out", "toy_serve_granite",
                           "record.json")) as f:
        record = json.load(f)
    mixer = 64 * (128 + 192 + 8) + 192 * 4 + 192 + 3 * 8 + 128 + 128 * 64
    attention = 64 * (4 + 2 * 2) * 16 + 4 * 16 * 64
    assert record["loaded"]["n_params"] == (
        3 * mixer + attention + 4 * (3 * 64 * 96 + 2 * 64) + 256 * 64 + 64)
    assert record["trace_loops"] is None
    assert record["new_tokens"] == 3 * 16
    assert record["tokens_compared"] == 3 * 16
    assert len(record["per_row_gap"]) == 3
    for r in record["requests"]:
        assert r["rows"] == 3 and r["cache_slots"] == 128
        assert r["cache_bytes"] == 1 * 2 * 3 * 2 * 128 * 16 * 2
        assert r["state_bytes"] == 3 * 3 * (3 * 192 + 8 * 16 * 32) * 2
        assert (r["attention_layers"], r["ssm_layers"]) == (1, 3)
        assert r["scan_chunks"] == 2 and r["ut_passes"] == 17


def test_a_program_that_cannot_say_the_configuration_fails_at_once(tmp_path):
    """The parent commit's ``ModelConfig`` has no per-layer kind: there
    the guest must raise at ``make_guest``, the worker exit before READY
    and the run exit non-zero, soon. Stood in for by a
    ``faabric_tpu.models`` whose ``ModelConfig`` is PR 32's."""
    shim = tmp_path / "sitecustomize.py"
    shim.write_text(
        "import dataclasses, sys\n"
        "if any(a.endswith('worker.py') for a in sys.argv):\n"
        "    import faabric_tpu.models as m\n"
        "    fields = ('vocab_size', 'd_model', 'n_layers', 'n_heads',\n"
        "              'd_ff', 'max_seq', 'rope_theta', 'compute_dtype',\n"
        "              'param_dtype', 'remat', 'attention_impl',\n"
        "              'norm_impl', 'ffn', 'norm_placement',\n"
        "              'rope_pairing', 'norm_eps', 'n_passes',\n"
        "              'exit_threshold', 'attention', 'q_lora_rank',\n"
        "              'kv_lora_rank', 'qk_nope_dim', 'qk_rope_dim',\n"
        "              'v_head_dim', 'layer', 'routed_experts',\n"
        "              'zero_experts', 'experts_held',\n"
        "              'experts_per_token', 'routed_scaling',\n"
        "              'expert_d_ff')\n"
        "    m.ModelConfig = dataclasses.make_dataclass(\n"
        "        'ModelConfig', [(f, object, None) for f in fields])\n")
    path = os.pathsep.join([str(tmp_path), REPO])
    p = run_cell(env_extra={"PYTHONPATH": path})
    assert p.returncode == 1, (p.returncode, p.stderr[-2000:])
    assert "before READY" in p.stderr
    assert p.stdout.strip() == ""
    with open(os.path.join(REPO, ".bench_out", "toy_serve_granite",
                           "worker.log")) as f:
        assert "unexpected keyword argument" in f.read()
