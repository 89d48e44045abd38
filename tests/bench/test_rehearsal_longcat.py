"""The batched-rollout cell end to end on the CPU at toy widths
(``tests/bench/data/toy_longcat_manifest.json``, found as files by name
like the real one): parent → planner + worker → REST → executor →
``guests/serve_longcat.py`` → the program's ``generate`` at batch 3
through latent caches and a held share of the experts →
``reference/longcat.py``. Every value of a metric is printed as null;
what is checked is the shape of the result, the counters that come back
with the replies, that the fp8 control fails the limit the program holds,
and that a program which cannot express the configuration fails at once
and not at the deadline."""

import json
import os
import subprocess
import sys

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(REPO, "tests", "bench", "data",
                        "toy_longcat_manifest.json")
RUN = os.path.join(REPO, "benchmarks", "run.py")
SEED = 2147484001  # more than 32 signed bits hold


def run_cell(*extra, trace=0, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               **(env_extra or {}))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, RUN, "--manifest", MANIFEST, "--rehearse",
         "--workload", "toy_serve_longcat", "--seed", str(SEED),
         "--seconds", "1", "--trace", str(trace), *extra],
        env=env, cwd=REPO, timeout=300, capture_output=True, text=True)


def test_rollout_rehearsal_its_counters_and_its_fp8_control():
    p = run_cell("--control", "fp8", trace=1)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    line = json.loads(p.stdout.strip().splitlines()[-1])
    manifest = cells.load_manifest(MANIFEST)
    wanted = {m["name"] for m in
              cells.metrics_of(manifest, "per_layer", "toy_serve_longcat")}
    assert {"step_mfu.serve_longcat", "decode_hbm_share.serve_longcat",
            "prefill_mfu.serve_longcat", "cache_share.serve_longcat",
            "expert_share.serve_longcat"} <= wanted
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    # a CPU trace holds no device plane: the new readers find nothing to
    # read and say nothing, the runtime's read the host's clock as ever
    assert {"launch_ms.serve", "return_ms.serve"} <= set(line["metrics"]) \
        <= wanted
    assert not [m for m in line["metrics"] if m.endswith("serve_longcat")]
    assert all(m["value"] is None for m in line["metrics"].values())
    # the fp8 control, held to the same limit by the run itself, fails it
    assert line["control_correct"] is False
    gap = line["control"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert line["compared"]["served_logit_gap"]["value"] < gap["limit"]
    assert line["compared"]["malformed_answers"] == {"value": 0.0,
                                                     "limit": 0}
    # every request of the window came back with the program's counters,
    # the static ones and those the call summed on the device
    with open(os.path.join(REPO, ".bench_out", "toy_serve_longcat",
                           "record.json")) as f:
        record = json.load(f)
    attention = 64 * 32 + 32 + 32 * 4 * 24 + 64 * 24 + 16 + 16 * 4 * 32 \
        + 4 * 16 * 64
    layer = 2 * (attention + 3 * 64 * 96) + 64 * 24 + 24 + 4 * 64 \
        + 4 * 3 * 64 * 48
    assert record["loaded"]["n_params"] == 2 * layer + 2 * 256 * 64 + 64
    assert record["trace_loops"] is None
    assert record["new_tokens"] == 3 * 16
    assert 0.0 <= record["routing_mismatch_share"] < 0.5
    assert record["tokens_compared"] == 3 * 16
    for r in record["requests"]:
        assert r["rows"] == 3 and r["cache_slots"] == 128
        assert r["cache_bytes"] == 2 * 2 * 3 * 128 * 24 * 2
        assert r["experts_held"] == 4 and r["router_width"] == 24
        assert r["picks_held"] + r["picks_zero"] + r["picks_absent"] \
            == 3 * (16 + 16) * 4 * 2
        assert 0 <= r["experts_hit_decode"] <= 16 * 2 * 4


def test_a_program_that_cannot_say_the_configuration_fails_at_once(tmp_path):
    """The parent commit's ``ModelConfig`` has no such kinds: there the
    guest must raise at ``make_guest``, the worker exit before READY and
    the run exit non-zero, soon. Stood in for by a ``faabric_tpu.models``
    whose ``ModelConfig`` is PR 30's."""
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
        "              'exit_threshold')\n"
        "    m.ModelConfig = dataclasses.make_dataclass(\n"
        "        'ModelConfig', [(f, object, None) for f in fields])\n")
    path = os.pathsep.join([str(tmp_path), REPO])
    p = run_cell(env_extra={"PYTHONPATH": path})
    assert p.returncode == 1, (p.returncode, p.stderr[-2000:])
    assert "before READY" in p.stderr
    assert p.stdout.strip() == ""
    with open(os.path.join(REPO, ".bench_out", "toy_serve_longcat",
                           "worker.log")) as f:
        assert "unexpected keyword argument" in f.read()
