"""The chip entry points, as far as a CPU can check them: where the
compile cache is placed, and that ``chip_smoke.py`` cannot pass without a
TPU while its whole flow (REST → planner → worker process → JaxExecutor
guests → jitted train / decode / gang collectives) still runs here at toy
width under ``--rehearse``."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _files_under(path) -> list[str]:
    return sorted(os.path.join(r, f) for r, _d, fs in os.walk(path)
                  for f in fs)


def test_compile_cache_default_is_the_fixed_in_checkout_path(
        tmp_path, monkeypatch):
    import jax

    from faabric_tpu.util import device_env

    assert device_env._REPO_ROOT == REPO
    monkeypatch.delenv(device_env.CACHE_ENV, raising=False)
    monkeypatch.setattr(device_env, "_REPO_ROOT", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        placed = device_env.configure_compile_cache()
        assert placed == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
        # the same answer every time: no temp name, pid or clock in it
        assert device_env.configure_compile_cache() == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_environment_wins_and_nothing_else_is_set(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory in
    code, and what JAX compiles lands under that directory only."""
    placed, other_root = tmp_path / "placed", tmp_path / "checkout"
    other_root.mkdir()
    code = (
        "import sys, os, jax\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from faabric_tpu.util import device_env\n"
        f"device_env._REPO_ROOT = {str(other_root)!r}\n"
        "set_before = jax.config.jax_compilation_cache_dir\n"
        "got = device_env.configure_compile_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == set_before\n"
        "assert got == os.environ['JAX_COMPILATION_CACHE_DIR'] == set_before\n"
        "jax.jit(lambda x: x * 2 + 1)(jax.numpy.arange(8.0))"
        ".block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(placed),
               # cache even a program that compiles in no time
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    p = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr[-2000:]
    assert _files_under(placed), "nothing was cached under the placed dir"
    assert _files_under(other_root) == []


def test_chip_smoke_refuses_to_pass_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, SMOKE], env=env, timeout=300,
                       capture_output=True, text=True)
    assert p.returncode == 3, (p.returncode, p.stderr[-2000:])
    assert "no TPU" in p.stderr and "platform=cpu" in p.stderr
    assert p.stdout.strip() == "", "no result may be printed without a chip"


def test_chip_smoke_rehearsal_drives_the_whole_flow_on_cpu():
    """Toy width, 4 virtual CPU devices: every phase of the smoke runs
    and passes, and the line a chip run ends with is never printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, SMOKE, "--rehearse"], env=env,
                       timeout=600, capture_output=True, text=True)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "rehearsal": "passed",
        "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    assert '"ok"' not in p.stdout
    summary = json.loads(lines[-2])
    phases = summary["phases"]
    assert set(phases) == {"kernels", "train", "decode", "latent_experts",
                           "state_space", "shared_state", "long_latent",
                           "gang"}
    # the feed-forward kernel at the state-space toy's widths: one tile
    kernels = phases["kernels"]
    assert kernels["gated_ffn_rel_err"] < 8e-3
    assert kernels["gated_ffn_plan"]["tile"] == 96
    assert kernels["on_kernel_path"]["gated_ffn"] is True
    # the cached-attention kernel at a toy's heads: 4 on 2 of 64 lanes
    assert kernels["cached_attention_rel_err"] < 8e-3
    assert kernels["cached_attention_plan"]["steps"] == 1
    assert kernels["on_kernel_path"]["cached_attention"] is True
    hybrid = phases["state_space"]
    assert max(hybrid["prefill_rel_err"],
               hybrid["cached_steps_rel_err"]) < 4e-2
    # one layer of each kind at the toy's widths, 4 rows: keys and values
    # of 2 heads over 128 slots; a window and a state, whatever the reach
    assert hybrid["cache_bytes"] == 2 * 4 * 2 * 128 * 16 * 2
    assert hybrid["state_bytes"] == 4 * (3 * 192 + 8 * 16 * 32) * 2
    assert hybrid["scan_chunks"] == 2
    # every kind of a decoder-hybrid-decoder at the toy's widths, 8 rows:
    # two rings of 16 slots, one shared cache of 128, three states; the
    # two windows, the full attention and the cross attention stream
    shared = phases["shared_state"]
    assert max(shared["prefill_rel_err"],
               shared["cached_steps_rel_err"]) < 4e-2
    assert (shared["rows"], shared["prompt"]) == (8, 20)
    one = 8 * 4 * 64 * 4
    assert shared["window_slots"] == 16
    assert shared["window_cache_bytes"] == 2 * 2 * 16 * one
    assert shared["shared_cache_bytes"] == 2 * 128 * one
    assert shared["state_bytes"] == 3 * 8 * (8 + 3) * 1024 * 4
    assert shared["attention_streamed_layers"] == 4
    assert shared["prefill_skipped_layers"] == 2
    latent = phases["latent_experts"]
    assert max(latent["prefill_rel_err"],
               latent["cached_steps_rel_err"]) < 4e-2
    counted = latent["picks_held_zero_absent_experts_hit_tiles"]
    assert len(counted) == 5 and sum(counted[:3]) == 4 * (32 + 2) * 4
    # tiles of 16 rows: one an expert hit and one more a full 16 picks
    assert counted[3] <= counted[4] <= counted[3] + counted[0] // 16
    # a dense layer, then an expert layer with a shared expert, at the
    # toy's widths, 8 rows: a prompt of 48 (past the toy's original reach
    # of 32) in three chunks, the dense feed-forward and the shared
    # expert of a step through the streaming kernel, no zero-compute pick
    long = phases["long_latent"]
    assert max(long["prefill_rel_err"], long["cached_steps_rel_err"]) < 4e-2
    assert (long["rows"], long["prompt"], long["prefill_chunks"]) == (8, 48, 3)
    assert (long["dense_layers"], long["expert_layers"],
            long["ffn_streamed_layers"], long["expanded_bytes"]) == (1, 1, 2, 0)
    # the toy's keys are no whole lane tiles: the plan refuses, the scores
    # go in blocks, a block a chunk
    assert (long["latent_streamed_layers"], long["latent_streamed_chunks"],
            long["score_blocks"]) == (0, 0, 3)
    counted = long["picks_held_zero_absent_experts_hit_tiles"]
    assert sum(counted[:3]) == 8 * (48 + 2) * 4 and counted[1] == 0
    assert phases["train"]["mesh"] == {"dp": 2, "tp": 2}
    assert phases["train"]["params_on_device_ids"] == [0, 1, 2, 3]
    assert phases["decode"]["request_compiles"][1] == 0
    assert sorted(r["device"]["id"] for r in phases["gang"]) == [0, 1, 2, 3]
    assert all(r["activated"] and r["fallbacks"] == 0
               and r["host_device_copies"] == 0 for r in phases["gang"])
    assert summary["parent_touched_jax"] is False
    assert summary["child_exit_codes"] == [0, 0]
