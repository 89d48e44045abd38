"""The yardstick of state-space layers beside grouped-query attention
(``benchmarks/flops_granite.py``, ``benchmarks/weights_granite.py``)
against the arithmetic ISSUE 33 and ``PERF.md`` state by hand, the
configuration file against the catalog's row, the readers and the guest's
trace rules on hand-made records, and the reference against the program
on the rehearsal's toy."""

import json
import os

import pytest

from benchmarks import cells, flops_granite, trace_loops, weights_granite

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PATTERN = (["mamba"] * 5 + ["attention"] + ["mamba"] * 9 + ["attention"]
           + ["mamba"] * 9 + ["attention"] + ["mamba"] * 9 + ["attention"]
           + ["mamba"] * 4)
# the catalog's row, /opt/skills/guides/model-configs/architectures.jsonl,
# as the driver drew it for ISSUE 33
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PATTERN, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return weights_granite.sizes_of(config)


def test_the_file_is_the_catalog_row_whole(config):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == []
    assert config["param_dtype"] == config["compute_dtype"] == "bfloat16"
    assert [i for i, kind in enumerate(PATTERN) if kind == "attention"] == [
        5, 15, 25, 35]
    for key in ("head_dim", "in_projection", "gate_and_norm", "dt", "state",
                "dtypes", "head", "weights", "multipliers", "biases"):
        assert config["assumed"][key], key
    manifest = cells.load_manifest()
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    cell = cells.load_cell(manifest, "serve_granite_1chip")
    traffic = cell["traffic_values"]
    assert (cell["chips"], cell["guest"]) == (1, "serve_granite")
    assert (traffic["rows"], traffic["new_tokens"], traffic["poll_ms"],
            traffic["prefill_chunk"]) == (64, 128, 5, 256)
    assert traffic["prompt_lengths"] == [{"tokens": 512, "count": 1}]
    assert traffic["trace"] == {"skip_requests": 2, "requests": 2}
    assert (traffic["check"]["sample_requests"],
            traffic["check"]["sample_rows"]) == (2, 4)


def test_what_the_weights_are_not_made_for_is_refused(config):
    for key, value in (("hidden_act", "gelu"), ("attention_bias", True),
                       ("position_embedding_type", "rope"),
                       ("mamba_proj_bias", True), ("num_local_experts", 8),
                       ("tie_word_embeddings", False),
                       ("layer_types", ["mamba"] * 39 + ["linear"])):
        with pytest.raises(ValueError, match="layers"):
            weights_granite.sizes_of(dict(config, **{key: value}))
    for key, value in (("mamba_expand", 3), ("num_hidden_layers", 39),
                       ("num_key_value_heads", 5), ("mamba_n_groups", 3)):
        with pytest.raises(ValueError, match="fit"):
            weights_granite.sizes_of(dict(config, **{key: value}))


def test_parameter_counts(sizes):
    """ISSUE 33's count, leaf by leaf."""
    p = weights_granite.n_params(sizes)
    assert p["mixer"] == (2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096
                          + 4096 * 2048) == 25_847_232
    assert p["ffn"] == 2048 * 16384 + 8192 * 2048 == 50_331_648
    assert p["mamba_layer"] == 76_182_976
    assert p["attention"] == (2048 * (2048 + 512 + 512) + 2048 * 2048) \
        == 10_485_760
    assert p["attention_layer"] == 60_821_504
    assert p["embed"] == 100352 * 2048 == 205_520_896
    assert (p["mamba_layers"], p["attention_layers"]) == (36, 4)
    assert p["total"] == (36 * 76_182_976 + 4 * 60_821_504 + 205_520_896
                          + 2048) == 3_191_396_096
    # what a token multiplies through, without the table
    assert p["matmul"] == 2_984_771_584


def test_a_cached_step_moves_the_state_twice(sizes):
    """ISSUE 33's 11.5 GB: the weights 6.38, S read and written 4.83, the
    windows, keys and values at the mean reach of 576."""
    assert flops_granite.state_bytes(sizes, 64) \
        == 36 * 64 * 64 * 64 * 128 * 2 == 2_415_919_104
    assert flops_granite.window_bytes(sizes, 64) \
        == 36 * 64 * 3 * 4352 * 2 == 60_162_048
    assert flops_granite.kv_bytes_per_position(sizes) == 8_192
    weights = 2 * 3_191_396_096
    bare = flops_granite.decode_step_bytes(sizes, 64, -1)
    assert bare == weights + 2 * 2_415_919_104 + 60_162_048 * 4 / 3
    at_576 = flops_granite.decode_step_bytes(sizes, 64, 576)
    assert at_576 - bare == 8_192 * 64 * 577
    assert at_576 == pytest.approx(11.6e9, rel=5e-3)
    assert 2 * 2_415_919_104 / at_576 == pytest.approx(0.417, abs=2e-3)
    step = flops_granite.state_step(sizes, 64)
    assert step == {"flops": 5.0 * 36 * 64 * 64 * 64 * 128,
                    "bytes": 2.0 * 2_415_919_104}
    # memory-bound: 5.9 ms at 819 GB/s against 0.03 ms of operations
    assert step["bytes"] / 819e9 == pytest.approx(5.9e-3, rel=1e-2)
    assert step["flops"] / 197e12 < 1e-4


def test_operations_of_a_request(sizes):
    """ISSUE 33's 196 TFLOP of matrices at prefill, 5 more for attention
    and the scan."""
    recurrence = 5 * 64 * 64 * 128 + 2 * 4 * 4352
    assert flops_granite.recurrence_flops_per_token(sizes) == recurrence
    prefill = flops_granite.prefill_flops(sizes, 64, 512)
    attention = 2 * (512 * 512 / 2) * 32 * 128 * 4
    assert prefill == 64 * (2 * 2_984_771_584 * 512 + attention
                            + recurrence * 512 * 36 + 2 * 205_520_896)
    assert 64 * 2 * 2_984_771_584 * 512 == pytest.approx(195.6e12, rel=1e-3)
    assert prefill == pytest.approx(199.0e12, rel=1e-3)
    step = flops_granite.decode_step_flops(sizes, 64, 600)
    assert step == 64 * (2 * (2_984_771_584 + 205_520_896)
                         + 2 * 600 * 32 * 128 * 4 + recurrence * 36)
    whole = flops_granite.request_flops(sizes, 64, 512, 128)
    assert whole == prefill + sum(
        flops_granite.decode_step_flops(sizes, 64, 512 + t)
        for t in range(1, 128))
    assert whole == pytest.approx(251.8e12, rel=1e-3)


def _record(config, loops, **request):
    return {
        "config": config, "new_tokens": 64 * 128,
        "traffic": {"new_tokens": 128,
                    "trace": {"skip_requests": 2, "requests": 2}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "requests": [
            dict({"index": i, "prompt_len": 512, "rows": 64,
                  "posted": 10.0 * i, "seen": 10.0 * i + 4.0,
                  "state_bytes": 2_476_081_152, "scan_chunks": 2}, **request)
            for i in range(5)],
        "trace_loops": {"decode_loops": loops},
    }


def test_the_readers_on_a_hand_made_record(config, sizes):
    manifest = cells.load_manifest()

    def read(name, record):
        return cells.load_module(manifest, "layer_metrics", name).read(record)

    loops = [{"seconds": 128 * 0.016, "before_s": 1.8, "cache_s": 0.9,
              "state_s": 0.8, "scan_s": 0.3, "inner_loops": 0},
             {"seconds": 128 * 0.017, "before_s": 1.9, "cache_s": 1.0,
              "state_s": 0.9, "scan_s": 0.4, "inner_loops": 0}]
    record = _record(config, loops)
    need = flops_granite.decode_step_bytes(sizes, 64, 512 + 64.5)
    assert read("decode_hbm_share.serve_granite", record) == pytest.approx(
        100 * need / 819e9 / 0.0165)
    assert 80 < read("decode_hbm_share.serve_granite", record) < 90
    assert read("prefill_mfu.serve_granite", record) == pytest.approx(
        100 * 2 * flops_granite.prefill_flops(sizes, 64, 512) / 3.7 / 197e12)
    assert read("ssm_share.serve_granite", record) == pytest.approx(
        100 * 1.9 / (128 * 0.033))
    assert read("state_roofline.serve_granite", record) == pytest.approx(
        100 * 256 * (2 * 2_415_919_104 / 819e9) / 1.7)
    assert 85 < read("state_roofline.serve_granite", record) < 95
    assert read("scan_share.serve_granite", record) == pytest.approx(
        100 * 0.7 / 3.7)
    whole = 5 * flops_granite.request_flops(sizes, 64, 512, 128)
    assert read("step_mfu.serve_granite", record) == pytest.approx(
        100 * whole / 20.0 / 197e12)
    assert 30 < read("step_mfu.serve_granite", record) < 35
    # the accepted readers of the serve cells read this record too
    e2e = cells.load_module(manifest, "end_to_end", "serve_tokens_per_s")
    assert e2e.read(dict(record, window_s=20.0)) == 5 * 8192 / 20.0
    # nothing to read is nothing reported, never an error: a run that was
    # not traced, a program that returns no counters (the parent's), a
    # trace that holds another number of decode loops than were traced, a
    # reduction that knows no state-space operations
    names = ("decode_hbm_share.serve_granite", "prefill_mfu.serve_granite",
             "ssm_share.serve_granite", "state_roofline.serve_granite",
             "scan_share.serve_granite")
    for name in names:
        assert read(name, dict(record, trace_loops=None)) is None
        assert read(name, {}) is None
        assert read(name, _record(config, loops[:1])) is None
    bare = _record(config, loops)
    for r in bare["requests"]:
        del r["state_bytes"]
    for name in names[:2] + ("step_mfu.serve_granite",):
        assert read(name, bare) is None
    plain = [{k: v for k, v in loop.items()
              if k not in ("state_s", "scan_s")} | {"cache_s": None}
             for loop in loops]
    for name in names[2:]:
        assert read(name, _record(config, plain)) is None
    assert read("step_mfu.serve_granite", {}) is None


def test_the_guests_rules_find_the_operations_and_the_decode_loop(sizes):
    """The mixer's operations by kind and shape, as the optimized HLO of
    the cell's program names them (compiled for a described v5e, PR 33),
    through ``trace_loops.reduce_loops``: the decode loop is the one
    ``while``, prefill before it holds none."""
    guest = cells.load_module(cells.load_manifest(), "guests",
                              "serve_granite")
    ops = guest.mixer_operations(sizes, 64)
    state = ("fusion bf16[64,64,64,128]", "copy-done bf16[64,64,64,128]",
             "slice-done bf16[16,64,64,128]",
             "custom-call bf16[64,64,64,128]")
    for label in state:
        assert label in ops["state"] and label in ops["ssm"]
    for label in (
            # a cached step: the window, dt and the decay, the read-out's
            # operands, the gate and the norm's statistic
            "fusion bf16[64,1,4352]", "pad_maximum_fusion bf16[64,3,4352]",
            "copy bf16[64,3,4352]", "multiply_exponential_fusion f32[64,64]",
            "fusion f32[64,64]", "convert_bitcast_fusion f32[64,128]",
            "slice_convert_fusion f32[64,1,4096]",
            "broadcast_multiply_fusion f32[64,64,64]",
            "multiply_reduce_fusion f32[64]", "rsqrt_convert_fusion bf16[64]",
            # the chunked form
            "divide_multiply_fusion bf16[64,256,4352]",
            "slice bf16[64,256,4096]", "fusion f32[64,256,256]",
            "fusion f32[64,256,1,64,64]", "fusion f32[64,64,64,256]",
            "reduce-window f32[64,64,2,128]", "fusion f32[64,64,256]",
            "bitcast_exponential_fusion f32[64,256,64]",
            "iota_compare_fusion pred[256,256]",
            "multiply_reduce_fusion f32[64,256]"):
        assert label in ops["ssm"] and label not in ops["state"], label
    for label in (
            # the two projections, the feed-forward, the block's norms
            "convolution_bitcast_fusion bf16[64,1,8512]",
            "fusion bf16[64,256,8512]", "fusion bf16[64,2048]",
            "fusion bf16[64,256,2048]", "fusion bf16[64,8192]",
            "convolution_multiply_fusion bf16[64,256,8192]",
            "rms_norm bf16[64,2048]", "rms_norm bf16[16384,2048]",
            # attention over 8 key/value heads of 4 query heads, 640 slots
            "convert_multiply_fusion f32[64,8,640,4]", "fusion f32[64,8,4]",
            "fusion bf16[64,8,64,4]", "fusion f32[64,8,4,256]",
            "dynamic_update_slice bf16[1,64,8,640,64]",
            "iota_compare_fusion pred[640]",
            # the head with its argmax, the served tokens, the loop
            "iota_reduce_fusion bf16[64]", "multiply_reduce_fusion bf16[64]",
            "dynamic_update_slice s32[128,64]", "fusion s32[64]",
            "custom-call bf16[2048,8512]", "slice-done bf16[1024,2048]",
            "while s32[]", "add s32[]"):
        assert label not in ops["ssm"] and label not in ops["state"], label

    ms = 10**6  # the trace counts nanoseconds
    events = [["fusion.1 bf16[64,256,8512]", 0, 300 * ms],
              ["fusion.2 f32[64,256,1,64,64]", 300 * ms, 50 * ms],
              ["fusion.3 bf16[64,64,64,128]", 350 * ms, 30 * ms],
              ["fusion.4 bf16[64,256,2048]", 390 * ms, 10 * ms],
              ["while.8 s32[]", 400 * ms, 2000 * ms],  # the decode loop
              ["convolution_bitcast_fusion.9 bf16[64,1,8512]", 410 * ms,
               100 * ms],
              ["fusion.5 bf16[64,64,64,128]", 520 * ms, 60 * ms],
              ["fusion.7 f32[64,64]", 600 * ms, 30 * ms],
              ["fusion.9 bf16[64,8192]", 700 * ms, 200 * ms]]
    compact = {"devices": {"/device:TPU:0": events}}
    found = trace_loops.reduce_loops(compact, ops["ssm"])
    (decode,) = found["decode_loops"]
    (of_state,) = trace_loops.reduce_loops(
        compact, ops["state"])["decode_loops"]
    assert decode["seconds"] == pytest.approx(2.0)
    assert decode["before_s"] == pytest.approx(0.39)
    assert decode["cache_s"] == pytest.approx(0.09)
    assert of_state["cache_s"] == pytest.approx(0.06)
    assert guest.time_before(compact, found, ops["ssm"]) == [
        pytest.approx(0.08)]


def test_the_reference_is_the_program_on_the_toy():
    """The rehearsal's configuration file through ``program_granite`` and
    ``weights_granite`` in float32: the program's forward pass and the
    reference agree to rounding; ``tests/unit/test_granite.py`` has the
    cached path and the forms of the scan."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program_granite
    from benchmarks.reference import granite as ref
    from faabric_tpu.models import forward

    with open(os.path.join(REPO, "tests", "bench", "data", "configs",
                           "toy_granite.json")) as f:
        toy = json.load(f)
    sz = weights_granite.sizes_of(toy)
    cfg = dataclasses.replace(
        program_granite.model_config(toy), compute_dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba")
    assert (cfg.kv_heads, cfg.tie_embeddings, cfg.position) == (
        2, True, "none")
    params = weights_granite.make_weights(7, sz, jnp.float32)
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == weights_granite.n_params(sz)["total"]
    tokens = jnp.asarray(weights_granite.token_rows(7, 1, 0, 2, 19, 256))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits_of_rows(params, tokens, sz))
        got = np.asarray(forward(params, tokens, cfg))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())
