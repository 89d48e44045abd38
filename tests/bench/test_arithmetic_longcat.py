"""The yardstick of latent attention with a held share of the experts
(``benchmarks/flops_longcat.py``, ``benchmarks/weights_longcat.py``)
against the arithmetic ISSUE 31 and ``PERF.md`` state by hand, the
configuration file against the catalog's row, and the readers and the
guest's trace rules on hand-made records."""

import json
import os

import pytest

from benchmarks import cells, flops_longcat, trace_loops, weights_longcat

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the catalog's row, /opt/skills/guides/model-configs/architectures.jsonl,
# as the driver drew it for ISSUE 31
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
CUT = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "longcat-flash-omni.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return weights_longcat.sizes_of(config)


def test_the_file_is_the_catalog_row_but_for_the_cut(config):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LongCat-Flash-Omni")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    assert {k: config[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    assert sorted(config["reduced"]) == sorted(CUT)
    assert config["deployment"]["published"] == {
        k: PUBLISHED[k] for k in CUT}
    assert config["param_dtype"] == config["compute_dtype"] == "bfloat16"
    entry = next(c for c in cells.load_manifest()["configs"]
                 if c["name"] == "longcat-flash-omni")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_the_share_is_the_stated_deployments(config, sizes):
    assert sizes["experts_held"] == (0, 16)
    assert sizes["routed_experts"] + sizes["zero_experts"] == 768
    assert sizes["top_k"] == 12
    for key, value in (("expert_parallel_chips", 16), ("rank", 1),
                       ("experts_held", [0, 8])):
        with pytest.raises(ValueError, match="share"):
            weights_longcat.sizes_of(dict(config, deployment=dict(
                config["deployment"], **{key: value})))
    for key, value in (("attention_method", "MHA"),
                       ("zero_expert_type", "constant"),
                       ("mla_scale_kv_lora", False)):
        with pytest.raises(ValueError, match="layer"):
            weights_longcat.sizes_of(dict(config, **{key: value}))


def test_parameter_counts(sizes):
    p = weights_longcat.n_params(sizes)
    assert p["attention"] == (6144 * 1536 + 1536 + 1536 * 64 * 192
                              + 6144 * 576 + 512 + 512 * 64 * 256
                              + 8192 * 6144) == 90_572_800
    assert p["dense_ffn"] == 226_492_416
    assert p["router"] == 4_719_360
    assert p["layer_outside_experts"] == 638_874_368
    assert p["expert"] == 37_748_736
    assert p["layer"] == 1_242_854_144
    assert p["total"] == 5_172_749_312
    # the whole model, as published: "560B"
    whole = 28 * (638_874_368 + 512 * 37_748_736) + 2 * 131072 * 6144 + 6144
    assert whole == pytest.approx(560.66e9, rel=1e-4)


def test_a_cached_step_reads_the_experts_hit_and_a_latent_a_position(sizes):
    assert flops_longcat.cache_bytes_per_position(sizes) == 9_216
    outside = 2 * (4 * 638_874_368 + 100_663_296 + 6144)
    assert flops_longcat.decode_step_bytes(sizes, 64, 0, 0) == outside
    assert outside == pytest.approx(5.31e9, rel=2e-3)
    # 10 experts hit in each of 4 layers: 3.0 GB more; all 16: 4.8 GB
    hit = flops_longcat.decode_step_bytes(sizes, 64, 0, 40) - outside
    assert hit == 40 * 37_748_736 * 2 == pytest.approx(3.02e9, rel=1e-3)
    every = flops_longcat.decode_step_bytes(sizes, 64, 0, 64) - outside
    assert every == pytest.approx(4.83e9, rel=1e-3)
    # the caches of 64 rows at 192 positions: 113 MB, under 2% of a step
    at_192 = flops_longcat.decode_step_bytes(sizes, 64, 192, 40)
    assert at_192 - outside - hit == 64 * 192 * 9_216
    assert (at_192 - outside - hit) / at_192 < 0.02


def test_operations_of_a_token_and_of_a_request(sizes):
    matrices = flops_longcat.matmul_params_outside_experts(sizes)
    # a layer outside its experts, less 4 + 4 norm scales and the bias
    assert matrices == 4 * (638_874_368 - 4 * 6144 - 2 * (1536 + 512) - 768)
    # ISSUE 31: 5.11 GFLOP a token outside the experts, 41.9 TFLOP a prefill
    assert 2 * matrices == pytest.approx(5.11e9, rel=2e-3)
    prefill = flops_longcat.prefill_flops(sizes, 64, 128)
    assert prefill == 64 * (2 * matrices * 128
                            + 2 * (128 * 128 / 2) * 64 * 320 * 8
                            + 2 * 100_663_296)
    assert prefill == pytest.approx(42.1e12, rel=5e-3)
    step = flops_longcat.decode_step_flops(sizes, 64, 200)
    assert step == 64 * (2 * (matrices + 100_663_296)
                         + 2 * 200 * 64 * (512 + 512 + 64) * 8)
    assert flops_longcat.expert_flops(sizes, 16_384) == pytest.approx(
        1.237e12, rel=1e-3)
    whole = flops_longcat.request_flops(sizes, 64, 128, 128, 16_384)
    assert whole == prefill + sum(
        flops_longcat.decode_step_flops(sizes, 64, 128 + t)
        for t in range(1, 128)) + flops_longcat.expert_flops(
            sizes, 16_384 * 255 / 256)
    assert whole == pytest.approx(87.6e12, rel=1e-2)


def _record(config, loops, **request):
    return {
        "config": config, "new_tokens": 64 * 128,
        "traffic": {"new_tokens": 128,
                    "trace": {"skip_requests": 2, "requests": 2}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "requests": [
            dict({"index": i, "prompt_len": 128, "rows": 64,
                  "posted": 10.0 * i, "seen": 10.0 * i + 2.0,
                  "picks_held": 16_000 + 100 * i,
                  "experts_hit_decode": 5_000 + 64 * i}, **request)
            for i in range(5)],
        "trace_loops": {"decode_loops": loops},
    }


def test_the_readers_on_a_hand_made_record(config, sizes):
    manifest = cells.load_manifest()

    def read(name, record):
        return cells.load_module(manifest, "layer_metrics", name).read(record)

    loops = [{"seconds": 128 * 0.014, "before_s": 0.40, "cache_s": 0.07,
              "expert_s": 0.8, "inner_loops": 0},
             {"seconds": 128 * 0.015, "before_s": 0.42, "cache_s": 0.08,
              "expert_s": 0.9, "inner_loops": 0}]
    record = _record(config, loops)
    # the traced requests are the third and the fourth
    hit = (5_128 + 5_192) / 2 / 128
    need = flops_longcat.decode_step_bytes(sizes, 64, 128 + 64.5, hit)
    assert read("decode_hbm_share.serve_longcat", record) == pytest.approx(
        100 * need / 819e9 / 0.0145)
    assert 65 < read("decode_hbm_share.serve_longcat", record) < 75
    ops = sum(flops_longcat.prefill_flops(sizes, 64, 128)
              + flops_longcat.expert_flops(sizes, held / 2)
              for held in (16_200, 16_300))
    assert read("prefill_mfu.serve_longcat", record) == pytest.approx(
        100 * ops / 0.82 / 197e12)
    assert read("cache_share.serve_longcat", record) == pytest.approx(
        100 * 0.15 / (128 * 0.029))
    assert read("expert_share.serve_longcat", record) == pytest.approx(
        100 * 1.7 / (128 * 0.029))
    whole = sum(flops_longcat.request_flops(sizes, 64, 128, 128,
                                            r["picks_held"])
                for r in record["requests"])
    assert read("step_mfu.serve_longcat", record) == pytest.approx(
        100 * whole / 10.0 / 197e12)
    assert 20 < read("step_mfu.serve_longcat", record) < 25
    # the accepted readers of the serve cells read this record too
    e2e = cells.load_module(manifest, "end_to_end", "serve_tokens_per_s")
    assert e2e.read(dict(record, window_s=10.0)) == 5 * 8192 / 10.0
    # nothing to read is nothing reported, never an error: a run that was
    # not traced, a program that returns no counters (the parent's), a
    # trace that holds another number of decode loops than were traced
    names = ("decode_hbm_share.serve_longcat", "prefill_mfu.serve_longcat",
             "cache_share.serve_longcat", "expert_share.serve_longcat")
    silent = dict(record, trace_loops=None)
    for name in names:
        assert read(name, silent) is None
        assert read(name, {}) is None
        assert read(name, _record(config, loops[:1])) is None
    bare = _record(config, loops)
    for r in bare["requests"]:
        del r["picks_held"], r["experts_hit_decode"]
    for name in names[:2] + ("step_mfu.serve_longcat",):
        assert read(name, bare) is None
    unscoped = [dict(loop, cache_s=None, expert_s=None) for loop in loops]
    assert read("cache_share.serve_longcat",
                _record(config, unscoped)) is None
    assert read("expert_share.serve_longcat",
                _record(config, unscoped)) is None
    assert read("step_mfu.serve_longcat", {}) is None


def test_the_guests_rules_find_the_operations_and_the_decode_loop(sizes):
    """The decode body's operations by kind and shape, as the optimized
    HLO of the cell's program names them (compiled for a described v5e,
    PR 31), through ``trace_loops.reduce_loops``: the decode loop is the
    outermost ``while`` that holds the grouped products' loops, and
    prefill's own loops before it are its prefill's time."""
    guest = cells.load_module(cells.load_manifest(), "guests",
                              "serve_longcat")
    ops = guest.decode_operations(sizes, 64, 256)
    for label in ("dynamic_update_slice bf16[1,64,256,576]",
                  "fusion f32[64,64]", "fusion bf16[64,64,512]",
                  "iota_compare_fusion pred[256]"):
        assert label in ops["cache"] and label not in ops["experts"]
    for label in ("fusion bf16[16,6144]",
                  "fusion bf16[16,2048]", "fusion pred[16]",
                  "select_dynamic-update-slice_fusion bf16[1024,6144]",
                  "sort s32[768]", "sort f32[64,768]",
                  "fusion bf16[768,6144]", "pad s32[784]",
                  "broadcast_add_fusion f32[64,768]", "fusion f32[64]",
                  "convert_reduce_fusion s32[16]",
                  "select_reduce_fusion bf16[64,6144]",
                  "custom-call bf16[6144,768]"):
        assert label in ops["experts"] and label not in ops["cache"]
    for label in ("fusion bf16[64,12288]", "rms_norm bf16[64,6144]",
                  "multiply_reduce_fusion f32[64]", "fusion bf16[64,6144]",
                  "convolution_add_fusion bf16[64,6144]",
                  "fusion bf16[64,1,64,512]", "fusion bf16[64,64,128]",
                  "while s32[]", "add s32[]"):
        assert label not in ops["cache"] and label not in ops["experts"]

    ms = 10**6  # the trace counts nanoseconds
    events = [["fusion.1 bf16[64,128,6144]", 0, 300 * ms],
              ["while.1 s32[]", 300 * ms, 20 * ms],   # prefill's own loop
              ["fusion.2 bf16[64,128,6144]", 330 * ms, 70 * ms],
              ["while.2 s32[]", 400 * ms, 1000 * ms],  # the decode loop
              ["while.3 s32[]", 410 * ms, 100 * ms],  # a product's tiles
              ["fusion.3 bf16[16,2048]", 420 * ms, 60 * ms],
              ["fusion.7 f32[64,64]", 520 * ms, 30 * ms],
              ["fusion.9 bf16[64,12288]", 560 * ms, 200 * ms]]
    compact = {"devices": {"/device:TPU:0": events}}
    (decode,) = trace_loops.reduce_loops(
        compact, ops["cache"])["decode_loops"]
    (by_experts,) = trace_loops.reduce_loops(
        compact, ops["experts"])["decode_loops"]
    assert decode["seconds"] == pytest.approx(1.0)
    assert decode["inner_loops"] == 1
    assert decode["before_s"] == pytest.approx(0.3 + 0.02 + 0.07)
    assert decode["cache_s"] == pytest.approx(0.03)
    # what the tiles' loop holds; a loop's own time is nobody's
    assert by_experts["cache_s"] == pytest.approx(0.06)
