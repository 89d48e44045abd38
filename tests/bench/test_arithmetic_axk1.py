"""The yardstick of latent attention under YaRN, a dense layer and expert
layers with a shared expert (``benchmarks/flops_axk1.py``,
``benchmarks/weights_axk1.py``) against the arithmetic ISSUE 41 and
``PERF.md`` state by hand, the configuration file against the catalog's
row, and the readers and the guest's trace rule on hand-made records."""

import json
import os

import pytest

from benchmarks import cells, flops_axk1, trace_loops, weights_axk1

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve_axk1_1chip"
NEW = ("step_mfu.serve_axk1", "prefill_mfu.serve_axk1",
       "decode_hbm_share.serve_axk1", "latent_prefill_share.serve_axk1",
       "expert_share.serve_axk1")
# the catalog's row, /opt/skills/guides/model-configs/architectures.jsonl,
# as the driver drew it for ISSUE 41
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 192,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
CUT = {"num_hidden_layers": 7, "n_routed_experts": 12, "vocab_size": 20480}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs", "a.x-k1.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return weights_axk1.sizes_of(config)


def test_the_file_is_the_catalog_row_but_for_the_cut(config):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "A.X-K1")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    assert {k: config[k] for k in PUBLISHED} == {**PUBLISHED, **CUT}
    assert sorted(config["reduced"]) == sorted(CUT)
    assert config["deployment"]["published"] == {
        k: PUBLISHED[k] for k in CUT}
    assert config["param_dtype"] == config["compute_dtype"] == "bfloat16"
    manifest = cells.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == "a.x-k1")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert {"topk_method", "router", "rotary", "shared_expert",
            "training_and_launch"} <= set(config["assumed"])


def test_the_manifest_has_the_cell_and_its_readers_behind_what_was_there():
    """Found by name, not by place: a later PR appends behind these, as
    this one appended behind PR 39's."""
    manifest = cells.load_manifest()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("a.x-k1", "longdoc_1caller", 1)
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + 5] == list(NEW)
    assert first > names.index("attention_roofline.serve_phi4flash")
    for m in manifest["per_layer"][first:first + 5]:
        assert m["workloads"] == [CELL]
    reported = {m["name"] for kind in ("end_to_end", "per_layer")
                for m in cells.metrics_of(manifest, kind, CELL)}
    assert {"serve_tokens_per_s", "request_p50_ms", "setup_s",
            "launch_ms.serve", "return_ms.serve", "request_p90_ms.serve",
            "device_idle_share.serve", "scope_coverage.serve",
            "prefill_share.serve", "attention_share.serve",
            "feed_forward_share.serve", "head_share.serve", *NEW} == reported
    traffic = cells.load_cell(manifest, CELL)["traffic_values"]
    assert (traffic["rows"], traffic["new_tokens"], traffic["prefill_chunk"],
            traffic["poll_ms"], traffic["prompt_lengths"], traffic["trace"]) \
        == (8, 64, 1024, 5, [{"tokens": 8192, "count": 1}],
            {"skip_requests": 2, "requests": 2})


def test_the_share_is_the_stated_deployments(config, sizes):
    assert sizes["experts_held"] == (0, 12)
    assert sizes["routed_experts"] == 192 and sizes["top_k"] == 8
    assert weights_axk1.ffn_kinds(sizes) == ("dense",) + ("experts",) * 6
    assert sizes["yarn"] == (32.0, 4096.0, 32.0, 1.0, 1.0, 1.0)
    whole = weights_axk1.sizes_of(config, published=True)
    assert (whole["n_layers"], whole["experts_held"], whole["vocab"]) \
        == (61, (0, 192), 163840)
    for key, value in (("expert_parallel_chips", 8), ("rank", 1),
                       ("experts_held", [0, 8]), ("vocabulary_chips", 4)):
        with pytest.raises(ValueError, match="share"):
            weights_axk1.sizes_of(dict(config, deployment=dict(
                config["deployment"], **{key: value})))
    for key, value in (("scoring_func", "softmax"),
                       ("topk_method", "noaux_tc"),
                       ("norm_topk_prob", False), ("model_type", "other")):
        with pytest.raises(ValueError, match="layer"):
            weights_axk1.sizes_of(dict(config, **{key: value}))


def test_parameter_counts(sizes, config):
    p = weights_axk1.n_params(sizes)
    assert p["attention"] == (7168 * 1536 + 1536 + 1536 * 64 * 192
                              + 7168 * 576 + 512 + 512 * 64 * 256
                              + 8192 * 7168) == 101_124_096
    assert p["dense_ffn"] == 396_361_728
    assert p["router"] == 1_376_256
    assert p["expert"] == p["shared"] == 3 * 7168 * 2048 == 44_040_192
    assert p["dense_layer"] == 497_500_160
    assert p["expert_layer"] == 146_554_880 + 12 * 44_040_192
    assert p["total"] == 497_500_160 + 6 * (146_554_880 + 12 * 44_040_192) \
        + 2 * 20_480 * 7168 + 7168 == 4_841_331_712
    whole = weights_axk1.n_params(weights_axk1.sizes_of(config, True))
    assert whole["total"] == 60 * (146_554_880 + 192 * 44_040_192) \
        + 497_500_160 + 2 * 163_840 * 7168 + 7168 == 518_982_622_208
    # every layer's attention less its two inner norms, the dense
    # feed-forward, six routers and six shared experts
    assert p["matrices_a_token"] == 7 * 101_122_048 + 396_361_728 \
        + 6 * (1_376_256 + 44_040_192)


def test_a_cached_step_reads_the_experts_hit_and_a_latent_a_position(sizes):
    assert flops_axk1.cache_bytes_per_position(sizes) == 7 * 1152 == 8_064
    outside = 2 * (497_500_160 + 6 * 146_554_880 + 20_480 * 7168 + 7168)
    assert flops_axk1.decode_step_bytes(sizes, 8, 0, 0) == outside
    # ISSUE 41: 1.41 GB of attention, 0.79 dense, 0.53 shared, 0.29 head
    assert outside == pytest.approx(3.05e9, rel=5e-3)
    hit = flops_axk1.decode_step_bytes(sizes, 8, 0, 3.4 * 6) - outside
    assert hit == pytest.approx(1.8e9, rel=5e-3)
    # the latents of 8 rows at 8,224 positions: 0.53 GB, a tenth of a step
    at_reach = flops_axk1.decode_step_bytes(sizes, 8, 8224, 3.4 * 6)
    assert at_reach - outside - hit == 8 * 8224 * 8_064
    assert at_reach == pytest.approx(5.4e9, rel=1e-2)


def test_operations_of_a_token_and_of_a_request(sizes):
    matrices = weights_axk1.n_params(sizes)["matrices_a_token"]
    assert 2 * matrices == pytest.approx(2.75e9, rel=2e-3)
    prefill = flops_axk1.prefill_flops(sizes, 8, 8192)
    # causal attention once, at a mean reach of 4,096: 1.17 GFLOP a token
    attention = 2 * (8192 * 8192 / 2) * 64 * 320 * 7
    assert attention / 8192 == pytest.approx(1.17e9, rel=5e-3)
    assert prefill == 8 * (2 * matrices * 8192 + attention
                           + 2 * 20_480 * 7168)
    # a sixteenth of the picks falls on the experts held: half an expert
    # a token a layer, 17.3 TFLOP a request; ISSUE 41's 275 in all
    held = 65_536 * 8 * 6 / 16
    assert flops_axk1.expert_flops(sizes, held) == pytest.approx(
        17.3e12, rel=2e-3)
    assert prefill + flops_axk1.expert_flops(sizes, held) \
        == pytest.approx(275e12, rel=2e-3)
    # the same whatever the chunks: nothing re-expanded is counted
    step = flops_axk1.decode_step_flops(sizes, 8, 8200)
    assert step == 8 * (2 * (matrices + 20_480 * 7168)
                        + 2 * 8200 * 64 * (512 + 512 + 64) * 7)
    whole = flops_axk1.request_flops(sizes, 8, 8192, 64, held)
    assert whole == prefill + sum(
        flops_axk1.decode_step_flops(sizes, 8, 8192 + t)
        for t in range(1, 64)) + flops_axk1.expert_flops(
            sizes, held * 8255 / 8256)
    assert whole == pytest.approx(280.3e12, rel=5e-3)


def _record(config, loops, **request):
    return {
        "config": config, "new_tokens": 8 * 64,
        "cell": {"name": CELL}, "trace": {"busy_s": 1.0},
        "traffic": {"new_tokens": 64,
                    "trace": {"skip_requests": 2, "requests": 2}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "requests": [
            dict({"index": i, "prompt_len": 8192, "rows": 8,
                  "posted": 10.0 * i, "seen": 10.0 * i + 4.0,
                  "shared_experts": 1, "picks_held": 196_000 + 100 * i,
                  "experts_hit_decode": 1_300 + 10 * i}, **request)
            for i in range(5)],
        "trace_loops": {"decode_loops": loops},
    }


def test_the_readers_on_a_hand_made_record(config, sizes, monkeypatch):
    from benchmarks import scope_times

    manifest = cells.load_manifest()

    def read(name, record):
        return cells.load_module(manifest, "layer_metrics", name).read(record)

    loops = [{"seconds": 64 * 0.0080, "before_s": 3.40, "inner_loops": 6},
             {"seconds": 64 * 0.0084, "before_s": 3.44, "inner_loops": 6}]
    record = _record(config, loops)
    # the traced requests are the third and the fourth
    hit = (1_320 + 1_330) / 2 / 64
    need = flops_axk1.decode_step_bytes(sizes, 8, 8192 + 32.5, hit)
    assert read("decode_hbm_share.serve_axk1", record) == pytest.approx(
        100 * need / 819e9 / 0.0082)
    assert 75 < read("decode_hbm_share.serve_axk1", record) < 85
    ops = sum(flops_axk1.prefill_flops(sizes, 8, 8192)
              + flops_axk1.expert_flops(sizes, held * 8192 / 8256)
              for held in (196_200, 196_300))
    assert read("prefill_mfu.serve_axk1", record) == pytest.approx(
        100 * ops / 6.84 / 197e12)
    assert 38 < read("prefill_mfu.serve_axk1", record) < 42
    whole = sum(flops_axk1.request_flops(sizes, 8, 8192, 64, r["picks_held"])
                for r in record["requests"])
    assert read("step_mfu.serve_axk1", record) == pytest.approx(
        100 * whole / 20.0 / 197e12)
    assert 34 < read("step_mfu.serve_axk1", record) < 37
    # the two readers by scope, on a hand-made reduction
    times = {"chip": "tpu0", "busy_s": 10.0, "covered_s": 9.8, "by_scope": {
        "prefill/attention": 5.0, "prefill/feed_forward": 1.0,
        "prefill/experts": 0.9, "prefill/router": 0.1, "prefill/head": 0.5,
        "decode_step/attention": 0.8, "decode_step/experts": 0.45,
        "decode_step/router": 0.05, "decode_step/feed_forward": 0.3,
        "-/-": 0.2}}
    monkeypatch.setattr(scope_times, "load", lambda out_dir: times)
    assert read("latent_prefill_share.serve_axk1", record) \
        == pytest.approx(100 * 5.0 / 7.5)
    assert read("expert_share.serve_axk1", record) == pytest.approx(15.0)
    times["covered_s"] = 8.0  # under 90% coverage the scopes are silent
    assert read("latent_prefill_share.serve_axk1", record) is None
    assert read("expert_share.serve_axk1", record) is None
    # the accepted readers of the serve cells read this record too
    e2e = cells.load_module(manifest, "end_to_end", "serve_tokens_per_s")
    assert e2e.read(dict(record, window_s=20.0)) == 5 * 512 / 20.0
    # nothing to read is nothing reported, never an error: a run that was
    # not traced, a program that returns no such counters (the parent's
    # longcat replies have picks_held and no shared_experts), a trace
    # that holds another number of decode loops than were traced
    silent = dict(record, trace_loops=None, trace=None)
    for name in NEW[1:]:
        assert read(name, silent) is None
        assert read(name, {}) is None
    for name in NEW[1:3]:
        assert read(name, _record(config, loops[:1])) is None
    bare = _record(config, loops)
    for r in bare["requests"]:
        del r["shared_experts"]
    for name in NEW[:3]:
        assert read(name, bare) is None
    assert read("step_mfu.serve_axk1", {}) is None


def test_the_guest_tells_the_decode_loop_from_prefills_loops():
    """Prefill holds a ``while`` an expert layer a chunk (the grouped
    product's row tiles) and attention's blocks of rows and queries; the
    guest keeps a run's one long loop, the 64 steps, and gives what came
    before it, those loops among it, to its prefill."""
    guest = cells.load_module(cells.load_manifest(), "guests", "serve_axk1")
    ms = 10**6  # the trace counts nanoseconds
    events = [["fusion.1 bf16[8,1024,7168]", 0, 100 * ms],
              ["while.1 s32[]", 100 * ms, 40 * ms],    # attention's blocks
              ["while.2 s32[]", 105 * ms, 10 * ms],    # a row's queries
              ["while.3 s32[]", 150 * ms, 20 * ms],    # an expert layer's
              ["fusion.2 bf16[8,1024,7168]", 170 * ms, 30 * ms],
              ["while.4 s32[]", 200 * ms, 500 * ms],   # the decode loop
              ["while.5 s32[]", 210 * ms, 5 * ms],     # a step's tiles
              ["fusion.3 bf16[8,7168]", 220 * ms, 60 * ms]]
    compact = {"devices": {"/device:TPU:0": events}}
    (decode,) = guest.decode_loops(
        trace_loops.reduce_loops(compact))["decode_loops"]
    assert decode["seconds"] == pytest.approx(0.5)
    assert decode["before_s"] == pytest.approx(0.1 + 0.04 + 0.02 + 0.03)
