"""The looped decoder's yardstick (``benchmarks/flops_ouro.py``,
``benchmarks/weights_ouro.py``) against the arithmetic ISSUE 27 and
``PERF.md`` state by hand, and the readers on a hand-made record."""

import json
import os

import pytest

from benchmarks import cells, flops_ouro, weights_ouro

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return weights_ouro.sizes_of(config)


def test_the_file_holds_every_number_of_the_catalog_row(config):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert config["source"] == row["source_url"]
    assert {k: config[k] for k in row["config"]} == row["config"]
    assert config["reduced"] == []
    assert config["param_dtype"] == config["compute_dtype"] == "bfloat16"


def test_parameter_count(sizes):
    p = weights_ouro.n_params(sizes)
    # wqkv 3·2048², wo 2048², gate, up and down 3·2048·5632, four scales
    assert p["block_matmul"] == 12_582_912 + 4_194_304 + 34_603_008
    assert p["block"] == 51_388_416
    assert p["embed"] == p["lm_head"] == 100_663_296
    assert p["exit_gate"] == 2_049
    assert p["total"] == 2_667_974_657


def test_a_cached_step_reads_the_stack_once_a_pass_and_four_caches(sizes):
    # 1.5 MiB of cache a position: 8 KiB a layer a pass, 48 layers, 4 passes
    assert flops_ouro.cache_bytes_per_position(sizes) == 1_572_864
    weights_only = flops_ouro.decode_step_bytes(sizes, 0)
    assert weights_only == 2 * (4 * 48 * 51_380_224 + 100_663_296 + 2_049)
    assert weights_only == pytest.approx(19.93e9, rel=1e-3)
    at_1100 = flops_ouro.decode_step_bytes(sizes, 1100)
    assert at_1100 - weights_only == 1100 * 1_572_864
    assert (at_1100 - weights_only) / at_1100 == pytest.approx(0.08, abs=0.01)


def test_operations_of_a_token_and_of_a_request(sizes):
    stack = 4 * 48 * 51_380_224
    assert flops_ouro.stack_params(sizes) == stack
    # 19.7 GFLOP a token before attention
    assert 2 * stack == pytest.approx(19.73e9, rel=1e-3)
    step = flops_ouro.decode_step_flops(sizes, 300)
    assert step == (2 * (stack + 100_663_296) + 4 * 300 * 2048 * 48 * 4
                    + 2 * 2048 * 4)
    prefill = flops_ouro.prefill_flops(sizes, 256)
    assert prefill == (2 * stack * 256 + 2 * 256 * 256 * 2048 * 48 * 4
                       + 2 * 2048 * 256 * 4 + 2 * 100_663_296)
    # a request: prefill yields the first token, 63 cached steps the rest
    assert flops_ouro.request_flops(sizes, 256, 64) == prefill + sum(
        flops_ouro.decode_step_flops(sizes, 256 + t) for t in range(1, 64))
    # a pass more is a quarter more work: nothing is counted once a call
    fewer = dict(sizes, passes=3)
    assert flops_ouro.stack_params(fewer) * 4 == stack * 3


def test_sizes_refuse_a_block_the_weights_are_not_made_for(config):
    for key, value in (("hidden_act", "gelu"), ("num_key_value_heads", 4),
                       ("head_dim", 64), ("tie_word_embeddings", True),
                       ("sliding_window", 4096)):
        with pytest.raises(ValueError):
            weights_ouro.sizes_of(dict(config, **{key: value}))


def _record(config, loops):
    return {
        "config": config, "new_tokens": 64,
        "traffic": {"trace": {"skip_requests": 2, "requests": 2}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "requests": [
            {"index": i, "prompt_len": n, "posted": 10.0 * i,
             "seen": 10.0 * i + 2.0, "cache_bytes": 1}
            for i, n in enumerate((256, 512, 256, 1024, 256))],
        "trace_loops": {"decode_loops": loops},
    }


def test_the_readers_on_a_hand_made_record(config, sizes):
    manifest = cells.load_manifest()

    def read(name, record):
        return cells.load_module(manifest, "layer_metrics", name).read(record)

    loops = [{"seconds": 64 * 0.030, "before_s": 0.05, "cache_s": 0.096,
              "inner_loops": 64},
             {"seconds": 64 * 0.032, "before_s": 0.20, "cache_s": 0.2048,
              "inner_loops": 64}]
    record = _record(config, loops)
    # the traced requests are the third and the fourth: 256 and 1024
    context = (256 + 1024) / 2 + 32.5
    need = flops_ouro.decode_step_bytes(sizes, context)
    assert read("decode_hbm_share.serve_ouro", record) == pytest.approx(
        100 * need / 819e9 / 0.031)
    assert 80 < read("decode_hbm_share.serve_ouro", record) < 90
    ops = flops_ouro.prefill_flops(sizes, 256) \
        + flops_ouro.prefill_flops(sizes, 1024)
    assert read("prefill_mfu.serve_ouro", record) == pytest.approx(
        100 * ops / 0.25 / 197e12)
    assert read("cache_share.serve_ouro", record) == pytest.approx(
        100 * (0.096 + 0.2048) / (64 * 0.062))
    whole = sum(flops_ouro.request_flops(sizes, r["prompt_len"], 64)
                for r in record["requests"])
    assert read("step_mfu.serve_ouro", record) == pytest.approx(
        100 * whole / 10.0 / 197e12)
    # nothing to read is nothing reported, never an error: a run that was
    # not traced, a reduction told of no cache operation, a trace that
    # holds another number of decode loops than requests were traced
    silent = dict(record, trace_loops=None)
    for name in ("decode_hbm_share.serve_ouro", "prefill_mfu.serve_ouro",
                 "cache_share.serve_ouro"):
        assert read(name, silent) is None
        assert read(name, {}) is None
        assert read(name, _record(config, loops[:1])) is None
    unscoped = [dict(loop, cache_s=None) for loop in loops]
    assert read("cache_share.serve_ouro", _record(config, unscoped)) is None
    assert read("decode_hbm_share.serve_ouro",
                _record(config, unscoped)) is not None
    assert read("step_mfu.serve_ouro", {}) is None
