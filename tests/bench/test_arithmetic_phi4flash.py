"""The yardstick of a decoder-hybrid-decoder
(``benchmarks/flops_phi4flash.py``, ``benchmarks/weights_phi4flash.py``)
against the arithmetic ISSUE 39 and ``PERF.md`` state by hand, the
configuration file against the catalog's row, every new reader and the
guest's rule for the decode loop on hand-made records (and None where
its input is missing), and the reference against the program on the
rehearsal's toy."""

import json
import os

import pytest

from benchmarks import cells, flops_phi4flash, trace_loops, weights_phi4flash

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve_phi4flash_1chip"
# the catalog's row, /opt/skills/guides/model-configs/architectures.jsonl,
# as the driver drew it for ISSUE 39
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
NEW = ("step_mfu.serve_phi4flash", "decode_hbm_share.serve_phi4flash",
       "prefill_mfu.serve_phi4flash", "mixer_share.serve_phi4flash",
       "scan_share.serve_phi4flash", "attention_roofline.serve_phi4flash")
FFN = 3 * 2560 * 10240
MAMBA1 = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
ATTENTION = 2560 * 5120 + 2560 * 2560
SELF = 9 * (MAMBA1 + FFN) + 9 * (ATTENTION + FFN)
CROSS = 7 * (2 * 2560 * 5120 + FFN) + 7 * (2 * 2560 * 2560 + FFN)
TABLE = 200064 * 2560


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sizes(config):
    return weights_phi4flash.sizes_of(config)


def test_the_file_is_the_catalog_row_whole(config):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Phi-4-mini-flash-reasoning")
        assert row["config"] == PUBLISHED
        assert config["source"] == row["source_url"]
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    assert config["reduced"] == []
    assert (config["param_dtype"], config["compute_dtype"]) == (
        "bfloat16", "bfloat16")
    # every size the source leaves to its class's defaults is named
    for point in ("head_dim", "position", "layer_kinds", "block", "mamba1",
                  "gated_memory", "differential_attention", "window",
                  "cross_attention", "biases", "state", "dtypes", "weights"):
        assert config["assumed"][point], point
    manifest = cells.load_manifest()
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "phi-4-mini-flash-reasoning"]
    assert entry == manifest["configs"][-1] and entry["reduced"] == []
    assert entry["source"] == config["source"]
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, entry["name"], "solve_1caller", 1)
    # the six new entries close ``per_layer``, each for the one cell
    assert [m["name"] for m in manifest["per_layer"][-6:]] == list(NEW)
    assert all(m["workloads"] == [CELL] for m in manifest["per_layer"][-6:])
    reported = {m["name"] for m in
                cells.metrics_of(manifest, "per_layer", CELL)}
    assert set(NEW) | {"scope_coverage.serve", "attention_share.serve",
                       "feed_forward_share.serve", "head_share.serve",
                       "prefill_share.serve", "device_idle_share.serve",
                       "launch_ms.serve", "return_ms.serve",
                       "request_p90_ms.serve"} == reported
    assert {m["name"] for m in cells.metrics_of(manifest, "end_to_end",
                                                CELL)} == {
        "serve_tokens_per_s", "request_p50_ms", "setup_s"}


def test_the_traffic_is_the_issues_to_the_number():
    cell = cells.load_cell(cells.load_manifest(), CELL)
    traffic = cell["traffic_values"]
    assert cell["guest"] == "serve_phi4flash"
    assert (traffic["rows"], traffic["new_tokens"], traffic["prefill_chunk"],
            traffic["poll_ms"]) == (64, 256, 256, 5)
    assert traffic["prompt_lengths"] == [{"tokens": 512, "count": 1}]
    assert traffic["trace"] == {"skip_requests": 2, "requests": 2}
    check = traffic["check"]
    assert (check["sample_requests"], check["sample_rows"]) == (2, 4)
    assert set(check["limits"]) == {"phi-4-mini-flash-reasoning"}
    assert check["limits"]["phi-4-mini-flash-reasoning"][
        "malformed_answers"] == 0


@pytest.mark.parametrize("broken", [
    {"hidden_act": "gelu"}, {"mlp_bias": True}, {"lm_head_bias": True},
    {"tie_word_embeddings": False}, {"model_type": "phi3"},
    {"num_attention_heads": 39}, {"num_key_value_heads": 15},
    {"mb_per_layer": 3}, {"num_hidden_layers": 6}])
def test_what_the_weights_are_not_made_for_is_refused(config, broken):
    with pytest.raises(ValueError):
        weights_phi4flash.sizes_of({**config, **broken})


def test_parameter_counts(sizes):
    """ISSUE 39's: feed-forward 78.64 M a layer, Mamba-1 41.2 M, attention
    19.66 M, gated memory 26.21 M, cross 13.11 M, the table 512.2 M."""
    p = weights_phi4flash.n_params(sizes)
    assert sizes["layer_kinds"] == (("mamba1", "window") * 8
                                    + ("mamba1", "full")
                                    + ("memory", "cross") * 7)
    assert (sizes["ssm_inner"], sizes["ssm_d_state"], sizes["ssm_d_conv"],
            sizes["ssm_dt_rank"], sizes["head_dim"], sizes["window"],
            sizes["memory_source"]) == (5120, 16, 4, 160, 64, 512, 16)
    assert p["ffn"] == FFN == 78_643_200
    assert p["mixer_matrices"] == {
        "mamba1": MAMBA1, "window": ATTENTION, "full": ATTENTION,
        "cross": 2 * 2560 * 2560, "memory": 2 * 2560 * 5120}
    assert MAMBA1 == 41_123_840 and ATTENTION == 19_660_800
    # with the convolution, dt_bias, A_log and D; the biases, four λ
    # vectors and the sub-norm
    assert p["mixer"]["mamba1"] == MAMBA1 + 4 * 5120 + 5120 + 5120 \
        + 5120 * 16 + 5120
    assert p["mixer"]["window"] == ATTENTION + 5120 + 2560 + 4 * 64 + 128
    assert p["mixer"]["cross"] == 2 * 2560 * 2560 + 2560 + 2560 + 384
    assert p["layers"] == {"mamba1": 9, "window": 8, "full": 1, "cross": 7,
                           "memory": 7}
    assert p["matmul"] == SELF + CROSS
    assert p["embed"] == TABLE == 512_163_840
    assert p["total"] == 3_852_562_944
    assert p["total"] * 2 == pytest.approx(7.705e9, rel=1e-3)


def test_operations_of_a_request(sizes):
    """ISSUE 39's 3.93 of 6.68 GFLOP a prompt token, 129 TFLOP of
    matrices at prefill where the whole stack would be 219."""
    assert 2 * SELF == pytest.approx(3.925e9, rel=1e-3)
    assert 2 * (SELF + CROSS) == pytest.approx(6.678e9, rel=1e-3)
    recurrence = 7 * 5120 * 16 + 2 * 4 * 5120
    assert flops_phi4flash.recurrence_flops_per_token(sizes) == recurrence
    # a query's scores over 64 lanes and sums over 128, every head
    assert flops_phi4flash.attention_flops(sizes, 100) \
        == 2 * 100 * 40 * (64 + 128)
    assert flops_phi4flash.attended(sizes, "window", 600) == 512
    assert flops_phi4flash.attended(sizes, "window", 100) == 100
    assert flops_phi4flash.attended(sizes, "cross", 600) == 600
    causal = sum(range(1, 513))
    prefill = flops_phi4flash.prefill_flops(sizes, 64, 512)
    last = 2 * (CROSS + TABLE) + 7 * 2 * 512 * 40 * 192
    assert prefill == pytest.approx(64 * (
        2 * SELF * 512 + (8 + 1) * 2 * causal * 40 * 192
        + recurrence * 512 * 9 + last), rel=1e-12)
    assert 64 * 2 * SELF * 512 == pytest.approx(128.6e12, rel=1e-3)
    assert prefill == pytest.approx(130.2e12, rel=1e-3)
    whole = flops_phi4flash.whole_stack_prefill_flops(sizes, 64, 512)
    assert whole == pytest.approx(221.1e12, rel=1e-3)
    step = flops_phi4flash.decode_step_flops(sizes, 64, 600)
    assert step == 64 * (2 * (SELF + CROSS + TABLE)
                         + 2 * 40 * 192 * (8 * 512 + 8 * 600)
                         + recurrence * 9)
    request = flops_phi4flash.request_flops(sizes, 64, 512, 256)
    assert request == prefill + sum(
        flops_phi4flash.decode_step_flops(sizes, 64, 512 + t)
        for t in range(1, 256))
    assert request == pytest.approx(258.3e12, rel=1e-3)


def test_a_cached_step_reads_the_written_slots_and_the_shared_cache_8_times(
        sizes):
    """ISSUE 39's 10.9 GB a step at the mean reach: 7.70 of weights, 1.34
    of window caches, 8 × 0.21 of the one shared cache, 0.2 of state."""
    assert flops_phi4flash.kv_bytes_per_position(sizes) == 5120
    assert flops_phi4flash.state_bytes(sizes, 64) == 9 * 64 * 5120 * 16 * 2
    assert flops_phi4flash.window_bytes(sizes, 64) == 9 * 64 * 3 * 5120 * 2
    attended = flops_phi4flash.attended_bytes(sizes, 64, 640.5)
    assert attended == 5120 * 64 * (8 * 512 + 8 * 640.5)
    assert 5120 * 64 * 8 * 512 == pytest.approx(1.342e9, rel=1e-3)
    assert 5120 * 64 * 640.5 == pytest.approx(0.2099e9, rel=1e-3)
    need = flops_phi4flash.decode_step_bytes(sizes, 64, 640.5)
    assert need == (3_852_562_944 * 2 + 2 * 9 * 64 * 5120 * 16 * 2
                    + 9 * 64 * 4 * 5120 * 2 + attended + 5120 * 64 * 9)
    assert need == pytest.approx(10.94e9, rel=1e-3)
    assert need / 819e9 == pytest.approx(13.36e-3, rel=1e-3)
    # a ring that is not full yet is read as far as it is written
    assert flops_phi4flash.attended_bytes(sizes, 64, 100) \
        == 5120 * 64 * 16 * 100


def _record(config, loops, **request):
    kernel_s = 2 * 256 * 16 * 260e-6
    return {
        "config": config, "new_tokens": 64 * 256,
        "cell": {"name": "no_such_cell"},
        "traffic": {"new_tokens": 256,
                    "trace": {"skip_requests": 2, "requests": 2}},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "requests": [
            dict({"index": i, "prompt_len": 512, "rows": 64,
                  "posted": 10.0 * i, "seen": 10.0 * i + 5.0,
                  "window_slots": 512, "attention_streamed_layers": 16,
                  "state_bytes": 112_066_560}, **request)
            for i in range(5)],
        "trace_loops": {"decode_loops": loops},
        "trace": {"busiest_chip": "tpu0", "kinds_by_chip": {"tpu0": {
            "cached_attention": {"count": 2 * 256 * 16,
                                 "seconds": kernel_s}}}},
    }


def test_the_readers_on_a_hand_made_record(config, sizes):
    manifest = cells.load_manifest()

    def read(name, record):
        return cells.load_module(manifest, "layer_metrics", name).read(record)

    loops = [{"seconds": 256 * 0.015, "before_s": 1.2, "scan_s": 0.3},
             {"seconds": 256 * 0.016, "before_s": 1.3, "scan_s": 0.3}]
    record = _record(config, loops)
    need = flops_phi4flash.decode_step_bytes(sizes, 64, 512 + 128.5)
    assert read("decode_hbm_share.serve_phi4flash", record) \
        == pytest.approx(100 * need / 819e9 / 0.0155)
    assert 80 < read("decode_hbm_share.serve_phi4flash", record) < 90
    assert read("prefill_mfu.serve_phi4flash", record) == pytest.approx(
        100 * 2 * flops_phi4flash.prefill_flops(sizes, 64, 512) / 2.5
        / 197e12)
    whole = 5 * flops_phi4flash.request_flops(sizes, 64, 512, 256)
    assert read("step_mfu.serve_phi4flash", record) == pytest.approx(
        100 * whole / 25.0 / 197e12)
    assert 25 < read("step_mfu.serve_phi4flash", record) < 30
    # the kernel: 16 calls a step share the written slots' bytes at the
    # mean reach; allocated slots are more, so a perfect stream of them
    # reads under 100
    attended = flops_phi4flash.attended_bytes(sizes, 64, 640.5)
    assert read("attention_roofline.serve_phi4flash", record) \
        == pytest.approx(100 * (attended / 819e9 / 16) / 260e-6)
    assert 85 < read("attention_roofline.serve_phi4flash", record) < 90
    allocated = 5120 * 64 * (8 * 512 + 8 * 768)
    perfect = dict(record, trace={"busiest_chip": "tpu0", "kinds_by_chip": {
        "tpu0": {"cached_attention": {
            "count": 8192, "seconds": 512 * allocated / 819e9}}}})
    assert read("attention_roofline.serve_phi4flash", perfect) \
        == pytest.approx(100 * (8 * 512 + 8 * 640.5) / (8 * 512 + 8 * 768))
    # the accepted readers of the serve cells read this record too
    e2e = cells.load_module(manifest, "end_to_end", "serve_tokens_per_s")
    assert e2e.read(dict(record, window_s=25.0)) == 5 * 64 * 256 / 25.0
    # nothing to read is nothing reported, never an error: a run that was
    # not traced, a program that returns no counters (the parent's), a
    # trace that holds another number of decode loops than were traced,
    # a trace without the kernel, no scope times
    traced = ("decode_hbm_share.serve_phi4flash",
              "prefill_mfu.serve_phi4flash",
              "attention_roofline.serve_phi4flash")
    for name in traced:
        assert read(name, dict(record, trace_loops=None)) is None
        assert read(name, {}) is None
        assert read(name, _record(config, loops[:1])) is None
    bare = _record(config, loops)
    for r in bare["requests"]:
        del r["window_slots"], r["attention_streamed_layers"]
    for name in traced + ("step_mfu.serve_phi4flash",):
        assert read(name, bare) is None
    assert read("attention_roofline.serve_phi4flash", dict(
        record, trace={"busiest_chip": "tpu0",
                       "kinds_by_chip": {"tpu0": {}}})) is None
    assert read("step_mfu.serve_phi4flash", {}) is None
    for name in ("mixer_share.serve_phi4flash", "scan_share.serve_phi4flash"):
        assert read(name, {}) is None
        assert read(name, dict(record, trace=None)) is None


def test_the_scope_readers_on_a_hand_made_reduction(config, monkeypatch):
    """``mixer_share`` and ``scan_share`` by ``scope_times``'s reduction:
    the mixers' own time under a phase over the phase's."""
    from benchmarks import scope_times

    times = {"chip": "tpu0", "busy_s": 10.0, "covered_s": 9.8, "by_scope": {
        "decode_step/mixer": 1.5, "decode_step/attention": 2.5,
        "decode_step/feed_forward": 3.0, "decode_step/head": 0.5,
        "prefill/mixer": 0.9, "prefill/attention": 0.6,
        "prefill/feed_forward": 0.8, "-/-": 0.2}}
    monkeypatch.setattr(scope_times, "load", lambda out_dir: times)
    manifest = cells.load_manifest()
    record = _record(config, [])

    def read(name):
        return cells.load_module(manifest, "layer_metrics", name).read(record)

    assert read("mixer_share.serve_phi4flash") == pytest.approx(20.0)
    assert read("scan_share.serve_phi4flash") == pytest.approx(
        100 * 0.9 / 2.3)
    # under 90% coverage every reader of the scopes is silent
    times["covered_s"] = 8.0
    assert read("mixer_share.serve_phi4flash") is None
    assert read("scan_share.serve_phi4flash") is None


def test_the_guest_tells_the_decode_loop_from_prefills_scans():
    """Prefill holds a ``while`` a Mamba-1 layer a chunk (the scan along
    positions); ``decode_loops`` keeps a run's one long loop and gives
    what came before it, the scans among it, to its prefill."""
    guest = cells.load_module(cells.load_manifest(), "guests",
                              "serve_phi4flash")
    ms = 10**6  # the trace counts nanoseconds
    events, at = [], 0
    for _run in range(2):
        for _scan in range(18):
            events.append(["fusion.1 bf16[64,256,10240]", at, 20 * ms])
            events.append(["while.3 s32[]", at + 20 * ms, 15 * ms])
            events.append(["fusion.2 f32[64,16,5120]", at + 21 * ms, 10 * ms])
            at += 40 * ms                         # 5 ms idle a scan
        events.append(["while.9 s32[]", at, 4000 * ms])
        events.append(["cached_attention.4 bf16[64,40,128]", at + ms,
                       2 * ms])
        at += 4010 * ms
    found = guest.decode_loops(trace_loops.reduce_loops(
        {"devices": {"/device:TPU:0": events}}))
    assert found["outermost_whiles"] == 38
    first, second = found["decode_loops"]
    for loop in (first, second):
        assert loop["seconds"] == pytest.approx(4.0)
        assert loop["before_s"] == pytest.approx(18 * 0.035)
        assert loop["scan_s"] == pytest.approx(18 * 0.015)
        assert loop["scans"] == 18
    record = {"trace_loops": found, "requests": [{}] * 5,
              "traffic": {"trace": {"skip_requests": 2, "requests": 2}}}
    assert trace_loops.traced(record) is not None
    assert guest.decode_loops({"decode_loops": []}) == {"decode_loops": []}


def test_the_reference_is_the_program_on_the_toy():
    """The rehearsal's configuration file through ``program_phi4flash``
    and ``weights_phi4flash``: the program's forward pass and the
    reference agree to rounding; ``tests/unit/test_phi4flash.py`` has the
    cached paths, the rings, the skip and the kernel."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program_phi4flash
    from benchmarks.reference import phi4flash as ref
    from faabric_tpu.models import forward

    with open(os.path.join(REPO, "tests", "bench", "data", "configs",
                           "toy_phi4flash.json")) as f:
        toy = json.load(f)
    sz = weights_phi4flash.sizes_of(toy)
    cfg = dataclasses.replace(program_phi4flash.model_config(toy),
                              remat=False)
    assert cfg.layer_types == (
        ("mamba1", "window_attention") * 3
        + ("mamba1", "attention", "gated_memory", "cross_attention",
           "gated_memory", "cross_attention"))
    assert (cfg.cache_source, cfg.memory_source, cfg.sliding_window,
            cfg.differential, cfg.norm, cfg.attention_bias,
            cfg.tie_embeddings, cfg.position) == (
        7, 6, 16, True, "layer", True, True, "none")
    params = weights_phi4flash.make_weights(7, sz, jnp.float32)
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == weights_phi4flash.n_params(sz)["total"]
    tokens = jnp.asarray(weights_phi4flash.token_rows(7, 1, 0, 2, 37, 512))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits_of_rows(params, tokens, sz))
        got = np.asarray(forward(params, tokens, cfg))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * np.abs(want).max())
