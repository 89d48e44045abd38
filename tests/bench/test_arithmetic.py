"""The yardstick's arithmetic against values worked out by hand: parameter
and operation counts of Pythia-1.4b, the kernels' operations and bytes, the
peaks table, and the end-to-end readers on a synthetic log with a stall."""

import json
import os

import pytest

from benchmarks import cells, flops, peaks, stats, weights

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D, F, V, S, HD = 2048, 8192, 50304, 2048, 128


def sizes(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
        return weights.sizes_of(json.load(f))


def test_parameter_counts_of_pythia_1_4b():
    n = weights.n_params(sizes("pythia-1.4b"))
    assert n["block_matmul"] == 4 * D * D + 2 * D * F == 50_331_648
    assert n["block"] == 50_331_648 + 2 * D
    assert n["embed"] == n["lm_head"] == V * D == 103_022_592
    assert n["total"] == 24 * 50_335_744 + 2 * 103_022_592 + D \
        == 1_414_105_088
    assert n["matmul"] == 24 * 50_331_648 + 103_022_592
    shallow = weights.n_params(sizes("pythia-1.4b-shallow"))
    assert shallow["total"] == 8 * 50_335_744 + 2 * 103_022_592 + D \
        == 608_733_184


@pytest.mark.parametrize("config, per_token", [
    # 6 x (layers x 50,331,648 + 103,022,592) + 6 x 2048 x 2048 x layers
    ("pythia-1.4b-shallow", 6 * 505_675_776 + 201_326_592),
    ("pythia-1.4b", 6 * 1_310_982_144 + 603_979_776),
])
def test_train_operations_a_token(config, per_token):
    got = flops.train_flops_per_token(sizes(config), S)
    assert got == per_token
    assert round(got / 1e9, 2) == {"pythia-1.4b-shallow": 3.24,
                                   "pythia-1.4b": 8.47}[config]


def test_serving_operations_and_bytes():
    sz = sizes("pythia-1.4b")
    matmul = 1_310_982_144
    assert flops.prefill_flops(sz, 128) == (
        2 * 24 * 50_331_648 * 128 + 2 * 128 * 128 * D * 24
        + 2 * 103_022_592)
    assert flops.decode_step_flops(sz, 300) == 2 * matmul + 4 * 300 * D * 24
    # prefill gives token 1; tokens 2..64 are 63 cached steps at contexts
    # prompt+1 .. prompt+63
    want = flops.prefill_flops(sz, 128) + sum(
        2 * matmul + 4 * (128 + t) * D * 24 for t in range(1, 64))
    assert flops.request_flops(sz, 128, 64) == want
    # a step reads every matmul weight once in bf16, and keys and values
    assert flops.decode_step_bytes(sz, 300) == \
        2 * matmul + 2 * 300 * D * 24 * 2
    assert flops.decode_step_bytes(sz, 300, weight_bytes=4) == \
        4 * matmul + 2 * 300 * D * 24 * 2


def test_flash_calls_and_their_roofline():
    b, h = 4, 16
    pairs = b * h * S * S / 2
    fwd = flops.flash_fwd_call(b, S, h, HD)
    assert fwd["flops"] == 4 * pairs * HD == 68_719_476_736
    assert fwd["bytes"] == 4 * b * S * h * HD * 2 + 4 * b * h * S
    dq = flops.flash_bwd_dq_call(b, S, h, HD)
    dkv = flops.flash_bwd_dkv_call(b, S, h, HD)
    assert dq["flops"] == 6 * pairs * HD and dkv["flops"] == 8 * pairs * HD
    assert dq["bytes"] == 5 * b * S * h * HD * 2 + 8 * b * h * S
    assert dkv["bytes"] == 6 * b * S * h * HD * 2 + 8 * b * h * S
    v5e = peaks.peaks_for("TPU v5 lite")
    least = flops.least_seconds(fwd, v5e)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(68_719_476_736 / 197e12)
    # few operations over many bytes: the memory peak bounds it
    thin = flops.least_seconds({"flops": 1e6, "bytes": 819e6}, v5e)
    assert thin == {"seconds": pytest.approx(1e-3), "bound": "memory"}


def test_peaks_table_knows_the_v5e_and_refuses_what_it_does_not_know():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bytes_per_s"] == 1600e9 / 8
    assert "source" in v5e
    with pytest.raises(KeyError, match="TPU v9"):
        peaks.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_percentile():
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(1, 12)), 90) == 10
    assert stats.percentile([10, 20], 25) == 12.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.tokens_per_s(10, 0.0)


def serve_record(stall_s=0.0, fail_last=False):
    """100 requests of 0.5 s back to back; request 40 may stall."""
    log, t = [], 1000.0
    for i in range(100):
        took = 0.5 + (stall_s if i == 40 else 0.0)
        log.append({"index": i, "prompt_len": 128, "posted": t,
                    "seen": t + took, "guest_start": t + 0.003,
                    "guest_end": t + took - 0.004})
        t += took
    if fail_last:
        log[-1]["failed"] = "refused"
    return {"requests": log, "new_tokens": 64, "window_s": t - 1000.0,
            "setup_s": 12.0}


def read(kind, name, record):
    return cells.load_module(cells.load_manifest(), kind, name).read(record)


def test_a_stall_moves_the_rate_and_the_tail_and_not_the_median():
    smooth, stalled = serve_record(), serve_record(stall_s=10.0)
    assert read("end_to_end", "serve_tokens_per_s", smooth) == \
        pytest.approx(100 * 64 / 50.0)
    assert read("end_to_end", "serve_tokens_per_s", stalled) == \
        pytest.approx(100 * 64 / 60.0)
    assert read("end_to_end", "request_p50_ms", smooth) == pytest.approx(500)
    assert read("end_to_end", "request_p50_ms", stalled) == \
        pytest.approx(500)
    assert read("layer_metrics", "request_p90_ms.serve", smooth) == pytest.approx(500)
    # one stall in a hundred is beyond the 90th percentile; eleven are not
    many = serve_record()
    for r in many["requests"][:11]:
        r["seen"] += 2.0
    assert read("layer_metrics", "request_p90_ms.serve",
                many) == pytest.approx(2500)
    assert read("layer_metrics", "launch_ms.serve", stalled) == \
        pytest.approx(3.0)
    assert read("layer_metrics", "return_ms.serve", stalled) == \
        pytest.approx(4.0)
    assert read("end_to_end", "setup_s", smooth) == 12.0


def test_a_failed_request_counts_as_the_slowest_and_yields_no_tokens():
    record = serve_record(stall_s=3.0, fail_last=True)
    ms = stats.request_latencies_ms(record["requests"])
    assert ms[-1] == max(ms) == pytest.approx(3500)
    assert read("end_to_end", "serve_tokens_per_s", record) == \
        pytest.approx(99 * 64 / 53.0)


def test_train_rate_is_all_steps_over_the_whole_window():
    steps = [{"step": k, "start": 10.0 + 0.4 * k, "end": 10.4 + 0.4 * k,
              "loss": 10.8} for k in range(50)]
    record = {"steps": steps, "tokens_per_step": 8192, "window_s": 20.0}
    assert read("end_to_end", "train_tokens_per_s", record) == \
        pytest.approx(50 * 8192 / 20.0)
    # a stalled step lengthens the window: the rate falls
    record["window_s"] = 25.0
    assert read("end_to_end", "train_tokens_per_s", record) == \
        pytest.approx(50 * 8192 / 25.0)
    # a reader that finds nothing to read returns nothing
    assert read("end_to_end", "train_tokens_per_s", serve_record()) is None
    assert read("end_to_end", "serve_tokens_per_s", record) is None


def test_whole_step_shares_of_the_peak():
    v5e = peaks.peaks_for("TPU v5 lite")
    with open(os.path.join(REPO, "benchmarks/configs/pythia-1.4b-shallow.json")) as f:
        config = json.load(f)
    # 50 steps of 0.4 s; the profiler's start stalls 5 s between two steps
    steps = [{"start": 10.0 + 0.4 * k + (5.0 if k >= 3 else 0.0),
              "end": 10.4 + 0.4 * k + (5.0 if k >= 3 else 0.0)}
             for k in range(50)]
    record = {"steps": steps, "tokens_per_step": 8192, "window_s": 25.0,
              "peaks": v5e, "config": config, "traffic": {"seq": S},
              "cell": {"chips": 1}}
    want = 100 * 3_235_381_248 * (50 * 8192 / 20.0) / 197e12
    assert read("layer_metrics", "step_mfu.train", record) == \
        pytest.approx(want)
    record["cell"] = {"chips": 4}
    assert read("layer_metrics", "step_mfu.train", record) == \
        pytest.approx(want / 4)
    # without a table of peaks (a rehearsal) there is no share to give
    assert read("layer_metrics", "step_mfu.train",
                dict(record, peaks=None)) is None

    serve = dict(serve_record(), peaks=v5e, config=dict(
        config, num_hidden_layers=24))
    sizes = weights.sizes_of(serve["config"])
    want = 100 * 100 * flops.request_flops(sizes, 128, 64) / 50.0 / 197e12
    assert read("layer_metrics", "step_mfu.serve", serve) == \
        pytest.approx(want)
    # a stall between two requests is no request's time
    for r in serve["requests"][50:]:
        r["posted"] += 7.0
        r["seen"] += 7.0
    assert read("layer_metrics", "step_mfu.serve", serve) == \
        pytest.approx(want)
