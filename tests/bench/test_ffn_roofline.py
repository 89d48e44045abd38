"""``ffn_roofline.serve_granite`` on hand-made records: one whose trace
holds the streaming kernel's calls, one with XLA's two fusions in the
kernel's place (the parent's), and runs with nothing to read."""

import json
import os

import pytest

from benchmarks import cells, weights_granite

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# a layer's three matrices in bfloat16
LAYER_BYTES = 2 * 3 * 2048 * 8192
STEPS = 2 * 128  # two traced requests of 128 cached steps


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reader():
    return cells.load_module(cells.load_manifest(), "layer_metrics",
                             "ffn_roofline.serve_granite")


def _record(config, kinds: dict, device_ops: list) -> dict:
    loops = [{"seconds": 128 * 0.02, "before_s": 1.4, "inner_loops": 0}] * 2
    return {
        "config": config, "peaks": PEAKS,
        "traffic": {"new_tokens": 128,
                    "trace": {"skip_requests": 2, "requests": 2}},
        "requests": [{"index": i, "prompt_len": 512, "rows": 64}
                     for i in range(5)],
        "trace_loops": {"decode_loops": loops},
        "trace": {"busiest_chip": "/device:TPU:0",
                  "kinds_by_chip": {"/device:TPU:0": kinds},
                  "device_ops": device_ops},
    }


def test_the_call_is_a_layers_three_matrices(config, reader):
    sizes = weights_granite.sizes_of(config)
    call = reader.feed_forward_call(sizes, 64)
    assert call == {"flops": 2.0 * 64 * 3 * 2048 * 8192,
                    "bytes": float(LAYER_BYTES)}
    # memory-bound by a factor of 3.8: 122.9 µs of bytes, 32.7 of products
    assert call["bytes"] / 819e9 == pytest.approx(122.9e-6, rel=1e-3)
    assert call["flops"] / 197e12 == pytest.approx(32.7e-6, rel=1e-2)
    # forty of them a step are the 4.03 GB of ISSUE 34
    assert 40 * 3 * 2048 * 8192 * 2 == pytest.approx(4.03e9, rel=1e-3)


RECORDS = {
    # the kernel's calls by their name: 40 a step, 135 µs each
    "kernel": ({"gated_ffn": {"count": 40 * STEPS, "seconds": 40 * STEPS
                              * 135e-6, "own_seconds": 40 * STEPS * 135e-6},
                "fusion": {"count": 9, "seconds": 1.0, "own_seconds": 1.0}},
               [["fusion bf16[64,64,64,128]", 1.68],
                ["gated_ffn bf16[64,2048]", 40 * STEPS * 135e-6]],
               100 * LAYER_BYTES / 819e9 / 135e-6),
    # the ledger's PR 33 line: XLA's two fusions, 6.78 ms a step
    "xla": ({"fusion": {"count": 9, "seconds": 3.0, "own_seconds": 3.0}},
            [["fusion bf16[64,64,64,128]", 1.6832534509999992],
             ["multiply_add_fusion bf16[64,2048]", 1.0710502440000003],
             ["fusion bf16[64,256,2048]", 0.6918290340000004],
             ["fusion bf16[64,8192]", 0.664182032]],
            100 * 40 * STEPS * (LAYER_BYTES / 819e9)
            / (1.0710502440000003 + 0.664182032)),
    # PR 34's traced run: XLA copies gate and up matrices into VMEM ahead
    # of the calls, in quarters; the kernel alone would read 115%
    "kernel_and_waits": (
        {"gated_ffn": {"count": 40 * STEPS, "seconds": 40 * STEPS * 106.9e-6,
                       "own_seconds": 40 * STEPS * 106.9e-6}},
        [["fusion bf16[64,64,64,128]", 1.9],
         ["gated_ffn bf16[64,2048]", 40 * STEPS * 106.9e-6],
         ["slice-done bf16[512,8192]", 0.2756],
         # not the feed-forward's: the in-projection's quarters, the
         # out-projection's (or the down matrix's: not told apart), S's
         ["slice-done bf16[2048,2128]", 0.2724],
         ["slice-done bf16[1024,2048]", 0.142],
         ["slice-done bf16[16,64,64,128]", 0.3],
         ["copy-done bf16[2048,8192]", 0.0041]],
        100 * 40 * STEPS * (LAYER_BYTES / 819e9)
        / (40 * STEPS * 106.9e-6 + 0.2756 + 0.0041)),
    # one of the two fusions is not among the ten operations kept
    "xla_one_missing": (
        {"fusion": {"count": 9, "seconds": 3.0, "own_seconds": 3.0}},
        [["fusion bf16[64,64,64,128]", 1.68],
         ["fusion bf16[64,8192]", 0.66]], None),
}


@pytest.mark.parametrize("case", sorted(RECORDS))
def test_the_reader_on_hand_made_records(case, config, reader):
    kinds, device_ops, want = RECORDS[case]
    got = reader.read(_record(config, kinds, device_ops))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
        assert 0 < got <= 100
    if case == "xla":
        # ISSUE 34's reading of the parent
        assert got == pytest.approx(72.6, abs=0.1)
    if case == "kernel":
        assert got == pytest.approx(91.0, abs=0.1)
    if case == "kernel_and_waits":
        assert got == pytest.approx(91.5, abs=0.5)


@pytest.mark.parametrize("case", ["untraced", "empty", "no_peaks",
                                  "one_loop_for_two_requests"])
def test_nothing_to_read_is_nothing_reported(case, config, reader):
    kinds, device_ops, _ = RECORDS["kernel"]
    record = _record(config, kinds, device_ops)
    if case == "untraced":
        record.update(trace=None, trace_loops=None)
    elif case == "empty":
        record = {}
    elif case == "no_peaks":
        record["peaks"] = None
    else:
        record["trace_loops"]["decode_loops"] = \
            record["trace_loops"]["decode_loops"][:1]
    assert reader.read(record) is None


def test_the_manifest_names_the_metric_once_for_the_one_cell():
    manifest = cells.load_manifest()
    entries = [m for m in manifest["per_layer"]
               if m["name"] == "ffn_roofline.serve_granite"]
    assert entries == [{
        "name": "ffn_roofline.serve_granite", "unit": "%",
        "better": "higher", "source": "device_trace",
        "layer": "kernels ops/gated_ffn.py", "moves": "serve_tokens_per_s",
        "workloads": ["serve_granite_1chip"]}]
    assert manifest["per_layer"][-1] == entries[0]
    assert "ffn_roofline.serve_granite" in [
        m["name"] for m in cells.metrics_of(manifest, "per_layer",
                                            "serve_granite_1chip")]
