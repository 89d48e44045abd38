"""The reduction from a device trace to numbers, on a small synthetic trace
in the compact form ``trace_reduce.load_xplane`` gives (names as a v5e
writes them: whole HLO instructions on the ``XLA Ops`` line), and the
per-layer readers that read the reduced trace."""

import pytest

from benchmarks import cells, peaks, trace_reduce

MS = 1_000_000  # ns


def op(text, start_ms, dur_ms):
    return [" ".join(trace_reduce.instruction_of(text)).strip(),
            int(start_ms * MS), int(dur_ms * MS)]


FUSION = ("%fusion.12 = bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)} "
          "fusion(bf16[4,2048,2048]{2,1,0} %p.1), kind=kLoop")
FLASH = ('%flash_fwd.3 = (bf16[4,16,2048,128]{3,2,1,0}, f32[4,16,2048]) '
         'custom-call(%a, %b, %c), custom_call_target="tpu_custom_call"')
WHILE = "%while.7 = (s32[], bf16[1,2048]{1,0}) while(%tuple.1), body=%b"
ALLREDUCE = "%all-reduce.5 = f32[2048,2048]{1,0} all-reduce(%x), to_apply=%s"


def compact():
    """Two steps of 100 ms on two chips. Chip 0: busy 10-50 and 60-100 of
    step one (a gap inside the step), 110-200 of step two (a gap between
    the steps, 100-110). Chip 1 is busy half as long."""
    return {
        "host": [["bench:step#3", 0 * MS, 100 * MS],
                 ["bench:step#4", 100 * MS, 100 * MS]],
        "devices": {
            "/device:TPU:0": [
                op(FUSION, 10, 40),
                op(FLASH, 60, 40),
                op(WHILE, 110, 90),
                # the loop's body lies inside the loop's span
                op(FUSION, 120, 30),
                op(ALLREDUCE, 150, 20),
                # before the window: cut away
                op(FUSION, -50, 20),
            ],
            "/device:TPU:1": [op(FUSION, 0, 50), op(FLASH, 100, 50)],
        },
    }


def test_instruction_names_and_kinds():
    assert trace_reduce.instruction_of(FUSION) == (
        "fusion.12", "bf16[4,2048,2048]")
    assert trace_reduce.instruction_of(FLASH) == (
        "flash_fwd.3", "bf16[4,16,2048,128]")
    assert trace_reduce.instruction_of(WHILE)[0] == "while.7"
    assert trace_reduce.kind_of("fusion.12") == "fusion"
    assert trace_reduce.kind_of("flash_bwd_dq.7.clone") == "flash_bwd_dq"
    assert trace_reduce.kind_of("all-reduce.5") == "all-reduce"
    assert trace_reduce.kind_of("rms_norm.49.remat2") == "rms_norm"
    assert trace_reduce.kind_of("copy") == "copy"


def test_busy_idle_and_the_window():
    r = trace_reduce.reduce(compact())
    assert r["window_s"] == pytest.approx(0.2)
    by_chip = r["busy_s_by_chip"]
    assert by_chip["/device:TPU:0"] == pytest.approx(0.17)
    assert by_chip["/device:TPU:1"] == pytest.approx(0.10)
    assert r["busy_s"] == pytest.approx(0.135)  # mean over the chips
    assert r["busiest_chip"] == "/device:TPU:0"


def test_time_by_kind_counts_a_loop_and_its_body_once():
    r = trace_reduce.reduce(compact())
    k = trace_reduce.kinds(r)
    assert k["while"]["count"] == 1
    assert k["while"]["seconds"] == pytest.approx(0.09)
    assert k["while"]["own_seconds"] == pytest.approx(0.04)
    assert k["fusion"]["count"] == 2  # the one before the window is cut
    assert k["fusion"]["own_seconds"] == pytest.approx(0.07)
    assert k["flash_fwd"]["seconds"] == pytest.approx(0.04)
    assert k["all-reduce"]["own_seconds"] == pytest.approx(0.02)
    own = sum(v["own_seconds"] for v in k.values())
    assert own == pytest.approx(r["busy_s_by_chip"]["/device:TPU:0"])
    assert trace_reduce.kinds(r, "/device:TPU:1")["flash_fwd"]["count"] == 1
    ops = dict(r["device_ops"])
    assert len(r["device_ops"]) <= 10
    assert ops["fusion bf16[4,2048,2048]"] == pytest.approx(0.07)
    assert ops["flash_fwd bf16[4,16,2048,128]"] == pytest.approx(0.04)
    assert all(len(name) < 80 for name in ops)
    assert list(ops.values()) == sorted(ops.values(), reverse=True)


def test_idle_gaps_are_labelled_by_what_the_host_was_doing():
    r = trace_reduce.reduce(compact())
    gaps = dict(r["idle_gaps"])
    # 0-10 and 50-60 inside step 3's span, 100-110 at the head of step 4's
    assert gaps == {"inside bench:step": pytest.approx(0.03)}
    c = compact()
    c["host"] = [["bench:request#1", 10 * MS, 40 * MS],
                 ["bench:request#2", 60 * MS, 140 * MS]]
    gaps = dict(trace_reduce.reduce(c)["idle_gaps"])
    assert gaps["between bench:request and bench:request"] == \
        pytest.approx(0.01)
    assert gaps["inside bench:request"] == pytest.approx(0.01)


def test_a_trace_with_no_device_operation_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce({"host": [["bench:step#1", 0, 5]],
                             "devices": {"/device:TPU:0": []}})


def test_without_host_spans_the_window_is_the_device_events_extent():
    c = compact()
    c["host"] = []
    r = trace_reduce.reduce(c)
    assert r["window_s"] == pytest.approx(0.25)
    assert dict(r["idle_gaps"]) == {"outside any span": pytest.approx(0.06)}


def read(name, record):
    return cells.load_module(cells.load_manifest(), "layer_metrics",
                             name).read(record)


def train_record():
    return {"trace": trace_reduce.reduce(compact()), "steps": [{}] * 2,
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "cell": {"chips": 1},
            "traffic": {"batch": 4, "seq": 2048, "tp": 1},
            "config": {"hidden_size": 2048, "num_attention_heads": 16,
                       "num_hidden_layers": 8, "intermediate_size": 8192,
                       "vocab_size": 50304, "max_position_embeddings": 2048,
                       "rotary_emb_base": 10000}}


def test_readers_of_the_reduced_trace():
    record = train_record()
    assert read("device_idle_share.train", record) == \
        pytest.approx(100 * (1 - 0.135 / 0.2))
    # one forward call of (4, 2048, 16, 128) needs 68.7 GFLOP: 0.349 ms at
    # the peak, over the 40 ms the trace gives it
    assert read("flash_fwd_roofline.train", record) == \
        pytest.approx(100 * (68_719_476_736 / 197e12) / 0.04)
    # a kernel that is not in the trace has no roofline share, never 0
    assert read("flash_bwd_roofline.train", record) is None
    # a serving reader finds no requests in a train record
    assert read("device_idle_share.serve", record) is None
    assert read("decode_hbm_share.serve", dict(record, trace=None)) is None


def test_decode_step_time_is_the_loops_span_over_its_steps():
    record = train_record()
    del record["steps"]
    record["config"]["num_hidden_layers"] = 24
    record.update(new_tokens=64, requests=[
        {"prompt_len": 128}, {"prompt_len": 512, "failed": "x"}])
    # one loop of 90 ms ran 64 steps; a step reads 2 bytes a matmul weight
    # and the cache of 128 + 32.5 positions
    need = 2 * 1_310_982_144 + 2 * 160.5 * 2048 * 24 * 2
    assert read("decode_hbm_share.serve", record) == \
        pytest.approx(100 * need / 819e9 / (0.09 / 64))
