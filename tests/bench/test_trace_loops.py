"""``benchmarks/trace_loops.py`` on hand-made compact traces: which
``while`` is the decode loop when loops nest, what lies before it, and the
time of the operations that touch a cache."""

import pytest

from benchmarks import trace_loops

MS = 1_000_000
CHIP = "/device:TPU:0"
SIZES = {"n_heads": 16, "head_dim": 128, "passes": 4}
CACHE_OPS = trace_loops.cache_operations(SIZES, {384})


def looped_run(t0, passes=4):
    """Embedding, prefill's pass loop (a ``while`` that holds none), then
    the decode scan holding one pass loop a step (two steps of ``passes``
    passes, each a cache update, an attention fusion and a matmul)."""
    events = [["fusion.1 bf16[256,2048]", t0, 2 * MS],
              ["while.7", t0 + 2 * MS, 8 * MS],
              ["fusion.2 bf16[16,384]", t0 + 3 * MS, 6 * MS]]
    d0 = t0 + 11 * MS
    events.append(["while.9", d0, 64 * MS])
    for step in range(2):
        s0 = d0 + step * 32 * MS
        events.append(["while.11", s0 + MS, 30 * MS])
        for k in range(passes):
            p0 = s0 + MS + k * 7 * MS
            events += [
                ["dynamic_update_slice.3 bf16[4,1,16,384,128]", p0, MS],
                ["fusion.5 bf16[16,128]", p0 + MS, 2 * MS],
                ["fusion.6 bf16[5632]", p0 + 3 * MS, 4 * MS]]
    return events


def compact_of(events):
    return {"devices": {CHIP: events}, "host": []}


def test_the_step_time_is_the_outer_loops_not_an_inner_ones():
    events = looped_run(0) + looped_run(100 * MS)
    got = trace_loops.reduce_loops(compact_of(events), CACHE_OPS)
    assert got["chip"] == CHIP
    # prefill's pass loop and the decode scan are both outermost
    assert got["outermost_whiles"] == 4
    loops = got["decode_loops"]
    assert [l["start_ns"] for l in loops] == [11 * MS, 111 * MS]
    for loop in loops:
        assert loop["seconds"] == pytest.approx(0.064)
        assert loop["inner_loops"] == 2
        # before it: the embedding and the whole of prefill's loop
        assert loop["before_s"] == pytest.approx(0.010)
        # cache operations inside the decode loop: 2 steps x 4 passes x
        # 3 ms; prefill's scores lie outside the loop and are not counted
        assert loop["cache_s"] == pytest.approx(0.024)


def test_a_model_of_one_pass_has_one_loop_and_it_is_the_decode_loop():
    events = [["fusion.1 bf16[128,2048]", 0, 2 * MS],
              ["while.3", 3 * MS, 20 * MS],
              ["fusion.4 bf16[2048]", 4 * MS, 5 * MS]]
    got = trace_loops.reduce_loops(compact_of(events))
    assert got["outermost_whiles"] == 1
    (loop,) = got["decode_loops"]
    assert loop["seconds"] == pytest.approx(0.020)
    assert loop["inner_loops"] == 0
    assert loop["before_s"] == pytest.approx(0.002)
    # no operation named: nothing said of the caches
    assert loop["cache_s"] is None


def test_nested_events_are_counted_once_and_the_busiest_chip_is_read():
    quiet = [["fusion.1 bf16[8]", 0, MS]]
    events = looped_run(0)
    compact = {"devices": {"/device:TPU:1": quiet, CHIP: events},
               "host": []}
    got = trace_loops.reduce_loops(compact, CACHE_OPS)
    assert got["chip"] == CHIP
    # an event nested under a cache operation is that one's own time no
    # longer: own time, as trace_reduce.self_times counts it
    nested = events + [["copy.1 bf16[16,128]", 11 * MS + 2 * MS + MS // 2,
                        MS // 2]]
    again = trace_loops.reduce_loops(compact_of(nested), CACHE_OPS)
    assert again["decode_loops"][0]["cache_s"] == pytest.approx(0.0235)


def test_cache_operations_are_named_by_kind_and_shape():
    assert CACHE_OPS == {
        "fusion bf16[16,128]", "fusion bf16[16,384]",
        "dynamic_update_slice bf16[4,1,16,384,128]"}
    more = trace_loops.cache_operations(SIZES, {384, 1152})
    assert more - CACHE_OPS == {
        "fusion bf16[16,1152]", "dynamic_update_slice bf16[4,1,16,1152,128]"}
    # softmax's normalisation has the scores' shape and another kind
    events = looped_run(0) + [["divide_convert_fusion.1 bf16[16,384]",
                               40 * MS, MS]]  # inside the first step
    got = trace_loops.reduce_loops(compact_of(events), CACHE_OPS)
    assert got["decode_loops"][0]["cache_s"] == pytest.approx(0.024)


def test_traced_pairs_requests_with_loops_or_gives_nothing():
    record = {"traffic": {"trace": {"skip_requests": 1, "requests": 2}},
              "requests": [{"index": i, "prompt_len": 256} for i in range(4)],
              "trace_loops": {"decode_loops": [{"seconds": 1.0},
                                               {"seconds": 2.0}]}}
    requests, loops = trace_loops.traced(record)
    assert [r["index"] for r in requests] == [1, 2] and len(loops) == 2
    assert trace_loops.traced(dict(record, trace_loops=None)) is None
    record["requests"][2]["failed"] = "x"
    assert trace_loops.traced(record) is None
    assert trace_loops.traced({}) is None
