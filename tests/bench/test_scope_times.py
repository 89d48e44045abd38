"""The join of a device trace to the program's scopes
(``benchmarks/scope_times.py``) and the eight per-layer metrics that read
it, on a hand-written compact trace with hand-written tables, and once end
to end on the CPU. No timing is asserted: every number checked is
arithmetic on the spans written below."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from benchmarks import cells, scope_times

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = "/device:TPU:0"
GENERATE, TRANSFER = "jit__generate_impl(7)", "jit_convert_element_type(3)"
SERVE = ["scope_coverage.serve", "prefill_share.serve",
         "attention_share.serve", "feed_forward_share.serve",
         "head_share.serve"]
TRAIN = ["scope_coverage.train", "optimizer_share.train",
         "optimizer_roofline.train"]
SERVE_CELLS = ["serve_chat_1chip", "serve_ouro_1chip", "serve_longcat_1chip",
               "serve_granite_1chip"]
J = "jit(_generate_impl)/"
LOOP = J + "while/body/closed_call/decode_step/"


def one_request():
    """One request of a made program, in ns. Before the decode loop a
    prefill fusion; the ``while`` 2000 → 8000 holds a step's events and
    500 ns of its own; after it an event whose instruction no table
    holds. Another module's transfer runs first, inside the window."""
    table = {
        "fusion.1": [J + "prefill/attention/dot_general", [], None],
        "while.2": [J + "while", [], None],
        "fusion.3": [LOOP + "attention/dot_general",
                     [LOOP + "attention/dot_general",
                      LOOP + "attention/mul"], None],
        "gated_ffn.4": [LOOP + "feed_forward/pallas_call", [], None],
        # the compiler's copy of a matrix ahead of the kernel that reads it
        "slice-start.5": ["", [], "slice-done.5"],
        "slice-done.5": ["", [], "gated_ffn.4"],
        # the final norm fused into the head's product: charged to the
        # root, both kept
        "fusion.6": [LOOP + "head/dot_general",
                     [LOOP + "final_norm/mul", LOOP + "head/dot_general"],
                     None],
        "add.7": [LOOP + "while/body/add", [], None],
        "copy.8": ["", [], "tuple.99"],  # read by nothing that has a scope
        "tuple.99": ["", [], None],
    }
    events = [
        ["copy.1 f32[16]", 100, 100],
        ["fusion.1 bf16[8,16]", 1000, 1000],
        ["while.2", 2000, 6000],
        ["fusion.3 bf16[1,16]", 2000, 2000],
        ["gated_ffn.4 bf16[1,16]", 4000, 1500],
        ["slice-done.5 bf16[4,16]", 5500, 500],
        ["fusion.6 f32[1,64]", 6000, 1000],
        ["add.7 s32[]", 7000, 300],
        ["copy.8 bf16[1,16]", 7300, 200],
        ["fusion.9 bf16[1,16]", 8000, 1000],
    ]
    return {"devices": {CHIP: events},
            "modules": {CHIP: [[TRANSFER, 100, 100],
                               [GENERATE, 1000, 8000]]},
            "host": [["bench:request#0", 0, 11000]],
            "tables": {GENERATE: table, TRANSFER: {}}}


def test_own_time_by_scope_with_a_loop_not_counted_twice():
    times = scope_times.reduce(one_request())
    ns = 1e-9
    assert times["chip"] == CHIP
    assert times["window_s"] == pytest.approx(11000 * ns)
    assert times["busy_s"] == pytest.approx(8100 * ns)
    assert times["events"] == 10 and times["host_spans"] == 1
    assert times["modules"] == sorted([TRANSFER, GENERATE])
    assert times["by_scope"] == pytest.approx({
        "prefill/attention": 1000 * ns,
        "decode_step/attention": 2000 * ns,
        # the kernel and the wait for the copy made for it
        "decode_step/feed_forward": 2000 * ns,
        "decode_step/head": 1000 * ns,
        # the loop's counter: a phase and no sub-layer
        "decode_step/-": 300 * ns,
        # the while's own 500, the copy nobody scoped reads 200, the
        # transfer 100 and the instruction no table holds 1000
        "-/-": 1800 * ns})
    assert times["unknown_s"] == pytest.approx(1100 * ns)
    assert times["covered_s"] == pytest.approx(6000 * ns)
    assert sum(times["by_scope"].values()) == pytest.approx(times["busy_s"])
    assert scope_times.coverage(times) == pytest.approx(100 * 6000 / 8100)
    # a fusion is charged to its root and keeps what was fused into it
    assert times["mixed_s"] == pytest.approx(1000 * ns)
    assert times["touching_s"] == pytest.approx({
        "prefill/attention": 1000 * ns, "decode_step/attention": 2000 * ns,
        "decode_step/feed_forward": 2000 * ns,
        "decode_step/head": 1000 * ns, "decode_step/final_norm": 1000 * ns})
    assert times["top"]["decode_step/feed_forward"] == [
        ["gated_ffn bf16[1,16]", pytest.approx(1500 * ns), 1,
         ["decode_step/feed_forward"]],
        ["slice-done bf16[4,16]", pytest.approx(500 * ns), 1,
         ["decode_step/feed_forward"]]]
    assert times["top"]["decode_step/head"][0][3] == [
        "decode_step/final_norm", "decode_step/head"]
    assert scope_times.phase_s(times, "decode_step") == \
        pytest.approx(5300 * ns)
    assert scope_times.phase_s(times, "decode_step", ("head", "sample")) \
        == pytest.approx(1000 * ns)


def test_the_window_clips_and_top_is_cut():
    compact = one_request()
    compact["host"] = [["bench:request#0", 1500, 3500]]  # 1500 → 5000
    times = scope_times.reduce(compact, top=1)
    assert times["busy_s"] == pytest.approx(3500e-9)
    assert times["by_scope"] == pytest.approx({
        "prefill/attention": 500e-9, "decode_step/attention": 2000e-9,
        "decode_step/feed_forward": 1000e-9, "-/-": 0.0})
    assert all(len(rows) == 1 for rows in times["top"].values())


def test_no_device_plane_or_no_vocabulary_reduces_to_nothing(monkeypatch):
    compact = one_request()
    assert scope_times.reduce(dict(compact, devices={})) is None
    assert scope_times.reduce(dict(compact, devices={CHIP: []})) is None
    # the parent commit's program under this benchmark: no scopes.py
    monkeypatch.setattr(scope_times, "SCOPES_FILE",
                        os.path.join(REPO, "no", "such", "scopes.py"))
    scope_times.program_scopes.cache_clear()
    try:
        assert scope_times.program_scopes() is None
        assert scope_times.reduce(compact) is None
        assert scope_times.load(os.path.join(REPO, "tests")) is None
    finally:
        scope_times.program_scopes.cache_clear()


def instruction(id, name, opcode, op_name="", operands=(), calls=()):
    return NS(id=id, name=name, opcode=opcode, operand_ids=list(operands),
              called_computation_ids=list(calls),
              metadata=NS(op_name=op_name))


def test_the_table_of_a_module_charges_a_fusion_to_its_root():
    """On what ``table_of`` reads of an ``HloModuleProto``: a fusion
    without an ``op_name`` of its own takes its called computation's
    root's, what is fused is kept, and an instruction without a name
    points at its first reader."""
    fused = NS(id=10, root_id=3, instructions=[
        instruction(1, "param_0", "parameter"),
        instruction(2, "dot.5", "dot", "jit(step)/loss/transpose(jvp("
                    "feed_forward))/dot_general", [1]),
        instruction(3, "add.6", "add", "jit(step)/optimizer/add", [2])])
    entry = NS(id=11, root_id=24, instructions=[
        instruction(20, "p", "parameter", "params['w1']"),
        instruction(21, "copy-start.1", "copy-start", "", [20]),
        instruction(22, "copy-done.1", "copy-done", "", [21]),
        instruction(23, "fusion.7", "fusion", "", [22], calls=[10]),
        instruction(24, "tuple.2", "tuple", "", [23])])
    table = scope_times.table_of(NS(computations=[fused, entry]))
    assert table["fusion.7"] == [
        "jit(step)/optimizer/add",
        ["jit(step)/loss/transpose(jvp(feed_forward))/dot_general",
         "jit(step)/optimizer/add"], None]
    assert table["copy-start.1"] == ["", [], "copy-done.1"]
    assert table["copy-done.1"] == ["", [], "fusion.7"]
    assert table["p"] == ["params['w1']", [], None]
    assert table["tuple.2"] == ["", [], None]
    assert scope_times._scope_of(table, "copy-start.1") == (
        "optimizer/-", ["loss/feed_forward", "optimizer/-"])
    assert scope_times._scope_of(table, "tuple.2") == ("-/-", [])
    assert scope_times._scope_of(table, "fusion.8") == (None, ())
    assert scope_times.tables_summary({"m": table}) == {"m": [8, 3]}


# ---------------------------------------------------------------------------
# The readers, on a made scope_times.json
# ---------------------------------------------------------------------------

def served():
    """Two requests: 30 s busy, 10 under prefill, 19.4 under decode_step,
    0.6 outside every scope."""
    return {"chip": CHIP, "busy_s": 30.0, "window_s": 30.5, "host_spans": 2,
            "by_scope": {
                "prefill/embed": 0.1, "prefill/attention": 4.0,
                "prefill/mixer": 3.0, "prefill/feed_forward": 2.5,
                "prefill/final_norm": 0.1, "prefill/head": 0.2,
                "prefill/sample": 0.1,
                "decode_step/embed": 0.2, "decode_step/attention": 2.0,
                "decode_step/mixer": 10.0, "decode_step/feed_forward": 5.0,
                "decode_step/router": 0.5, "decode_step/experts": 1.0,
                "decode_step/final_norm": 0.1, "decode_step/head": 0.3,
                "decode_step/sample": 0.1, "decode_step/-": 0.2,
                "-/-": 0.6},
            "covered_s": 29.2, "unknown_s": 0.1, "mixed_s": 0.4,
            "touching_s": {}, "top": {}}


def trained():
    """Four steps, 1 s busy: 0.25 charged to the optimizer or fused with
    something of it."""
    return {"chip": CHIP, "busy_s": 1.0, "window_s": 1.01, "host_spans": 4,
            "by_scope": {"loss/attention": 0.3, "loss/feed_forward": 0.3,
                         "loss/head": 0.14, "optimizer/-": 0.24,
                         "-/-": 0.02},
            "covered_s": 0.98, "unknown_s": 0.0, "mixed_s": 0.2,
            "touching_s": {"optimizer/-": 0.25, "loss/feed_forward": 0.45,
                           "loss/attention": 0.35, "loss/head": 0.14},
            "top": {}}


def config_of(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ADAMW_BYTES = 17_044_529_152  # 608,733,184 parameters at 28 bytes
WANT = {
    "scope_coverage.serve": 100 * 29.2 / 30.0,
    "prefill_share.serve": 100 * 10.0 / 30.0,
    "attention_share.serve": 100 * 2.0 / 19.4,
    "feed_forward_share.serve": 100 * 5.0 / 19.4,
    "head_share.serve": 100 * 0.5 / 19.4,
    "scope_coverage.train": 98.0,
    "optimizer_share.train": 25.0,
    "optimizer_roofline.train": 100 * 4 * (ADAMW_BYTES / 819e9) / 0.25,
}


def record_of(name):
    if name in SERVE:
        return {"cell": {"name": "serve_granite_1chip"}, "trace": {"x": 1},
                "peaks": PEAKS}
    return {"cell": {"name": "train_2k_1chip"}, "trace": {"x": 1},
            "peaks": PEAKS, "config": config_of("pythia-1.4b-shallow")}


def test_adamw_moves_28_bytes_a_parameter():
    from benchmarks.weights import n_params, sizes_of

    total = n_params(sizes_of(config_of("pythia-1.4b-shallow")))["total"]
    assert total == 608_733_184
    assert scope_times.adamw_call(total)["bytes"] == ADAMW_BYTES
    # 20.8 ms at the v5e's 819 GB/s
    assert ADAMW_BYTES / 819e9 == pytest.approx(0.02081, rel=1e-3)


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_each_new_entry_has_its_reader(name, monkeypatch):
    manifest = cells.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"] == (SERVE_CELLS if name in SERVE
                                  else ["train_2k_1chip"])
    assert (entry["unit"], entry["source"]) == ("%", "device_trace")
    # appended: every entry the benchmark had keeps its place
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(name) > names.index("ffn_roofline.serve_granite")
    read = cells.load_module(manifest, "layer_metrics", name).read
    record = record_of(name)
    times = served() if name in SERVE else trained()
    seen = []

    def load(out_dir):
        seen.append(out_dir)
        return times

    monkeypatch.setattr(scope_times, "load", load)
    assert read(record) == pytest.approx(WANT[name])
    assert seen == [os.path.join(REPO, ".bench_out", record["cell"]["name"])]
    # the names in the trace are not the vocabulary's (a program from a
    # stale compile cache, or the parent's): under 90% every reader is
    # silent
    times["covered_s"] = 0.89 * times["busy_s"]
    assert read(record) is None
    # a trace without a device plane, as the child leaves it
    monkeypatch.setattr(scope_times, "load",
                        lambda out_dir: {"chip": None, "tables": {}})
    assert read(record) is None
    # no trace at all, or one that could not be read
    monkeypatch.setattr(scope_times, "load", lambda out_dir: None)
    assert read(record) is None


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_an_untraced_or_rehearsed_record_reads_as_nothing(name, monkeypatch):
    read = cells.load_module(cells.load_manifest(), "layer_metrics",
                             name).read

    def load(out_dir):
        raise AssertionError("an untraced run has no trace to read")

    monkeypatch.setattr(scope_times, "load", load)
    record = record_of(name)
    # --trace 0: the guest returns no reduced trace; a rehearsal's traced
    # run holds no device plane, so ``trace`` is None there too
    assert read(dict(record, trace=None)) is None
    assert read({k: v for k, v in record.items() if k != "trace"}) is None
    assert read({}) is None


def test_a_run_without_a_trace_has_no_times(tmp_path):
    assert scope_times.load(str(tmp_path)) is None
    # a file that is no trace: the child fails, the reader says nothing
    broken = tmp_path / "bad" / "trace" / "plugins" / "profile" / "x"
    broken.mkdir(parents=True)
    (broken / "host.xplane.pb").write_bytes(b"not a trace")
    assert scope_times.load(str(tmp_path / "bad")) is None


def test_the_child_reads_a_rehearsals_trace_end_to_end(tmp_path):
    """``--rehearse --trace 1`` on the CPU, on a copy of the toy manifest
    with the serve entries (and a cell of its own, so that its logs are no
    other test's). A CPU's trace holds no device plane, so no reader
    reports; the child still reads the profiler's file, and the tables it
    finds there are the program's: ``generate``'s instructions carry the
    vocabulary's scopes."""
    with open(os.path.join(REPO, "tests", "bench", "data",
                           "toy_manifest.json")) as f:
        toy = json.load(f)
    real = cells.load_manifest()
    cell = "toy_serve_scopes"
    toy["workloads"].append({
        "name": cell, "config": "toy", "traffic": "toy_chat", "chips": 1,
        "why": "rehearsal of the scope readers"})
    for m in toy["end_to_end"] + toy["per_layer"]:
        if "toy_serve" in m.get("workloads", []):
            m["workloads"].append(cell)
    toy["per_layer"] += [dict(m, workloads=[cell])
                         for m in real["per_layer"] if m["name"] in SERVE]
    manifest = tmp_path / "toy_manifest.json"
    manifest.write_text(json.dumps(toy))

    # a compile cache of its own: the cache's key leaves metadata out, so
    # the checkout's may hold this program with an older tree's names
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--manifest", str(manifest), "--rehearse", "--workload", cell,
         "--seed", "2147483999", "--seconds", "1", "--trace", "1"],
        env=env, cwd=REPO, timeout=300, capture_output=True, text=True)
    assert p.returncode == 0, (p.returncode, p.stderr[-3000:])
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert "launch_ms.serve" in line["metrics"]
    assert not set(SERVE) & set(line["metrics"])

    out_dir = os.path.join(REPO, ".bench_out", cell)
    assert not os.path.exists(os.path.join(out_dir, scope_times.CACHE_NAME))
    scope_times.load.cache_clear()
    times = scope_times.load(out_dir)
    assert times["chip"] is None and times["xplane_bytes"] > 0
    generate = {module: counts for module, counts in times["tables"].items()
                if module.startswith("jit__generate_impl(")}
    assert generate
    for instructions, scoped in generate.values():
        assert scoped > instructions // 4
    with open(os.path.join(out_dir, scope_times.CACHE_NAME)) as f:
        assert json.load(f) == times
