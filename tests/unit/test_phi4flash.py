"""A decoder-hybrid-decoder (``benchmarks/configs/
phi-4-mini-flash-reasoning.json``'s kinds: Mamba-1 layers, windowed and
full differential attention, gated memory units, cross attention over one
shared cache, LayerNorm with bias, biased projections, a tied head) at a
small size on the CPU, float32 parameters from a seed: the program
(``models/ssm.py``, ``models/transformer.py``, ``models/generate.py``,
``ops/cached_attention.py``) against the plain reference
(``benchmarks/reference/phi4flash.py``), which shares no code with it.

The toy (``tests/bench/data/configs/toy_phi4flash.json``) has 12 layers:
four Mamba-1 (the last the memory's source), three windowed attentions of
16 positions, one full attention (the shared cache's source), two gated
memory units and two cross attentions; 8 heads of 64 lanes as 4 pairs on 2
groups, so two pairs share a group; prompts of 30 and reaches of 40 (2.5
windows), so the rings wrap in prefill and again in decoding.

Tolerances. Program and reference compute the same float32 mathematics in
another order (a ring, a cache and chunks against every position over
every position; grouped pairs against repeated heads; a scan that starts
from a stored state against one from zero), so they differ by rounding
alone: logits of spread 0.7 agree to 2e-5 here. ``RTOL`` 1e-4 of the
largest logit (3e-4 absolute) leaves room for another BLAS and fails on
any term left out; with the program's products and state in bfloat16 the
same comparison reads 100 times the tolerance
(``test_bfloat16_in_float32s_place_fails``).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import program_phi4flash, weights_phi4flash
from benchmarks.reference import phi4flash as ref
from faabric_tpu.models import ModelConfig, forward, init_params
from faabric_tpu.models import transformer
from faabric_tpu.models.generate import (
    call_sizes,
    forward_with_cache,
    generate,
    init_kv_cache,
)
from faabric_tpu.ops import cached_attention
from tests.unit.test_models import _walk_jaxpr

RTOL = 1e-4
SEED = 2147484039
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROMPT, REACH = 30, 40


def values(name="toy"):
    path = {"toy": os.path.join(REPO, "tests", "bench", "data", "configs",
                                "toy_phi4flash.json"),
            "published": os.path.join(REPO, "benchmarks", "configs",
                                      "phi-4-mini-flash-reasoning.json")}
    with open(path[name]) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy():
    config = values()
    sizes = weights_phi4flash.sizes_of(config)
    cfg = dataclasses.replace(program_phi4flash.model_config(config),
                              remat=False)
    return sizes, cfg, weights_phi4flash.make_weights(SEED, sizes,
                                                      jnp.float32)


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def tokens(sizes, rows, length=REACH):
    return jnp.asarray(weights_phi4flash.token_rows(
        SEED, 0, 0, rows, length, sizes["vocab"]))


def close(got, want, rtol=RTOL):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=rtol * float(np.abs(want).max()))


def through_the_caches(params, toks, cfg, chunks, last_only=False):
    """Prefill in ``chunks`` (static starts), then a cached step a
    position (traced starts) → logits at every position given."""
    rows = toks.shape[0]
    cache = init_kv_cache(cfg, rows, 128)
    out, at = [], 0
    for length in chunks:
        logits, cache = forward_with_cache(
            params, toks[:, at:at + length], cache, at, cfg,
            last_only=last_only)
        out.append(logits)
        at += length
    step = jax.jit(lambda tok, cache, pos: forward_with_cache(
        params, tok, cache, pos, cfg))
    for pos in range(at, toks.shape[1]):
        logits, cache = step(toks[:, pos:pos + 1], cache, jnp.int32(pos))
        out.append(logits)
    return jnp.concatenate(out, axis=1)


def test_forward_matches_the_reference(toy):
    sizes, cfg, params = toy
    toks = tokens(sizes, 2)
    want = ref.logits_of_rows(params, toks, sizes)
    assert float(jnp.std(want)) > 0.3          # logits worth comparing
    close(forward(params, toks, cfg), want)
    # remat wraps every kind's block and changes nothing
    close(forward(params, toks, dataclasses.replace(cfg, remat=True)), want)


# name → (rows, the prompt's chunks): unchunked, chunks longer and shorter
# than the window of 16 (a chunk of 24 wraps the ring while it is
# written; chunks of 7 carry S, the convolution window and the ring four
# times), and 8 rows, from which a cached step attends through the kernel
# in all six attending layers. Every chunk of more than one position runs
# the four Mamba-1 layers' recurrence through the kernel that keeps S on
# the chip (1024 lanes, a state of 8): chunks of 17 and 13 are two sublane
# tiles of positions and one position more, then one tile and five, and
# the second kernel call starts from the S the first one left
CACHED = {"one_chunk": (2, (PROMPT,)), "chunks_of_24": (2, (24, 6)),
          "chunks_of_7": (3, (7, 7, 7, 7, 2)), "one_row": (1, (16, 14)),
          "streamed_8_rows": (8, (24, 6)),
          "scan_kernel_twice": (3, (17, 13))}


@pytest.mark.parametrize("case", sorted(CACHED))
def test_prefill_then_cached_decoding_matches_the_full_forward(toy, case):
    """Prefill in chunks, then ten cached steps, against the reference's
    full forward at the same positions: the reach is 2.5 windows, so the
    rings wrap in prefill and in decoding."""
    sizes, cfg, params = toy
    rows, chunks = CACHED[case]
    toks = tokens(sizes, rows)
    got = through_the_caches(params, toks, cfg, chunks)
    close(got, ref.logits_of_rows(params, toks, sizes))


def test_prefill_with_the_skip_is_the_whole_stacks_last_position(toy):
    """``last_only``: the cross-decoder, the final norm and the head run
    on the last position alone, and the state they leave serves the
    cached steps that follow."""
    sizes, cfg, params = toy
    assert cfg.stateless_from == 8 and cfg.n_layers == 12
    toks = tokens(sizes, 2)
    want = ref.logits_of_rows(params, toks, sizes)
    got = through_the_caches(params, toks, cfg, (24, 6), last_only=True)
    # the two chunks' last positions, then every cached step
    close(got, jnp.concatenate([want[:, 23:24], want[:, 29:]], axis=1))
    jaxpr = jax.make_jaxpr(lambda t, c: forward_with_cache(
        params, t, c, 0, cfg, last_only=True))(
        toks[:, :PROMPT], init_kv_cache(cfg, 2, 128))
    # a gated memory unit's gate has one position a row, not the prompt's
    inner = cfg.ssm_inner
    shapes = [v.aval.shape for e, _ in _walk_jaxpr(jaxpr.jaxpr)
              for v in e.outvars if e.primitive.name == "logistic"]
    assert (2, 1, inner) in shapes and (2, PROMPT, inner) in shapes
    # the four Mamba-1 layers' two (the convolution's and the gate's)
    assert shapes.count((2, PROMPT, inner)) == 8


def _kernels(traced) -> list:
    """The names of the Pallas calls a traced program holds."""
    return [e.params["name"] if "name" in e.params else
            e.params["name_and_src_info"].name
            for e, _ in _walk_jaxpr(traced.jaxpr)
            if e.primitive.name == "pallas_call"]


def _scans_and_kernels(traced, state: tuple) -> tuple:
    """(the ``lax.scan``s whose carry is S, the ``selective_scan``
    kernels) a traced program holds."""
    scans = [e for e, _ in _walk_jaxpr(traced.jaxpr)
             if e.primitive.name == "scan"
             and any(v.aval.shape == state for v in e.outvars)]
    return len(scans), _kernels(traced).count("selective_scan")


def test_the_scan_keeps_its_loop_where_no_kernel_may_run(toy):
    """A cached call on one chip runs the four Mamba-1 layers' recurrence
    through the kernel and holds no loop along the positions; ``forward``
    without a cache (a gradient may be taken) and a block under a mesh
    keep the ``lax.scan``, and a cached step ``_step1``."""
    from faabric_tpu.parallel import MeshConfig, build_mesh

    sizes, cfg, params = toy
    toks = tokens(sizes, 2, PROMPT)
    state = (2, cfg.ssm_d_state, cfg.ssm_inner)
    cache = init_kv_cache(cfg, 2, 128)
    assert _scans_and_kernels(jax.make_jaxpr(
        lambda t, c: forward_with_cache(params, t, c, 0, cfg))(toks, cache),
        state) == (0, 4)
    assert _scans_and_kernels(jax.make_jaxpr(
        lambda t, c: forward_with_cache(params, t, c, PROMPT, cfg))(
        toks[:, :1], cache), state) == (0, 0)
    assert _scans_and_kernels(jax.make_jaxpr(
        lambda t: forward(params, t, cfg))(toks), state) == (4, 0)
    assert cfg.mixers[0] == "mamba1"
    x = jnp.zeros((2, PROMPT, cfg.d_model), jnp.float32)
    where = jnp.broadcast_to(jnp.arange(PROMPT)[None], (2, PROMPT))
    for mesh, held in ((build_mesh(config=MeshConfig(tp=2)), (1, 0)),
                       (None, (0, 1))):
        assert _scans_and_kernels(jax.make_jaxpr(
            lambda x, c: transformer._block(
                x, params["blocks"][0], where, cfg, mesh, cache=c,
                slot=(0, 0), kind="mamba1"))(x, cache[0]), state) == held


def test_call_sizes_counts_the_layers_whose_scan_streams():
    """``scan_streamed_layers`` and ``scan_streamed_bytes`` at the cell's
    sizes, from ``selective_scan.plan``; a call of one position a chunk
    and a configuration without a "mamba1" layer read 0, and one that
    names no kinds carries the key no more than ``scan_chunks``."""
    from benchmarks import program, program_granite
    from faabric_tpu.ops import selective_scan

    cfg = program_phi4flash.model_config(values("published"))
    sized = call_sizes(cfg, 64, 512, 256, 256)
    a_call = selective_scan.plan(64, 256, 5120, 16, jnp.bfloat16)
    assert sized["scan_chunks"] == 2 and sized["scan_streamed_layers"] == 9
    assert sized["scan_streamed_bytes"] \
        == 9 * 2 * a_call["streamed_bytes"] == 12_645_826_560
    # the smoke's chunks, 384 and 256, and a last chunk of one position
    assert call_sizes(cfg, 64, 640, 2, 384)["scan_streamed_bytes"] == 9 * (
        selective_scan.plan(64, 384, 5120, 16)["streamed_bytes"]
        + a_call["streamed_bytes"])
    assert call_sizes(cfg, 64, 257, 2, 256)["scan_streamed_bytes"] \
        == 9 * a_call["streamed_bytes"]
    alone = call_sizes(cfg, 64, 1, 2)
    assert (alone["scan_streamed_layers"], alone["scan_streamed_bytes"]) \
        == (0, 0)
    # lanes 128 does not divide: the plan refuses and the scan stays
    odd = dataclasses.replace(cfg, ssm_inner=5000)
    assert call_sizes(odd, 64, 512, 256, 256)["scan_streamed_layers"] == 0
    for name, build in (("granite-4.0-h-micro", program_granite),
                        ("pythia-1.4b", program)):
        with open(os.path.join(REPO, "benchmarks", "configs",
                               name + ".json")) as f:
            other = call_sizes(build.model_config(json.load(f)), 64, 512,
                               128, 256)
        assert other.get("scan_streamed_layers", 0) == 0
        assert ("scan_chunks" in other) == (name != "pythia-1.4b")
    assert "scan_streamed_layers" not in other


def test_a_ring_takes_a_chunk_only_from_a_static_start(toy):
    """A chunk's place in the ring decides how it is cut in two: prefill's
    starts are static, and a traced one is refused, not guessed at."""
    sizes, cfg, params = toy
    toks = tokens(sizes, 2)
    cache = init_kv_cache(cfg, 2, 128)
    with pytest.raises(ValueError, match="static start"):
        jax.jit(lambda pos: forward_with_cache(
            params, toks[:, :2], cache, pos, cfg))(jnp.int32(0))


def test_generate_serves_the_references_best_and_counts_its_state(toy):
    sizes, cfg, params = toy
    toks = tokens(sizes, 8, PROMPT)
    out = generate(params, toks, cfg, 10, prefill_chunk=24)
    whole = jnp.concatenate([toks, out], axis=1)
    want = ref.logits_of_rows(params, whole[:, :-1], sizes)[:, PROMPT - 1:]
    gaps = jnp.max(want, -1) - jnp.take_along_axis(
        want, out[..., None], axis=-1)[..., 0]
    assert float(jnp.max(gaps)) < RTOL * float(jnp.abs(want).max())
    sized = call_sizes(cfg, 8, PROMPT, 10, 24)
    one = 8 * 4 * 64 * 4              # rows × kv heads × lanes × float32
    assert sized["cache_slots"] == 128 and sized["window_slots"] == 16
    assert sized["window_cache_bytes"] == 3 * 2 * 16 * one
    assert sized["shared_cache_bytes"] == 2 * 128 * one
    assert sized["cache_bytes"] == (sized["window_cache_bytes"]
                                    + sized["shared_cache_bytes"])
    assert sized["state_bytes"] == 4 * 8 * (8 + 3) * cfg.ssm_inner * 4
    assert (sized["window_layers"], sized["cross_layers"],
            sized["memory_layers"], sized["ssm_layers"],
            sized["attention_layers"]) == (3, 2, 2, 4, 1)
    assert sized["scan_chunks"] == 2 and sized["prefill_skipped_layers"] == 4
    # every attending layer reads its cache through the kernel: a ring of
    # 16 slots three times, the one shared cache three times
    assert sized["attention_streamed_layers"] == 6
    assert sized["attention_streamed_bytes"] == 3 * 2 * 16 * one \
        + 3 * 2 * 128 * one
    assert sized["ffn_streamed_layers"] == 12
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 8, 128))
    assert [None if c is None else sorted(c) for c in cache[6:10]] == [
        ["conv", "state"], ["k", "v"], None, None]
    assert cache[1]["k"].shape == (1, 8, 16, 256)
    assert cache[7]["k"].shape == (1, 8, 128, 256)
    assert cache[0]["state"].shape == (8, 8, cfg.ssm_inner)
    # one row: no kernel, the same dense caches
    assert call_sizes(cfg, 1, PROMPT, 10)["attention_streamed_layers"] == 0
    traced = jax.make_jaxpr(lambda p: generate(params, p, cfg, 10,
                                               prefill_chunk=24))(toks)
    kernels = _kernels(traced)
    # six in the decode loop's step, and the two cross attentions of each
    # of prefill's two chunks, whose last position goes on alone
    assert kernels.count("cached_attention") == 6 + 2 * 2


@pytest.mark.parametrize("form", ["pairs_full", "pairs_ring",
                                  "pairs_lent_pass_1", "plain_heads"])
def test_the_kernel_is_the_jnp_lines(form):
    """``cached_attention`` in the differential form (a head keeps its
    pair's two value heads), over a ring whose written slots are in no
    order, over a cache it is lent (read, another pass of two, and the
    cache comes back unchanged), against ``transformer._attend`` over the
    same cache; and the plain form untouched beside it."""
    rows, heads, kv, slots, d = 8, 8, 4, 24, 64
    key = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(key[0], (rows, heads, d))
    k, v = (jax.random.normal(one, (2, rows, slots, kv * d))
            for one in key[1:])
    paired = form != "plain_heads"
    length = {"pairs_ring": slots}.get(form, 17)
    t = 1 if form == "pairs_lent_pass_1" else 0
    assert cached_attention.plan(rows, heads, kv, slots, d, jnp.float32,
                                 paired=paired) is not None
    got = cached_attention.cached_attention(q, k, v, jnp.int32(length),
                                            0.125, jnp.int32(t),
                                            paired=paired)
    at = jnp.arange(slots)
    want = transformer._attend(
        q[:, None], k[t].reshape(rows, slots, kv, d),
        v[t].reshape(rows, slots, kv, d), (at < length)[None], at < length,
        0.125, paired)
    close(got.reshape(want.shape), want, 1e-5)
    assert got.shape == ((rows, heads, 2 * d) if paired
                         else (rows, heads, d))
    # unwritten slots hold anything, NaN too, and never reach the sum
    dirty = v.at[:, :, length:].set(jnp.nan) if length < slots else v
    again = cached_attention.cached_attention(q, k, dirty, length, 0.125, t,
                                              paired=paired)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
    # pairs need whole lanes a pair and an even number of key heads
    assert cached_attention.plan(8, 8, 4, 24, 32, paired=True) is None
    assert cached_attention.plan(8, 6, 3, 24, 128, paired=True) is None


def test_bfloat16_in_float32s_place_fails(toy):
    """The comparison is tight enough to see a lower precision: the same
    program with bfloat16 products and state misses ``RTOL`` by far."""
    sizes, cfg, params = toy
    toks = tokens(sizes, 2)
    want = ref.logits_of_rows(params, toks, sizes)
    low = dataclasses.replace(cfg, compute_dtype=jnp.bfloat16)
    got = forward(params, toks, low).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) \
        > 30 * RTOL * float(jnp.abs(want).max())


def test_the_planted_faults_move_the_logits(toy):
    sizes, _cfg, params = toy
    toks = tokens(sizes, 2)
    want = ref.logits_of_rows(params, toks, sizes)
    for fault in ref.FAULTS:
        got = ref.logits_of_rows(params, toks, sizes, fault=fault,
                                 handover=PROMPT)
        moved = jnp.max(jnp.abs(got - want), axis=(0, 2))
        assert float(moved.max()) > 0.05 * float(jnp.abs(want).max()), fault
        if fault == "state_dropped":      # seen only after the hand-over
            assert float(moved[:PROMPT].max()) == 0.0
        if fault == "window_unbounded":   # and only beyond one window
            assert float(moved[:16].max()) == 0.0
    with pytest.raises(ValueError, match="not one of"):
        ref.logits_of_rows(params, toks, sizes, fault="none")


def test_the_parameter_count_at_the_published_sizes():
    """``n_params`` against the program's own tree by ``jax.eval_shape``:
    nothing is allocated. The file carries the figure."""
    config = values("published")
    sizes = weights_phi4flash.sizes_of(config)
    cfg = program_phi4flash.model_config(config)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    counted = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert counted == weights_phi4flash.n_params(sizes)["total"] \
        == config["n_params"] == 3_852_562_944
    made = jax.eval_shape(lambda: weights_phi4flash.make_weights(
        0, sizes, jnp.bfloat16))
    assert jax.tree.map(lambda x: x.shape, made) \
        == jax.tree.map(lambda x: x.shape, shapes)
    assert config["reduced"] == [] and cfg.stateless_from == 18
    kinds = cfg.mixers
    assert kinds.count("mamba1") == 9 and kinds[16] == "mamba1"
    assert kinds.count("window_attention") == 8 and kinds[17] == "attention"
    assert kinds.count("cross_attention") == kinds.count("gated_memory") == 7
    assert (cfg.cache_source, cfg.memory_source) == (17, 16)
    # the cell's call: what the reply's counters have to say
    sized = call_sizes(cfg, 64, 512, 256, 256)
    assert sized["attention_streamed_layers"] == 16
    assert (sized["window_layers"], sized["window_slots"]) == (8, 512)
    assert sized["window_cache_bytes"] == 8 * 64 * 512 * 2 * 1280 * 2
    assert sized["shared_cache_bytes"] == 64 * 768 * 2 * 1280 * 2
    assert sized["state_bytes"] == 9 * 64 * (16 + 3) * 5120 * 2
    assert sized["prefill_skipped_layers"] == 14
    assert sized["ffn_streamed_layers"] == 32
    assert sized["attention_streamed_bytes"] == sized["window_cache_bytes"] \
        + 8 * sized["shared_cache_bytes"]


def test_the_granite_cell_builds_what_it_built():
    """The nearest accepted configuration shares ``call_sizes``, the
    caches' layout and the kernel's plan: they are PR 38's."""
    from benchmarks import program_granite

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = program_granite.model_config(json.load(f))
    assert call_sizes(cfg, 64, 512, 128, 256) == {
        "attention_layers": 4, "attention_streamed_bytes": 335544320,
        "attention_streamed_layers": 4, "cache_bytes": 335544320,
        "cache_slots": 640, "ffn_streamed_bytes": 4026531840,
        "ffn_streamed_layers": 40, "scan_chunks": 2, "ssm_layers": 36,
        "state_bytes": 2476081152, "ut_passes": 129}
    assert not transformer.lays_dense(cfg) and cfg.stateless_from == 40
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 64, 640))
    assert cache[5]["k"].shape == (1, 64, 640, 512)
    assert cache[4]["state"].shape == (64, 64, 64, 128)
    assert jax.eval_shape(lambda: init_kv_cache(cfg, 2, 640)
                          )[5]["k"].shape == (1, 2, 8, 640, 64)
    assert cached_attention.plan(64, 32, 8, 640, 64) == {
        "block_rows": 2, "steps": 32, "vmem_bytes": 6995968,
        "streamed_bytes": 83886080}


PLAIN = dict(vocab_size=64, d_model=64, n_layers=4, n_heads=4, d_ff=64,
             max_seq=64)
MAMBA1 = dict(ssm_inner=128, ssm_d_state=4, ssm_d_conv=4, ssm_dt_rank=4)
# what a configuration may not say: name → fields
ILL_FORMED = {
    "an unknown kind": dict(layer_types=("mamba1", "linear", "attention",
                                         "attention"), **MAMBA1),
    "a mamba1 layer without its sizes": dict(
        layer_types=("mamba1",) + ("attention",) * 3),
    "a window layer without a window": dict(
        layer_types=("window_attention",) + ("attention",) * 3),
    "a window without a window layer": dict(sliding_window=8),
    "a cross layer without a source": dict(
        layer_types=("attention",) * 3 + ("cross_attention",)),
    "a cross layer before its source": dict(
        layer_types=("cross_attention",) + ("attention",) * 3,
        cache_source=1),
    "a source that is not a full attention": dict(
        layer_types=("window_attention", "attention", "cross_attention",
                     "attention"), sliding_window=8, cache_source=0),
    "a memory unit on an attention": dict(
        layer_types=("attention", "gated_memory") * 2, memory_source=0,
        **MAMBA1),
    "a memory source nobody reads": dict(
        layer_types=("mamba1",) + ("attention",) * 3, memory_source=0,
        **MAMBA1),
    "pairs of an odd number of heads": dict(differential=True, n_heads=1),
    "pairs on one key head": dict(differential=True, n_kv_heads=1),
    "shared state in a looped stack": dict(
        layer_types=("attention",) * 3 + ("cross_attention",),
        cache_source=0, n_passes=2),
    "an unknown norm": dict(norm="batch"),
}


@pytest.mark.parametrize("what", sorted(ILL_FORMED))
def test_an_ill_formed_pattern_is_refused(what):
    with pytest.raises(ValueError):
        ModelConfig(**{**PLAIN, **ILL_FORMED[what]})


# every new field by name → a value a well-formed configuration gives it
NAMED = {
    "sliding_window": dict(
        sliding_window=8,
        layer_types=("window_attention",) + ("attention",) * 3),
    "cache_source": dict(
        cache_source=0,
        layer_types=("attention",) * 3 + ("cross_attention",)),
    "memory_source": dict(
        memory_source=0, **MAMBA1,
        layer_types=("mamba1", "gated_memory", "attention", "attention")),
    "differential": dict(differential=True),
    "norm": dict(norm="layer"),
    "attention_bias": dict(attention_bias=True),
    "ssm_inner": dict(**MAMBA1,
                      layer_types=("mamba1",) + ("attention",) * 3),
}


@pytest.mark.parametrize("field", sorted(NAMED))
def test_train_pipeline_moe_and_a_mesh_refuse_the_kinds_by_name(field):
    from faabric_tpu.models import param_shardings
    from faabric_tpu.models.moe import MoEConfig, init_moe_params
    from faabric_tpu.models.train import make_train_step
    from faabric_tpu.parallel import MeshConfig, build_mesh
    from faabric_tpu.parallel.pipeline import make_pp_loss

    cfg = ModelConfig(**PLAIN, **NAMED[field])
    assert any(name.startswith(field + "=")
               for name in transformer.served_only(cfg))
    with pytest.raises(ValueError, match=f"train step.*{field}"):
        make_train_step(cfg)
    with pytest.raises(ValueError, match=f"pipeline.*{field}"):
        make_pp_loss(cfg, build_mesh(config=MeshConfig(pp=2)))
    with pytest.raises(ValueError, match=f"MoE family.*{field}"):
        init_moe_params(jax.random.PRNGKey(0),
                        MoEConfig(**PLAIN, **NAMED[field]))
    mesh = build_mesh(config=MeshConfig(tp=2))
    with pytest.raises(ValueError, match=f"under a mesh.*{field}"):
        generate({}, jnp.zeros((2, 4), jnp.int32), cfg, 2, mesh=mesh)
    with pytest.raises(ValueError, match="no layout over a mesh"):
        param_shardings(mesh, cfg)
