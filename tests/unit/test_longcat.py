"""Latent attention in shortcut-connected double layers with a held share
of the experts (``benchmarks/configs/longcat-flash-omni.json``'s kinds) at
a small size on the CPU, float32 parameters from a seed: the program
(``models/transformer.py``, ``models/moe.py:expert_layer``,
``models/generate.py``) against the plain reference
(``benchmarks/reference/longcat.py``), which shares no code with it.

Tolerances. Program and reference compute the same float32 mathematics in
another order (a cache, absorbed projections, a grouped product over the
sorted picks' row tiles against a masked loop over experts), so they differ by
rounding alone: logits of size about 1 after 2 double layers agree to a
few 1e-6 here; 1e-4 leaves room for another BLAS and fails on any term
left out (a dropped norm scale alone moves logits by 1e-1). The routing
is a selection: at float32 on both sides no pick differs at these sizes.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import weights_longcat
from benchmarks.reference import longcat as ref
from faabric_tpu.models import ModelConfig, forward, init_params
from faabric_tpu.models import moe, transformer
from faabric_tpu.models.generate import (
    call_sizes,
    forward_with_cache,
    generate,
    generate_with_counters,
    init_kv_cache,
)
from tests.unit.test_models import _walk_jaxpr

ATOL = 1e-4
SEED = 2147483999
CHIPS = 4  # the toy deployment: 16 routed experts over 4 chips


def sizes(rank=1, layers=2, held=None):
    share = 16 // CHIPS
    return {"vocab": 256, "d_model": 64, "n_layers": layers, "n_heads": 4,
            "d_ff": 96, "expert_d_ff": 48, "max_seq": 512,
            "rope_theta": 1e4, "norm_eps": 1e-5, "q_rank": 32,
            "kv_rank": 16, "qk_nope": 16, "qk_rope": 8, "v_head": 16,
            "routed_experts": 16, "zero_experts": 8,
            "experts_held": held or (rank * share, share), "top_k": 4,
            "routed_scaling": 6.0}


def config(sz, **other):
    return ModelConfig(**{**dict(
        vocab_size=sz["vocab"], d_model=sz["d_model"],
        n_layers=sz["n_layers"], n_heads=sz["n_heads"], d_ff=sz["d_ff"],
        max_seq=sz["max_seq"], rope_theta=sz["rope_theta"], ffn="swiglu",
        norm_eps=sz["norm_eps"], attention="latent",
        q_lora_rank=sz["q_rank"], kv_lora_rank=sz["kv_rank"],
        qk_nope_dim=sz["qk_nope"], qk_rope_dim=sz["qk_rope"],
        v_head_dim=sz["v_head"], layer="shortcut",
        routed_experts=sz["routed_experts"],
        zero_experts=sz["zero_experts"], experts_held=sz["experts_held"],
        experts_per_token=sz["top_k"],
        routed_scaling=sz["routed_scaling"], expert_d_ff=sz["expert_d_ff"],
        compute_dtype=jnp.float32, param_dtype=jnp.float32, remat=False),
        **other})


def weights(sz):
    return weights_longcat.make_weights(SEED, sz, jnp.float32)


def ids(rows, length, index=0):
    return weights_longcat.token_rows(SEED, 1, index, rows, length, 256)


def reference_logits(params, tokens, sz):
    return np.stack([np.asarray(ref.logits_of(params, jnp.asarray(row), sz))
                     for row in tokens])


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def test_forward_matches_the_reference():
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = ids(3, 24)
    got = forward(params, jnp.asarray(tokens), cfg)
    np.testing.assert_allclose(np.asarray(got),
                               reference_logits(params, tokens, sz),
                               atol=ATOL, rtol=0)


@pytest.mark.parametrize("rows", [1, 3])
def test_prefill_then_cached_decoding_matches_the_full_forward(rows):
    """Prefill of 16 positions (keys and values expanded from the latent
    cache), then 8 single-token steps over the latent cache as it lies
    (the up-projections absorbed), every row at its own positions:
    logits against the reference's full forward pass, which has no
    cache."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = ids(rows, 24, index=rows)
    cache = init_kv_cache(cfg, rows, 128)
    logits, cache = forward_with_cache(params, jnp.asarray(tokens[:, :16]),
                                       cache, 0, cfg)
    got = [np.asarray(logits)]
    for pos in range(16, 24):
        logits, cache = forward_with_cache(
            params, jnp.asarray(tokens[:, pos:pos + 1]), cache,
            jnp.int32(pos), cfg)
        got.append(np.asarray(logits))
    np.testing.assert_allclose(np.concatenate(got, axis=1),
                               reference_logits(params, tokens, sz),
                               atol=ATOL, rtol=0)
    # a layer's state: two latent caches, 24 of 128 slots written, and
    # the expert layer's counters over the 24 positions
    layer = cache[1]
    assert [c["latent"].shape for c in layer["attn"]] == [
        (1, rows, 128, 16 + 8)] * 2
    assert not np.asarray(layer["attn"][1]["latent"][:, :, 24:]).any()
    assert np.asarray(layer["attn"][1]["latent"][:, :, :24]).any()
    assert int(layer["counters"][:3].sum()) == rows * 24 * sz["top_k"]


def test_the_absorbed_path_equals_the_expanded_one():
    """The same queries against the same latents: every head's keys and
    values expanded from them, or the up-projection's halves moved onto
    the query and the weighted sum. Also with further slots, unwritten
    and full of NaN, behind the positions attended."""
    sz = sizes()
    cfg = config(sz)
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q_nope = jax.random.normal(k[0], (3, 2, 4, 16))
    q_rope = jax.random.normal(k[1], (3, 2, 4, 8))
    latent = jax.random.normal(k[2], (3, 11, 24))
    wkvb = jax.random.normal(k[3], (16, 4, 32)) / 4.0
    expanded = transformer._latent_expanded(q_nope, q_rope, latent, wkvb,
                                            cfg)
    cache = jnp.concatenate(
        [latent, jnp.full((3, 5, 24), jnp.nan)], axis=1)
    absorbed = transformer._latent_absorbed(q_nope, q_rope, cache, 11,
                                            wkvb, cfg)
    assert expanded.shape == absorbed.shape == (3, 2, 4, 16)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5, rtol=0)


def test_attention_never_reads_a_slot_the_call_has_not_written():
    """PR 27's lesson for the latent cache: what an unwritten slot holds,
    NaN included, reaches no logit, in prefill or in a step."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = jnp.asarray(ids(2, 13))

    def through(cache):
        logits, cache = forward_with_cache(params, tokens[:, :12], cache,
                                           0, cfg)
        step, _ = forward_with_cache(params, tokens[:, 12:], cache,
                                     jnp.int32(12), cfg)
        return np.asarray(logits), np.asarray(step)

    clean = init_kv_cache(cfg, 2, 32)
    dirty = [dict(layer, attn=[{"latent": jnp.full_like(c["latent"], jnp.nan)}
                               for c in layer["attn"]]) for layer in clean]
    for got, want in zip(through(dirty), through(clean)):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)


def test_the_shares_add_up_to_the_uncut_layer():
    """Over all four ranks of the toy deployment, each holding 4 of the
    16 routed experts: the layers' outputs, with what every chip computes
    alike (attention, the dense feed-forwards, the zero-compute experts)
    counted once, add up to what the reference gives for the whole layer,
    all 16 experts in one place."""
    whole = sizes(held=(0, 16), layers=1)
    blk = weights(whole)["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 12, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    uncut = np.stack([np.asarray(ref.layer(row, blk, whole)[0])
                      for row in x])
    # what all chips compute alike: the layer with no routed expert held
    nobody = dict(whole, experts_held=(0, 0))
    alike = np.stack([np.asarray(ref.layer(row, blk, nobody)[0])
                      for row in x])
    total = np.zeros_like(uncut)
    for rank in range(CHIPS):
        sz = sizes(rank, layers=1)
        first, count = sz["experts_held"]
        mine = dict(blk, experts=jax.tree.map(
            lambda w: w[first:first + count], blk["experts"]))
        got, *_ = transformer._block(x, mine, positions, config(sz))
        total += np.asarray(got) - alike
        # and each share is the reference's share
        share = np.stack([np.asarray(ref.layer(row, mine, sz)[0])
                          for row in x])
        np.testing.assert_allclose(np.asarray(got), share, atol=ATOL,
                                   rtol=0)
    assert np.abs(uncut - alike).max() > 0.05  # the experts do something
    np.testing.assert_allclose(total + alike, uncut, atol=ATOL, rtol=0)


def _expert_layer(sz, u, router, experts):
    m, counters = moe.expert_layer(u, router, experts, config(sz))
    return np.asarray(m), dict(zip(moe.COUNTERS, np.asarray(counters)))


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """A selection bias that puts one held expert among every token's
    picks: all 40 tokens go through it, none is dropped, and the layer
    is the reference's."""
    sz = sizes(layers=1)
    blk = weights(sz)["blocks"][0]
    first = sz["experts_held"][0]
    router = dict(blk["router"],
                  bias=blk["router"]["bias"].at[first + 2].set(10.0))
    u = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 64), jnp.float32)
    got, counted = _expert_layer(sz, u, router, blk["experts"])
    want = np.stack([np.asarray(ref.expert_layer(
        row, dict(blk, router=router), sz, "float32")[0]) for row in u])
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    assert counted["picks_held"] >= 40 and counted["experts_hit"] >= 1
    assert sum(counted[n] for n in ("picks_held", "picks_zero",
                                    "picks_absent")) == 40 * sz["top_k"]
    # that expert alone, for every token: its weight times its output
    picks, weights_ = moe.route(u.reshape(40, 64), router, config(sz))
    assert (np.asarray(picks) == first + 2).sum() == 40


def test_a_pick_of_an_absent_expert_adds_exactly_nothing():
    """Every pick forced onto routed experts that other chips hold: the
    layer gives exact zeros, and says where the picks went."""
    sz = sizes(rank=1, layers=1)
    blk = weights(sz)["blocks"][0]
    elsewhere = jnp.asarray([0, 3, 9, 15])  # rank 1 holds 4..7
    router = dict(blk["router"],
                  bias=blk["router"]["bias"].at[elsewhere].set(10.0))
    u = jax.random.normal(jax.random.PRNGKey(3), (3, 7, 64), jnp.float32)
    got, counted = _expert_layer(sz, u, router, blk["experts"])
    assert not got.any()
    assert counted == {"picks_held": 0, "picks_zero": 0,
                       "picks_absent": 21 * sz["top_k"], "experts_hit": 0,
                       "tiles": 0}


@pytest.mark.parametrize("held, favoured, shape, hit", [
    # rank 1 holds experts 4..7 of 16; 16..23 are zero-compute
    pytest.param((4, 4), [5, 0, 9, 15], (1, 12), {5},
                 id="every_pick_on_one_expert"),
    pytest.param((4, 4), [4, 7, 0, 12, 16, 20], (3, 7), {4, 7},
                 id="the_first_and_the_last_held"),
    pytest.param((0, 16), [2, 7, 8, 13, 17, 22], (3, 7), {2, 7, 8, 13},
                 id="runs_of_unpicked_experts_between"),
    pytest.param((4, 4), [6, 1, 2, 3], (2, 20), {6},
                 id="an_expert_with_three_tiles"),
    pytest.param((0, 16), [], (4, 16), set(range(16)),
                 id="the_routers_own_picks"),
    pytest.param((4, 4), [0, 3, 9, 15], (3, 7), set(),
                 id="no_pick_held"),
])
def test_the_tiles_follow_the_picks(held, favoured, shape, hit):
    """A selection bias that confines the picks to the ``favoured``
    experts (each token's own four of them): the grouped product runs
    ceil(picks / tile) tiles for an expert and none for one nobody
    picked, wherever those lie among the experts held, and the layer is
    the reference's masked loop over every expert held."""
    sz = sizes(layers=1, held=held)
    blk = weights(sz)["blocks"][0]
    router = blk["router"]
    if favoured:
        router = dict(router, bias=router["bias"].at[
            jnp.asarray(favoured)].set(10.0))
    u = jax.random.normal(jax.random.PRNGKey(5), (*shape, 64), jnp.float32)
    got, counted = _expert_layer(sz, u, router, blk["experts"])
    want, picks = zip(*(ref.expert_layer(row, dict(blk, router=router), sz,
                                         "float32") for row in u))
    picks = np.asarray(picks)
    first, count = held
    picked = np.asarray([(picks == first + e).sum() for e in range(count)])
    assert {first + e for e in np.flatnonzero(picked)} == hit
    tile = moe._row_tile(shape[0] * shape[1])
    assert tile == 16
    assert counted["tiles"] == sum(-(-n // tile) for n in picked)
    assert counted["experts_hit"] == len(hit)
    assert counted["picks_held"] == picked.sum()
    if hit:
        np.testing.assert_allclose(got, np.stack(want), atol=ATOL, rtol=0)
        assert np.abs(got).max() > 0.05
    else:
        assert not got.any()


def test_generate_is_one_scan_and_its_counters_add_up():
    """The jaxpr of ``generate()`` at batch 3 holds one scan, the decode
    loop; inside it one loop a layer, the grouped product's over its row
    tiles (three products a tile, no loop inside: the readers take the
    outermost ``while`` that holds inner ones for the decode loop), and
    prefill's own beside it; the counters come back from the same call
    and add up to rows × positions × picks × layers; the served tokens are
    the reference's best."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    prompt = jnp.asarray(ids(3, 16, index=7))
    jaxpr = jax.make_jaxpr(lambda p, t: generate(p, t, cfg, 8))(
        params, prompt)

    def loops_in(j):
        return [e for e, _ in _walk_jaxpr(j)
                if e.primitive.name in ("scan", "while")]

    loops = loops_in(jaxpr.jaxpr)
    (decode,) = [e for e in loops if e.primitive.name == "scan"]
    assert decode.params["length"] == 8
    inner = loops_in(decode.params["jaxpr"].jaxpr)
    assert [e.primitive.name for e in inner] == ["while"] * sz["n_layers"]
    assert len(loops) == 1 + 2 * sz["n_layers"]  # prefill's, the scan, its
    for tiles in inner:
        body = tiles.params["body_jaxpr"].jaxpr
        assert not loops_in(body)
        assert sum(e.primitive.name == "dot_general"
                   for e, _ in _walk_jaxpr(body)) == 3

    tokens, counters = generate_with_counters(params, prompt, cfg, 8)
    counters = {k: int(v) for k, v in counters.items()}
    assert set(counters) == {"picks_held", "picks_zero", "picks_absent",
                             "experts_hit_decode", "tiles_decode"}
    assert (counters["picks_held"] + counters["picks_zero"]
            + counters["picks_absent"]) == 3 * (16 + 8) * sz["top_k"] * 2
    assert 0 < counters["experts_hit_decode"] <= 8 * 2 * 4
    # a tile an expert that got a token (3 rows a step: never two) and
    # none for the others, so never above experts_held × layers × steps
    assert counters["tiles_decode"] == counters["experts_hit_decode"]
    served = np.asarray(tokens)
    assert served.shape == (3, 8)
    np.testing.assert_array_equal(served,
                                  np.asarray(generate(params, prompt, cfg, 8)))
    full = np.concatenate([np.asarray(prompt), served[:, :-1]], axis=1)
    want = reference_logits(params, full, sz)[:, 15:]
    np.testing.assert_array_equal(served, want.argmax(-1))
    # a model without an expert layer counts nothing
    plain = ModelConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                        d_ff=64, max_seq=64, compute_dtype=jnp.float32)
    _, nothing = generate_with_counters(
        init_params(jax.random.PRNGKey(0), plain), prompt[:, :4] % 64,
        plain, 2)
    assert nothing == {}


def test_call_sizes_at_the_cells_widths():
    """The static counters of the cell's call, from the configuration
    file: 9,216 bytes a position for the 8 attentions' latents."""
    import json
    import os

    from benchmarks import program_longcat

    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(here, "benchmarks", "configs",
                           "longcat-flash-omni.json")) as f:
        cfg = program_longcat.model_config(json.load(f))
    assert call_sizes(cfg, 64, 128, 128) == {
        "cache_slots": 256, "cache_bytes": 9216 * 64 * 256,
        "ut_passes": 129, "experts_held": 16, "router_width": 768,
        # the two dense feed-forwards of each of the 4 double layers
        "ffn_streamed_layers": 8,
        "ffn_streamed_bytes": 8 * 3 * 6144 * 12288 * 2,
        # latent caches are attended as they lie, by no kernel
        "attention_streamed_layers": 0, "attention_streamed_bytes": 0}
    assert call_sizes(cfg, 1, 1, 127)["cache_bytes"] == 9216 * 128
    shapes = jax.eval_shape(lambda: init_kv_cache(cfg, 64, 256))
    assert len(shapes) == 4
    assert [c["latent"].shape for c in shapes[0]["attn"]] == [
        (1, 64, 256, 576)] * 2
    assert shapes[0]["counters"].shape == (len(moe.COUNTERS),)


def test_the_new_leaves_have_shardings_and_the_kinds_are_checked():
    from faabric_tpu.models import param_shardings
    from faabric_tpu.models.train import make_train_step
    from faabric_tpu.parallel import MeshConfig, build_mesh
    from faabric_tpu.parallel.pipeline import make_pp_loss

    sz = sizes()
    cfg = config(sz)
    params = init_params(jax.random.PRNGKey(0), cfg)
    made = weights(sz)
    assert jax.tree.structure(params) == jax.tree.structure(made)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, made)
    mesh = build_mesh(config=MeshConfig(tp=2))
    shardings = param_shardings(mesh, cfg)
    assert jax.tree.structure(shardings) == jax.tree.structure(params)
    half = shardings["blocks"][0]["halves"][1]
    assert half["wqb"].spec == half["wkvb"].spec
    assert half["wqa"].spec == half["ln1"].spec
    # latent attention alone, in single layers, is a kind too
    single = config(sz, layer="single")
    blk = init_params(jax.random.PRNGKey(0), single)["blocks"][0]
    assert set(blk) == {"ln1", "wqa", "q_norm", "wqb", "wkva", "kv_norm",
                        "wkvb", "wo", "ln2", "wg", "w1", "w2"}
    assert forward(init_params(jax.random.PRNGKey(0), single),
                   jnp.asarray(ids(1, 8)), single).shape == (1, 8, 256)
    for kind in ("attention", "layer"):
        with pytest.raises(ValueError, match=kind):
            dataclasses.replace(cfg, **{kind: "other"})
    with pytest.raises(ValueError, match="experts_held"):
        dataclasses.replace(cfg, experts_held=(14, 4))
    with pytest.raises(ValueError, match="qk_rope_dim"):
        dataclasses.replace(cfg, qk_rope_dim=7)
    # who does not implement the kinds says so by name
    with pytest.raises(ValueError, match="attention='heads' only"):
        make_train_step(cfg)
    with pytest.raises(ValueError, match="attention='heads'"):
        generate(params, jnp.asarray(ids(1, 8)), cfg, 2, mesh=mesh)
    with pytest.raises(ValueError, match="attention='heads' only"):
        make_pp_loss(dataclasses.replace(single, ffn="gelu", norm_eps=1e-6),
                     build_mesh(config=MeshConfig(pp=2)))
    moe_cfg = moe.MoEConfig(vocab_size=64, d_model=32, n_layers=1,
                            n_heads=2, d_ff=64, max_seq=64)
    with pytest.raises(ValueError, match="layer='single' only"):
        moe.init_moe_params(jax.random.PRNGKey(0), dataclasses.replace(
            moe_cfg, **{f.name: getattr(cfg, f.name)
                        for f in dataclasses.fields(cfg)
                        if f.name in ("layer", "routed_experts",
                                      "zero_experts", "experts_held",
                                      "experts_per_token", "expert_d_ff")}))
