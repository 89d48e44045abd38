"""Latent attention under YaRN in single layers, a leading dense layer and
expert layers with a shared expert under a sigmoid router
(``benchmarks/configs/a.x-k1.json``'s kinds) at a small size on the CPU,
float32 parameters from a seed: the program (``models/transformer.py``,
``models/moe.py:expert_layer``, ``models/generate.py``) against the plain
reference (``benchmarks/reference/axk1.py``), which shares no code with
it. The toy stretches a reach of 32 by 4 and every case runs past 32
positions, so the scaled frequencies and the scores' scale act.

Tolerances. Program and reference compute the same float32 mathematics in
another order (a cache, absorbed projections, blocks of queries, a grouped
product over the sorted picks' row tiles against a masked loop over
experts), so they differ by rounding alone: logits of size about 0.6 after
3 layers agree to a few 1e-6 here; 1e-4 leaves room for another BLAS and
fails on any term left out (the reference's planted faults move logits by
0.7 to 2.1) and on bfloat16 in float32's place (5e-2:
``test_bfloat16_in_float32s_place_fails_the_tolerance``). The routing is a
selection: at float32 on both sides no pick differs at these sizes.
"""

import dataclasses
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import program_axk1, weights_axk1
from benchmarks.reference import axk1 as ref
from faabric_tpu.models import ModelConfig, forward, init_params
from faabric_tpu.models import moe, transformer
from faabric_tpu.models.generate import (
    call_sizes,
    forward_with_cache,
    generate,
    generate_with_counters,
    init_kv_cache,
)

ATOL = 1e-4
SEED = 2147484041
CHIPS = 4  # the toy deployment: 16 routed experts over 4 chips
HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sizes(rank=1, layers=3, held=None):
    share = 16 // CHIPS
    return {"vocab": 256, "d_model": 64, "n_layers": layers,
            "dense_layers": 1, "n_heads": 4, "d_ff": 96, "expert_d_ff": 48,
            "shared_experts": 1, "max_seq": 512, "rope_theta": 1e4,
            "yarn": (4.0, 32.0, 32.0, 1.0, 1.0, 1.0), "norm_eps": 1e-6,
            "q_rank": 32, "kv_rank": 16, "qk_nope": 16, "qk_rope": 8,
            "v_head": 16, "routed_experts": 16,
            "experts_held": held or (rank * share, share), "top_k": 4,
            "routed_scaling": 2.5}


def config(sz, dtype="float32", **other):
    cfg = program_axk1.model_config(
        {"compute_dtype": dtype, "param_dtype": "float32"}, sz)
    return dataclasses.replace(cfg, remat=False, **other)


def weights(sz):
    return weights_axk1.make_weights(SEED, sz, jnp.float32)


def ids(rows, length, index=0):
    return weights_axk1.token_rows(SEED, 1, index, rows, length, 256)


def reference_logits(params, tokens, sz, **how):
    return np.asarray(ref.logits_of_rows(params, jnp.asarray(tokens), sz,
                                         **how))


def published():
    with open(os.path.join(HERE, "benchmarks", "configs",
                           "a.x-k1.json")) as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def test_forward_matches_the_reference():
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = ids(3, 48)
    got = forward(params, jnp.asarray(tokens), cfg)
    want = reference_logits(params, tokens, sz)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)
    # and every planted fault is another network
    for fault in ref.FAULTS:
        other = reference_logits(params, tokens, sz, fault=fault,
                                 handover=40, chunk=16)
        assert np.abs(other - want).max() > 0.1, fault


@pytest.mark.parametrize("length, block, blocks", [
    (48, 16, 3), (47, 16, 3), (48, 7, 8)])
def test_the_reference_in_blocks_of_positions_is_the_reference_whole(
        monkeypatch, length, block, blocks):
    """On the chip the reference goes a block of positions at a time so
    that it fits beside the weights: equal blocks where the length has a
    divisor near ``BLOCK``, a shorter last one where not; the same
    logits and picks, a planted fault included."""
    sz = sizes()
    params = weights(sz)
    tokens = jnp.asarray(ids(1, length, index=3)[0])
    how = dict(fault="chunk_carry_dropped", handover=40, chunk=16)
    whole, picks = ref.logits_of(params, tokens, sz, with_picks=True)
    faulty = ref.logits_of(params, tokens, sz, **how)
    monkeypatch.setattr(ref, "BLOCK", block)
    monkeypatch.setattr(ref, "SCORE_BYTES", 4 * 2 * 16 * 48)
    assert len(ref._blocks(length)) == blocks
    got, got_picks = ref.logits_of(params, tokens, sz, with_picks=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(got_picks), np.asarray(picks))
    np.testing.assert_allclose(
        np.asarray(ref.logits_of(params, tokens, sz, **how)),
        np.asarray(faulty), atol=1e-5, rtol=0)


def test_bfloat16_in_float32s_place_fails_the_tolerance():
    sz = sizes()
    params = weights(sz)
    tokens = ids(2, 48)
    got = forward(params, jnp.asarray(tokens), config(sz, "bfloat16"))
    gap = np.abs(np.asarray(got) - reference_logits(params, tokens, sz)).max()
    assert gap > 50 * ATOL


@pytest.mark.parametrize("rows, chunk", [(1, 0), (3, 0), (2, 16)])
def test_prefill_then_cached_decoding_matches_the_full_forward(rows, chunk):
    """Prefill of 40 positions (keys and values expanded from the latent
    cache; with ``chunk`` in chunks of 16, 16 and 8, the later ones
    attending the chunks before them over the latents as they lie), then
    8 single-token steps over the latent cache (the up-projections
    absorbed), every row at its own positions: logits against the
    reference's full forward pass, which has no cache and no chunk."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = ids(rows, 48, index=rows)
    cache = init_kv_cache(cfg, rows, 128)
    got = []
    for pos in range(0, 40, chunk or 40):
        end = min(40, pos + (chunk or 40))
        logits, cache = forward_with_cache(
            params, jnp.asarray(tokens[:, pos:end]), cache, pos, cfg)
        got.append(np.asarray(logits))
    for pos in range(40, 48):
        logits, cache = forward_with_cache(
            params, jnp.asarray(tokens[:, pos:pos + 1]), cache,
            jnp.int32(pos), cfg)
        got.append(np.asarray(logits))
    np.testing.assert_allclose(np.concatenate(got, axis=1),
                               reference_logits(params, tokens, sz),
                               atol=ATOL, rtol=0)
    # a layer's state: one latent cache, 48 of 128 slots written, and,
    # in an expert layer alone, its counters over the 48 positions
    assert set(cache[0]) == {"latent"}
    assert set(cache[1]) == {"latent", "counters"}
    assert cache[1]["latent"].shape == (1, rows, 128, 16 + 8)
    assert not np.asarray(cache[1]["latent"][:, :, 48:]).any()
    assert np.asarray(cache[1]["latent"][:, :, :48]).any()
    assert int(cache[1]["counters"][:3].sum()) == rows * 48 * sz["top_k"]
    assert int(cache[1]["counters"][1]) == 0  # no zero-compute expert


def test_chunked_generate_serves_what_unchunked_does():
    """``generate()`` with a prompt of 40 in chunks of 16 and whole: the
    same tokens, the reference's best, and the same counters."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    prompt = jnp.asarray(ids(2, 40, index=5))
    whole, counted = generate_with_counters(params, prompt, cfg, 8)
    chunked, counted_chunked = generate_with_counters(
        params, prompt, cfg, 8, prefill_chunk=16)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(chunked))
    assert {k: int(v) for k, v in counted.items()} \
        == {k: int(v) for k, v in counted_chunked.items()}
    full = np.concatenate([np.asarray(prompt), np.asarray(whole)[:, :-1]],
                          axis=1)
    want = reference_logits(params, full, sz)[:, 39:]
    np.testing.assert_array_equal(np.asarray(whole), want.argmax(-1))
    counted = {k: int(v) for k, v in counted.items()}
    assert set(counted) == {"picks_held", "picks_zero", "picks_absent",
                            "experts_hit_decode", "tiles_decode"}
    # two expert layers count; the dense layer has no router
    assert counted["picks_held"] + counted["picks_absent"] \
        == 2 * (40 + 8) * sz["top_k"] * 2
    assert counted["picks_zero"] == 0
    assert 0 < counted["experts_hit_decode"] <= 8 * 2 * 4
    np.testing.assert_array_equal(
        np.asarray(whole), np.asarray(generate(params, prompt, cfg, 8)))


@pytest.mark.parametrize("limit, blocks", [
    (4 * 4 * 8 * 40, (1, 8)),       # a row's scores of 8 queries
    (4 * 4 * 24 * 40 * 3, (3, 24)),  # three rows' whole
    (4 * 4 * 1 * 40, (1, 1)),       # one query's
    (1, (1, 1)),                    # nothing cuts the keys
])
def test_expanded_attention_in_blocks_equals_the_unblocked_lines(
        monkeypatch, limit, blocks):
    """``_latent_expanded`` of 6 rows × 24 queries over 40 keys, whole and
    with ``SCORE_BYTES`` so small that rows, then queries, go in blocks:
    the same attention, and no block's float32 scores above the limit
    where a query's fit it."""
    sz = sizes()
    cfg = config(sz)
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q_nope = jax.random.normal(k[0], (6, 24, 4, 16))
    q_rope = jax.random.normal(k[1], (6, 24, 4, 8))
    latent = jax.random.normal(k[2], (6, 40, 24))
    wkvb = jax.random.normal(k[3], (16, 4, 32)) / 4.0
    whole = transformer._latent_expanded(q_nope, q_rope, latent, wkvb, cfg)
    monkeypatch.setattr(transformer, "SCORE_BYTES", limit)
    assert transformer.score_blocks(6, 4, 24, 40) == blocks
    jaxpr = jax.make_jaxpr(
        lambda *a: transformer._latent_expanded(*a, wkvb, cfg))(
            q_nope, q_rope, latent)
    widest = max(math.prod(v.aval.shape) * 4
                 for eqn, _ in _walk(jaxpr.jaxpr) for v in eqn.outvars
                 if v.aval.dtype == jnp.float32 and len(v.aval.shape) == 4
                 and v.aval.shape[-1] == 40)
    assert widest <= max(limit, 4 * 4 * 40)
    got = transformer._latent_expanded(q_nope, q_rope, latent, wkvb, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               atol=1e-5, rtol=0)
    # the absorbed form over the same latents, a later chunk's and a
    # cached step's path, goes in the same blocks and agrees too
    absorbed = transformer._latent_absorbed(q_nope, q_rope, latent, 40,
                                            wkvb, cfg)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(whole),
                               atol=1e-5, rtol=0)
    step = transformer._latent_absorbed(
        q_nope[:, -1:], q_rope[:, -1:], latent, 40, wkvb, cfg)
    np.testing.assert_allclose(np.asarray(step), np.asarray(whole[:, -1:]),
                               atol=1e-5, rtol=0)


def _walk(jaxpr):
    from tests.unit.test_models import _walk_jaxpr

    return _walk_jaxpr(jaxpr)


def test_call_sizes_counts_the_blocks_of_a_chunked_prefill(monkeypatch):
    """``score_blocks`` of a call: the blocks a layer's scores go in over
    the prompt's chunks, from the sizes ``_latent_expanded`` itself
    uses."""
    sz = sizes()
    cfg = config(sz)
    monkeypatch.setattr(transformer, "SCORE_BYTES", 4 * 4 * 8 * 32)
    # chunks of 16 at reaches 16, 32, 48 over 2 rows: a row's 16 queries
    # fit at 16 keys (2 rows in one block), 8 at 32 (2 × 2), 4 at 48
    assert [transformer.score_blocks(2, 4, 16, reach)
            for reach in (16, 32, 48)] == [(1, 16), (1, 8), (1, 4)]
    got = call_sizes(cfg, 2, 48, 8, 16)
    assert got["score_blocks"] == 2 * 1 + 2 * 2 + 2 * 4
    assert got["prefill_chunks"] == 3 and got["expanded_bytes"] == 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Over all four ranks of the toy deployment, each holding 4 of the
    16 routed experts: the expert layers' outputs, with what every chip
    computes alike (attention, the shared expert) counted once, add up
    to what the reference gives for the whole layer, all 16 experts in
    one place."""
    whole = sizes(held=(0, 16), layers=2)
    blk = weights(whole)["blocks"][1]
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 40, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    uncut = np.stack([np.asarray(ref.layer(row, blk, whole)[0])
                      for row in x])
    # what all chips compute alike: the layer with no routed expert held
    nobody = dict(whole, experts_held=(0, 0))
    alike = np.stack([np.asarray(ref.layer(row, blk, nobody)[0])
                      for row in x])
    total = np.zeros_like(uncut)
    for rank in range(CHIPS):
        sz = sizes(rank, layers=2)
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


def test_sigmoid_weights_sum_to_the_scaling_and_softmax_stays():
    sz = sizes()
    cfg = config(sz)
    u = jax.random.normal(jax.random.PRNGKey(3), (50, 64))
    router = {"w": jax.random.normal(jax.random.PRNGKey(4), (64, 16)) / 8,
              "bias": jnp.linspace(-0.5, 0.5, 16)}
    picks, w = moe.route(u, router, cfg)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-6)
    scores = jax.nn.sigmoid(u @ router["w"])
    want = jax.lax.top_k(scores, 4)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(want[1]))
    np.testing.assert_allclose(
        np.asarray(w), 2.5 * np.asarray(want[0] / want[0].sum(-1,
                                                              keepdims=True)),
        rtol=1e-6)
    # the three fields one by one: the score, the sum, the stored bias
    plain = dataclasses.replace(cfg, router_renormalise=False)
    np.testing.assert_allclose(np.asarray(moe.route(u, router, plain)[1]),
                               2.5 * np.asarray(want[0]), rtol=1e-6)
    soft = dataclasses.replace(plain, router_score="softmax")
    sm = jax.nn.softmax(u @ router["w"], axis=-1)
    np.testing.assert_allclose(
        np.asarray(moe.route(u, router, soft)[1]),
        2.5 * np.asarray(jax.lax.top_k(sm, 4)[0]), rtol=1e-5)
    biased = dataclasses.replace(soft, router_bias=True)
    np.testing.assert_array_equal(
        np.asarray(moe.route(u, router, biased)[0]),
        np.asarray(jax.lax.top_k(sm + router["bias"], 4)[1]))


def test_yarn_at_the_published_sizes_against_hand_values():
    """low 10, high 23, the scores' scale 0.13086, cosines and sines as
    they are; fast pairs turn as they did, slow ones 32 times slower."""
    cfg = program_axk1.model_config(published())
    scaling = cfg.rope_scaling
    assert transformer.yarn_range(scaling, 64, 1e4) == (10, 23)
    assert ref.yarn_range(weights_axk1.sizes_of(published())) == (10, 23)
    assert abs(transformer.yarn_mscale(32.0, 1.0) - 1.34657) < 1e-5
    assert abs(cfg.score_scale - 0.13086) < 1e-5
    plain = 1e4 ** (-np.arange(32) / 32.0)
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    want = plain * (1 - ramp) + plain / 32 * ramp
    got = np.asarray(transformer.rope_frequencies(64, 1e4, scaling))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 32, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.frequencies(
        weights_axk1.sizes_of(published()))), want, rtol=1e-6)
    # mscale = mscale_all_dim: the turn keeps a pair's length
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    at = jnp.asarray([[0, 1, 4096, 8191, 131071]])
    turned = transformer._rope(x, at, 1e4, "neighbours", scaling)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(turned), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # without scaling the frequencies and the scale are what they were
    unscaled = dataclasses.replace(cfg, rope_scaling=None)
    np.testing.assert_allclose(
        np.asarray(transformer.rope_frequencies(64, 1e4)), plain, rtol=1e-6)
    assert abs(unscaled.score_scale - 1 / math.sqrt(192)) < 1e-9


def test_n_params_at_the_published_and_the_held_sizes():
    """By ``jax.eval_shape``: nothing of the 519 B is allocated."""
    config_file = published()
    held = weights_axk1.n_params(weights_axk1.sizes_of(config_file))
    whole = weights_axk1.n_params(weights_axk1.sizes_of(config_file, True))
    assert whole["total"] == 518_982_622_208 \
        == config_file["parameters"]["published_sizes"]
    assert held["total"] == 4_841_331_712 \
        == config_file["parameters"]["held_here"]
    assert held["attention"] == 101_122_048 + 2_048
    assert held["expert"] == held["shared"] == 44_040_192
    assert held["router"] == 1_376_256
    assert held["dense_layer"] == 497_500_160
    assert whole["expert_layer"] == 146_554_880 + 192 * 44_040_192
    # and the program's own tree at the held sizes is the same tree
    cfg = program_axk1.model_config(config_file)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(math.prod(leaf.shape)
               for leaf in jax.tree.leaves(shapes)) == held["total"]


def test_call_sizes_at_the_cells_widths():
    """The static counters of the cell's call, from the configuration
    file: 8,064 bytes a position for the 7 attentions' latents."""
    cfg = program_axk1.model_config(published())
    got = call_sizes(cfg, 8, 8192, 64, 1024)
    dense, shared = 3 * 7168 * 18432 * 2, 3 * 7168 * 2048 * 2
    assert got == {
        "cache_slots": 8320, "cache_bytes": 8064 * 8 * 8320,
        "ut_passes": 65, "experts_held": 12, "router_width": 192,
        "shared_experts": 1, "dense_layers": 1, "expert_layers": 6,
        "prefill_chunks": 8,
        # every chunk of 1,024 queries over 1,024 … 8,192 keys keeps its
        # scores on the chip (ops/latent_attention.py): no block of
        # float32 scores goes through HBM
        "score_blocks": 0, "expanded_bytes": 0,
        "latent_streamed_layers": 7, "latent_streamed_chunks": 8,
        # queries in, outputs out, the reach's keys and values once
        "latent_streamed_bytes": 7 * sum(
            8 * 64 * 2 * (128 + 64 + 128) * (1024 + reach)
            for reach in range(1024, 8193, 1024)),
        # the dense layer's feed-forward and the six shared experts
        "ffn_streamed_layers": 7, "ffn_streamed_bytes": dense + 6 * shared,
        # latent caches are attended as they lie, by no kernel
        "attention_streamed_layers": 0, "attention_streamed_bytes": 0}
    # where the plan refuses (a prompt the tiles do not divide), a chunk
    # of 1,000 queries over 1,000 … 8,000 keys, 64 heads, goes in blocks:
    # two rows at once, then a row, then a row's queries in 2, 2, 4, 4, 4
    # and 4 blocks
    ragged = call_sizes(cfg, 8, 8000, 64, 1000)
    assert ragged["score_blocks"] == 4 + 8 * (1 + 2 + 2 + 4 + 4 + 4 + 4)
    assert ragged["latent_streamed_layers"] == 0
    for pos in range(1024, 8193, 1024):
        rows, queries = transformer.score_blocks(8, 64, 1024, pos)
        assert 4 * rows * 64 * queries * pos <= transformer.SCORE_BYTES
    shapes = jax.eval_shape(lambda: init_kv_cache(cfg, 8, 8320))
    assert [sorted(layer) for layer in shapes] == [["latent"]] + [
        ["counters", "latent"]] * 6
    assert shapes[1]["latent"].shape == (1, 8, 8320, 576)
    assert shapes[1]["counters"].shape == (len(moe.COUNTERS),)


def test_the_shared_expert_of_a_cached_step_streams_through_the_kernel():
    """At 8 rows a cached step's dense feed-forward and shared expert go
    through ``ops/gated_ffn.py`` (interpreted here), planned from the
    matrices' own widths; the logits are the lines'."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    assert transformer.feed_forward_widths(cfg) == [96, 48, 48]
    assert call_sizes(cfg, 8, 40, 8)["ffn_streamed_layers"] == 3
    assert call_sizes(cfg, 7, 40, 8)["ffn_streamed_layers"] == 0
    tokens = ids(8, 41, index=9)
    cache = init_kv_cache(cfg, 8, 128)
    _, cache = forward_with_cache(params, jnp.asarray(tokens[:, :40]),
                                  cache, 0, cfg)
    jaxpr = jax.make_jaxpr(lambda c: forward_with_cache(
        params, jnp.asarray(tokens[:, 40:]), c, jnp.int32(40), cfg))(cache)
    assert sum(eqn.primitive.name == "pallas_call"
               for eqn, _ in _walk(jaxpr.jaxpr)) == 3
    step, _ = forward_with_cache(params, jnp.asarray(tokens[:, 40:]),
                                 cache, jnp.int32(40), cfg)
    np.testing.assert_allclose(
        np.asarray(step)[:, 0], reference_logits(params, tokens, sz)[:, 40],
        atol=ATOL, rtol=0)


def test_the_leaves_are_the_benchmarks_and_the_kinds_are_checked():
    sz = sizes()
    cfg = config(sz)
    params = init_params(jax.random.PRNGKey(0), cfg)
    made = weights(sz)
    assert jax.tree.structure(params) == jax.tree.structure(made)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, made)
    assert "router" not in params["blocks"][0]
    assert set(params["blocks"][1]["router"]) == {"w"}
    for bad, match in (
            (dict(ffn_types=("dense", "experts")), "ffn_types"),
            (dict(ffn_types=("dense", "sparse", "experts")), "ffn_types"),
            (dict(layer="shortcut"), "ffn_types"),
            (dict(ffn="gelu"), "swiglu"),
            (dict(ffn_types=(), shared_experts=1), "shared_experts"),
            (dict(experts_held=(14, 4)), "experts_held"),
            (dict(router_score="tanh"), "router_score"),
            (dict(rope_scaling=transformer.RopeScaling(0.5, 32)),
             "rope_scaling"),
            (dict(position="none"), "rope_scaling")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(cfg, **bad)
    from faabric_tpu.models import param_shardings
    from faabric_tpu.parallel import MeshConfig, build_mesh

    with pytest.raises(ValueError, match="ffn_types"):
        param_shardings(build_mesh(config=MeshConfig(tp=2)), cfg)


NEW_FIELDS = {
    "ffn_types": dict(ffn_types=("dense",)),
    "shared_experts": dict(
        ffn_types=("experts",), shared_experts=1, ffn="swiglu",
        routed_experts=4, experts_held=(0, 2), experts_per_token=2,
        expert_d_ff=16),
    "router_score": dict(router_score="sigmoid"),
    "router_renormalise": dict(router_renormalise=True),
    "router_bias": dict(router_bias=False),
    "latent_scale": dict(latent_scale=False),
    "rope_scaling": dict(rope_scaling=transformer.RopeScaling(4.0, 32)),
}


@pytest.mark.parametrize("field", sorted(NEW_FIELDS))
def test_who_does_not_implement_a_new_field_refuses_it_by_name(field):
    """``served_only`` names every field this configuration brought; the
    train step, the pipeline, the MoE family and ``generate()`` under a
    mesh refuse each by name. The benchmark's LongCat configuration names
    none of them: its program is as it was."""
    from faabric_tpu.models.train import make_train_step
    from faabric_tpu.parallel import MeshConfig, build_mesh
    from faabric_tpu.parallel.pipeline import make_pp_loss

    plain = ModelConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=2,
                        d_ff=64, max_seq=64, compute_dtype=jnp.float32)
    assert transformer.served_only(plain) == []
    other = dataclasses.replace(plain, **NEW_FIELDS[field])
    assert any(name.startswith(field + "=")
               for name in transformer.served_only(other))
    with pytest.raises(ValueError, match=f"train step.*{field}"):
        make_train_step(other)
    if field != "shared_experts":
        # (the pipeline's stages and the MoE family are GELU alone, which
        # they say before they come to a gated layer's shared experts)
        with pytest.raises(ValueError, match=f"pipeline.*{field}"):
            make_pp_loss(dataclasses.replace(
                other, n_layers=2, ffn_types=other.ffn_types * 2),
                build_mesh(config=MeshConfig(pp=2)))
        with pytest.raises(ValueError, match=f"MoE family.*{field}"):
            moe.init_moe_params(jax.random.PRNGKey(0), moe.MoEConfig(**{
                f.name: getattr(other, f.name)
                for f in dataclasses.fields(other)}))
    params = init_params(jax.random.PRNGKey(0), other)
    with pytest.raises(ValueError, match=f"under a mesh.*{field}"):
        generate(params, jnp.zeros((1, 4), jnp.int32), other, 2,
                 mesh=build_mesh(config=MeshConfig(tp=2)))
    full = set(name.split("=")[0] for name in transformer.served_only(
        program_axk1.model_config(published())))
    assert set(NEW_FIELDS) <= full
    from benchmarks import program_longcat

    with open(os.path.join(HERE, "benchmarks", "configs",
                           "longcat-flash-omni.json")) as f:
        longcat = program_longcat.model_config(json.load(f))
    assert transformer.served_only(longcat) == []
