"""Layers of two kinds in one model (``benchmarks/configs/
granite-4.0-h-micro.json``'s: Mamba-2 state-space layers beside
grouped-query attention without rotary, the four multipliers, a tied head)
at a small size on the CPU, float32 parameters from a seed: the program
(``models/ssm.py``, ``models/transformer.py``, ``models/generate.py``)
against the plain reference (``benchmarks/reference/granite.py``), which
shares no code with it.

The toy has both kinds with an attention layer that is neither first nor
last, 4 query heads on 2 key/value heads, every multiplier off 1, a tied
head and chunks of 8 positions.

Tolerances. Program and reference compute the same float32 mathematics in
another order (chunks and a carried state against a scan over positions, a
cache against a full forward pass, grouped heads against repeated ones), so
they differ by rounding alone: logits of spread 0.01 agree to a few 1e-8
here. ``RTOL`` 1e-4 of the largest logit leaves room for another BLAS and
fails on any term left out: a dropped D alone moves the logits by 1e-1 of
their size, a state not carried between chunks by more.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import weights_granite
from benchmarks.reference import granite as ref
from faabric_tpu.models import ModelConfig, forward, init_params, ssm
from faabric_tpu.models import transformer
from faabric_tpu.ops.attention import attend
from faabric_tpu.models.generate import (
    call_sizes,
    forward_with_cache,
    generate,
    init_kv_cache,
)
from tests.unit.test_models import _walk_jaxpr

RTOL = 1e-4
SEED = 2147483999
KINDS = ("mamba", "mamba", "attention", "mamba")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def sizes(kinds=KINDS):
    return {"vocab": 256, "d_model": 64, "n_layers": len(kinds),
            "layer_types": tuple(kinds), "n_heads": 4, "n_kv_heads": 2,
            "head_dim": 16, "d_ff": 96, "max_seq": 512, "norm_eps": 1e-5,
            "attention_multiplier": 0.05, "embedding_multiplier": 12.0,
            "residual_multiplier": 0.22, "logits_scaling": 8.0,
            "ssm_heads": 8, "ssm_head_dim": 16, "ssm_d_state": 32,
            "ssm_d_conv": 4, "ssm_groups": 2, "ssm_chunk": 8}


def config(sz, **other):
    return ModelConfig(**{**dict(
        vocab_size=sz["vocab"], d_model=sz["d_model"],
        n_layers=sz["n_layers"], n_heads=sz["n_heads"], d_ff=sz["d_ff"],
        max_seq=sz["max_seq"], ffn="swiglu", norm_eps=sz["norm_eps"],
        layer_types=sz["layer_types"], n_kv_heads=sz["n_kv_heads"],
        position="none", attention_scale=sz["attention_multiplier"],
        embedding_multiplier=sz["embedding_multiplier"],
        residual_multiplier=sz["residual_multiplier"],
        logits_scaling=sz["logits_scaling"], tie_embeddings=True,
        ssm_d_state=sz["ssm_d_state"], ssm_d_conv=sz["ssm_d_conv"],
        ssm_heads=sz["ssm_heads"], ssm_head_dim=sz["ssm_head_dim"],
        ssm_groups=sz["ssm_groups"], ssm_chunk=sz["ssm_chunk"],
        compute_dtype=jnp.float32, param_dtype=jnp.float32, remat=False),
        **other})


def weights(sz):
    return weights_granite.make_weights(SEED, sz, jnp.float32)


def ids(rows, length, index=0):
    return weights_granite.token_rows(SEED, 1, index, rows, length, 256)


def close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=RTOL * np.abs(want).max())


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def mixer_inputs(length, rows=3):
    sz = sizes()
    cfg = config(sz)
    blk = weights(sz)["blocks"][0]
    h = jax.random.normal(jax.random.PRNGKey(length), (rows, length, 64))
    shapes = ssm.state_shapes(cfg, rows)
    started = {name: 0.3 * jax.random.normal(jax.random.PRNGKey(i), shape)
               for i, (name, shape) in enumerate(shapes.items())}
    zero = {name: jnp.zeros(shape) for name, shape in shapes.items()}
    return cfg, blk, h, zero, started


@pytest.mark.parametrize("start", ["zero", "non_zero"])
@pytest.mark.parametrize("length", [2, 7, 8, 9, 16, 21])
def test_the_chunked_form_is_the_recurrence(length, start):
    """Lengths below, at and across the chunk's 8 positions, a last chunk
    that is not full, and a start from a state and a window that are not
    zero: the chunked form gives the outputs, the state and the window
    that one step a position gives."""
    cfg, blk, h, zero, started = mixer_inputs(length)
    cache = zero if start == "zero" else started
    chunked, left = ssm.mixer(h, blk, cfg, cache)
    steps, state = [], cache
    for t in range(length):
        out, state = ssm.mixer(h[:, t:t + 1], blk, cfg, state)
        steps.append(out)
    close(chunked, jnp.concatenate(steps, axis=1))
    close(left["state"], state["state"])
    close(left["conv"], state["conv"])
    # the window is the last three inputs of the convolution, whatever
    # the form; a start that is not zero moved the output
    assert left["conv"].shape == (3, 3, 8 * 16 + 2 * 2 * 32)
    if start == "non_zero":
        assert np.abs(np.asarray(chunked - ssm.mixer(h, blk, cfg, zero)[0])
                      ).max() > 1e-2


def test_the_mixer_is_the_references():
    """One state-space mixer alone against the reference's scan over
    positions, groups of heads sharing B and C."""
    cfg, blk, h, _, _ = mixer_inputs(21)
    sz = sizes()
    want = jnp.stack([ref.mamba(row, blk, sz, "float32") for row in h])
    close(ssm.mixer(h, blk, cfg)[0], want)


def test_forward_matches_the_reference():
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = ids(3, 21)
    want = ref.logits_of_rows(params, jnp.asarray(tokens), sz)
    close(forward(params, jnp.asarray(tokens), cfg), want)
    # the multipliers, the scale and the tied head are all in it
    for field in ("embedding_multiplier", "residual_multiplier",
                  "logits_scaling", "attention_scale"):
        other = dataclasses.replace(cfg, **{field: 1.0})
        moved = np.abs(np.asarray(
            forward(params, jnp.asarray(tokens), other) - want)).max()
        assert moved > 100 * RTOL * np.abs(np.asarray(want)).max(), field


@pytest.mark.parametrize("rows", [1, 3])
def test_prefill_then_cached_decoding_matches_the_full_forward(rows):
    """Prefill of 13 positions (two chunks, the second not full) and then
    one-token steps through both kinds of state against the reference's
    full forward pass: logits, not tokens."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = jnp.asarray(ids(rows, 21, index=1))
    cache = init_kv_cache(cfg, rows, 128)
    logits, cache = forward_with_cache(params, tokens[:, :13], cache, 0, cfg)
    got = [logits]
    for pos in range(13, 21):
        logits, cache = forward_with_cache(params, tokens[:, pos:pos + 1],
                                           cache, jnp.int32(pos), cfg)
        got.append(logits)
    close(jnp.concatenate(got, axis=1),
          ref.logits_of_rows(params, tokens, sz))
    # the head at the last position alone is the same numbers
    last, _ = forward_with_cache(params, tokens[:, :13],
                                 init_kv_cache(cfg, rows, 128), 0, cfg,
                                 last_only=True)
    assert last.shape == (rows, 1, 256)
    close(last, got[0][:, -1:])


def test_a_prefill_chunk_starts_from_the_state_the_one_before_left():
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = jnp.asarray(ids(3, 21, index=2))
    whole_logits, whole = forward_with_cache(
        params, tokens, init_kv_cache(cfg, 3, 128), 0, cfg)
    cache = init_kv_cache(cfg, 3, 128)
    pieces = []
    for pos in range(0, 21, 5):   # chunks that are no multiple of 8
        logits, cache = forward_with_cache(params, tokens[:, pos:pos + 5],
                                           cache, pos, cfg)
        pieces.append(logits)
    close(jnp.concatenate(pieces, axis=1), whole_logits)
    for layer, kind in enumerate(KINDS):
        for name in ("conv", "state") if kind == "mamba" else ("k", "v"):
            a, b = cache[layer][name], whole[layer][name]
            close(a[..., :21, :] if kind == "attention" else a,
                  b[..., :21, :] if kind == "attention" else b)
    np.testing.assert_array_equal(
        np.asarray(generate(params, tokens[:, :13], cfg, 6)),
        np.asarray(generate(params, tokens[:, :13], cfg, 6,
                            prefill_chunk=8)))


def test_grouped_query_attention_reads_the_cache_as_it_lies():
    """4 query heads on 2 key/value heads through the cache against the
    reference's attention with every key/value head repeated; and the
    grouped product against the equal-heads one on a repeated cache."""
    sz = sizes()
    blk = weights(sz)["blocks"][2]
    h = jax.random.normal(jax.random.PRNGKey(3), (21, 64))
    want = ref.attention(h, jax.tree.map(jnp.asarray, blk), sz, "float32")
    cfg = config(sz)
    q = jnp.einsum("sd,dhe->she", h, blk["wq"])[None]
    k, v = jnp.einsum("sd,dtke->tske", h, blk["wkv"])[:, None]
    cache = {name: jnp.zeros((1, 1, 2, 128, 16)) for name in ("k", "v")}
    assert transformer.cache_layout(cfg, 1, 128) == "head_major"
    attn, cache = transformer._attend_cached(q, k, v, cache, (0, 0), cfg,
                                             "attention", None)
    close(jnp.einsum("she,hed->sd", attn[0], blk["wo"]), want)
    assert cache["k"].shape == (1, 1, 2, 128, 16)
    repeated = [jnp.repeat(cache[name][0], 2, axis=1) for name in ("k", "v")]
    at = jnp.arange(128)
    close(attn, attend(q, *repeated, at[None, :] <= at[:21, None], at < 21,
                       cfg.score_scale, head_major=True))
    # without a cache too (``forward``)
    causal = at[None, :21] <= at[:21, None]
    close(attend(q, k, v, causal, None, cfg.score_scale), attn)


def test_attention_never_reads_a_slot_the_call_has_not_written():
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    tokens = jnp.asarray(ids(2, 9))
    clean = init_kv_cache(cfg, 2, 128)
    dirty = [{name: (jnp.full_like(a, jnp.nan) if name in ("k", "v") else a)
              for name, a in layer.items()} for layer in clean]
    want, _ = forward_with_cache(params, tokens, clean, 0, cfg)
    got, _ = forward_with_cache(params, tokens, dirty, 0, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_call_sizes_and_the_two_kinds_of_state():
    """``cache_bytes`` is the attention layers' alone and grows with the
    reach; ``state_bytes`` is the same at any reach; at the cell's sizes
    they are ISSUE 33's."""
    cfg = config(sizes())
    short, long = call_sizes(cfg, 3, 13, 5), call_sizes(cfg, 3, 300, 100)
    assert short["cache_slots"] == 128 and long["cache_slots"] == 512
    assert short["cache_bytes"] == 1 * 2 * 3 * 2 * 128 * 16 * 4
    assert long["cache_bytes"] == 4 * short["cache_bytes"]
    state = 3 * (3 * (3 * 256 + 8 * 16 * 32)) * 4
    assert short["state_bytes"] == long["state_bytes"] == state
    assert (short["attention_layers"], short["ssm_layers"]) == (1, 3)
    assert short["scan_chunks"] == 2 and long["scan_chunks"] == 38
    assert call_sizes(cfg, 3, 13, 5, prefill_chunk=5)["scan_chunks"] == 3
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 3, 128))
    assert [sorted(layer) for layer in cache] == [
        ["conv", "state"], ["conv", "state"], ["k", "v"], ["conv", "state"]]
    assert cache[2]["k"].shape == (1, 3, 2, 128, 16)
    assert cache[0]["state"].shape == (3, 8, 16, 32)
    assert cache[0]["conv"].shape == (3, 3, 256)

    from benchmarks import program_granite

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cell = program_granite.model_config(json.load(f))
    assert call_sizes(cell, 64, 512, 128, prefill_chunk=256) == {
        "cache_slots": 640, "cache_bytes": 8192 * 64 * 640,
        "ut_passes": 129, "attention_layers": 4, "ssm_layers": 36,
        "state_bytes": 36 * 64 * (64 * 64 * 128 + 3 * 4352) * 2,
        "scan_chunks": 2,
        # every layer's feed-forward streams its three matrices a step
        "ffn_streamed_layers": 40,
        "ffn_streamed_bytes": 40 * 3 * 2048 * 8192 * 2,
        # and the four attentions stream their dense keys and values
        "attention_streamed_layers": 4,
        "attention_streamed_bytes": 4 * 2 * 64 * 640 * 512 * 2,
        # both prefill chunks of the four keep their scores on the chip
        "prefill_attention_streamed_layers": 4,
        "prefill_attention_streamed_chunks": 2,
        "prefill_attention_streamed_bytes": 4 * (184549376 + 201326592)}
    shapes = jax.eval_shape(lambda: init_kv_cache(cell, 64, 640))
    # 64 rows: dense, a position's 8 heads of 64 one row of 512 lanes
    assert shapes[5]["k"].shape == (1, 64, 640, 512)
    # a row alone: head-major, through ``attend``
    assert jax.eval_shape(lambda: init_kv_cache(cell, 1, 640)
                          )[5]["k"].shape == (1, 1, 8, 640, 64)
    assert shapes[0]["state"].shape == (64, 64, 64, 128)
    assert shapes[0]["state"].dtype == jnp.bfloat16
    assert shapes[0]["conv"].shape == (64, 3, 4352)


def test_generate_is_one_loop_and_serves_the_references_best():
    """The jaxpr of ``generate()`` holds one loop, the decode scan, with
    or without ``prefill_chunk`` (the chunks of the state-space scan are
    unrolled: the readers find the decode loop as the trace's one
    ``while``); the served tokens are the reference's best."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    prompt = jnp.asarray(ids(3, 21, index=7))
    for chunk in (0, 8):
        jaxpr = jax.make_jaxpr(lambda p, t: generate(
            p, t, cfg, 6, prefill_chunk=chunk))(params, prompt)
        loops = [e for e, _ in _walk_jaxpr(jaxpr.jaxpr)
                 if e.primitive.name in ("scan", "while")]
        assert [e.primitive.name for e in loops] == ["scan"]
        assert loops[0].params["length"] == 6
    served = np.asarray(generate(params, prompt, cfg, 6, prefill_chunk=8))
    full = np.concatenate([np.asarray(prompt), served[:, :-1]], axis=1)
    want = np.asarray(ref.logits_of_rows(params, jnp.asarray(full), sz))
    np.testing.assert_array_equal(served, want[:, 20:].argmax(-1))


def test_generate_through_the_prefill_kernel_serves_the_lines_tokens(
        monkeypatch):
    """Heads of the cell's 64 lanes (8 query heads on 2 key/value heads,
    two a lane tile), 8 rows (a dense cache), a prompt of 256 in two
    chunks of 128: both chunks' attention goes through the kernel, the
    program holds one loop (the decode scan, which the readers find as
    the trace's one ``while``), and the tokens are those of the same call
    through ``attend``."""
    sz = dict(sizes(), d_model=512, n_heads=8, n_kv_heads=2, head_dim=64)
    cfg, params = config(sz), weights(sz)
    prompt = jnp.asarray(ids(8, 256, index=9))
    sized = call_sizes(cfg, 8, 256, 4, 128)
    assert (sized["prefill_attention_streamed_layers"],
            sized["prefill_attention_streamed_chunks"]) == (1, 2)

    def traced():
        jaxpr = jax.make_jaxpr(lambda p, t: generate(
            p, t, cfg, 4, prefill_chunk=128))(params, prompt)
        eqns = [e for e, _ in _walk_jaxpr(jaxpr.jaxpr)]
        return ([e.params["name"] for e in eqns
                 if e.primitive.name == "pallas_call"
                 and "prefill" in e.params["name"]],
                [e.primitive.name for e in eqns
                 if e.primitive.name in ("scan", "while")])

    assert traced() == (["prefill_attention"] * 2, ["scan"])
    through_kernel = generate(params, prompt, cfg, 4, prefill_chunk=128)
    monkeypatch.setattr(transformer, "streams_prefill_attention",
                        lambda *a, **k: None)
    jax.clear_caches()
    assert traced()[0] == []
    lines = generate(params, prompt, cfg, 4, prefill_chunk=128)
    jax.clear_caches()
    assert through_kernel.shape == (8, 4)
    np.testing.assert_array_equal(np.asarray(through_kernel),
                                  np.asarray(lines))


def test_the_planted_faults_are_seen_only_after_the_hand_over():
    sz = sizes()
    params = weights(sz)
    tokens = jnp.asarray(ids(2, 21, index=4))
    good = np.asarray(ref.logits_of_rows(params, tokens, sz))
    for fault in ref.FAULTS:
        bad = np.asarray(ref.logits_of_rows(params, tokens, sz, fault=fault,
                                            handover=13))
        np.testing.assert_array_equal(bad[:, :13], good[:, :13])
        assert np.abs(bad[:, 13] - good[:, 13]).max() \
            > 1e-2 * np.abs(good).max(), fault
    with pytest.raises(ValueError, match="fault"):
        ref.logits_of_rows(params, tokens, sz, fault="other")


@pytest.mark.parametrize("name", ["pythia-1.4b", "pythia-1.4b-shallow",
                                  "ouro-2.6b", "longcat-flash-omni"])
def test_the_accepted_configurations_build_what_they_built(name):
    """None names a layer's kind, grouped heads, a multiplier or a tied
    head: their ``ModelConfig``s hold the defaults, their caches and
    ``call_sizes`` are PR 32's, and they stay trainable where they were."""
    from benchmarks import program, program_longcat, program_ouro

    with open(os.path.join(REPO, "benchmarks", "configs",
                           name + ".json")) as f:
        values = json.load(f)
    build = {"ouro-2.6b": program_ouro, "longcat-flash-omni": program_longcat
             }.get(name, program).model_config
    cfg = build(values)
    plain = ModelConfig()
    for field in ("layer_types", "n_kv_heads", "position", "attention_scale",
                  "embedding_multiplier", "residual_multiplier",
                  "logits_scaling", "tie_embeddings", "ssm_d_state",
                  "ssm_heads"):
        assert getattr(cfg, field) == getattr(plain, field), field
    assert transformer.served_only(cfg) == []
    assert cfg.mixers == ("attention",) * cfg.n_layers
    assert cfg.kv_heads == cfg.n_heads
    sized = call_sizes(cfg, 2, 128, 64)
    # two rows: below the few rows the streaming kernel starts at
    assert sized["ffn_streamed_layers"] == sized["ffn_streamed_bytes"] == 0
    # and below the rows at which a step attends a dense cache
    assert (sized["attention_streamed_layers"]
            == sized["attention_streamed_bytes"] == 0)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 256))
    assert len(cache) == cfg.n_layers
    if name == "longcat-flash-omni":
        assert set(sized) == {"cache_slots", "cache_bytes", "ut_passes",
                              "experts_held", "router_width",
                              "prefill_expert_tile",
                              "ffn_streamed_layers", "ffn_streamed_bytes",
                              "attention_streamed_layers",
                              "attention_streamed_bytes"}
        # 256 tokens × 12 picks over 768 outputs: 4 rows, a tile of 128
        assert sized["prefill_expert_tile"] == 128
        assert cache[0]["attn"][1]["latent"].shape == (1, 2, 256, 576)
        assert sized["cache_bytes"] == 8 * 2 * 256 * 576 * 2
    else:
        assert set(sized) == {"cache_slots", "cache_bytes", "ut_passes",
                              "ffn_streamed_layers", "ffn_streamed_bytes",
                              "attention_streamed_layers",
                              "attention_streamed_bytes"}
        assert sorted(cache[0]) == ["k", "v"]
        assert cache[-1]["k"].shape == (cfg.n_passes, 2, 16, 256, 128)
        assert sized["cache_bytes"] == (cfg.n_layers * cfg.n_passes * 2 * 2
                                        * 16 * 256 * 128
                                        * jnp.dtype(cfg.compute_dtype).itemsize)
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert "lm_head" in shapes
    half = shapes["blocks"][0]
    half = half["halves"][0] if name == "longcat-flash-omni" else half
    assert ("wqkv" in half) == (name != "longcat-flash-omni")
    assert not {"wq", "wkv", "ssm_in"} & set(half)


def test_the_new_leaves_have_shardings_and_the_kinds_are_checked():
    from faabric_tpu.models import param_shardings
    from faabric_tpu.models.moe import MoEConfig, init_moe_params
    from faabric_tpu.models.train import make_train_step
    from faabric_tpu.parallel import MeshConfig, build_mesh
    from faabric_tpu.parallel.pipeline import make_pp_loss

    sz = sizes()
    cfg = config(sz)
    params = init_params(jax.random.PRNGKey(0), cfg)
    made = weights(sz)
    assert jax.tree.structure(params) == jax.tree.structure(made)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, made)
    assert "lm_head" not in params
    mesh = build_mesh(config=MeshConfig(tp=2))
    shardings = param_shardings(mesh, cfg)
    assert jax.tree.structure(shardings) == jax.tree.structure(params)

    # every new kind is refused by name where it is not implemented
    plain = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
                 max_seq=64)
    new = {"layer_types": ("attention", "mamba"), "n_kv_heads": 2,
           "position": "none", "attention_scale": 0.1,
           "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
           "logits_scaling": 8.0, "tie_embeddings": True}
    mamba = dict(ssm_d_state=8, ssm_d_conv=4, ssm_heads=4, ssm_head_dim=8)
    for field, value in new.items():
        other = ModelConfig(**plain, **mamba, **{field: value})
        assert transformer.served_only(other) == [f"{field}={value!r}"]
        with pytest.raises(ValueError, match=f"train step.*{field}"):
            make_train_step(other)
        with pytest.raises(ValueError, match=f"pipeline.*{field}"):
            make_pp_loss(other, build_mesh(config=MeshConfig(pp=2)))
        with pytest.raises(ValueError, match=f"MoE family.*{field}"):
            init_moe_params(jax.random.PRNGKey(0), MoEConfig(
                **plain, **mamba, **{field: value}))
        with pytest.raises(ValueError, match=f"under a mesh.*{field}"):
            generate(params, jnp.zeros((2, 4), jnp.int32), other, 2,
                     mesh=mesh)
    # what a configuration may not say
    for bad in (dict(layer_types=("mamba",)),
                dict(layer_types=("mamba", "linear")),
                dict(layer_types=("mamba", "attention")),   # no sizes
                dict(layer_types=("mamba", "attention"), **mamba,
                     n_passes=2),
                dict(n_kv_heads=3), dict(position="alibi")):
        with pytest.raises(ValueError):
            ModelConfig(**plain, **bad)


# ---------------------------------------------------------------------------
# Which feed-forward streams its matrices through ops/gated_ffn.py
# ---------------------------------------------------------------------------

def _plain(**other):
    return ModelConfig(**{**dict(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq=256, ffn="swiglu", compute_dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False), **other})


def _kernel_calls(fn, *args) -> int:
    return sum(e.primitive.name == "pallas_call" for e, _ in
               _walk_jaxpr(jax.make_jaxpr(fn)(*args).jaxpr))


# name → (what the configuration names, rows, positions a row, the calls a
# layer that the traced program must hold)
STREAMED = {
    "rows_8": ({}, 8, 1, 1),
    "rows_64": ({}, 64, 1, 1),
    "rows_128": ({}, 128, 1, 1),
    "bfloat16_parameters_and_products": (
        {"compute_dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16},
        8, 1, 1),
    "one_row": ({}, 1, 1, 0),
    "rows_7": ({}, 7, 1, 0),
    "rows_129": ({}, 129, 1, 0),
    "a_prefill_chunk": ({}, 8, 16, 0),
    "a_sandwich_norm": ({"norm_placement": "sandwich"}, 8, 1, 0),
    "gelu": ({"ffn": "gelu"}, 8, 1, 0),
    "float32_parameters_under_bfloat16_products": (
        {"compute_dtype": jnp.bfloat16}, 8, 1, 0),
}


@pytest.mark.parametrize("case", sorted(STREAMED))
def test_which_cached_call_streams_its_feed_forward(case):
    """The rule is over what the call can see: the cached path, one
    position a row, 8 to 128 rows, a gated feed-forward with no norm
    behind it, the matrices in the compute type. ``call_sizes`` counts
    what the traced program holds."""
    named, rows, positions, per_layer = STREAMED[case]
    cfg = _plain(**named)
    # every leaf in the parameters' type, as a server's weights lie
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, cfg.param_dtype),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, rows, 128))
    tokens = jax.ShapeDtypeStruct((rows, positions), jnp.int32)
    held = _kernel_calls(
        lambda p, t, c: forward_with_cache(p, t, c, 20, cfg),
        params, tokens, cache)
    assert held == per_layer * cfg.n_layers
    assert (transformer.streams_feed_forward(
        cfg, rows, positions, cfg.param_dtype) is not None) == bool(per_layer)
    if positions == 1:
        sized = call_sizes(cfg, rows, 20, 5)
        assert sized["ffn_streamed_layers"] == per_layer * cfg.n_layers
        assert sized["ffn_streamed_bytes"] == (
            per_layer * cfg.n_layers * 3 * 64 * 128
            * jnp.dtype(cfg.compute_dtype).itemsize)


def test_no_kernel_without_a_cache_or_under_a_mesh():
    """``forward()`` keeps no cache, so a gradient may be taken through
    it; under a mesh the matrices are laid over chips. Neither streams."""
    from faabric_tpu.parallel import MeshConfig, build_mesh

    cfg = _plain()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32)
    assert _kernel_calls(lambda p, t: forward(p, t, cfg), params, tokens) == 0
    mesh = build_mesh(jax.devices()[:1], MeshConfig())
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 8, 128))
    assert _kernel_calls(
        lambda p, t, c: forward_with_cache(p, t, c, 20, cfg, mesh=mesh),
        params, tokens, cache) == 0
    assert transformer.streams_feed_forward(
        cfg, 8, 1, jnp.float32, mesh) is None
    assert transformer.streams_feed_forward(cfg, 8, 1, jnp.float32)


@pytest.mark.parametrize("rows", [8, 64])
def test_the_streamed_step_serves_what_the_plain_lines_serve(rows):
    """Rows do not mix, and four rows a call are too few for the kernel:
    the tokens of ``rows`` rows served together, every layer's cached
    step through the kernel, are those of the same rows served four at a
    time through the feed-forward's own lines. Both kinds of layer, the
    residual's multiplier."""
    sz = sizes()
    cfg, params = config(sz), weights(sz)
    prompts = jnp.asarray(ids(rows, 13, index=3))
    assert call_sizes(cfg, rows, 13, 5)["ffn_streamed_layers"] == 4
    assert call_sizes(cfg, 4, 13, 5)["ffn_streamed_layers"] == 0
    jaxpr = jax.make_jaxpr(lambda p, t: generate(p, t, cfg, 5))(
        params, prompts).jaxpr
    in_loop = [inside for e, inside in _walk_jaxpr(jaxpr)
               if e.primitive.name == "pallas_call"]
    # one a layer, all in the decode loop: prefill holds none
    assert in_loop == [True] * sz["n_layers"]
    together = np.asarray(generate(params, prompts, cfg, 5))
    apart = np.concatenate([
        np.asarray(generate(params, prompts[at:at + 4], cfg, 5))
        for at in range(0, rows, 4)])
    np.testing.assert_array_equal(together, apart)
