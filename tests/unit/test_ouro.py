"""The looped decoder (``benchmarks/configs/ouro-2.6b.json``'s kinds) at a
small size on the CPU, float32 parameters from a seed: the program
(``models/transformer.py``, ``models/generate.py``) against the plain
reference (``benchmarks/reference/ouro.py``), which shares no code with
it.

Tolerances. Program and reference compute the same float32 mathematics in
another order (fused projections, a cache, a rolled loop), so they differ
by rounding alone: logits of size about 1 after 6 to 12 blocks agree to a
few 1e-6 here; 1e-4 leaves room for another BLAS and fails on any term
left out (a dropped norm scale alone moves logits by 1e-1).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import weights_ouro
from benchmarks.reference import ouro as ref
from faabric_tpu.models import ModelConfig, forward
from faabric_tpu.models.generate import (
    call_sizes,
    forward_with_cache,
    generate,
    init_kv_cache,
)
from tests.unit.test_models import _walk_jaxpr

ATOL = 1e-4
SEED = 2147483999


def sizes(passes, threshold=1.0):
    return {"vocab": 256, "d_model": 64, "n_layers": 3, "n_heads": 4,
            "head_dim": 16, "d_ff": 176, "max_seq": 512,
            "rope_theta": 1e6, "norm_eps": 1e-6, "passes": passes,
            "exit_threshold": threshold}


def config(sz):
    return ModelConfig(
        vocab_size=sz["vocab"], d_model=sz["d_model"],
        n_layers=sz["n_layers"], n_heads=sz["n_heads"], d_ff=sz["d_ff"],
        max_seq=sz["max_seq"], rope_theta=sz["rope_theta"], ffn="swiglu",
        norm_placement="sandwich", rope_pairing="halves",
        norm_eps=sz["norm_eps"], n_passes=sz["passes"],
        exit_threshold=sz["exit_threshold"], compute_dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)


def weights(sz):
    return weights_ouro.make_weights(SEED, sz, jnp.float32)


def ids(length, index=0):
    return weights_ouro.token_rows(SEED, 1, index, 1, length, 256)


@pytest.mark.parametrize("passes", [2, 4])
def test_forward_matches_the_reference(passes):
    sz = sizes(passes)
    params, tokens = weights(sz), ids(24)
    got = forward(params, jnp.asarray(tokens), config(sz))[0]
    want = ref.logits_of(params, jnp.asarray(tokens[0]), sz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    # not a stub: the passes are not one pass repeated on the embedding
    fewer = ref.logits_of(params, jnp.asarray(tokens[0]),
                          dict(sz, passes=passes - 1))
    assert float(jnp.max(jnp.abs(fewer - want))) > 0.1


@pytest.mark.parametrize("passes", [2, 4])
def test_prefill_then_cached_decoding_matches_the_full_forward(passes):
    """Logits, not tokens: the prompt through the caches at once, then
    eight tokens one at a time, each against the reference's full forward
    pass over the whole sequence."""
    sz = sizes(passes)
    cfg, params = config(sz), weights(sz)
    tokens = ids(20)
    s_p = 12
    want = np.asarray(ref.logits_of(params, jnp.asarray(tokens[0]), sz))
    cache = init_kv_cache(cfg, 1, 32)
    logits, cache = forward_with_cache(
        params, jnp.asarray(tokens[:, :s_p]), cache, 0, cfg)
    np.testing.assert_allclose(np.asarray(logits[0]), want[:s_p], atol=ATOL)
    for pos in range(s_p, 20):
        logits, cache = forward_with_cache(
            params, jnp.asarray(tokens[:, pos:pos + 1]), cache, pos, cfg)
        np.testing.assert_allclose(np.asarray(logits[0, 0]), want[pos],
                                   atol=ATOL)


def test_a_pass_reads_and_writes_only_its_own_cache():
    sz = sizes(4)
    cfg, params = config(sz), weights(sz)
    tokens = ids(13)
    cache = init_kv_cache(cfg, 1, 32)
    assert cache[0]["k"].shape == (4, 1, 4, 32, 16)
    _, cache = forward_with_cache(params, jnp.asarray(tokens[:, :12]),
                                  cache, 0, cfg)
    for layer in cache:
        for t in range(1, 4):
            assert not np.allclose(layer["k"][t, :, :, :12],
                                   layer["k"][0, :, :, :12], atol=1e-2)
        assert not np.asarray(layer["v"][:, :, :, 12:]).any()
    nxt = jnp.asarray(tokens[:, 12:13])
    base, _ = forward_with_cache(params, nxt, cache, 12, cfg)
    for t in range(4):
        wiped = [{n: a.at[t].set(0.0) for n, a in layer.items()}
                 for layer in cache]
        got, after = forward_with_cache(params, nxt, wiped, 12, cfg)
        assert float(jnp.max(jnp.abs(got - base))) > 1e-2, t
        # the step wrote position 12 of every pass and nothing else
        for layer, was in zip(after, wiped):
            assert np.asarray(layer["k"][:, :, :, 12]).all()
            np.testing.assert_array_equal(layer["k"][:, :, :, :12],
                                          was["k"][:, :, :, :12])


@pytest.mark.parametrize("passes", [1, 4])
def test_attention_never_reads_a_slot_the_call_has_not_written(passes):
    """On the chip the compiler leaves a cache that is written only
    through a loop unfilled (my chip run, PR 27: the served tokens were
    NaN's argmax at 512 and 1024 tokens): what an unwritten slot holds,
    NaN included, must not reach a logit, in prefill or in a step."""
    sz = sizes(passes)
    cfg, params = config(sz), weights(sz)
    tokens = jnp.asarray(ids(13))

    def through(cache):
        logits, cache = forward_with_cache(params, tokens[:, :12], cache,
                                           0, cfg)
        step, _ = forward_with_cache(params, tokens[:, 12:], cache, 12, cfg)
        return np.asarray(logits), np.asarray(step)

    clean = init_kv_cache(cfg, 1, 32)
    dirty = [{n: jnp.full_like(a, jnp.nan) for n, a in layer.items()}
             for layer in clean]
    for got, want in zip(through(dirty), through(clean)):
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)


def test_the_exit_rule_in_forward_and_its_refusal_in_cached_decoding():
    sz = sizes(4, 0.5)
    cfg, params = config(sz), weights(sz)
    tokens = ids(48)
    states, chosen = ref.states_of(params, jnp.asarray(tokens[0]), sz)
    chosen = np.asarray(chosen)
    # the seed's gate sends positions out at more than one pass
    assert len(set(chosen.tolist())) > 1 and chosen.min() < 3
    got = forward(params, jnp.asarray(tokens), cfg)[0]
    want = ref.logits_of(params, jnp.asarray(tokens[0]), sz)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)
    # at 1.0 every position reads the last pass, whatever the gate says
    at_one = forward(params, jnp.asarray(tokens),
                     dataclasses.replace(cfg, exit_threshold=1.0))[0]
    last = ref._head_jit(states[-1], params["lm_head"], "float32")
    np.testing.assert_allclose(np.asarray(at_one), np.asarray(last),
                               atol=ATOL)
    assert float(jnp.max(jnp.abs(at_one - got))) > 0.1
    with pytest.raises(ValueError, match="exit_threshold"):
        forward_with_cache(params, jnp.asarray(tokens[:, :8]),
                           init_kv_cache(cfg, 1, 16), 0, cfg)
    with pytest.raises(ValueError, match="exit_threshold"):
        generate(params, jnp.asarray(tokens[:, :8]), cfg, 4)


def test_one_pass_of_the_gelu_kinds_is_the_first_block_bit_for_bit():
    """The block as it stood before it took kinds (pre-norm, neighbouring
    rotary lanes, two-matrix GELU), written out here from the same
    primitives: the one block with the default kinds computes the same
    bits, without a cache."""
    from faabric_tpu.models import init_params
    from faabric_tpu.models.transformer import _block, _rms_norm, _rope
    from faabric_tpu.ops.attention import attend

    cfg = ModelConfig(vocab_size=128, d_model=32, n_layers=1, n_heads=4,
                      d_ff=64, max_seq=32, compute_dtype=jnp.float32,
                      attention_impl="reference", norm_impl="reference")
    blk = init_params(jax.random.PRNGKey(3), cfg)["blocks"][0]
    assert set(blk) == {"ln1", "wqkv", "wo", "ln2", "w1", "w2"}
    blk = dict(blk, ln1=blk["ln1"] * 1.1, ln2=blk["ln2"] * 0.9)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 32), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))

    def first_block(x):
        h = _rms_norm(x, blk["ln1"])
        qkv = jnp.einsum("bsd,dthe->tbshe", h, blk["wqkv"])
        q = _rope(qkv[0], positions, cfg.rope_theta)
        k = _rope(qkv[1], positions, cfg.rope_theta)
        causal = jnp.tril(jnp.ones((16, 16), dtype=bool))
        x = x + jnp.einsum("bshe,hed->bsd", attend(q, k, qkv[2], causal),
                           blk["wo"])
        h = _rms_norm(x, blk["ln2"])
        return x + jax.nn.gelu(h @ blk["w1"]) @ blk["w2"]

    got, cache, _ = _block(x, blk, positions, cfg)
    assert cache is None
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(first_block(x)))


def test_generate_is_one_outer_loop_with_the_passes_rolled_inside():
    """The jaxpr of the looped ``generate``: one outermost loop a call
    beside prefill's own pass loop (the decode scan; the readers take the
    outermost ``while`` that holds inner ones), the passes one rolled loop
    inside it, and the blocks traced once a program, not once a pass: the
    decode body holds 7 matrix products a layer (qkv, scores, weighted
    sum, out, gate, up, down) and the head."""
    sz = sizes(4)
    cfg, params = config(sz), weights(sz)
    prompt = jnp.asarray(ids(128))
    jaxpr = jax.make_jaxpr(lambda p, t: generate(p, t, cfg, 64))(
        params, prompt)

    def loops_of(j):
        return [e for e in j.eqns if e.primitive.name in ("scan", "while")]

    (impl,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "jit"]
    assert impl.params["name"] == "_generate_impl"
    top = loops_of(impl.params["jaxpr"].jaxpr)
    assert [e.params.get("length") for e in top] == [4, 64]
    decode = top[1].params["jaxpr"].jaxpr
    inner = loops_of(decode)
    assert [e.params.get("length") for e in inner] == [4]
    assert not [e for e, _ in _walk_jaxpr(inner[0].params["jaxpr"].jaxpr)
                if e.primitive.name in ("scan", "while")]
    dots = [e for e, _ in _walk_jaxpr(decode)
            if e.primitive.name == "dot_general"]
    assert len(dots) == 7 * sz["n_layers"] + 1
    # and the call's counters are its own sizing
    assert call_sizes(cfg, 1, 128, 64) == {
        "cache_slots": 256, "ut_passes": 4 * 65,
        "cache_bytes": 2 * 4 * 3 * 4 * 256 * 16 * 4,
        # one row, and a norm behind the down product: XLA's lines
        "ffn_streamed_layers": 0, "ffn_streamed_bytes": 0,
        "attention_streamed_layers": 0, "attention_streamed_bytes": 0}
    served = np.asarray(generate(params, prompt, cfg, 4))
    assert served.shape == (1, 4)
    want = ref.logits_of(params, jnp.concatenate(
        [prompt[0], jnp.asarray(served[0, :-1])]), sz, at=slice(127, None))
    np.testing.assert_array_equal(served[0], np.argmax(want, axis=-1))


def test_the_new_leaves_have_shardings_and_the_kinds_are_checked():
    from faabric_tpu.models import init_params, param_shardings
    from faabric_tpu.parallel import MeshConfig, build_mesh

    cfg = config(sizes(2))
    params = init_params(jax.random.PRNGKey(0), cfg)
    made = weights(sizes(2))
    assert jax.tree.structure(params) == jax.tree.structure(made)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, made)
    mesh = build_mesh(config=MeshConfig(tp=2))
    shardings = param_shardings(mesh, cfg)
    assert jax.tree.structure(shardings) == jax.tree.structure(params)
    blk = shardings["blocks"][0]
    assert blk["wg"].spec == blk["w1"].spec
    for name in ("ln1_post", "ln2_post"):
        assert blk[name].spec == blk["ln1"].spec
    from faabric_tpu.models import shard_params

    sharded = shard_params(params, mesh, cfg)
    assert sharded["blocks"][1]["wg"].sharding.spec == blk["wg"].spec
    for kind in ("ffn", "norm_placement", "rope_pairing"):
        with pytest.raises(ValueError, match=kind):
            dataclasses.replace(cfg, **{kind: "other"})
