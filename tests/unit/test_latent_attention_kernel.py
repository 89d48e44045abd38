"""Latent attention's prefill kernel (``ops/latent_attention.py``,
interpreted here on the CPU) against the ``jnp`` lines it replaces
(``transformer._latent_expanded``) on the same inputs; its plan at the
``serve_axk1_1chip`` cell's shapes; ``generate()`` through it against
``generate()`` with the plan forced to refuse; and the counters
``call_sizes`` brings.

Tolerances. Kernel and lines compute the same float32 mathematics in
another order (a running softmax over blocks of keys against one whole
softmax): float32 operands agree to a few 1e-6 on outputs of size 1;
bfloat16 operands round the probabilities to 8 bits before the weighted
sum on both sides, at other values of the running maximum, so they agree
to the last bits of a bfloat16 of that size (2e-2).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import program_axk1, weights_axk1
from faabric_tpu.models import transformer
from faabric_tpu.models.generate import (
    call_sizes,
    forward_with_cache,
    generate,
    init_kv_cache,
)
from faabric_tpu.ops import latent_attention
from tests.unit.test_models import _walk_jaxpr

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2147484042
HEADS, NOPE, ROPE, V, RANK = 2, 128, 64, 128, 32
ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def sizes(layers=2):
    """The cell's kinds with keys and values of the cell's lanes (192 on
    128) and everything else small: what the plan takes."""
    return {"vocab": 256, "d_model": 64, "n_layers": layers,
            "dense_layers": 1, "n_heads": HEADS, "d_ff": 96,
            "expert_d_ff": 48, "shared_experts": 1, "max_seq": 2048,
            "rope_theta": 1e4, "yarn": (4.0, 128.0, 32.0, 1.0, 1.0, 1.0),
            "norm_eps": 1e-6, "q_rank": 32, "kv_rank": RANK,
            "qk_nope": NOPE, "qk_rope": ROPE, "v_head": V,
            "routed_experts": 16, "experts_held": (4, 4), "top_k": 4,
            "routed_scaling": 2.5}


def config(dtype=jnp.float32, **other):
    cfg = program_axk1.model_config(
        {"compute_dtype": jnp.dtype(dtype).name, "param_dtype": "float32"},
        sizes())
    return dataclasses.replace(cfg, remat=False, **other)


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("stated", [0.0, 0.0625], ids=["yarn", "stated"])
@pytest.mark.parametrize("chunks_before", [0, 1, 3])
def test_the_kernel_is_the_expanded_lines(chunks_before, stated, dtype):
    """256 queries a row, the last of 256, 512 and 1,024 positions (a
    first chunk and two later ones), keys of 128 + 64 lanes on values of
    128, under YaRN's scale and under a stated one: what
    ``_latent_expanded`` gives on the same latents."""
    cfg = config(dtype, attention_scale=stated, rope_scaling=None) \
        if stated else config(dtype)
    yarn = (0.1 * np.log(4.0) + 1.0) ** 2 / np.sqrt(NOPE + ROPE)
    assert cfg.score_scale == pytest.approx(stated or yarn)
    rows, queries = 2, 256
    reach = queries * (1 + chunks_before)
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    q_nope = jax.random.normal(k[0], (rows, queries, HEADS, NOPE), dtype)
    q_rope = jax.random.normal(k[1], (rows, queries, HEADS, ROPE), dtype)
    latent = jax.random.normal(k[2], (rows, reach, RANK + ROPE), dtype)
    wkvb = (jax.random.normal(k[3], (RANK, HEADS, NOPE + V))
            / np.sqrt(RANK)).astype(dtype)
    how = transformer.streams_latent_prefill(cfg, rows, queries, reach)
    assert how is not None and how["block_q"] == how["block_k"] == 256
    want = transformer._latent_expanded(q_nope, q_rope, latent, wkvb, cfg)
    for at_once in (2, 1):  # both rows in one call; a row after the other
        got = transformer._latent_streamed(q_nope, q_rope, latent, wkvb,
                                           cfg, at_once)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=ATOL[dtype], rtol=0)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 512)])
def test_blocks_off_the_diagonal_and_unequal_ones_agree(blocks):
    """Blocks the plan would not pick: smaller ones, so that steps above
    the diagonal are skipped and steps below it run unmasked; unequal
    ones, whose diagonal steps mask their whole tile."""
    cfg = config()
    rows, queries, reach = 1, 256, 512
    k = jax.random.split(jax.random.PRNGKey(6), 5)
    q_nope = jax.random.normal(k[0], (rows, queries, HEADS, NOPE))
    q_rope = jax.random.normal(k[1], (rows, queries, HEADS, ROPE))
    latent = jax.random.normal(k[2], (rows, reach, RANK + ROPE))
    wkvb = jax.random.normal(k[3], (RANK, HEADS, NOPE + V)) / np.sqrt(RANK)
    how = latent_attention.plan(rows, HEADS, queries, reach, NOPE, ROPE, V,
                                jnp.float32, blocks)
    assert how["visited"] + how["skipped"] \
        == HEADS * (queries // blocks[0]) * (reach // blocks[1])
    assert (how["skipped"] > 0) == (blocks == (128, 128))
    keys, values = (latent[..., :RANK] @ w.reshape(RANK, -1)
                    for w in (wkvb[..., :NOPE], wkvb[..., NOPE:]))
    got = latent_attention.latent_attention(
        q_nope, q_rope, keys, latent[..., RANK:], values,
        scale=cfg.score_scale, blocks=blocks)
    want = transformer._latent_expanded(q_nope, q_rope, latent, wkvb, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("reach", range(1024, 8193, 1024))
def test_the_plan_takes_the_cells_chunks(reach):
    """8 rows × 64 heads × 1,024 queries over 1,024 … 8,192 keys: square
    blocks of 1,024 whose diagonal computes its triangle alone, within
    the VMEM a plan may fill, the rows a call takes expanding no more
    than ``EXPANDED_BYTES``."""
    how = latent_attention.plan(8, 64, 1024, reach, 128, 64, 128)
    assert (how["block_q"], how["block_k"]) == (1024, 1024)
    assert how["grid"] == (8, 64, 1, reach // 1024)
    assert (how["visited"], how["masked"], how["skipped"]) \
        == (8 * 64 * reach // 1024, 8 * 64, 0)
    assert how["vmem_bytes"] <= latent_attention.VMEM_LIMIT_BYTES // 2
    expanded = how["rows"] * reach * 64 * 256 * 2
    assert expanded <= latent_attention.EXPANDED_BYTES
    assert how["rows"] == 8 or 2 * expanded > latent_attention.EXPANDED_BYTES
    assert how["rows"] == {1024: 8, 2048: 8, 3072: 4, 4096: 4}.get(reach, 2)
    # queries, outputs, and every key block once: one query block
    assert how["streamed_bytes"] == 8 * 64 * 2 * 320 * (1024 + reach)


@pytest.mark.parametrize("why, shape", [
    ("toy lanes", (8, 4, 16, 48, 16, 8, 16)),
    ("rotary lanes no sublane tile", (8, 64, 1024, 1024, 128, 60, 128)),
    ("values of half a tile", (8, 64, 1024, 1024, 128, 64, 64)),
    ("ragged queries", (8, 64, 1000, 2048, 128, 64, 128)),
    ("ragged reach", (8, 64, 1024, 2000, 128, 64, 128)),
    ("a reach of one tile", (64, 64, 128, 128, 128, 64, 128)),
    ("one query, a cached step", (8, 64, 1, 8192, 128, 64, 128)),
    ("more queries than keys", (8, 64, 1024, 512, 128, 64, 128)),
])
def test_the_plan_refuses(why, shape):
    assert latent_attention.plan(*shape) is None, why
    *_, nope, rope, v = shape
    with pytest.raises(ValueError, match="does not take"):
        rows, heads, queries, reach = shape[:4]
        latent_attention.latent_attention(
            jnp.zeros((rows, queries, heads, nope)),
            jnp.zeros((rows, queries, heads, rope)),
            jnp.zeros((rows, reach, heads * nope)),
            jnp.zeros((rows, reach, rope)),
            jnp.zeros((rows, reach, heads * v)), scale=1.0)


def test_forced_blocks_that_do_not_divide_are_refused():
    take = (2, 2, 256, 512, 128, 64, 128, jnp.float32)
    assert latent_attention.plan(*take, (128, 256)) is not None
    assert latent_attention.plan(*take, (192, 256)) is None
    assert latent_attention.plan(*take, (256, 384)) is None
    assert latent_attention.plan(*take, (64, 64)) is None


def test_only_latent_attention_on_one_chip_streams():
    cfg = config()
    assert transformer.streams_latent_prefill(cfg, 2, 256, 512) is not None
    assert transformer.streams_latent_prefill(
        cfg, 2, 256, 512, mesh=object()) is None
    heads = dataclasses.replace(cfg, attention="heads")
    assert transformer.streams_latent_prefill(heads, 2, 256, 512) is None


def test_generate_through_the_kernel_serves_the_lines_tokens(monkeypatch):
    """A dense layer and an expert layer at the toy's widths but for the
    keys' and values' lanes, 2 rows, a prompt of 512 in two chunks of
    256: both chunks go through the kernel, no array with a query and a
    key axis is left in the prefill, and the tokens are those of the same
    call with the plan forced to refuse."""
    sz = sizes()
    cfg = config()
    params = weights_axk1.make_weights(SEED, sz, jnp.float32)
    prompt = jnp.asarray(weights_axk1.token_rows(SEED, 1, 0, 2, 512, 256))
    sized = call_sizes(cfg, 2, 512, 4, 256)
    assert (sized["latent_streamed_layers"], sized["latent_streamed_chunks"],
            sized["score_blocks"], sized["expanded_bytes"]) == (2, 2, 0, 0)
    assert sized["latent_streamed_bytes"] == 2 * sum(
        latent_attention.plan(2, HEADS, 256, reach, NOPE, ROPE, V,
                              jnp.float32)["streamed_bytes"]
        for reach in (256, 512))

    def scores_in_hbm(cfg):
        """float32 arrays (…, 256 queries, 512 keys) of the second chunk's
        forward, outside any kernel."""
        cache = init_kv_cache(cfg, 2, 640)
        jaxpr = jax.make_jaxpr(lambda p, t, c: forward_with_cache(
            p, t, c, 256, cfg, last_only=True))(params, prompt[:, 256:],
                                                cache)
        kernels = sum(eqn.primitive.name == "pallas_call"
                      for eqn, _ in _walk_jaxpr(jaxpr.jaxpr))
        return kernels, sum(
            v.aval.shape[-2:] == (256, 512)
            for eqn, _ in _walk_jaxpr(jaxpr.jaxpr)
            if eqn.primitive.name != "pallas_call"
            for v in eqn.outvars if hasattr(v.aval, "shape"))

    assert scores_in_hbm(cfg) == (2, 0)
    through_kernel = generate(params, prompt, cfg, 4, prefill_chunk=256)

    monkeypatch.setattr(transformer, "streams_latent_prefill",
                        lambda *a, **k: None)
    jax.clear_caches()
    kernels, scores = scores_in_hbm(cfg)
    assert kernels == 0 and scores > 0
    lines = generate(params, prompt, cfg, 4, prefill_chunk=256)
    jax.clear_caches()
    assert through_kernel.shape == (2, 4)
    np.testing.assert_array_equal(np.asarray(through_kernel),
                                  np.asarray(lines))


def test_call_sizes_of_the_cell_counts_every_chunk_streamed():
    """``serve_axk1_1chip``'s call: all 8 chunks of all 7 attentions go
    through the kernel, no block of scores through HBM."""
    with open(os.path.join(HERE, "benchmarks", "configs",
                           "a.x-k1.json")) as f:
        cfg = program_axk1.model_config(json.load(f))
    got = call_sizes(cfg, 8, 8192, 64, 1024)
    assert (got["latent_streamed_layers"], got["latent_streamed_chunks"],
            got["score_blocks"], got["expanded_bytes"]) == (7, 8, 0, 0)
    assert got["latent_streamed_bytes"] == 7 * sum(
        8 * 64 * 2 * 320 * (1024 + reach)
        for reach in range(1024, 8193, 1024))
    # without chunks the prompt is one call of 8,192 queries: taken too
    whole = call_sizes(cfg, 8, 8192, 64)
    assert (whole["prefill_chunks"], whole["latent_streamed_chunks"],
            whole["score_blocks"]) == (1, 1, 0)
    # a prompt the tiles do not divide keeps the lines for every chunk
    ragged = call_sizes(cfg, 8, 1000, 64, 500)
    assert (ragged["latent_streamed_layers"],
            ragged["latent_streamed_chunks"]) == (0, 0)
    assert ragged["score_blocks"] > 0
