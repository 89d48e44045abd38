"""Prefill attention's kernel over a dense cache
(``ops/prefill_attention.py``, interpreted here on the CPU) against the
``jnp`` lines it replaces (``transformer._attend_cached`` through
``attend`` where ``streams_prefill_attention`` refuses) on the same
inputs, at reduced widths with the cells' head layouts; its plan at the
``serve_phi4flash_1chip`` and ``serve_granite_1chip`` cells' shapes.

Tolerances. Kernel and lines compute the same float32 mathematics in
another order (a running softmax over blocks of keys against one whole
softmax): float32 operands agree to a few 1e-7 on outputs of size 1;
bfloat16 operands round the scores (the lines) and the probabilities
(both) to 8 bits at other values of the running maximum, so they agree
to the last bits of a bfloat16 of that size (2e-2).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from faabric_tpu.models import ModelConfig, transformer
from faabric_tpu.ops import prefill_attention
from tests.unit.test_models import _walk_jaxpr

ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}

# name → (paired, rows, heads, key/value heads, head_dim, queries, slots,
# start, window, dtype): phi4flash's pairs over a window's ring that is
# empty (the first chunk), half written (the second) and wrapped, and over
# the full cache whose slots past the reach hold NaN; granite's grouped
# heads (four on a key/value head of 64, two key/value heads a lane tile);
# the ring in bfloat16; pairs of half a lane tile, which the plan refuses
CASES = {
    "pairs_ring_empty": (True, 2, 8, 4, 64, 128, 256, 0, 256, jnp.float32),
    "pairs_ring_half_written": (True, 2, 8, 4, 64, 128, 256, 128, 256,
                                jnp.float32),
    "pairs_ring_wrapped": (True, 2, 8, 4, 64, 256, 256, 640, 300,
                           jnp.float32),
    "pairs_full_nan_past_the_reach": (True, 2, 8, 4, 64, 128, 384, 128, 0,
                                      jnp.float32),
    "grouped_two_heads_a_tile": (False, 8, 16, 4, 64, 128, 384, 128, 0,
                                 jnp.float32),
    "pairs_ring_bfloat16": (True, 2, 8, 4, 64, 128, 256, 128, 256,
                            jnp.bfloat16),
    "refused_pairs_of_half_a_tile": (True, 2, 8, 4, 32, 128, 256, 128, 256,
                                     jnp.float32),
}


@pytest.fixture(autouse=True)
def float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def config(paired, heads, kv, head_dim, window, dtype):
    kinds = ("window_attention", "attention") if window else ()
    return ModelConfig(d_model=heads * head_dim, n_heads=heads,
                       n_kv_heads=kv, n_layers=len(kinds) or 1,
                       layer_types=kinds, sliding_window=window,
                       differential=paired, position="none",
                       compute_dtype=dtype, param_dtype=jnp.float32,
                       remat=False)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_attend(case, monkeypatch):
    """One chunk through ``_attend_cached`` at pass 1 of two: what the
    lines give, and the cache written as they write it; unwritten slots
    (NaN) never reach an output; the kernel runs where the plan takes the
    shape and nowhere else."""
    paired, rows, heads, kv, d, queries, slots, start, window, dtype = \
        CASES[case]
    cfg = config(paired, heads, kv, d, window, dtype)
    kind = "window_attention" if window else "attention"
    assert transformer.cache_layout(cfg, rows, slots) == "dense"
    key = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(key[0], (rows, queries, heads, d), dtype)
    k, v = (jax.random.normal(one, (rows, queries, kv, d), dtype)
            for one in key[1:3])
    held = (np.asarray(transformer._ring_positions(slots, start)) if window
            else np.arange(slots))
    unwritten = (held < 0) | (held >= start + queries)
    assert unwritten.any() == (case != "pairs_ring_wrapped")
    cache = {name: jax.random.normal(one, (2, rows, slots, kv * d), dtype)
             .at[:, :, unwritten].set(jnp.nan)
             for name, one in zip("kv", key[3:])}

    def attended():
        # a function of its own each time: jit keeps one trace a function
        return jax.jit(lambda q, k, v, cache, t: transformer._attend_cached(
            q, k, v, cache, (t, start), cfg, kind, None))

    how = transformer.streams_prefill_attention(cfg, rows, queries, slots,
                                                dtype, start, window)
    assert (how is None) == case.startswith("refused")
    through = attended()
    jaxpr = jax.make_jaxpr(through)(q, k, v, cache, jnp.int32(1))
    kernels = [eqn.params["name"] for eqn, _ in _walk_jaxpr(jaxpr.jaxpr)
               if eqn.primitive.name == "pallas_call"]
    assert kernels == ([] if how is None else ["prefill_attention"])
    got, got_cache = through(q, k, v, cache, jnp.int32(1))
    monkeypatch.setattr(transformer, "streams_prefill_attention",
                        lambda *a, **kw: None)
    want, want_cache = attended()(q, k, v, cache, jnp.int32(1))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert got.shape == ((rows, queries, kv // 2, heads // kv, 2, 2 * d)
                         if paired else (rows, queries, heads, d))
    assert not np.isnan(np.asarray(got, np.float32)).any()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=0)
    for name in "kv":  # (NaN is NaN in float32)
        np.testing.assert_array_equal(
            np.asarray(got_cache[name], np.float32),
            np.asarray(want_cache[name], np.float32))
    if how is None:
        with pytest.raises(ValueError, match="does not take"):
            prefill_attention.prefill_attention(
                q, cache["k"], cache["v"], k, v, start=start, scale=0.125,
                window=window, paired=paired)


# (rows, heads, key/value heads, queries, slots, head_dim, paired, start,
# window) → the plan: serve_phi4flash_1chip's two chunks of 256 over a
# ring of 512 (8 layers) and over the shared cache of 768 (layer 17),
# serve_granite_1chip's two chunks over its cache of 640 (4 layers)
CELLS = {
    "serve_phi4flash_1chip_ring_first": (
        (64, 40, 20, 256, 512, 64, True, 0, 512),
        (256, 256, (64, 10, 1, 1), 640, 640, 1280, 3145728, 335544320)),
    "serve_phi4flash_1chip_ring_second": (
        (64, 40, 20, 256, 512, 64, True, 256, 512),
        (256, 256, (64, 10, 1, 2), 1280, 640, 640, 3145728, 419430400)),
    "serve_phi4flash_1chip_full_first": (
        (64, 40, 20, 256, 768, 64, True, 0, 0),
        (256, 256, (64, 10, 1, 1), 640, 640, 1280, 3145728, 335544320)),
    "serve_phi4flash_1chip_full_second": (
        (64, 40, 20, 256, 768, 64, True, 256, 0),
        (256, 256, (64, 10, 1, 2), 1280, 640, 640, 3145728, 419430400)),
    "serve_granite_1chip_first": (
        (64, 32, 8, 256, 640, 64, False, 0, 0),
        (128, 128, (64, 4, 2, 2), 768, 512, 1792, 2359296, 184549376)),
    "serve_granite_1chip_second": (
        (64, 32, 8, 256, 640, 64, False, 256, 0),
        (256, 128, (64, 4, 1, 4), 1024, 512, 256, 4587520, 201326592)),
}


@pytest.mark.parametrize("call", sorted(CELLS))
def test_the_plan_of_the_cells_chunks(call):
    """The blocks, the grid, the steps visited, masked and skipped (a
    ring's empty slots and the cache past the reach are never fetched),
    the VMEM within what a plan may fill, and the bytes streamed: queries,
    outputs (a pair keeps a whole tile a head) and each visited key block
    once a query block."""
    (rows, heads, kv, queries, slots, d, paired, start, window), want = \
        CELLS[call]
    how = prefill_attention.plan(rows, heads, kv, queries, slots, d,
                                 jnp.bfloat16, paired, start, window)
    assert (how["block_q"], how["block_k"], how["grid"], how["visited"],
            how["masked"], how["skipped"], how["vmem_bytes"],
            how["streamed_bytes"]) == want
    assert how["vmem_bytes"] <= prefill_attention.VMEM_LIMIT_BYTES // 2
    tiles, group = kv * d // 128, heads // kv
    out = group * 128 * (2 if paired else 1)
    visited_a_tile = how["visited"] // (rows * tiles)
    assert how["streamed_bytes"] == rows * tiles * 2 * (
        queries * (group * 128 + out)
        + visited_a_tile * 2 * how["block_k"] * 128)


@pytest.mark.parametrize("why, shape", [
    ("heads of 48 lanes", (8, 8, 4, 256, 512, 48, False)),
    ("a pair's values half a tile", (8, 8, 4, 256, 512, 32, True)),
    ("key heads not whole tiles", (8, 6, 3, 256, 512, 64, False)),
    ("ragged queries", (8, 8, 4, 200, 512, 64, False)),
    ("ragged slots", (8, 8, 4, 256, 500, 64, False)),
    ("one query, a cached step", (8, 8, 4, 1, 512, 64, False)),
    ("past a full cache's slots", (8, 8, 4, 256, 384, 64, False, 256)),
])
def test_the_plan_refuses(why, shape):
    assert prefill_attention.plan(*shape[:6], jnp.bfloat16,
                                  *shape[6:]) is None, why


def test_only_heads_on_one_chip_in_the_compute_type_stream():
    cfg = config(True, 8, 4, 64, 256, jnp.float32)
    take = (cfg, 2, 128, 256, jnp.float32, 128, 256)
    assert transformer.streams_prefill_attention(*take) is not None
    assert transformer.streams_prefill_attention(*take, mesh=object()) \
        is None
    assert transformer.streams_prefill_attention(
        cfg, 2, 128, 256, jnp.bfloat16, 128, 256) is None
    assert transformer.streams_prefill_attention(
        cfg, 2, 128, 256, jnp.float32, jnp.int32(128), 256) is None
    assert transformer.streams_prefill_attention(
        cfg, 2, 1, 256, jnp.float32, 128, 256) is None
    # a head-major cache (grouped heads at two rows) keeps the lines
    grouped = config(False, 16, 4, 64, 0, jnp.float32)
    assert transformer.cache_layout(grouped, 2, 384) == "head_major"
    assert transformer.streams_prefill_attention(
        grouped, 2, 128, 384, jnp.float32, 128) is None
    assert transformer.streams_prefill_attention(
        grouped, 8, 128, 384, jnp.float32, 128) is not None
