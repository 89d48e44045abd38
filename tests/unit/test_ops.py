"""Pallas kernels + ring attention, all checked against reference
numerics. Kernels run in interpreter mode on the CPU test mesh; on real
hardware the identical code compiles for the MXU/VMEM."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from faabric_tpu.ops import flash_attention, rms_norm
from faabric_tpu.ops.attention import attend
from faabric_tpu.ops.flash_attention import _mask
from faabric_tpu.ops.rms_norm import _reference_rms_norm
from faabric_tpu.parallel import (
    MeshConfig,
    build_mesh,
    ring_attention,
    shard_sequence,
)


def reference(q, k, v, causal=True):
    """The ``jnp`` lines the flash kernels fall back to."""
    return attend(q, k, v, _mask(q, k, causal))


def qkv(b=2, s=256, h=4, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, d), dtype=jnp.float32)
                 for _ in range(3))


# ---------------------------------------------------------------------------
# The one jnp attention
# ---------------------------------------------------------------------------

def _numpy_attention(q, keys, values, mask, written, scale, pairs):
    """Float64, one head at a time, keys and values (B, K, KV, D)."""
    q, keys, values = (np.asarray(x, np.float64) for x in (q, keys, values))
    b, s, h, d = q.shape
    reach, kv = keys.shape[1:3]
    if written is not None:
        values = np.where(np.asarray(written)[None, :, None, None], values, 0)
    mask = np.ones((s, reach), bool) if mask is None else np.asarray(mask)
    heads = []
    for head in range(h):
        if pairs:
            group, c = head // (2 * (h // kv)), head % 2
            key = keys[:, :, 2 * group + c]
            value = np.concatenate([values[:, :, 2 * group],
                                    values[:, :, 2 * group + 1]], axis=-1)
        else:
            key, value = keys[:, :, head // (h // kv)], \
                values[:, :, head // (h // kv)]
        with np.errstate(invalid="ignore"):
            scores = np.einsum("bqd,bkd->bqk", q[:, :, head], key) * scale
        scores = np.where(mask, scores, -np.inf)
        probs = np.exp(scores - scores.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        heads.append(np.einsum("bqk,bke->bqe", probs, value))
    out = np.stack(heads, axis=2)
    return out.reshape(b, s, kv // 2, h // kv, 2, 2 * d) if pairs else out


def _attention_case(case):
    """(q, keys, values, mask, written, scale, pairs, head_major) as the
    callers of ``attend`` build them; keys and values position-major."""
    rng = np.random.RandomState(len(case))
    b, s, h, kv, d = 2, 5, 4, 4, 8

    def normal(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    def causal_over(slots, length):
        # the cached path's: the queries the last of ``length`` written
        q_pos = (length - s) + jnp.arange(s)
        return q_pos[:, None] >= jnp.arange(slots)[None, :]

    def unwritten(x, written):
        return jnp.where(written[None, :, None, None], x, jnp.nan)

    if case == "whole_sequence_causal":
        at = jnp.arange(s)
        return (normal(b, s, h, d), normal(b, s, kv, d), normal(b, s, kv, d),
                at[None, :] <= at[:, None], None, 0.3, False, False)
    if case == "query_offset":
        # the pipeline's: a shard's queries are rows 6..10 of 16
        k_pos = jnp.arange(16)
        return (normal(b, s, h, d), normal(b, 16, kv, d),
                normal(b, 16, kv, d),
                k_pos[None, :] <= 6 + jnp.arange(s)[:, None], None, None,
                False, False)
    if case in ("head_major_cache", "dense_cache"):
        written = jnp.arange(12) < 9
        keys, values = (unwritten(normal(b, 12, kv, d), written)
                        for _ in range(2))
        return (normal(b, s, h, d), keys, values, causal_over(12, 9),
                written, 0.25, False, case == "head_major_cache")
    if case == "grouped_heads":
        written = jnp.arange(12) < 9
        keys, values = (unwritten(normal(b, 12, 2, d), written)
                        for _ in range(2))
        return (normal(b, s, 8, d), keys, values, causal_over(12, 9),
                written, 0.25, False, False)
    if case == "window_band_over_a_ring":
        # a ring of 6 left by positions 0..3 (slots 4, 5 never written),
        # then 5 positions from 4 on under a window of 3
        ring, window, start = 6, 3, 4
        held = jnp.array([0, 1, 2, 3, -5, -4])
        q_pos = start + jnp.arange(s)
        k_pos = jnp.concatenate([held, q_pos])
        written = k_pos >= 0
        keys, values = (unwritten(normal(b, ring + s, kv, d), written)
                        for _ in range(2))
        mask = (k_pos[None, :] <= q_pos[:, None]) \
            & (k_pos[None, :] > q_pos[:, None] - window) & written[None, :]
        return (normal(b, s, h, d), keys, values, mask, written, 0.25,
                False, False)
    assert case in ("differential_pairs", "rows_in_blocks")
    at = jnp.arange(s)
    return (normal(4, s, 8, d), normal(4, s, 4, d), normal(4, s, 4, d),
            at[None, :] <= at[:, None], None, 0.2,
            case == "differential_pairs", False)


@pytest.mark.parametrize("case", [
    "whole_sequence_causal", "query_offset", "head_major_cache",
    "dense_cache", "grouped_heads", "window_band_over_a_ring",
    "differential_pairs", "rows_in_blocks"])
def test_attend_is_a_float64_attention(case, monkeypatch):
    """``ops/attention.attend`` in every form its callers take against a
    float64 attention written out here: NaN in slots never written never
    reaches the output, and rows whose scores pass ``SCORE_BYTES`` go in
    blocks with the same result."""
    from faabric_tpu.ops import attention

    q, keys, values, mask, written, scale, pairs, head_major = \
        _attention_case(case)
    if case == "rows_in_blocks":
        # a row's scores alone: each row a block of its own
        monkeypatch.setattr(attention, "SCORE_BYTES",
                            4 * q.shape[2] * q.shape[1] * keys.shape[1])
    laid = [x.transpose(0, 2, 1, 3) for x in (keys, values)] \
        if head_major else (keys, values)
    got = np.asarray(attend(q, *laid, mask, written, scale, pairs,
                            head_major))
    want = _numpy_attention(q, keys, values, mask, written,
                            scale or 1 / np.sqrt(q.shape[-1]), pairs)
    assert got.shape == want.shape
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    blocked = "scan" in str(jax.make_jaxpr(
        lambda *x: attend(*x, mask, written, scale, pairs, head_major))(
            q, *laid))
    assert blocked == (case == "rows_in_blocks")


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

def test_flash_attention_matches_reference_causal():
    q, k, v = qkv()
    out = flash_attention(q, k, v)
    ref = reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_non_causal():
    q, k, v = qkv(s=128)
    out = flash_attention(q, k, v, False)
    ref = reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("s", [256, 1024])
def test_flash_attention_gradients(s):
    """Pallas two-pass backward (dQ + dK/dV kernels) vs reference autodiff
    at fp32 tolerances."""
    q, k, v = qkv(b=1, s=s, h=2, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_flash_attention_gradients_non_causal():
    q, k, v = qkv(b=1, s=256, h=2, d=16, seed=7)
    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, False) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        reference(q, k, v, False) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_flash_attention_gradients_cross_length():
    """s_k > s_q runs the kernels with the end-aligned causal offset."""
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 128, 2, 16), dtype=jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2, 16), dtype=jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, 2, 16), dtype=jnp.float32)
    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        reference(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_flash_attention_gradients_ragged_fallback():
    """Ragged shapes take the reference path in both directions."""
    q, k, v = qkv(s=100, d=16)
    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        reference(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_attention_ragged_shape_falls_back():
    q, k, v = qkv(s=100)  # not divisible by any block size
    out = flash_attention(q, k, v)
    ref = reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _qkv_lengths(s_q, s_k, dtype=jnp.float32, seed=29):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(1, s, 2, 16), dtype=dtype)
                 for s in (s_q, s_k, s_k))


# The plans the gridded tiling has and the whole-sequence one had not:
# (s_q, s_k, causal, block_q, block_k, dtype); blocks None = by shape
TILINGS = {
    "inner_blocks_skipped_256x256": (1024, 1024, True, 256, 256, jnp.float32),
    "unequal_512x128": (1024, 1024, True, 512, 128, jnp.float32),
    "unequal_128x512": (1024, 1024, True, 128, 512, jnp.float32),
    "by_shape_1024_diagonal_strips": (1024, 1024, True, None, None,
                                      jnp.float32),
    "by_shape_384_ladder_bottom": (384, 384, True, None, None, jnp.float32),
    "cross_length_aligned": (256, 512, True, 128, 256, jnp.float32),
    "cross_length_by_shape_unaligned": (128, 384, True, None, None,
                                        jnp.float32),
    "non_causal_128x256": (512, 512, False, 128, 256, jnp.float32),
    "non_causal_by_shape": (512, 256, False, None, None, jnp.float32),
    "bf16_by_shape_strips": (512, 512, True, None, None, jnp.bfloat16),
    "bf16_256x128": (512, 512, True, 256, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("tiling", sorted(TILINGS))
def test_flash_attention_tilings_match_reference(tiling):
    """Forward and all three gradients against the reference, over block
    plans forced through the arguments and chosen by the shape."""
    from faabric_tpu.ops.flash_attention import uses_kernel

    s_q, s_k, causal, block_q, block_k, dtype = TILINGS[tiling]
    q, k, v = _qkv_lengths(s_q, s_k, dtype)
    assert uses_kernel(q.shape, k.shape, causal, block_q, block_k)

    def loss(attention):
        return lambda q, k, v: jnp.sum(
            attention(q, k, v).astype(jnp.float32) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal, block_q, block_k)

    def ref(q, k, v):
        return reference(q, k, v, causal)

    f32 = dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(ref(q, k, v), np.float32), atol=2e-5 if f32 else 3e-2)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-4 if f32 else 0.5, rtol=1e-3 if f32 else 0.1)


@pytest.mark.parametrize("blocks", [(128, 128), (None, None)])
def test_flash_attention_never_reads_a_skipped_block(blocks):
    """NaN in the keys and values from position ``cut`` on, as in a cache
    whose tail was never written: the queries before ``cut`` see none of
    them, so a block above the diagonal (128-blocks) or the part of a
    diagonal block above it (one 1024-block in strips) must not be read:
    0 × NaN would poison the row."""
    s, cut = 1024, 512
    q, k, v = _qkv_lengths(s, s)
    clean = flash_attention(q, k, v, True, *blocks)
    poisoned = flash_attention(q, k.at[:, cut:].set(jnp.nan),
                               v.at[:, cut:].set(jnp.nan), True, *blocks)
    np.testing.assert_array_equal(np.asarray(poisoned[:, :cut]),
                                  np.asarray(clean[:, :cut]))
    assert np.isnan(np.asarray(poisoned[:, cut:])).all()


def test_block_plan_counts_the_grid():
    from faabric_tpu.ops.flash_attention import (
        KERNELS,
        block_plan,
        uses_kernel,
    )

    # train_2k_1chip's call, the plan PERF.md quotes: two 1024-blocks a
    # side, one of four steps above the diagonal, two on it in strips of
    # 256 rows (10/16 of a block each), one below
    cell = (4, 2048, 16, 128)
    plan = block_plan(cell, cell)
    assert tuple(plan) == KERNELS
    for kernel in KERNELS:
        p = plan[kernel]
        assert (p.block_q, p.block_k, p.grid) == (1024, 1024, (64, 2, 2))
        assert (p.visited, p.masked, p.skipped) == (192, 128, 64)
        assert p.computed == (1 + 2 * 10 / 16) / 4

    # whatever the blocks: every inner step is visited or skipped, more
    # than the causal half is computed and never more than the square,
    # and without a mask nothing is skipped
    for block_q, block_k in [(512, 512), (256, 1024), (1024, 128),
                             (None, None)]:
        for q_shape, k_shape in [(cell, cell), ((2, 1024, 4, 64),
                                                (2, 2048, 4, 64))]:
            for causal in (True, False):
                for p in block_plan(q_shape, k_shape, causal, block_q,
                                    block_k).values():
                    b_h, outer, inner = p.grid
                    assert b_h == q_shape[0] * q_shape[2]
                    assert p.visited + p.skipped == b_h * outer * inner
                    assert 0 <= p.masked <= p.visited
                    assert 0.5 < p.computed <= 1.0
                    if not causal:
                        assert (p.masked, p.skipped, p.computed) == (0, 0, 1.0)
    forced = block_plan(cell, cell, True, 512, 512)["flash_bwd_dkv"]
    assert (forced.grid, forced.visited, forced.masked, forced.skipped) == (
        (64, 4, 4), 640, 256, 384)

    # S = 128 · 9: the largest lane-tile multiple that divides it
    odd = block_plan((1, 1152, 2, 128), (1, 1152, 2, 128))["flash_fwd"]
    assert (odd.block_q, odd.block_k) == (384, 384)
    # uses_kernel is block_plan's "is there one": ragged lengths and
    # causal s_q > s_k take the reference
    for q_shape, k_shape, causal in [((1, 200, 2, 16), (1, 200, 2, 16), True),
                                     ((1, 512, 2, 16), (1, 256, 2, 16), True),
                                     ((1, 512, 2, 16), (1, 256, 2, 16), False)]:
        assert uses_kernel(q_shape, k_shape, causal) == (
            block_plan(q_shape, k_shape, causal) is not None) == (not causal)


def test_flash_attention_gradient_holds_the_three_kernels():
    """The benchmark's rooflines find the kernels by these names: one
    forward and the two backward passes, no fourth kernel and no fusion
    of the two."""
    q, k, v = _qkv_lengths(256, 256)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v)),
        argnums=(0, 1, 2)))(q, k, v)

    def kernel_names(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from kernel_names(sub)

    assert sorted(kernel_names(jaxpr.jaxpr)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def test_model_flash_attention_impl_matches_reference():
    from faabric_tpu.models import ModelConfig, forward, init_params

    cfg_ref = ModelConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                          d_ff=128, max_seq=128,
                          compute_dtype=jnp.float32)
    cfg_flash = ModelConfig(vocab_size=128, d_model=64, n_layers=2,
                            n_heads=4, d_ff=128, max_seq=128,
                            compute_dtype=jnp.float32,
                            attention_impl="flash")
    params = init_params(jax.random.PRNGKey(0), cfg_ref)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (2, 128)), dtype=jnp.int32)
    ref = forward(params, tokens, cfg_ref)
    out = forward(params, tokens, cfg_flash)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


# ---------------------------------------------------------------------------
# The gated feed-forward of a few rows
# ---------------------------------------------------------------------------

# (rows, d_model, d_ff, tile, dtype): the rule's edges and its middle, a
# d_ff of several tiles and of one, both types
GATED_FFN_CALLS = {
    "rows_8_f32": (8, 64, 256, 64, jnp.float32),
    "rows_64_f32": (64, 64, 256, 64, jnp.float32),
    "rows_128_f32": (128, 64, 256, 64, jnp.float32),
    "rows_8_bf16": (8, 64, 256, 64, jnp.bfloat16),
    "rows_64_bf16": (64, 128, 512, 128, jnp.bfloat16),
    "rows_128_bf16": (128, 64, 256, 64, jnp.bfloat16),
    "one_tile_f32": (16, 64, 128, None, jnp.float32),
    "planned_tile_bf16": (64, 128, 384, None, jnp.bfloat16),
}


@pytest.mark.parametrize("call", sorted(GATED_FFN_CALLS))
def test_gated_ffn_matches_the_feed_forwards_own_lines(call):
    """The kernel, interpreted, against ``_feed_forward``'s ``swiglu``
    lines on the same operands."""
    from faabric_tpu.models.transformer import ModelConfig, _feed_forward
    from faabric_tpu.ops.gated_ffn import gated_ffn, plan

    rows, d_model, d_ff, tile, dtype = GATED_FFN_CALLS[call]
    rng = np.random.RandomState(rows + d_ff)
    h = jnp.asarray(rng.randn(rows, 1, d_model), dtype)
    blk = {"wg": jnp.asarray(rng.randn(d_model, d_ff) / np.sqrt(d_model),
                             dtype),
           "w1": jnp.asarray(rng.randn(d_model, d_ff) / np.sqrt(d_model),
                             dtype),
           "w2": jnp.asarray(rng.randn(d_ff, d_model) / np.sqrt(d_ff), dtype)}
    cfg = ModelConfig(d_model=d_model, d_ff=d_ff, n_heads=2, ffn="swiglu",
                      compute_dtype=dtype, param_dtype=dtype)
    how = plan(rows, d_model, d_ff, dtype, tile)
    assert how["steps"] * how["tile"] == d_ff
    if tile is not None:
        assert how["steps"] == d_ff // tile > 1
    got = gated_ffn(h[:, 0], blk["wg"], blk["w1"], blk["w2"], tile=tile)
    want = _feed_forward(h, blk, cfg)[:, 0]
    assert got.shape == want.shape and got.dtype == want.dtype
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if f32 else 3e-2, rtol=0 if f32 else 2e-2)


def test_gated_ffn_plan_holds_the_two_cells_calls():
    """``plan`` from shapes alone, as ``block_plan`` for the flash
    kernels: the cached steps of ``serve_granite_1chip`` (64 × 2048 ×
    8192) and ``serve_longcat_1chip`` (64 × 6144 × 12288) in bfloat16, the
    rows it takes, and what it refuses."""
    from faabric_tpu.ops.gated_ffn import (
        MAX_ROWS,
        MIN_ROWS,
        STEP_BYTES,
        gated_ffn,
        plan,
    )

    granite = plan(64, 2048, 8192, jnp.bfloat16)
    assert granite == {
        "tile": 2048, "steps": 4,
        # h, three tiles and the output twice; the float32 accumulator;
        # gate, up and activation of a tile in float32
        "vmem_bytes": 2 * (2 * 64 * 2048 + 3 * 2048 * 2048) * 2
        + 64 * 2048 * 4 + 3 * 64 * 2048 * 4,
        "streamed_bytes": 3 * 2048 * 8192 * 2}
    assert granite["vmem_bytes"] == 53_477_376
    assert 3 * 2048 * granite["tile"] * 2 == STEP_BYTES
    longcat = plan(64, 6144, 12288, jnp.bfloat16)
    assert longcat == {"tile": 512, "steps": 24, "vmem_bytes": 42_860_544,
                       "streamed_bytes": 3 * 6144 * 12288 * 2}
    assert 3 * 6144 * 512 * 2 <= STEP_BYTES < 3 * 6144 * 768 * 2
    # float32 operands: half the columns a step
    assert plan(64, 2048, 8192, jnp.float32)["tile"] == 1024
    # a matrix wider than the budget at one lane tile still runs, at 128
    assert plan(8, 65536, 256, jnp.bfloat16)["tile"] == 128
    assert (MIN_ROWS, MAX_ROWS) == (8, 128)
    for rows in (1, 7, 129, 8192):
        assert plan(rows, 2048, 8192, jnp.bfloat16) is None
    for rows in (8, 128):
        assert plan(rows, 2048, 8192, jnp.bfloat16)["tile"] == 2048
    # a tile that does not divide d_ff is refused, and the call raises
    assert plan(64, 64, 256, jnp.float32, tile=96) is None
    with pytest.raises(ValueError, match="gated_ffn does not take"):
        gated_ffn(jnp.zeros((4, 64)), jnp.zeros((64, 256)),
                  jnp.zeros((64, 256)), jnp.zeros((256, 64)))


# ---------------------------------------------------------------------------
# Cached attention over a dense cache
# ---------------------------------------------------------------------------

# (rows, heads, key/value heads, slots, head_dim, dtype, rows a grid step):
# grouped heads of 64 lanes at the cell's grouping (32 on 8) and equal
# heads of 128 lanes, both types, the plan's own block and a smaller one
CACHED_ATTENTION_CALLS = {
    "grouped_32_on_8_of_64_bf16": (8, 32, 8, 128, 64, jnp.bfloat16, None),
    "grouped_32_on_8_of_64_f32": (8, 32, 8, 128, 64, jnp.float32, 2),
    "equal_heads_of_128_bf16": (16, 4, 4, 256, 128, jnp.bfloat16, 4),
    "equal_heads_of_128_f32": (8, 2, 2, 128, 128, jnp.float32, None),
}


@pytest.mark.parametrize("where", ["first", "mid_reach", "last_slot"])
@pytest.mark.parametrize("call", sorted(CACHED_ATTENTION_CALLS))
def test_cached_attention_matches_the_blocks_own_lines(call, where):
    """The kernel, interpreted, over a dense cache against ``attend``
    over the same values head-major: a stated scale, pass 1 of 2, and NaN
    planted in every slot of K and V the call has not written, which never
    reaches the output."""
    from faabric_tpu.ops.cached_attention import cached_attention, plan

    rows, heads, kv, slots, d, dtype, block_rows = CACHED_ATTENTION_CALLS[call]
    length = {"first": 1, "mid_reach": slots // 2 + 3, "last_slot": slots}[
        where]
    scale = 0.37 / d
    rng = np.random.RandomState(rows + slots + length)
    q = jnp.asarray(rng.randn(rows, heads, d), dtype)
    unwritten = (np.arange(slots) >= length)[None, None, :, None]
    dense = [jnp.asarray(np.where(unwritten, np.nan,
                                  rng.randn(2, rows, slots, kv * d)), dtype)
             for _ in range(2)]
    how = plan(rows, heads, kv, slots, d, dtype, block_rows)
    assert how["steps"] * how["block_rows"] == rows
    got = cached_attention(q, *dense, jnp.int32(length), scale,
                           t=jnp.int32(1), block_rows=block_rows)
    head_major = [c[1].reshape(rows, slots, kv, d).transpose(0, 2, 1, 3)
                  for c in dense]
    written = jnp.arange(slots) < length
    want = attend(q[:, None], *head_major, written[None, :], written, scale,
                  head_major=True)[:, 0]
    assert got.shape == want.shape == (rows, heads, d)
    assert got.dtype == want.dtype
    assert not np.isnan(np.asarray(got, np.float32)).any()
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-6 if f32 else 2e-2, rtol=0 if f32 else 2e-2)
    # pass 0 holds other values: the kernel read pass 1 alone
    other = cached_attention(q, *dense, jnp.int32(length), scale, t=0,
                             block_rows=block_rows)
    if length > 1:
        assert np.abs(np.asarray(other, np.float32)
                      - np.asarray(got, np.float32)).max() > 1e-2


def test_cached_attention_plan_holds_the_cells_call():
    """``plan`` from shapes alone: the cached step of
    ``serve_granite_1chip`` (64 rows, 32 heads on 8 of 64 lanes, 640
    slots) in bfloat16, and what it refuses."""
    from faabric_tpu.ops.cached_attention import (
        MIN_ROWS,
        STEP_BYTES,
        cached_attention,
        plan,
    )

    granite = plan(64, 32, 8, 640, 64, jnp.bfloat16)
    a_row = 2 * 640 * 512 * 2           # a row's keys and values
    assert granite == {
        "block_rows": 2, "steps": 32,
        # two rows' keys, values, block-diagonal queries and outputs,
        # twice; the values once more, zeroed; a row's scores,
        # probabilities and weighted sum in float32
        "vmem_bytes": 2 * 2 * (a_row + (32 + 4) * 512 * 2) + 2 * a_row // 2
        + 32 * (2 * 640 + 2 * 512) * 4,
        "streamed_bytes": 64 * a_row}
    assert granite["vmem_bytes"] == 6_995_968
    assert granite["streamed_bytes"] == 83_886_080
    assert 2 * a_row <= STEP_BYTES < 4 * a_row
    assert MIN_ROWS == 8
    for rows in (1, 7):
        assert plan(rows, 32, 8, 640, 64) is None
    assert plan(8, 32, 8, 640, 64)["steps"] * \
        plan(8, 32, 8, 640, 64)["block_rows"] == 8
    # a position's keys that are not whole lanes; heads no multiple of
    # the key/value heads; a row whose reach is over a step's room
    assert plan(64, 4, 4, 640, 16) is None
    assert plan(64, 12, 8, 640, 64) is None
    assert plan(64, 32, 8, STEP_BYTES // (2 * 512 * 2) + 128, 64) is None
    # rows a step that do not divide the rows: refused, and the call raises
    assert plan(64, 32, 8, 640, 64, block_rows=3) is None
    with pytest.raises(ValueError, match="cached_attention does not take"):
        cached_attention(jnp.zeros((4, 4, 64)), jnp.zeros((1, 4, 128, 128)),
                         jnp.zeros((1, 4, 128, 128)), 5, 1.0)


# ---------------------------------------------------------------------------
# Mamba-1's recurrence over a chunk of positions
# ---------------------------------------------------------------------------

# (rows, positions, lanes, state size, dtype, from a carried state, tile):
# a length the block of positions divides (three blocks of 128) and
# lengths it does not (5, a block of its own and no sublane tile; 200, a
# short last block), 8 and 3 rows, both types, from zero and from a state
# the chunk before left, the plan's own tile and a smaller one
SELECTIVE_SCAN_CALLS = {
    "rows_8_of_384_bf16_carried": (8, 384, 256, 16, jnp.bfloat16, True, None),
    "rows_8_of_384_f32_zero": (8, 384, 256, 16, jnp.float32, False,
                               (4, 128, 128)),
    "rows_3_of_5_bf16_zero": (3, 5, 256, 8, jnp.bfloat16, False, None),
    "rows_3_of_5_f32_carried": (3, 5, 128, 16, jnp.float32, True, None),
    "rows_8_of_200_bf16_zero": (8, 200, 128, 8, jnp.bfloat16, False, None),
    "rows_3_of_200_f32_carried": (3, 200, 256, 16, jnp.float32, True,
                                  (1, 128, 128)),
}


@pytest.mark.parametrize("call", sorted(SELECTIVE_SCAN_CALLS))
def test_selective_scan_matches_mixer1s_own_lines(call):
    """The kernel, interpreted, against ``mixer1``'s lines on the same
    operands: ``_scan1``, a ``lax.scan`` of ``_step1`` along the positions,
    ``D · x`` added in float32 and one cast. ``y`` and the state it leaves."""
    from faabric_tpu.models.ssm import _scan1
    from faabric_tpu.ops.selective_scan import plan, selective_scan

    rows, length, lanes, n, dtype, carried, tile = SELECTIVE_SCAN_CALLS[call]
    rng = np.random.RandomState(rows + length + lanes)
    f32 = jnp.float32
    x = jnp.asarray(rng.randn(rows, length, lanes), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(rows, length, lanes) - 2, f32))
    b, c = (jnp.asarray(rng.randn(rows, length, n), dtype) for _ in range(2))
    a = -jnp.exp(jnp.asarray(rng.randn(n, lanes), f32))
    d = jnp.asarray(rng.randn(lanes), f32)
    state = jnp.asarray(rng.randn(rows, n, lanes) if carried
                        else np.zeros((rows, n, lanes)), dtype)
    how = plan(rows, length, lanes, n, dtype, tile)
    assert how["grid"] == (rows // how["rows"],
                           -(-length // how["positions"]),
                           lanes // how["lanes"])
    if tile is not None:
        assert (how["rows"], how["lanes"], how["positions"]) == tile
    got_y, got_state = selective_scan(x, dt, b, c, a, d, state, tile=tile)

    def own_lines(x, dt, b, c, state):
        state, y = _scan1(state.astype(f32), x, b, c, dt, a)
        return (y + d * x.astype(f32)).astype(dtype), state

    want_y, want_state = jax.jit(own_lines)(x, dt, b, c, state)
    assert got_y.shape == want_y.shape and got_y.dtype == want_y.dtype
    assert got_state.shape == want_state.shape and got_state.dtype == f32
    for got, want, tol in ((got_y, want_y,
                            1e-5 if dtype == jnp.float32 else 1e-2),
                           (got_state, want_state, 1e-5)):
        want = np.asarray(want, np.float32)
        assert np.abs(want).max() > 1.0
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=tol, atol=tol * np.abs(want).max())


def test_selective_scan_plan_holds_the_cells_call():
    """``plan`` from shapes alone: a prefill chunk of
    ``serve_phi4flash_1chip`` (64 rows × 256 positions × 5120 lanes, a
    state of 16) in bfloat16, and what it refuses."""
    from faabric_tpu.ops.selective_scan import (
        BLOCK,
        MAX_LANES,
        MAX_ROWS,
        plan,
        selective_scan,
    )

    cell = plan(64, 256, 5120, 16, jnp.bfloat16)
    a_step = 8 * 128 * 512                # rows × positions × lanes
    assert cell == {
        "rows": 8, "lanes": 512, "positions": 128, "grid": (8, 2, 10),
        # x and y in bfloat16 and dt in float32, B and C padded to a lane
        # tile, A and D, the state in and out, all twice; eight rows' S,
        # their B and C along the lanes, a row's dt · x and y
        "vmem_bytes": 2 * (a_step * (2 + 2 + 4) + 2 * 8 * 128 * 128 * 2
                           + 17 * 512 * 4 + 8 * 16 * 512 * (2 + 4))
        + 8 * 16 * 5120 * 4 + 2 * 8 * 128 * 16 * 128 * 4
        + 2 * 128 * 512 * 4,
        # x, dt and y once, S in bfloat16 in and in float32 out
        "streamed_bytes": 64 * (256 * 5120 * (2 + 4 + 2)
                                + 16 * 5120 * (2 + 4))}
    assert cell["vmem_bytes"] == 30_216_192
    assert cell["streamed_bytes"] == 702_545_920
    assert (MAX_ROWS, MAX_LANES, BLOCK) == (8, 512, 128)
    # the smoke's longer chunk: three blocks; a length the block does not
    # divide: a short last block; one below the block: a block of its own
    assert plan(64, 384, 5120, 16)["grid"] == (8, 3, 10)
    assert plan(8, 200, 5120, 16)["grid"] == (1, 2, 10)
    assert plan(3, 5, 256, 8) == dict(
        plan(3, 5, 256, 8), rows=3, lanes=256, positions=5, grid=(1, 1, 1))
    # rows of no divisor up to 8 go one a step; lanes of no wider divisor
    assert plan(11, 256, 5120, 16)["rows"] == 1
    assert plan(64, 256, 128 * 7, 16)["lanes"] == 128
    # a single position is the cached step's; lanes 128 does not divide;
    # a state size 8 does not divide
    assert plan(64, 1, 5120, 16) is None
    assert plan(64, 256, 5000, 16) is None
    assert plan(64, 256, 5120, 12) is None
    # a tile that does not divide the call is refused, and the call raises
    assert plan(64, 256, 5120, 16, tile=(5, 512, 128)) is None
    assert plan(64, 256, 5120, 16, tile=(8, 768, 128)) is None
    assert plan(64, 256, 5120, 16, tile=(8, 512, 100)) is None
    with pytest.raises(ValueError, match="selective_scan does not take"):
        selective_scan(jnp.zeros((2, 1, 128)), jnp.zeros((2, 1, 128)),
                       jnp.zeros((2, 1, 8)), jnp.zeros((2, 1, 8)),
                       jnp.zeros((8, 128)), jnp.zeros((128,)),
                       jnp.zeros((2, 8, 128)))


# ---------------------------------------------------------------------------
# RMS norm
# ---------------------------------------------------------------------------

def test_rms_norm_matches_reference():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 128, 64), dtype=jnp.float32)
    scale = jnp.asarray(rng.rand(64), dtype=jnp.float32)
    out = rms_norm(x, scale)
    ref = _reference_rms_norm(x, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_rms_norm_gradients():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 128, 32), dtype=jnp.float32)
    scale = jnp.asarray(rng.rand(32), dtype=jnp.float32)
    g1 = jax.grad(lambda x, s: jnp.sum(rms_norm(x, s) ** 2),
                  argnums=(0, 1))(x, scale)
    g2 = jax.grad(lambda x, s: jnp.sum(_reference_rms_norm(x, s) ** 2),
                  argnums=(0, 1))(x, scale)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# Ring attention (sequence parallelism over the sp axis)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_attention_matches_reference(sp):
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=8 // sp, sp=sp))
    q, k, v = qkv(b=2, s=512, h=4, d=32)
    qs, ks, vs = (shard_sequence(x, mesh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh)
    ref = reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_non_causal():
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=2, sp=4))
    q, k, v = qkv(b=1, s=256, h=2, d=16, seed=3)
    qs, ks, vs = (shard_sequence(x, mesh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh, causal=False)
    ref = reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_single_device_axis():
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=8, sp=1))
    q, k, v = qkv(b=1, s=64, h=2, d=16)
    out = ring_attention(q, k, v, mesh)
    ref = reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_model_fused_norm_matches_reference():
    from faabric_tpu.models import ModelConfig, forward, init_params

    kw = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
              max_seq=128, compute_dtype=jnp.float32)
    cfg_ref = ModelConfig(**kw)
    cfg_fused = ModelConfig(**kw, norm_impl="fused")
    params = init_params(jax.random.PRNGKey(0), cfg_ref)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (2, 128)), dtype=jnp.int32)
    np.testing.assert_allclose(
        np.asarray(forward(params, tokens, cfg_fused)),
        np.asarray(forward(params, tokens, cfg_ref)), atol=2e-3)


def test_flash_cross_length_causal():
    """s_k > s_q end-aligns the causal mask (tril k=s_k-s_q), matching the
    reference and the recompute backward."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 128, 2, 32), dtype=jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2, 32), dtype=jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, 2, 32), dtype=jnp.float32)
    out = flash_attention(q, k, v)
    ref = reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_attention_gradients(sp):
    """Reverse-mode through the ppermute ring (fori_loop + collectives
    under shard_map) equals reference autodiff — the long-context training
    path must be differentiable, not just its forward."""
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=8 // sp, sp=sp))
    q, k, v = qkv(b=1, s=256, h=2, d=16, seed=11)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference(q, k, v, causal=True) ** 2)

    qs, ks, vs = (shard_sequence(x, mesh) for x in (q, k, v))
    gf = jax.grad(loss_ring, argnums=(0, 1, 2))(qs, ks, vs)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("sp", [2, 4])
def test_train_step_with_ring_attention(sp):
    """Full training step with attention_impl="ring" over an sp mesh:
    finite loss that decreases and matches the dense-attention step."""
    from faabric_tpu.models import (
        ModelConfig,
        data_sharding,
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    kw = dict(vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
              max_seq=64, compute_dtype=jnp.float32)
    rng = np.random.RandomState(13)
    tokens = rng.randint(0, 64, (4, 64), dtype=np.int32)
    targets = rng.randint(0, 64, (4, 64), dtype=np.int32)

    losses = {}
    for impl, mesh_cfg in [("reference", MeshConfig(dp=2)),
                           ("ring", MeshConfig(dp=8 // sp // 2 or 1, sp=sp))]:
        cfg = ModelConfig(**kw, attention_impl=impl)
        n_dev = mesh_cfg.dp * mesh_cfg.sp
        mesh = build_mesh(jax.devices()[:n_dev], mesh_cfg)
        opt = make_optimizer()
        params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg,
                                             mesh, opt)
        step_fn = make_train_step(cfg, mesh, opt)
        t = jax.device_put(tokens, data_sharding(mesh))
        y = jax.device_put(targets, data_sharding(mesh))
        seq = []
        for _ in range(3):
            params, opt_state, loss = step_fn(params, opt_state, t, y)
            seq.append(float(loss))
        losses[impl] = seq
        assert all(np.isfinite(x) for x in seq)
        assert seq[-1] < seq[0]
    # Same seed, same data: ring and dense attention train identically
    np.testing.assert_allclose(losses["ring"], losses["reference"],
                               rtol=1e-4)


def test_ring_attention_cached_compilation():
    from faabric_tpu.parallel.ring_attention import _compiled_ring

    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=2, sp=4))
    f1 = _compiled_ring(mesh, "sp", True)
    f2 = _compiled_ring(mesh, "sp", True)
    assert f1 is f2  # eager callers hit the jit cache


@pytest.mark.parametrize("attention_impl,mesh_cfg", [
    ("flash", MeshConfig(dp=4, tp=2)),       # shard_mapped Pallas kernel
    ("ring", MeshConfig(dp=2, tp=2, sp=2)),  # sequence-parallel ring
    ("flash", MeshConfig(dp=2, sp=4)),       # flash downgrades to ring
])
def test_model_attention_impls_match_reference_under_mesh(attention_impl,
                                                          mesh_cfg):
    """Every attention implementation under every supported mesh topology
    equals the unsharded reference forward."""
    from faabric_tpu.models import (
        ModelConfig,
        data_sharding,
        forward,
        init_params,
        param_shardings,
    )

    kw = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
              max_seq=128, compute_dtype=jnp.float32)
    cfg_ref = ModelConfig(**kw)
    cfg_impl = ModelConfig(**kw, attention_impl=attention_impl)
    params = init_params(jax.random.PRNGKey(2), cfg_ref)
    tokens = jnp.asarray(
        np.random.RandomState(2).randint(0, 128, (4, 128)), dtype=jnp.int32)
    ref = np.asarray(forward(params, tokens, cfg_ref))

    mesh = build_mesh(jax.devices()[:8], mesh_cfg)
    sharded_params = jax.device_put(params, param_shardings(mesh, cfg_impl))
    sharded_tokens = jax.device_put(tokens, data_sharding(mesh))
    out = jax.jit(lambda p, t: forward(p, t, cfg_impl, mesh))(
        sharded_params, sharded_tokens)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-3)


def test_long_context_ring_training_step():
    """Long-context path at S=2048 over sp=8: one full train step with
    ring attention + remat stays finite — the sequence never gathers."""
    from faabric_tpu.models import (
        ModelConfig,
        data_sharding,
        init_train_state,
        make_optimizer,
        make_train_step,
    )

    cfg = ModelConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                      d_ff=128, max_seq=2048, compute_dtype=jnp.float32,
                      attention_impl="ring", remat=True)
    mesh = build_mesh(jax.devices()[:8], MeshConfig(dp=1, sp=8))
    opt = make_optimizer()
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, mesh,
                                         opt)
    step_fn = make_train_step(cfg, mesh, opt)
    rng = np.random.RandomState(21)
    tokens = jax.device_put(
        rng.randint(0, 128, (1, 2048), dtype=np.int32), data_sharding(mesh))
    _, _, loss = step_fn(params, opt_state, tokens, tokens)
    assert np.isfinite(float(loss)), float(loss)


# ---------------------------------------------------------------------------
# (out, lse) variant + block merging (flash-decoding building block)
# ---------------------------------------------------------------------------

def test_flash_with_lse_matches_logsumexp():
    from faabric_tpu.ops.flash_attention import flash_attention_with_lse

    q, k, v = qkv(b=2, s=256, h=2, d=16)
    out, lse = flash_attention_with_lse(q, k, v)
    ref = reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    scale = 1.0 / np.sqrt(16)
    logits = np.einsum("bqhd,bkhd->bhqk", np.asarray(q),
                       np.asarray(k)) * scale
    mask = np.tril(np.ones((256, 256), bool))
    logits = np.where(mask[None, None], logits, -1e30)
    expect = np.log(np.exp(logits - logits.max(-1, keepdims=True)
                           ).sum(-1)) + logits.max(-1)
    np.testing.assert_allclose(np.asarray(lse), expect.reshape(4, 256),
                               atol=2e-4)


def test_flash_with_lse_gradients_including_lse_cotangent():
    """Backward with a loss that USES the lse output: the g_lse folds
    into the kernels as a delta adjustment and must match reference
    autodiff."""
    from faabric_tpu.ops.flash_attention import (
        _reference_lse,
        flash_attention_with_lse,
    )

    q, k, v = qkv(b=1, s=256, h=2, d=16, seed=17)

    def loss_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v)
        return jnp.sum(out ** 2) + 0.3 * jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        out = reference(q, k, v, causal=True)
        lse = _reference_lse(q, k, True)
        return jnp.sum(out ** 2) + 0.3 * jnp.sum(jnp.sin(lse))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-3)


def test_merge_attention_blocks():
    """Partial attentions over disjoint key blocks merge exactly into the
    full attention (non-causal; the flash-decoding combine)."""
    from faabric_tpu.ops.flash_attention import (
        flash_attention_with_lse,
        merge_attention_blocks,
    )

    q, k, v = qkv(b=2, s=256, h=2, d=16, seed=19)
    full, full_lse = flash_attention_with_lse(q, k, v, False)

    k1, k2 = k[:, :128], k[:, 128:]
    v1, v2 = v[:, :128], v[:, 128:]
    o1, l1 = flash_attention_with_lse(q, k1, v1, False)
    o2, l2 = flash_attention_with_lse(q, k2, v2, False)
    merged, merged_lse = merge_attention_blocks([o1, o2], [l1, l2])
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(merged_lse),
                               np.asarray(full_lse), atol=2e-4)


def test_flash_attention_bf16_forward_and_gradients():
    """bf16 inputs (the TPU compute dtype): kernel forward and two-pass
    backward stay within bf16 tolerances of the reference."""
    rng = np.random.RandomState(23)
    q, k, v = (jnp.asarray(rng.randn(1, 256, 2, 16), jnp.bfloat16)
               for _ in range(3))
    out = flash_attention(q, k, v)
    ref = reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)

    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        reference(q, k, v).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=0.5, rtol=0.1)
