"""Latent attention's prefill as one forward Pallas TPU kernel.

What a prefill chunk runs in every latent-attention layer
(models/transformer.py: ``_latent_attention`` with a cache, at a static
``start``): S_q queries a row, the last of K positions, against every
head's keys and values expanded from the K latents. A key is
``nope + rope`` lanes, of which the ``rope`` rotary lanes are one array
for all heads, as the cache holds them; a value is ``v`` lanes; the scale
is the configuration's own (YaRN's, not 1 / sqrt(lanes)). The ``jnp``
lines write every float32 score to HBM and read it back; here no array
with both a query and a key axis leaves the chip. One kernel,
``latent_attention``, over a grid of (row, head, query block, key block),
the key blocks sequential: a step scores ``q_nope · k_nopeᵀ + q_rope ·
k_ropeᵀ`` from two pairs of references, so no (B, K, H, nope + rope)
concatenation is made and no 192-lane tile is padded to 256, keeps the
running maximum, the running sum and the float32 accumulator in VMEM
scratch as ``flash_fwd`` does, and writes only the (B, S_q, H · v) output.
Forward only: a cached call is never differentiated.

Nothing is transposed on the way in. Queries, keys and values are read
where a matrix product leaves them, a position's heads side by side,
``(B, S, H · lanes)``: head h's block is lane tile h of every row. Only
the queries' rotary lanes, ``rope`` a head and no whole lane tile, come
head-major, ``(B, H, S_q, rope)``.

The tiling of the (query, key) rectangle, the strips inside a block, the
statistics' lane-broadcast tiles and the ladder of block sizes are
``ops/flash_attention.py``'s, by import: a block above the diagonal
neither runs nor fetches, a block on it computes its lower triangle in
strips, the mask is end-aligned (query row i sees keys up to i + K −
S_q). The scale rides the exponential's constant as there.

:func:`plan` gives the blocks, the grid, the rows a call should take at
once (so that their expanded keys and values stay within
``EXPANDED_BYTES``), the VMEM asked for and the bytes streamed, from the
call's shape alone; the kernel takes its blocks from it. It is None where
the kernel does not apply (lanes that are no whole tiles, lengths that
128 does not divide, a reach under ``MIN_REACH``, more queries than
keys), and the caller keeps its own lines.

On CPU (tests) the kernel runs in interpreter mode automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from faabric_tpu.ops.flash_attention import (
    _NN,
    _NT,
    _VMEM_PLAN_BYTES,
    LANE,
    LOG2_E,
    NEG_INF,
    VMEM_LIMIT_BYTES,
    _ladder,
    _lane_sums,
    _last_visible_k,
    _run_visible,
    _stat_cols,
    _strip_rows,
    _unmasked,
    _visible,
)

# What the keys and values expanded for one call may take in HBM: a caller
# with more rows than ``plan["rows"]`` sends them a block at a time (8 rows
# × 8,192 positions × 64 heads × 256 lanes are 2.1 GB, beside 9.8 GB of
# weights).
EXPANDED_BYTES = 512 * 1024 * 1024
# Under this reach a grid step is one 128 × 128 tile, 10 MFLOP behind a
# step's fixed cost, and the scores are few enough for the ``jnp`` lines:
# 64 rows × 128 over 128 take them 3.9 ms and this path 4.7, 64 × 256
# over 256 take them 12.5 and this path 7.7 (PERF.md section 6, PR 42).
MIN_REACH = 256


def _vmem_bytes(block_q: int, block_k: int, nope: int, rope: int, v: int,
                itemsize: int) -> int:
    """What the blocks take of VMEM: every operand and result block twice
    (Pallas double-buffers; ``rope`` lanes lie padded to a tile), the
    float32 scratch, and two float32 score tiles of a strip."""
    lanes = -(-rope // LANE) * LANE
    blocks = (block_q * (nope + lanes + v)
              + block_k * (nope + lanes + v)) * itemsize
    scratch = block_q * (2 * LANE + v) * 4
    return 2 * blocks + scratch + 2 * _strip_rows(block_q) * block_k * 4


def _pick_blocks(queries: int, reach: int, nope: int, rope: int, v: int,
                 itemsize: int):
    """The largest blocks that divide the lengths and fit the budget: of
    two that fit, one whose diagonal blocks compute their triangle alone
    (square, the offset a whole block), then the larger score tile, then
    the longer key block."""
    offset = reach - queries
    fits = [(bq == bk and offset % bk == 0, bq * bk, bk, bq)
            for bq in _ladder(queries) for bk in _ladder(reach)
            if _vmem_bytes(bq, bk, nope, rope, v, itemsize)
            <= _VMEM_PLAN_BYTES]
    if not fits:
        return None
    *_, block_k, block_q = max(fits)
    return block_q, block_k


def plan(rows: int, heads: int, queries: int, reach: int, nope: int,
         rope: int, v: int, dtype=jnp.bfloat16, blocks: tuple | None = None):
    """How :func:`latent_attention` runs ``queries`` positions a row, the
    last of ``reach``, or None where it does not: ``block_q`` and
    ``block_k``, the ``grid`` (rows, heads, query blocks, key blocks),
    the grid steps ``visited`` (``masked`` of them on the diagonal) and
    ``skipped``, ``rows`` (the most rows, dividing ``rows``, whose
    expanded keys and values stay within ``EXPANDED_BYTES``: what one
    call should take), ``vmem_bytes`` (:func:`_vmem_bytes`) and
    ``streamed_bytes`` (queries in, outputs out, and a key block's keys,
    rotary lanes and values once for each query block that sees it, every
    row and head). A pure function of its arguments. ``blocks`` =
    (block_q, block_k) overrides the choice (sweeps and tests)."""
    if (nope % LANE or v % LANE or rope % 8 or rope > LANE
            or queries % LANE or reach % LANE or reach < MIN_REACH
            or queries > reach):
        return None
    item = jnp.dtype(dtype).itemsize
    if blocks is None:
        blocks = _pick_blocks(queries, reach, nope, rope, v, item)
        if blocks is None:
            return None
    block_q, block_k = blocks
    if (queries % block_q or reach % block_k or block_q % LANE
            or block_k % LANE):
        return None
    n_q, n_k, offset = queries // block_q, reach // block_k, reach - queries
    seen = [(q_blk, k_blk) for q_blk in range(n_q) for k_blk in range(n_k)
            if _visible(q_blk, k_blk, block_q, block_k, offset)]
    masked = sum(not _unmasked(q_blk, k_blk, block_q, block_k, offset)
                 for q_blk, k_blk in seen)
    a_row = reach * heads * (nope + v) * item
    fits = [r for r in range(1, rows + 1)
            if rows % r == 0 and r * a_row <= EXPANDED_BYTES]
    return {"block_q": block_q, "block_k": block_k,
            "grid": (rows, heads, n_q, n_k),
            "visited": rows * heads * len(seen),
            "masked": rows * heads * masked,
            "skipped": rows * heads * (n_q * n_k - len(seen)),
            "rows": max(fits, default=1),
            "vmem_bytes": _vmem_bytes(block_q, block_k, nope, rope, v, item),
            "streamed_bytes": rows * heads * item * (
                queries * (nope + rope + v)
                + len(seen) * block_k * (nope + rope + v))}


def _latent_attention_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref,
                             m_scr, l_scr, acc_scr, *, scale: float,
                             offset: int):
    """One grid step: one (row, head, query block, key block). qn
    (block_q, nope) and qr (block_q, rope) stay while kn (block_k, nope),
    kr (block_k, rope) and v (block_k, v) stream; m, l (block_q, LANE) and
    acc (block_q, v) are the running softmax's float32 state. The scores
    stay raw and the scale rides the exponential's constant, as in
    ``flash_fwd``."""
    block_q, block_k = qn_ref.shape[0], kn_ref.shape[0]
    lanes = v_ref.shape[1]
    q_blk, k_blk = pl.program_id(2), pl.program_id(3)
    to_log2 = scale * LOG2_E

    @pl.when(k_blk == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step(q_rows, k_rows, mask):
        v = v_ref[k_rows]
        scores = jax.lax.dot_general(
            qn_ref[q_rows], kn_ref[k_rows], _NT,
            preferred_element_type=jnp.float32) + jax.lax.dot_general(
            qr_ref[q_rows], kr_ref[k_rows], _NT,
            preferred_element_type=jnp.float32)
        if mask is not None:
            scores = jnp.where(mask(scores.shape, 0), scores, NEG_INF)
        m_prev = m_scr[q_rows]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        correction = jnp.exp2((m_prev - m_new) * to_log2)
        p = jnp.exp2((scores - _stat_cols(m_new, scores.shape[1])) * to_log2)
        m_scr[q_rows] = m_new
        l_scr[q_rows] = l_scr[q_rows] * correction + _lane_sums(p)
        acc_scr[q_rows] = (acc_scr[q_rows] * _stat_cols(correction, lanes)
                           + jax.lax.dot_general(
                               p.astype(v.dtype), v, _NN,
                               preferred_element_type=jnp.float32))

    _run_visible(step, True, q_blk, k_blk, block_q, block_k, offset)

    @pl.when(k_blk == pl.num_programs(3) - 1)
    def _():
        l = jnp.sum(l_scr[...], axis=1, keepdims=True)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


# jitted so that a program's calls of one shape share one trace and one
# lowering of the kernel's body (PERF.md section 6, PR 40)
@functools.partial(jax.jit, static_argnames=("scale", "blocks"))
def latent_attention(q_nope, q_rope, k_nope, k_rope, v, scale: float,
                     blocks: tuple | None = None):
    """q_nope (B, S_q, H, nope) and q_rope (B, S_q, H, rope), the last
    S_q of the K positions whose expanded keys k_nope (B, K, H · nope),
    shared rotary lanes k_rope (B, K, rope) and expanded values v (B, K,
    H · v) are given → (B, S_q, H, v) of q_nope's type: causal attention
    with float32 scores times ``scale``, float32 statistics and
    accumulator, the probabilities cast to the values' type before the
    weighted sum. The caller asks :func:`plan` first: a shape it refuses
    is an error here."""
    b, s_q, h, nope = q_nope.shape
    rope, reach, lanes = q_rope.shape[-1], k_nope.shape[1], v.shape[-1] // h
    how = plan(b, h, s_q, reach, nope, rope, lanes, q_nope.dtype, blocks)
    if how is None:
        raise ValueError(
            f"latent_attention does not take {b} rows of {s_q} queries over "
            f"{reach} keys, {h} heads of {nope} + {rope} lanes on values "
            f"of {lanes} (blocks {blocks})")
    block_q, block_k = how["block_q"], how["block_k"]
    offset = reach - s_q

    def k_at(qi, ki):
        # a skipped step names the block already resident: nothing is
        # fetched for it
        return jnp.minimum(ki, _last_visible_k(qi, block_q, block_k, offset))

    out = pl.pallas_call(
        functools.partial(_latent_attention_kernel, scale=float(scale),
                          offset=offset),
        grid=how["grid"],
        in_specs=[
            pl.BlockSpec((None, block_q, nope),
                         lambda r, hd, qi, ki: (r, qi, hd)),
            pl.BlockSpec((None, None, block_q, rope),
                         lambda r, hd, qi, ki: (r, hd, qi, 0)),
            pl.BlockSpec((None, block_k, nope),
                         lambda r, hd, qi, ki: (r, k_at(qi, ki), hd)),
            pl.BlockSpec((None, block_k, rope),
                         lambda r, hd, qi, ki: (r, k_at(qi, ki), 0)),
            pl.BlockSpec((None, block_k, lanes),
                         lambda r, hd, qi, ki: (r, k_at(qi, ki), hd)),
        ],
        out_specs=pl.BlockSpec((None, block_q, lanes),
                               lambda r, hd, qi, ki: (r, qi, hd)),
        out_shape=jax.ShapeDtypeStruct((b, s_q, h * lanes), q_nope.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANE), jnp.float32),
            pltpu.VMEM((block_q, LANE), jnp.float32),
            pltpu.VMEM((block_q, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * how["visited"] * block_q * block_k
            * (nope + rope + lanes),
            transcendentals=how["visited"] * block_q * block_k,
            bytes_accessed=how["streamed_bytes"]),
        interpret=jax.default_backend() == "cpu",
        name="latent_attention",
    )(q_nope.reshape(b, s_q, h * nope), q_rope.transpose(0, 2, 1, 3),
      k_nope, k_rope, v)
    return out.reshape(b, s_q, h, lanes)
