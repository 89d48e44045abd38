"""A cached step's attention over a dense key/value cache as one Pallas
TPU kernel.

What a decode step of many rows runs in every attention layer: one query
position a row against that row's whole reach of keys and values. The
cache lies position-major, ``(passes, rows, slots, kv_heads · head_dim)``:
a position's keys of all key/value heads are one row of whole lanes
whatever the head's width (8 heads of 64 are 512 lanes; head-major, a
head of 64 is padded to the tile's 128 and the cache lies at twice its
size). One kernel, ``cached_attention``, over a sequential grid of blocks
of rows: a step brings its rows' keys and values by the pipeline's
double-buffered DMA while the step before computes; scores, mask, softmax
and weighted sum happen in VMEM and only the heads' outputs go back, so a
layer reads its keys and values from HBM once and nothing else of their
size moves.

No lane is sliced. The query heads enter block-diagonal, ``(heads,
kv_heads · head_dim)``: head (k, g)'s lanes stand in key/value head k's
columns, zeros elsewhere, so one product with a row's keys gives every
head's scores ``(heads, slots)`` and one product of the probabilities with
its values gives ``(heads, kv_heads · head_dim)``, of which a head keeps
its own key/value head's columns. The heads of a group share the group's
keys and values with no copy a head; the MXU does ``kv_heads`` times the
useful work and idles all the same.

The arithmetic is ``ops/attention.py:attend``'s: scores accumulated in
float32 times the scale, positions at or beyond ``length``
masked to −1e30, softmax in float32, probabilities cast to the inputs'
type before the product with the values, and unwritten slots of the values
zeroed before it (a slot the call has not written may hold anything, and
0 × NaN is NaN).

:func:`plan` gives the rows a grid step takes, the grid, the VMEM the
call asks for and the bytes it streams, from the call's shape alone; the
kernel takes its blocks from it. It is None where the kernel does not
apply (fewer than ``MIN_ROWS`` rows, a cache row that is not whole lanes,
a row's reach that does not fit ``STEP_BYTES``), and the caller attends
through ``ops/attention.py`` over its cache as it lies.

On CPU (tests) the kernel runs in interpreter mode automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# Below MIN_ROWS a step's caches are a few small fusions that XLA runs at
# the weights' rate (PERF.md section 5, the batch-1 cells).
MIN_ROWS = 8
# What one grid step's keys and values may hold; the call keeps two steps'
# blocks in VMEM. A row whose own reach is above it has no plan (a running
# softmax over blocks of slots would take it: ROADMAP B-I).
STEP_BYTES = 4 * 1024 * 1024
# Mosaic's own temporaries beside what :func:`plan` counts
_VMEM_HEADROOM = 4 * 1024 * 1024
_MASKED = -1e30


def plan(rows: int, heads: int, kv_heads: int, slots: int, head_dim: int,
         dtype=jnp.bfloat16, block_rows: int | None = None,
         paired: bool = False):
    """How :func:`cached_attention` runs a call of these shapes, or None
    where it does not: ``block_rows`` (rows a grid step), ``steps``,
    ``vmem_bytes`` (what the call asks for: a step's keys, values,
    block-diagonal queries and outputs twice, as Pallas double-buffers
    every block, the values once more with their unwritten slots zeroed,
    and a row's scores, probabilities and weighted sum in float32) and
    ``streamed_bytes`` (the keys and values once). A pure function of its
    arguments. ``block_rows`` overrides the choice (sweeps and tests).
    ``paired``: the differential form, in which a head keeps the columns
    of its pair's two value heads (whole lanes: ``2 · head_dim``), so the
    outputs are twice as wide."""
    width = kv_heads * head_dim
    if rows < MIN_ROWS or heads % kv_heads or width % LANE:
        return None
    if paired and (kv_heads % 2 or (2 * head_dim) % LANE):
        return None
    item = jnp.dtype(dtype).itemsize
    a_row = 2 * slots * width * item
    if block_rows is None:
        fits = [r for r in range(1, rows + 1)
                if rows % r == 0 and r * a_row <= STEP_BYTES]
        if not fits:
            return None
        block_rows = max(fits)
    elif rows % block_rows:
        return None
    kept = 2 * heads * head_dim if paired else heads // kv_heads * width
    blocks = block_rows * (a_row + (heads * width + kept) * item)
    temporaries = (block_rows * a_row // 2
                   + heads * (2 * slots + 2 * width) * 4)
    return {"block_rows": block_rows, "steps": rows // block_rows,
            "vmem_bytes": 2 * blocks + temporaries,
            "streamed_bytes": rows * a_row}


def _cached_attention_kernel(scalars, q_ref, k_ref, v_ref, o_ref, *,
                             scale: float, kv_heads: int, paired: bool):
    """One grid step: a block of rows. q (rows, heads, width) holds the
    block-diagonal queries, the heads ordered (place in the group,
    key/value head); k and v (rows, slots, width) are those rows' caches;
    o (rows, heads / kv_heads, width) takes, at place g of the group and
    key/value head k's columns, head (k, g)'s weighted sum. ``paired``:
    the heads in their own order, head h on key/value head 2·(h // (2·G))
    + h % 2, G heads a key/value head; o (rows, heads, 2·head_dim) takes
    head h's weighted sum over the columns of value heads 2·(h // (2·G))
    and the next: whole lanes, sliced where they start."""
    length = scalars[0]
    block_rows, heads, width = q_ref.shape
    slots = k_ref.shape[1]
    head_dim = width // kv_heads
    f32 = jnp.float32
    reached = jax.lax.broadcasted_iota(jnp.int32, (1, slots), 1) < length
    written = jax.lax.broadcasted_iota(jnp.int32, (slots, 1), 0) < length
    own = (jax.lax.broadcasted_iota(jnp.int32, (kv_heads, width), 1)
           // head_dim
           == jax.lax.broadcasted_iota(jnp.int32, (kv_heads, width), 0))
    for r in range(block_rows):
        scores = jax.lax.dot_general(
            q_ref[r], k_ref[r], (((1,), (1,)), ((), ())),
            preferred_element_type=f32) * scale
        scores = jnp.where(reached, scores, _MASKED)
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        probs = (weights / jnp.sum(weights, axis=-1, keepdims=True)
                 ).astype(v_ref.dtype)
        mixed = jnp.dot(probs, jnp.where(written, v_ref[r], 0),
                        preferred_element_type=f32)
        if paired:
            lanes = 2 * head_dim
            pair = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0) \
                // (2 * heads // kv_heads)
            o_ref[r] = sum(
                jnp.where(pair == g, mixed[:, g * lanes:(g + 1) * lanes], 0.0)
                for g in range(kv_heads // 2)).astype(o_ref.dtype)
            continue
        mixed = mixed.reshape(heads // kv_heads, kv_heads, width)
        o_ref[r] = jnp.sum(jnp.where(own[None], mixed, 0.0), axis=1
                           ).astype(o_ref.dtype)


def cached_attention(q, cache_k, cache_v, length, scale: float, t=0,
                     block_rows: int | None = None, paired: bool = False):
    """q (rows, heads, head_dim), one position a row, the last of the
    first ``length`` positions of pass ``t`` of the dense caches
    (passes, rows, slots, kv_heads · head_dim) → (rows, heads, head_dim)
    of q's type. Head ``h`` attends key/value head ``h // (heads /
    kv_heads)``. ``length`` and ``t`` may be traced. The caller asks
    :func:`plan` first: a shape it refuses is an error here."""
    rows, heads, head_dim = q.shape
    _, _, slots, width = cache_k.shape
    kv_heads = width // head_dim
    how = plan(rows, heads, kv_heads, slots, head_dim, q.dtype, block_rows,
               paired)
    if how is None:
        raise ValueError(
            f"cached_attention does not take {rows} rows of {heads} heads "
            f"on {kv_heads} × {head_dim} over {slots} slots "
            f"(block_rows {block_rows})")
    block_rows, group = how["block_rows"], heads // kv_heads
    item = jnp.dtype(q.dtype).itemsize
    if paired:
        # head h's lanes into the columns of its key head, in place
        h = jnp.arange(heads)
        mine = (2 * (h // (2 * group)) + h % 2)[:, None] \
            == jnp.arange(kv_heads)[None, :]
        diagonal = jnp.where(mine[None, :, :, None], q[:, :, None, :],
                             0).reshape(rows, heads, width)
        out_block = (heads, 2 * head_dim)
    else:
        # head (k, g)'s lanes into key/value head k's columns of row (g, k)
        grouped = q.reshape(rows, kv_heads, group, head_dim
                            ).transpose(0, 2, 1, 3)
        diagonal = jnp.where(
            jnp.eye(kv_heads, dtype=bool)[None, None, :, :, None],
            grouped[:, :, :, None, :], 0).reshape(rows, heads, width)
        out_block = (group, width)
    scalars = jnp.stack([jnp.asarray(length, jnp.int32),
                         jnp.asarray(t, jnp.int32)])
    interpret = jax.default_backend() == "cpu"
    if not interpret:
        # the caches stay where they lie: XLA's memory-space assignment
        # would else copy a whole cache into VMEM ahead of the call and
        # back behind it, twice its bytes for one reading
        cache_k, cache_v = (pltpu.with_memory_space_constraint(c, pltpu.HBM)
                            for c in (cache_k, cache_v))
    out = pl.pallas_call(
        functools.partial(_cached_attention_kernel, scale=float(scale),
                          kv_heads=kv_heads, paired=paired),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(how["steps"],),
            in_specs=[
                pl.BlockSpec((block_rows, heads, width),
                             lambda i, s: (i, 0, 0)),
                pl.BlockSpec((None, block_rows, slots, width),
                             lambda i, s: (s[1], i, 0, 0)),
                pl.BlockSpec((None, block_rows, slots, width),
                             lambda i, s: (s[1], i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, *out_block),
                                   lambda i, s: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, *out_block), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=how["vmem_bytes"] + _VMEM_HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * heads * slots * width,
            transcendentals=rows * heads * slots,
            bytes_accessed=how["streamed_bytes"]
            + rows * (heads * width + out_block[0] * out_block[1]) * item),
        interpret=interpret,
        name="cached_attention",
    )(scalars, diagonal, cache_k, cache_v)
    if paired:
        return out
    return out.reshape(rows, group, kv_heads, head_dim
                       ).transpose(0, 2, 1, 3).reshape(rows, heads, head_dim)
