"""Prefill attention over a dense key/value cache as one forward Pallas TPU
kernel.

What a prefill chunk runs in every attention layer whose cache lies dense
(models/transformer.py: ``_attend_cached`` at a static ``start``, more than
one position a row): S_q queries a row, positions ``start`` on, against
the cache ``(passes, rows, slots, kv_heads · head_dim)`` as it lies or,
where the layer's cache is a window's ring, against the ring and the
chunk's own keys and values in one array (the ring is attended before the
chunk overwrites it, and copied as the ``jnp`` lines copy it: read in
place, the ring's writes in every later decode step went through VMEM,
XLA's memory-space assignment moved by the read). The
``jnp`` lines score every key and mask afterwards, every float32 score
through HBM; here no array with both a query and a key axis leaves the
chip. One kernel, ``prefill_attention``, over a grid of (row, key tile,
query block, step), the steps sequential: a step brings one block of
keys and values of one lane tile, scores every query head that reads
that tile against it, and keeps the running maximum, the running sum and
the float32 accumulator of each head in VMEM scratch, as ``flash_fwd``
does. Forward only: a cached call is never differentiated.

Heads by lanes. A key tile holds ``LANE / head_dim`` key/value heads side
by side (two of 64), and a query lane tile the same number of query
heads, each in the lanes of the key head it reads: its score is the
query tile with the other heads' lanes zeroed, times the key tile. The
weighted sum over the value tile gives every value head of the tile; a
head keeps its own lanes, or with ``paired`` (``attend``'s differential
pairs: head (g, p, c) scores key head (g, c) and weights the group's two
value heads side by side) all of them, one tile a head, written with
the positions along the lanes, as the lines' weighted sum leaves them for
the differential (a head's maps laid otherwise were copied to that
layout, in float32). Grouped heads are put in that order on the way in
(head (key head, place) to (tile, place, key head in the tile)) and back
on the way out; pairs lie in it already.

Which keys a query sees is static: every key's position follows from the
static ``start`` (a ring's slot holds the last position below ``start``
that falls on it, negative where none does; a full cache's slot i holds
position i), and a query at position p sees the written keys at p and
below, within ``window`` where there is one. :func:`plan` works out in
numpy, when the kernel is traced, which key blocks each query block
visits: a block no query of it can see neither runs nor fetches (the
empty slots of a ring and of a cache past the reach among them), one all
of whose pairs are visible runs without a mask, the rest under the mask
from positions, with the values of unwritten slots zeroed (they may hold
NaN, and 0 × NaN is NaN). The steps are a table in SMEM that the index
maps and the kernel read.

The arithmetic is ``ops/attention.py:attend``'s in ``flash_fwd``'s order:
scores in float32, the scale riding the exponential's constant, the
probabilities cast to the values' type before the weighted sum, the sum
divided at the end. The statistics' lane-broadcast tiles and the ladder of
block sizes are ``ops/flash_attention.py``'s, by import.

:func:`plan` gives the blocks, the grid, the steps visited, masked and
skipped, the VMEM asked for and the bytes streamed, from the call's shape
alone; the kernel takes its blocks from it. It is None where the kernel
does not apply (lanes that are no whole tiles, lengths that 128 does not
divide, pairs whose two value heads are not one tile, more queries than a
full cache holds), and the caller attends through ``attend``.

On CPU (tests) the kernel runs in interpreter mode automatically.
"""

from __future__ import annotations

import functools
import math

import numpy as np

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
    _stat_cols,
)

# What a step's fixed cost is worth in pairs scored: about 0.35 µs a grid
# step at the 2.0e11 pairs a second of ``flash_fwd`` and
# ``latent_attention`` (PERF.md section 5). Blocks are chosen for
# the least visited steps × (this + the pairs a step scores).
_STEP_PAIRS = 70_000
# A masked score: below NEG_INF, where the running maximum starts
_UNSEEN = 2 * NEG_INF
# A step's kind in the table: 0 neither runs nor fetches
FULL, MASKED = 1, 2
# The table's columns: kind, source (0 the cache, 1 the chunk's own keys),
# the cache's block, the own keys' block
_COLUMNS = 4


def _key_positions(slots: int, queries: int, start: int,
                   window: int) -> list:
    """The positions the keys of each source hold when a chunk of
    ``queries`` at ``start`` attends: the cache's slots (a ring's under
    ``window``: the last position below ``start`` on the slot, negative
    where none was written) and, beside a ring, the chunk's own keys."""
    if not window:
        return [np.arange(slots)]
    slot = np.arange(slots)
    return [start - 1 - ((start - 1 - slot) % slots),
            start + np.arange(queries)]


# kept: a server's reply counts its call's plans again every request
@functools.lru_cache(maxsize=1024)
def _schedule(queries: int, slots: int, start: int, window: int,
              block_q: int, block_k: int) -> tuple:
    """For each query block, the key blocks it visits in order: (kind,
    source, block), FULL where every pair is visible, MASKED where some
    are; a block with none is left out. A query at q sees a written key
    at p (p ≥ 0) where p ≤ q, and q < p + ``window`` where there is one:
    a key block against the query block's first and last position."""
    sources = [k_pos.reshape(-1, block_k) for k_pos in
               _key_positions(slots, queries, start, window)]
    out = []
    for q_blk in range(queries // block_q):
        first = start + q_blk * block_q
        last = first + block_q - 1
        steps = []
        for source, k_pos in enumerate(sources):
            written = k_pos >= 0
            upto = np.minimum(last, k_pos + window - 1) if window else last
            some = (written & (np.maximum(first, k_pos) <= upto)).any(1)
            every = written & (k_pos <= first)
            if window:
                every &= k_pos > last - window
            every = every.all(1)
            steps += [(FULL if every[k_blk] else MASKED, source,
                       int(k_blk)) for k_blk in np.flatnonzero(some)]
        out.append(tuple(steps))
    return tuple(out)


def _table(schedule: tuple, n_steps: int) -> np.ndarray:
    """The schedule as the kernel and the index maps read it: (query
    blocks × steps × ``_COLUMNS``) int32, flat. A step past the query
    block's last keeps the source and blocks of the one before, so that
    nothing is fetched for it; a source's column at a step of the other
    source holds the block that source had last (its first, before it
    has had one)."""
    table = np.zeros((len(schedule), n_steps, _COLUMNS), np.int32)
    for q_blk, steps in enumerate(schedule):
        held = [next((blk for _, src, blk in steps if src == source), 0)
                for source in (0, 1)]
        source = steps[0][1] if steps else 0
        for at in range(n_steps):
            kind = 0
            if at < len(steps):
                kind, source, held[source] = steps[at]
            table[q_blk, at] = (kind, source, *held)
    return table.reshape(-1)


def _vmem_bytes(block_q: int, block_k: int, group: int, halves: int,
                paired: bool, itemsize: int) -> int:
    """What the blocks take of VMEM: the query block, the output block and
    the key and value blocks twice (Pallas double-buffers), the float32
    statistics and accumulator of every head a step scores, and two
    float32 score tiles."""
    heads = group * halves
    blocks = (block_q * group * LANE * (1 + (halves if paired else 1))
              + 2 * block_k * LANE) * itemsize
    scratch = 3 * heads * block_q * LANE * 4
    return 2 * blocks + scratch + 2 * block_q * block_k * 4


def plan(rows: int, heads: int, kv_heads: int, queries: int, slots: int,
         head_dim: int, dtype=jnp.bfloat16, paired: bool = False,
         start: int = 0, window: int = 0, blocks: tuple | None = None):
    """How :func:`prefill_attention` runs ``queries`` positions a row from
    ``start`` over a dense cache of ``slots`` (a ring where ``window``;
    its keys beside the chunk's own), or None where it does not:
    ``block_q`` and ``block_k``, the ``grid`` (rows, key tiles, query
    blocks, steps), the steps ``visited`` (``masked`` of them under the
    mask from positions) and the key blocks ``skipped`` (neither run nor
    fetched), ``vmem_bytes`` (:func:`_vmem_bytes`) and ``streamed_bytes``
    (queries in, outputs out, and a visited key block's keys and values
    once a query block, every row and key tile). A pure function of its
    arguments. ``blocks`` = (block_q, block_k) overrides the choice
    (sweeps and tests)."""
    width = kv_heads * head_dim
    if (head_dim > LANE or LANE % head_dim or width % LANE
            or heads % kv_heads or queries < LANE or queries % LANE
            or slots % LANE or start < 0
            or (paired and 2 * head_dim != LANE)
            or (not window and start + queries > slots)):
        return None
    item = jnp.dtype(dtype).itemsize
    group, halves = heads // kv_heads, LANE // head_dim
    keys = math.gcd(slots, queries) if window else slots

    def counted(block_q, block_k):
        schedule = _schedule(queries, slots, start, window, block_q,
                             block_k)
        return schedule, sum(map(len, schedule))

    if blocks is None:
        fits = []
        for bq in _ladder(queries):
            for bk in _ladder(keys):
                if _vmem_bytes(bq, bk, group, halves, paired,
                               item) > _VMEM_PLAN_BYTES:
                    continue
                visited = counted(bq, bk)[1]
                fits.append((visited * (_STEP_PAIRS
                                        + group * halves * bq * bk),
                             -bk, -bq))
        if not fits:
            return None
        _, block_k, block_q = min(fits)
        block_k, block_q = -block_k, -block_q
    else:
        block_q, block_k = blocks
        if (queries % block_q or keys % block_k or block_q % LANE
                or block_k % LANE):
            return None
    schedule, visited = counted(block_q, block_k)
    tiles = width // LANE
    n_q = queries // block_q
    n_keys = (slots + (queries if window else 0)) // block_k
    masked = sum(kind == MASKED for steps in schedule
                 for kind, _, _ in steps)
    out_lanes = group * LANE * (halves if paired else 1)
    return {"block_q": block_q, "block_k": block_k,
            "grid": (rows, tiles, n_q, max(1, max(map(len, schedule)))),
            "visited": rows * tiles * visited,
            "masked": rows * tiles * masked,
            "skipped": rows * tiles * (n_q * n_keys - visited),
            "vmem_bytes": _vmem_bytes(block_q, block_k, group, halves,
                                      paired, item),
            "streamed_bytes": rows * tiles * item * (
                queries * (group * LANE + out_lanes)
                + visited * 2 * block_k * LANE)}


def _prefill_attention_kernel(t_ref, table, q_ref, k_ref, v_ref, o_ref,
                              m_scr, l_scr, acc_scr, *, scale: float,
                              start: int, reach: int, slots: int,
                              window: int, n_steps: int, head_dim: int,
                              paired: bool):
    """One grid step: one (row, key tile, query block, step). q
    (block_q, group · LANE) stays while k and v (block_k, LANE) stream, a
    block of the step's source; m, l and acc (heads, block_q, LANE) are
    the running softmax's float32 state of the step's ``group · halves``
    heads, head (u, e) the part e of query lane tile u; o is (block_q,
    group · LANE), a head its own lanes, or with ``paired`` (halves,
    group, LANE, block_q), a head's maps with the positions along the
    lanes. The scores stay raw and the scale rides the exponential's
    constant, as in ``flash_fwd``. ``t_ref``, the pass, is the index
    maps'."""
    del t_ref
    heads, block_q, _ = m_scr.shape
    block_k = k_ref.shape[0]
    halves = LANE // head_dim
    group = heads // halves
    q_blk, k_at = pl.program_id(2), pl.program_id(3)
    at = (q_blk * n_steps + k_at) * _COLUMNS
    kind, source = table[at], table[at + 1]
    to_log2 = scale * LOG2_E
    part = jax.lax.broadcasted_iota(jnp.int32, (block_q, LANE), 1) \
        // head_dim

    @pl.when(k_at == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def positions(src: int, first, shape, axis: int):
        """The positions the source's keys hold from its block's first
        slot on, along ``axis`` of ``shape`` (as :func:`_key_positions`)."""
        slot = first + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        if src == 1:
            return start + slot
        if not window:
            return slot
        wrap = start % slots
        return slot + (start - wrap) - jnp.where(slot >= wrap, slots, 0)

    def step(k, v, seen):
        for u in range(group):
            q = q_ref[:, u * LANE:(u + 1) * LANE]
            for e in range(halves):
                h = u * halves + e
                mine = q if halves == 1 else jnp.where(part == e, q, 0)
                scores = jax.lax.dot_general(
                    mine, k, _NT, preferred_element_type=jnp.float32)
                if seen is not None:
                    # below the running maximum's start, so that a row
                    # with nothing seen yet adds exp2(−1e30 · …) = 0
                    scores = jnp.where(seen, scores, _UNSEEN)
                m_prev = m_scr[h]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(scores, axis=1, keepdims=True))
                correction = jnp.exp2((m_prev - m_new) * to_log2)
                p = jnp.exp2((scores - _stat_cols(m_new, block_k))
                             * to_log2)
                m_scr[h] = m_new
                l_scr[h] = l_scr[h] * correction + _lane_sums(p)
                acc_scr[h] = (acc_scr[h] * correction
                              + jax.lax.dot_general(
                                  p.astype(v.dtype), v, _NN,
                                  preferred_element_type=jnp.float32))

    for src in range(1 + bool(window)):
        first = table[at + 2 + src] * block_k
        here = source == src

        @pl.when(here & (kind == FULL))
        def _():
            step(k_ref[...], v_ref[...], None)

        @pl.when(here & (kind == MASKED))
        def _():
            q_pos = start + q_blk * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = positions(src, first, (block_q, block_k), 1)
            seen = (k_pos <= q_pos) & (k_pos >= 0)
            if window:
                seen &= k_pos > q_pos - window
            v = v_ref[...]
            if src == 0:
                held = positions(src, first, (block_k, 1), 0)
                v = jnp.where((held >= 0) & (held < reach), v, 0)
            step(k_ref[...], v, seen)

    @pl.when(k_at == n_steps - 1)
    def _():
        def out(h):
            return acc_scr[h] / jnp.sum(l_scr[h], axis=1, keepdims=True)

        for u in range(group):
            if paired:
                for e in range(halves):
                    o_ref[e, u] = out(u * halves + e).T.astype(o_ref.dtype)
                continue
            tile = out(u * halves)
            for e in range(1, halves):
                tile = jnp.where(part == e, out(u * halves + e), tile)
            o_ref[:, u * LANE:(u + 1) * LANE] = tile.astype(o_ref.dtype)


# jitted so that a program's calls of one shape share one trace and one
# lowering of the kernel's body (PERF.md section 6)
@functools.partial(jax.jit, static_argnames=(
    "start", "scale", "window", "paired", "blocks"))
def prefill_attention(q, cache_k, cache_v, k=None, v=None, t=0, *,
                      start: int, scale: float, window: int = 0,
                      paired: bool = False, blocks: tuple | None = None):
    """q (B, S_q, H, D), positions ``start`` on, over pass ``t`` of the
    dense caches (passes, B, slots, KV · D): a full cache holding every
    position up to the chunk's last, or with ``window`` a ring attended
    beside the chunk's own keys and values k, v (B, S_q, KV, D) under the
    window's band → (B, S_q, H, D) of q's type; with ``paired`` (B, S_q,
    KV / 2, H / KV, 2, 2·D), ``attend``'s two maps of every pair. Head h
    attends key/value head h // (H / KV). ``t`` may be traced. The caller
    asks :func:`plan` first: a shape it refuses is an error here."""
    b, s_q, h, d = q.shape
    _, _, slots, width = cache_k.shape
    kv = width // d
    how = plan(b, h, kv, s_q, slots, d, q.dtype, paired, start, window,
               blocks)
    if how is None:
        raise ValueError(
            f"prefill_attention does not take {b} rows of {s_q} queries "
            f"from {start} over {slots} slots (window {window}), {h} heads "
            f"on {kv} × {d} (paired {paired}, blocks {blocks})")
    block_q, block_k = how["block_q"], how["block_k"]
    rows, tiles, n_q, n_steps = how["grid"]
    group, halves = h // kv, LANE // d
    table = jnp.asarray(_table(
        _schedule(s_q, slots, start, window, block_q, block_k), n_steps))
    # (tile, place in the group, key head in the tile): pairs lie so
    order = (b, s_q, tiles, halves, group, d)
    lanes = q.reshape(b, s_q, h * d) if paired else q.reshape(order) \
        .transpose(0, 1, 2, 4, 3, 5).reshape(b, s_q, h * d)
    interpret = jax.default_backend() == "cpu"
    if window:
        # the ring and the chunk's own keys one array, a copy of the ring
        # as the lines make it: a ring read in place by this call moved
        # every later decode step's writes to it through VMEM (XLA's
        # memory-space assignment; PERF.md section 6)
        operands = [jnp.concatenate(
            [jax.lax.dynamic_index_in_dim(c, t, 0, keepdims=False),
             own.reshape(b, s_q, width)], axis=1)
            for c, own in ((cache_k, k), (cache_v, v))]
        ring = slots // block_k

        def at_block(r, j, qi, ki, t, tb):
            at = (qi * n_steps + ki) * _COLUMNS
            return (r, jnp.where(tb[at + 1] == 0, tb[at + 2],
                                 ring + tb[at + 3]), j)

        spec = pl.BlockSpec((None, block_k, LANE), at_block)
    else:
        operands = [cache_k, cache_v]
        if not interpret:
            # the caches stay where they lie, as for ``cached_attention``
            operands = [pltpu.with_memory_space_constraint(c, pltpu.HBM)
                        for c in operands]
        spec = pl.BlockSpec((None, None, block_k, LANE),
                            lambda r, j, qi, ki, t, tb: (
                                t[0], r, tb[(qi * n_steps + ki)
                                            * _COLUMNS + 2], j))
    if paired:
        # a head's two maps, the positions along the lanes, the pairs'
        # first heads apart from their second: the layout the
        # differential reads (any other is copied to it in float32)
        out_shape = (b, halves, h // halves, LANE, s_q)
        out_spec = pl.BlockSpec((None, halves, group, LANE, block_q),
                                lambda r, j, qi, ki, t, tb: (r, 0, j, 0, qi))
    else:
        out_shape = (b, s_q, h * d)
        out_spec = pl.BlockSpec((None, block_q, group * LANE),
                                lambda r, j, qi, ki, t, tb: (r, qi, j))
    out = pl.pallas_call(
        functools.partial(_prefill_attention_kernel, scale=float(scale),
                          start=start, reach=start + s_q, slots=slots,
                          window=window, n_steps=n_steps, head_dim=d,
                          paired=paired),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=how["grid"],
            in_specs=[pl.BlockSpec((None, block_q, group * LANE),
                                   lambda r, j, qi, ki, t, tb: (r, qi, j)),
                      spec, spec],
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((group * halves, block_q, LANE),
                                       jnp.float32)] * 3,
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * how["visited"] * group * halves * block_q * block_k
            * LANE,
            transcendentals=how["visited"] * group * halves * block_q
            * block_k,
            bytes_accessed=how["streamed_bytes"]),
        interpret=interpret,
        name="prefill_attention",
    )(jnp.reshape(jnp.asarray(t, jnp.int32), (1,)), table, lanes,
      *operands)
    if paired:
        return out.transpose(0, 4, 2, 1, 3).reshape(b, s_q, tiles, group, 2,
                                                    2 * d)
    return out.reshape(b, s_q, tiles, group, halves, d) \
        .transpose(0, 1, 2, 4, 3, 5).reshape(b, s_q, h, d)
