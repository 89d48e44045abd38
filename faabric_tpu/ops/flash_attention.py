"""Fused causal flash attention as Pallas TPU kernels (fwd + bwd).

The hot op of the flagship model, tiled for the MXU. Three kernels,
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv``, share one tiling of
the (query, key) square: the grid is (batch·head, outer block, inner
block) with the inner axis sequential, so Pallas streams and
double-buffers the inner blocks itself and nothing of sequence length
sits in VMEM. The forward and dQ passes hold a query block and stream
K/V; the dK/dV pass holds a key block and streams Q/dO. Running softmax
statistics and accumulators live in VMEM scratch in float32, initialised
on the first inner step and written out on the last; matrix operands
stay in the input type (bfloat16 rides the MXU at full rate). No (S, S)
score matrix ever materialises in HBM, and memory stays O(S·D) in both
directions.

Training runs the standard two-pass flash backward: the forward kernel
additionally emits the per-row log-sum-exp, and the backward kernels
recompute probabilities in-block from (Q, K, LSE).

Causal work is skipped in the grid, not in a loop: a block above the
diagonal does not run, and its ``index_map`` names the block already
resident, so nothing is fetched for it. Only a block the diagonal passes
through builds the mask, and it computes its lower triangle in strips
(:func:`_diagonal_strips`) rather than the whole tile; a block wholly
below the diagonal takes the unmasked body, a strip of rows a trip of a
rolled loop (the code stays a strip's). With ``s_k > s_q`` the mask
is end-aligned (query row i sees keys up to i + s_k − s_q), as the
reference's ``tril(k=s_k − s_q)``.

Blocks are sized by the call's shape: :func:`block_plan` picks, per
kernel, the largest blocks (whole lane tiles up to ``MAX_BLOCK``) that
divide the lengths and fit the VMEM budget, and counts the grid steps
visited, masked and skipped and the share of the square computed. The
explicit ``block_q`` / ``block_k`` arguments override it for all three
kernels.

Which path runs is a pure function of the shapes and the backend:
:func:`uses_kernel` answers it, so a caller can assert the kernel was
taken. Shapes the tiling cannot take (ragged, sub-tile, causal with
s_q > s_k) run the jnp reference; every other shape runs the kernels,
and a shape the Mosaic compiler refuses is an error, never the
reference.

On CPU (tests) the kernels run in interpreter mode automatically.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from faabric_tpu.ops.attention import attend

NEG_INF = -1e30
# Inside the kernels a per-row softmax statistic (m, l, lse, delta) is a
# (rows, LANE) float32 tile with every lane holding the row's value: it
# expands over a score tile by reusing registers, where a 1-D carry would
# pay a lane ↔ sublane relayout on every block (the official TPU flash
# kernel keeps its m/l statistics the same way).
LANE = 128
# Between the kernels lse and delta travel as rows, (B·H, SUBLANE, S_q)
# with every sublane alike: 1/16 of the lane-broadcast form's bytes (which
# was twice q's, written by every forward call and read by both backward
# passes), Mosaic-tileable, and what the dK/dV pass wants as it is, since
# it works on transposed score tiles (keys × queries). The forward and
# dQ passes turn rows and columns into each other once a query block.
SUBLANE = 8

# A block is the largest whole number of lane tiles, up to MAX_BLOCK, that
# divides the length: 1024 × 1024 won the sweep on a v5e at (4, 2048, 16,
# 128) and (1, 8192, 16, 128) in bfloat16 for all three kernels, and a
# 128-block loses to everything (PERF.md, PR 28).
MAX_BLOCK = 1024
# A grid step works through its block in strips of this many rows of the
# outer side (of 128 where the block is no multiple): a block below the
# diagonal in a rolled loop, so that the kernel's code is a strip's and
# not a block's (a 1024 × 1024 tile unrolled doubles the size of a train
# step's executable, and its loading shows in set-up); a block on the
# diagonal strip by strip of its lower triangle. Of 512, 256 and 128 in
# 1024-blocks, 256 won.
STRIP_ROWS = 256
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# What the kernels may take of a v5e's 128 MiB of VMEM (twice the
# compiler's own default), and the half of it a plan may fill by
# :func:`_vmem_bytes`' reckoning. libtpu compiles 1024 × 1024 blocks at
# head size 128 within 8 MiB in bfloat16 and 16 MiB in float32.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_VMEM_PLAN_BYTES = VMEM_LIMIT_BYTES // 2

_NT = (((1,), (1,)), ((), ()))  # a · bᵀ: contract the last dims of both
_NN = (((1,), (0,)), ((), ()))  # a · b


def _lane_sums(x):
    """(rows, cols) → (rows, LANE) whose lanes add up to the row sums: the
    LANE-wide column chunks added elementwise, so that the running sum l
    needs no cross-lane reduction until it is read. A narrower tile (CPU
    interpret path) spreads its row sums evenly."""
    rows, cols = x.shape
    if cols % LANE:
        return jnp.broadcast_to(jnp.sum(x, axis=1, keepdims=True) / LANE,
                                (rows, LANE))
    out = x[:, :LANE]
    for i in range(1, cols // LANE):
        out = out + x[:, i * LANE:(i + 1) * LANE]
    return out


def _stat_cols(stat, n_cols: int):
    """Expand a (rows, LANE) lane-broadcast statistic to (rows, n_cols)
    (every lane holds the same per-row value; n_cols may be < LANE on the
    CPU interpret path)."""
    reps = max(1, -(-n_cols // LANE))
    return jnp.tile(stat, (1, reps))[:, :n_cols]


def _mask(q, k, causal: bool):
    """What :func:`attend` (the ``jnp`` lines, where no plan tiles a call,
    and their backward pass) masks: row i sees keys up to i + S_k − S_q,
    the queries the last positions; None where not ``causal``."""
    s_q, s_k = q.shape[1], k.shape[1]
    return jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q) \
        if causal else None


# ---------------------------------------------------------------------------
# The tiling: which blocks of the (query, key) square a causal call visits.
# The same arithmetic runs on Python ints (block_plan), on the grid's
# program ids inside the kernels, and in the index maps.
# ---------------------------------------------------------------------------

def _last_visible_k(q_blk, block_q: int, block_k: int, offset: int):
    """The last key block that the query block's last row may see."""
    return (q_blk * block_q + block_q - 1 + offset) // block_k


def _first_visible_q(k_blk, block_q: int, block_k: int, offset: int):
    """The first query block with a row that sees the key block's first
    key (row i sees keys up to i + offset)."""
    return jnp.maximum(k_blk * block_k - offset, 0) // block_q


def _visible(q_blk, k_blk, block_q: int, block_k: int, offset: int):
    return k_blk <= _last_visible_k(q_blk, block_q, block_k, offset)


def _unmasked(q_blk, k_blk, block_q: int, block_k: int, offset: int):
    """The block lies wholly below the diagonal: the query block's first
    row sees the key block's last key."""
    return k_blk * block_k + block_k - 1 <= q_blk * block_q + offset


def _strip_rows(block: int) -> int:
    return next((c for c in (STRIP_ROWS, LANE) if block % c == 0), block)


def _diagonal_strips(block_q: int, block_k: int, offset: int,
                     q_outer: bool):
    """How a block on the diagonal is cut so that the part of it above the
    diagonal is not computed: (query rows, key rows) slices of the block,
    each one body. Where blocks are square and aligned (every masked block
    then has the diagonal as its own), the block's lower triangle, a strip
    a query chunk of :func:`_strip_rows` (or, where the key block is the
    grid's outer one, a key chunk); else the whole block."""
    chunk = _strip_rows(block_q)
    if block_q != block_k or offset % block_k:
        return [(slice(0, block_q), slice(0, block_k))]
    starts = range(0, block_q, chunk)
    if q_outer:
        return [(slice(r, r + chunk), slice(0, r + chunk)) for r in starts]
    return [(slice(c, block_q), slice(c, c + chunk)) for c in starts]


def _run_visible(step, causal: bool, q_blk, k_blk, block_q: int,
                 block_k: int, offset: int, q_outer: bool = True):
    """Run ``step(q_rows, k_rows, mask)`` over this grid step's block: not
    at all above the diagonal; below it without a mask, a strip of the
    outer side's rows a trip of a rolled loop; and where the diagonal
    passes through it once a strip of :func:`_diagonal_strips` with the
    causal mask. ``q_rows`` and ``k_rows`` index the query-side and
    key-side blocks (static slices, or the loop's ``pl.ds``); ``mask`` is
    None or a function of the score tile's shape and of which of its axes
    is the query's, giving the tile's visibility."""
    whole_q, whole_k = slice(0, block_q), slice(0, block_k)
    rows = _strip_rows(block_q if q_outer else block_k)

    def unmasked_strips():
        def strip(i, _):
            at = pl.ds(pl.multiple_of(i * rows, rows), rows)
            step(*((at, whole_k) if q_outer else (whole_q, at)), None)

        jax.lax.fori_loop(0, (block_q if q_outer else block_k) // rows,
                          strip, None)

    if not causal:
        unmasked_strips()
        return
    unmasked = _unmasked(q_blk, k_blk, block_q, block_k, offset)
    visible = _visible(q_blk, k_blk, block_q, block_k, offset)

    def masked_strips():
        for q_rows, k_rows in _diagonal_strips(block_q, block_k, offset,
                                               q_outer):
            def mask(shape, q_axis: int):
                # q_pos ≥ k_pos, as one compare of an iota difference
                # (the same for every block) with a scalar
                gap = (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
                       - jax.lax.broadcasted_iota(jnp.int32, shape,
                                                  1 - q_axis))
                return gap >= (k_blk * block_k + k_rows.start
                               - q_blk * block_q - q_rows.start - offset)

            step(q_rows, k_rows, mask)

    pl.when(unmasked)(unmasked_strips)
    pl.when(jnp.logical_and(visible, jnp.logical_not(unmasked)))(
        masked_strips)


# ---------------------------------------------------------------------------
# Kernels. Refs carry a leading singleton: the folded batch·head block.
# No score tile is multiplied by 1/√d: the scores stay raw, q·kᵀ, and the
# scale rides the constant every exponential multiplies by anyway,
# exp(x/√d) = 2^(x · log₂e/√d). The running maximum m is kept in raw
# units; what leaves a kernel (lse, dQ, dK) is scaled once, in float32.
# ---------------------------------------------------------------------------

LOG2_E = float(np.log2(np.e))


def _scale(d: int) -> float:
    return 1.0 / float(np.sqrt(d))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, causal: bool, causal_offset: int):
    """One grid step: one (batch·head, q-block, k-block). q (1, block_q,
    d) stays while k/v (1, block_k, d) stream; m, l (block_q, LANE) and
    acc (block_q, d) are the online softmax's float32 state."""
    _, block_q, d = q_ref.shape
    block_k = k_ref.shape[1]
    q_blk, k_blk = pl.program_id(1), pl.program_id(2)
    to_log2 = _scale(d) * LOG2_E

    @pl.when(k_blk == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def step(q_rows, k_rows, mask):
        v = v_ref[0, k_rows]
        scores = jax.lax.dot_general(q_ref[0, q_rows], k_ref[0, k_rows], _NT,
                                     preferred_element_type=jnp.float32)
        if mask is not None:
            scores = jnp.where(mask(scores.shape, 0), scores, NEG_INF)
        m_prev = m_scr[q_rows]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        correction = jnp.exp2((m_prev - m_new) * to_log2)
        p = jnp.exp2((scores - _stat_cols(m_new, scores.shape[1])) * to_log2)
        m_scr[q_rows] = m_new
        l_scr[q_rows] = l_scr[q_rows] * correction + _lane_sums(p)
        acc_scr[q_rows] = (acc_scr[q_rows] * _stat_cols(correction, d)
                           + jax.lax.dot_general(
                               p.astype(v.dtype), v, _NN,
                               preferred_element_type=jnp.float32))

    _run_visible(step, causal, q_blk, k_blk, block_q, block_k, causal_offset)

    @pl.when(k_blk == pl.num_programs(2) - 1)
    def _():
        l = jnp.sum(l_scr[...], axis=1, keepdims=True)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        # Per-row log-sum-exp: the only softmax statistic the backward
        # needs, every lane alike and so, transposed, every sublane
        lse_ref[0] = (m_scr[...] * _scale(d) + jnp.log(l)).T[:SUBLANE]


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, lse_scr, delta_scr, *, causal: bool,
                         causal_offset: int):
    """dQ pass: one grid step per (batch·head, q-block, k-block), K/V
    streaming. P is recomputed from (Q, K, LSE) — the (S, S) matrix never
    exists. dS goes unscaled through the MXU; the 1/√d lands on the
    float32 accumulator once."""
    _, block_q, d = q_ref.shape
    block_k = k_ref.shape[1]
    q_blk, k_blk = pl.program_id(1), pl.program_id(2)
    to_log2 = _scale(d) * LOG2_E

    @pl.when(k_blk == 0)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        # The statistics' rows, once a query block, to lane-broadcast
        # columns: (SUBLANE, block_q) → (LANE, block_q) → (block_q, LANE);
        # lse in the exponent's base
        for rows, cols_scr in ((lse_ref[0] * LOG2_E, lse_scr),
                               (delta_ref[0], delta_scr)):
            cols_scr[...] = jnp.tile(rows, (LANE // SUBLANE, 1)).T

    def step(q_rows, k_rows, mask):
        k = k_ref[0, k_rows]
        scores = jax.lax.dot_general(q_ref[0, q_rows], k, _NT,
                                     preferred_element_type=jnp.float32)
        if mask is not None:
            scores = jnp.where(mask(scores.shape, 0), scores, NEG_INF)
        # masked entries underflow to 0
        p = jnp.exp2(scores * to_log2
                     - _stat_cols(lse_scr[q_rows], scores.shape[1]))
        dp = jax.lax.dot_general(do_ref[0, q_rows], v_ref[0, k_rows], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _stat_cols(delta_scr[q_rows], scores.shape[1]))
        dq_scr[q_rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _run_visible(step, causal, q_blk, k_blk, block_q, block_k, causal_offset)

    @pl.when(k_blk == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_scr[...] * _scale(d)).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                          causal_offset: int):
    """dK/dV pass: one grid step per (batch·head, k-block, q-block), Q/dO
    streaming from the first causally-visible block. The score tile is
    built transposed, (block_k, block_q) = K·Qᵀ, so that Pᵀ·dO and dSᵀ·Q
    are plain products and nothing is transposed on the way to the MXU;
    the statistics' rows broadcast down it as they are."""
    _, block_q, d = q_ref.shape
    block_k = k_ref.shape[1]
    k_blk, q_blk = pl.program_id(1), pl.program_id(2)
    to_log2 = _scale(d) * LOG2_E

    @pl.when(q_blk == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def step(q_rows, k_rows, mask):
        q, do = q_ref[0, q_rows], do_ref[0, q_rows]
        scores_t = jax.lax.dot_general(k_ref[0, k_rows], q, _NT,
                                       preferred_element_type=jnp.float32)
        if mask is not None:
            scores_t = jnp.where(mask(scores_t.shape, 1), scores_t, NEG_INF)
        p_t = jnp.exp2(scores_t * to_log2
                       - lse_ref[0, :1, q_rows] * LOG2_E)
        dv_scr[k_rows] += jax.lax.dot_general(
            p_t.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v_ref[0, k_rows], do, _NT,
                                   preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta_ref[0, :1, q_rows])
        dk_scr[k_rows] += jax.lax.dot_general(
            ds_t.astype(q.dtype), q, _NN,
            preferred_element_type=jnp.float32)

    _run_visible(step, causal, q_blk, k_blk, block_q, block_k, causal_offset,
                 q_outer=False)

    @pl.when(q_blk == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = (dk_scr[...] * _scale(d)).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# The plan: blocks by shape, and how often the skipping engages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """One kernel's tiling of one call. ``grid`` is (batch·heads, outer
    blocks, inner blocks); the counts are inner steps of the whole grid:
    ``visited`` run the body (``masked`` of them on the diagonal, with the
    causal mask), ``skipped`` neither run nor fetch. ``computed`` is the
    share of the (query, key) square whose scores are computed: a causal
    call needs just over half."""
    block_q: int
    block_k: int
    grid: tuple
    visited: int
    masked: int
    skipped: int
    computed: float


def _vmem_bytes(kernel: str, block_q: int, block_k: int, d: int,
                itemsize: int) -> int:
    """What a kernel's blocks take of VMEM: every operand and result
    block twice (Pallas double-buffers), the float32 scratch, and two
    float32 score tiles (the compiler streams a tile through its
    temporaries and keeps less than that)."""
    q_rows, k_rows = block_q * d, block_k * d
    stat_rows = SUBLANE * block_q * 4
    stat_cols = block_q * LANE * 4
    if kernel == "flash_fwd":
        blocks = (2 * q_rows + 2 * k_rows) * itemsize + stat_rows
        scratch = 2 * stat_cols + q_rows * 4
    elif kernel == "flash_bwd_dq":
        blocks = (3 * q_rows + 2 * k_rows) * itemsize + 2 * stat_rows
        scratch = 2 * stat_cols + q_rows * 4
    else:
        blocks = (2 * q_rows + 4 * k_rows) * itemsize + 2 * stat_rows
        scratch = 2 * k_rows * 4
    return 2 * blocks + scratch + 2 * block_q * block_k * 4


def _ladder(length: int):
    """The blocks a length can be cut into: the multiples of LANE up to
    MAX_BLOCK that divide it; a length under LANE (CPU tests) is its own
    block."""
    if length < LANE:
        return [length]
    return [b for b in range(LANE, MAX_BLOCK + 1, LANE) if length % b == 0]


def _pick_blocks(kernel: str, s_q: int, s_k: int, d: int, itemsize: int):
    """The largest blocks that divide the lengths and fit the budget: of
    two that fit, the larger score tile, then the longer key block."""
    fits = [(bq * bk, bk, bq) for bq in _ladder(s_q) for bk in _ladder(s_k)
            if _vmem_bytes(kernel, bq, bk, d, itemsize) <= _VMEM_PLAN_BYTES]
    if not fits:
        return None
    _, block_k, block_q = max(fits)
    return block_q, block_k


def _count_steps(kernel: str, b_h: int, s_q: int, s_k: int, block_q: int,
                 block_k: int, causal: bool) -> KernelPlan:
    n_q, n_k = s_q // block_q, s_k // block_k
    offset = s_k - s_q
    q_outer = kernel != "flash_bwd_dkv"
    visited = masked = 0
    for q_blk in range(n_q):
        for k_blk in range(n_k):
            if not causal or _visible(q_blk, k_blk, block_q, block_k, offset):
                visited += 1
                masked += causal and not _unmasked(q_blk, k_blk, block_q,
                                                   block_k, offset)
    strips = sum((q_rows.stop - q_rows.start) * (k_rows.stop - k_rows.start)
                 for q_rows, k_rows in _diagonal_strips(block_q, block_k,
                                                        offset, q_outer))
    scores = (visited - masked) * block_q * block_k + masked * strips
    grid = (b_h, n_q, n_k) if q_outer else (b_h, n_k, n_q)
    return KernelPlan(block_q, block_k, grid, b_h * visited, b_h * masked,
                      b_h * (n_q * n_k - visited), scores / (s_q * s_k))


def block_plan(q_shape, k_shape, causal: bool = True,
               block_q: int | None = None, block_k: int | None = None,
               dtype=jnp.bfloat16):
    """How :func:`flash_attention` tiles a call on (B, S, H, D) shapes on
    the current backend: kernel name → :class:`KernelPlan`, or None where
    the call runs the jnp reference. A pure function of its arguments
    and the backend; the kernels take their blocks from it."""
    b, s_q, h, d = q_shape
    s_k = k_shape[1]
    on_tpu = jax.default_backend() == "tpu"
    # On real TPU hardware, sub-tile shapes (short sequences / narrow
    # heads vs the 128-lane register tiling) stay on the reference path —
    # Mosaic lowering of tiny blocks is at best wasteful padding. CPU
    # interpret mode has no tiling, so tests exercise small shapes.
    if on_tpu and (s_q < LANE or s_k < LANE or d < 64):
        return None
    # The degenerate causal s_q > s_k case, where fully-masked query rows
    # need the reference's uniform-softmax treatment rather than a 0/0
    # accumulator, uses the reference path
    if causal and s_q > s_k:
        return None
    plan = {}
    for kernel in KERNELS:
        if block_q is None and block_k is None:
            blocks = _pick_blocks(kernel, s_q, s_k, d,
                                  jnp.dtype(dtype).itemsize)
        else:
            forced_q = min(block_q or LANE, s_q)
            forced_k = min(block_k or LANE, s_k)
            # Ragged lengths take the reference, and on the chip so do
            # blocks that are not whole lane tiles (both are a score
            # tile's lane axis in one of the passes)
            ragged = s_q % forced_q or s_k % forced_k
            untileable = on_tpu and (forced_q % LANE or forced_k % LANE)
            blocks = None if ragged or untileable else (forced_q, forced_k)
        if blocks is None:
            return None
        plan[kernel] = _count_steps(kernel, b * h, s_q, s_k, *blocks, causal)
    return plan


def uses_kernel(q_shape, k_shape, causal: bool = True,
                block_q: int | None = None,
                block_k: int | None = None) -> bool:
    """True when :func:`flash_attention` on (B, S, H, D) shapes runs the
    Pallas kernels on the current backend, False when it runs the jnp
    reference."""
    return block_plan(q_shape, k_shape, causal, block_q, block_k) is not None


def _fold_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _block_specs(block_q: int, block_k: int, d: int, causal: bool,
                 offset: int, q_outer: bool):
    """BlockSpecs of a (rows, d) query-side block, a key-side block and
    the query side's statistics' rows, for a grid of
    (batch·head, q-block, k-block) or, for the dK/dV pass, (batch·head,
    k-block, q-block). The inner side's index is clamped to the blocks
    the outer one can see, so a skipped step names the block already
    resident and nothing is fetched for it."""
    if q_outer:
        def q_at(bh, qi, ki):
            return qi

        def k_at(bh, qi, ki):
            if causal:
                ki = jnp.minimum(
                    ki, _last_visible_k(qi, block_q, block_k, offset))
            return ki
    else:
        def q_at(bh, ki, qi):
            if causal:
                qi = jnp.maximum(
                    qi, _first_visible_q(ki, block_q, block_k, offset))
            return qi

        def k_at(bh, ki, qi):
            return ki

    return {
        "q": pl.BlockSpec((1, block_q, d),
                          lambda *g: (g[0], q_at(*g), 0)),
        "k": pl.BlockSpec((1, block_k, d),
                          lambda *g: (g[0], k_at(*g), 0)),
        "q_stat": pl.BlockSpec((1, SUBLANE, block_q),
                               lambda *g: (g[0], 0, q_at(*g))),
    }


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None):
    """Causal attention, (B, S, H, D) → (B, S, H, D). Blocks are sized by
    the shape (:func:`block_plan`) unless given."""
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k)
    return out


def _flash_forward(q, k, v, causal, block_q, block_k):
    """Returns (out, lse) — lse is None on the reference fallback path,
    (B·H, SUBLANE, S_q) fp32 rows otherwise (slice ``[:, 0]`` for the
    per-row value; kept 3-D so the backward can feed it straight back
    into the kernels)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    plan = block_plan(q.shape, k.shape, causal, block_q, block_k, q.dtype)
    if plan is None:
        return attend(q, k, v, _mask(q, k, causal)), None
    fwd = plan["flash_fwd"]

    # Fold (B, H) into the grid's first axis; kernel sees 2-D tiles
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)

    spec = _block_specs(fwd.block_q, fwd.block_k, d, causal, s_k - s_q,
                        q_outer=True)
    kernel = functools.partial(_flash_kernel, causal=causal,
                               causal_offset=s_k - s_q)
    out, lse = pl.pallas_call(
        kernel,
        grid=fwd.grid,
        in_specs=[spec["q"], spec["k"], spec["k"]],
        out_specs=[spec["q"], spec["q_stat"]],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, SUBLANE, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((fwd.block_q, LANE), jnp.float32),
            pltpu.VMEM((fwd.block_q, LANE), jnp.float32),
            pltpu.VMEM((fwd.block_q, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=jax.default_backend() == "cpu",
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, s_q, d).transpose(0, 2, 1, 3), lse


def _flash_fwd(q, k, v, causal, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k)
    # Named where they are made, so that a ``jax.checkpoint`` policy can
    # keep them (models/transformer.py:KEPT): the backward kernels read
    # these two, and a checkpoint that lost them would run flash_fwd again
    out = checkpoint_name(out, "attn_out")
    if lse is not None:
        lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _run_bwd_kernels(q, k, v, g_out, out, lse, causal, block_q, block_k,
                     g_lse=None):
    """Launch the two-pass backward kernels. ``lse`` is the forward
    kernel's (B·H, SUBLANE, S_q) statistic, fed back verbatim. ``g_lse``
    (the lse output's cotangent, when the caller exposed lse) folds into
    the row correction: ds = p·(dp − (Δ − g_lse)), since ∂lse/∂s = p."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    plan = block_plan(q.shape, k.shape, causal, block_q, block_k, q.dtype)
    dq_plan, dkv_plan = plan["flash_bwd_dq"], plan["flash_bwd_dkv"]

    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    dof, of = _fold_heads(g_out), _fold_heads(out)
    # delta_i = Σ_d dO·O — the softmax-jacobian row correction, O(S·D)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[:, None, :], lse.shape)

    interpret = jax.default_backend() == "cpu"
    offset = s_k - s_q

    spec = _block_specs(dq_plan.block_q, dq_plan.block_k, d, causal, offset,
                        q_outer=True)
    dqf = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal,
                          causal_offset=offset),
        grid=dq_plan.grid,
        in_specs=[spec["q"], spec["k"], spec["k"], spec["q"],
                  spec["q_stat"], spec["q_stat"]],
        out_specs=spec["q"],
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((dq_plan.block_q, d), jnp.float32),
                        pltpu.VMEM((dq_plan.block_q, LANE), jnp.float32),
                        pltpu.VMEM((dq_plan.block_q, LANE), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lse, delta)

    spec = _block_specs(dkv_plan.block_q, dkv_plan.block_k, d, causal,
                        offset, q_outer=False)
    dkf, dvf = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                          causal_offset=offset),
        grid=dkv_plan.grid,
        in_specs=[spec["q"], spec["k"], spec["k"], spec["q"],
                  spec["q_stat"], spec["q_stat"]],
        out_specs=[spec["k"], spec["k"]],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((dkv_plan.block_k, d), jnp.float32),
                        pltpu.VMEM((dkv_plan.block_k, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lse, delta)

    def unfold(x, s):
        return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return unfold(dqf, s_q), unfold(dkf, s_k), unfold(dvf, s_k)


def _flash_bwd(causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if lse is None:
        # Forward fell back to reference numerics; match them in reverse
        _, vjp = jax.vjp(
            lambda q, k, v: attend(q, k, v, _mask(q, k, causal)), q, k, v)
        return vjp(g)
    return _run_bwd_kernels(q, k, v, g, out, lse, causal, block_q, block_k)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# (out, lse) variant — the building block for flash-decoding-style block
# merging: partial attentions over key blocks combine exactly via
#   lse = logaddexp(lse_a, lse_b)
#   out = out_a·exp(lse_a − lse) + out_b·exp(lse_b − lse)
# ---------------------------------------------------------------------------

def _reference_lse(q, k, causal: bool):
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        logits = jnp.where(_mask(q, k, causal), logits, NEG_INF)
    # (B, H, S_q) → fold to the kernel's (B·H, S_q) layout
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    b, h, s_q = lse.shape
    return lse.reshape(b * h, s_q)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal: bool = True,
                             block_q: int | None = None,
                             block_k: int | None = None):
    """Attention plus the per-row log-sum-exp: (out (B,S,H,D),
    lse (B·H, S) fp32). Differentiable in BOTH outputs — the lse
    cotangent folds into the existing backward kernels as a delta
    adjustment (ds = p·(dp − (Δ − g_lse)), since ∂lse/∂s = p)."""
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k)
    if lse is None:  # reference fallback path
        return out, _reference_lse(q, k, causal)
    return out, lse[:, 0]


def _flash_lse_fwd(q, k, v, causal, block_q, block_k):
    out, kernel_lse = _flash_forward(q, k, v, causal, block_q, block_k)
    lse = (kernel_lse[:, 0] if kernel_lse is not None
           else _reference_lse(q, k, causal))
    return (out, lse), (q, k, v, out, kernel_lse)


def _flash_lse_bwd(causal, block_q, block_k, res, cotangents):
    g_out, g_lse = cotangents
    q, k, v, out, lse = res
    if lse is None:
        # Reference numerics in reverse for the fallback path
        def ref(q, k, v):
            return (attend(q, k, v, _mask(q, k, causal)),
                    _reference_lse(q, k, causal))

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp((g_out, g_lse))
    return _run_bwd_kernels(q, k, v, g_out, out, lse, causal,
                            block_q, block_k, g_lse=g_lse)


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def merge_attention_blocks(outs, lses):
    """Combine partial attentions over disjoint key blocks (each an
    (out, lse) pair from flash_attention_with_lse) into the attention
    over their union — the flash-decoding merge."""
    lse_total = lses[0]
    for l in lses[1:]:
        lse_total = jnp.logaddexp(lse_total, l)
    b_h, s_q = lse_total.shape
    out = None
    for o, l in zip(outs, lses):
        # lse layout (B·H, S) → broadcast over (B, S, H, D)
        w = jnp.exp(l - lse_total)
        b = o.shape[0]
        h = b_h // b
        w = w.reshape(b, h, s_q).transpose(0, 2, 1)[..., None]
        term = o.astype(jnp.float32) * w
        out = term if out is None else out + term
    return out.astype(outs[0].dtype), lse_total
