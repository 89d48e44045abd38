"""The gated feed-forward of a few rows as one Pallas TPU kernel.

``out[rows, d_model] = (silu(h · wg) * (h · w1)) · w2``: what a cached
decode step at a small batch runs in every layer, three matrices read
once for a handful of rows, so the bytes of the matrices are all it costs
and the only question is how fast they arrive. One kernel, ``gated_ffn``,
over a sequential grid of tiles of ``d_ff``: a step brings ``wg[:, tile]``,
``w1[:, tile]`` and ``w2[tile, :]`` by the pipeline's double-buffered DMA
while the step before computes, ``h`` stays in VMEM, a float32
``(rows, d_model)`` accumulator lives in VMEM scratch and the output is
written at the last step. The ``(rows, d_ff)`` activation never exists in
HBM, and a layer pays one pipeline fill where three fused products pay
one each. The matrices are ordinary operands: where XLA finds room it
copies one into VMEM ahead of the call (its sliced prefetch), and the
pipeline then reads that copy.

Products are in the inputs' type with float32 accumulation, gate and up
stay float32 through the silu and their product, and the activation is
rounded to the inputs' type before the down product: the rounding points
of ``models/transformer.py:_feed_forward``'s ``swiglu`` lines as XLA
fuses them.

The tile comes from the call's shape alone: :func:`plan` picks the widest
whole number of lane tiles that divides ``d_ff`` and keeps a step's three
tiles within ``STEP_BYTES``, and gives the steps, the VMEM the call asks
for and the bytes it streams; the kernel takes its blocks from it.
:func:`plan` is None where the kernel does not apply (rows outside
``MIN_ROWS`` … ``MAX_ROWS``, widths the lanes do not divide on the chip),
and the caller keeps its own lines.

On CPU (tests) the kernel runs in interpreter mode automatically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# Below MIN_ROWS the products are matrix-vector work that XLA's own fusions
# stream at 91% of the HBM roofline (PERF.md section 5, the batch-1 cells);
# MAX_ROWS is one pass of the MXU's rows: above it the products stop being
# bound by the matrices' bytes alone.
MIN_ROWS, MAX_ROWS = 8, 128
# What one grid step's three tiles may hold; the call keeps two steps'
# tiles in VMEM. Alone on the chip the tile hardly matters (128 to 1024
# columns of 2048 × 8192 stream at 749 to 727 GB/s, XLA's own lines at
# 755): what it decides is how much of the 128 MiB of VMEM the call
# takes from XLA for its length, and so what XLA does with the rest
# around it. In a step that also carries a recurrent state of 67 MB a
# layer, 4 MiB a step (256 columns) left XLA streaming the states through
# VMEM and the step as long as without the kernel; from 8 MiB on it stops,
# prefetches the feed-forwards' matrices while the state's update computes,
# and the step is a tenth shorter; 24 MiB was the shortest (PERF.md
# section 6, PR 34, has the sweep).
STEP_BYTES = 24 * 1024 * 1024
# Mosaic's own temporaries beside what :func:`plan` counts (the products'
# operands in the MXU's layout, the silu's intermediates)
_VMEM_HEADROOM = 4 * 1024 * 1024


def _tiles(d_ff: int):
    """The tiles ``d_ff`` can be cut into: the multiples of LANE that divide
    it; a width under LANE (CPU tests) is its own tile."""
    if d_ff < LANE:
        return [d_ff]
    return [t for t in range(LANE, d_ff + 1, LANE) if d_ff % t == 0]


def plan(rows: int, d_model: int, d_ff: int, dtype=jnp.bfloat16,
         tile: int | None = None):
    """How :func:`gated_ffn` runs a call of these shapes on the current
    backend, or None where it does not: ``tile`` (columns of ``d_ff`` a
    grid step), ``steps``, ``vmem_bytes`` (what the call asks for: ``h``,
    the three tiles and the output twice, as Pallas double-buffers every
    block, the float32 accumulator, and the float32 gate, up and
    activation of one tile) and ``streamed_bytes`` (the three matrices
    once). A pure function of its arguments and the backend. ``tile``
    overrides the choice (sweeps and tests)."""
    if not MIN_ROWS <= rows <= MAX_ROWS:
        return None
    if jax.default_backend() == "tpu" and (d_model % LANE or d_ff % LANE):
        return None
    item = jnp.dtype(dtype).itemsize
    if tile is None:
        fits = [t for t in _tiles(d_ff)
                if 3 * d_model * t * item <= STEP_BYTES]
        tile = max(fits) if fits else _tiles(d_ff)[0]
    elif d_ff % tile:
        return None
    blocks = (2 * rows * d_model + 3 * d_model * tile) * item
    scratch = rows * d_model * 4
    temporaries = 3 * rows * tile * 4
    return {"tile": tile, "steps": d_ff // tile,
            "vmem_bytes": 2 * blocks + scratch + temporaries,
            "streamed_bytes": 3 * d_model * d_ff * item}


def _gated_ffn_kernel(h_ref, wg_ref, w1_ref, w2_ref, o_ref, acc_scr):
    """One grid step: one tile of ``d_ff``. h (rows, d_model) stays while
    wg, w1 (d_model, tile) and w2 (tile, d_model) stream; acc is the down
    product's float32 sum over the tiles."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    h = h_ref[...]
    gate = jnp.dot(h, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(h, w1_ref[...], preferred_element_type=jnp.float32)
    act = (jax.nn.silu(gate) * up).astype(w2_ref.dtype)
    acc_scr[...] += jnp.dot(act, w2_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def gated_ffn(h, wg, w1, w2, tile: int | None = None):
    """h (rows, d_model), wg and w1 (d_model, d_ff), w2 (d_ff, d_model),
    all of one type → (rows, d_model) of that type. The caller asks
    :func:`plan` first: a shape it refuses is an error here."""
    rows, d_model = h.shape
    d_ff = wg.shape[1]
    how = plan(rows, d_model, d_ff, h.dtype, tile)
    if how is None:
        raise ValueError(f"gated_ffn does not take {rows} rows of "
                         f"{d_model} × {d_ff} (tile {tile})")
    tile = how["tile"]
    return pl.pallas_call(
        _gated_ffn_kernel,
        grid=(how["steps"],),
        in_specs=[
            pl.BlockSpec((rows, d_model), lambda j: (0, 0)),
            pl.BlockSpec((d_model, tile), lambda j: (0, j)),
            pl.BlockSpec((d_model, tile), lambda j: (0, j)),
            pl.BlockSpec((tile, d_model), lambda j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((rows, d_model), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d_model), h.dtype),
        scratch_shapes=[pltpu.VMEM((rows, d_model), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=how["vmem_bytes"] + _VMEM_HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=6 * rows * d_model * d_ff,
            transcendentals=rows * d_ff,
            bytes_accessed=how["streamed_bytes"]
            + 2 * rows * d_model * jnp.dtype(h.dtype).itemsize),
        interpret=jax.default_backend() == "cpu",
        name="gated_ffn",
    )(h, wg, w1, w2)
