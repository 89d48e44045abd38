"""Rows added into an accumulator at rows that a list names, as one Pallas
TPU kernel.

What a prefill chunk's held experts run after each row tile of their
grouped product (models/moe.py: ``_held_experts`` where a call has more
than one position a row): the tile's weighted output rows, one expert's
picks, are added into a float32 accumulator of the call's tokens at those
picks' tokens. XLA's scatter-add does it a row at a time (218 µs a tile of
128 rows × 7,168 lanes, `tools/probe_expert_combine.py`, PERF.md section
6); here every row of the accumulator that the tile names is copied into
VMEM at once, one DMA a row all in flight, added to as one block, and
copied back in the same way: the accumulator is read and written at the
tile's rows alone, and nothing else of it moves.

Mosaic copies no single row of a tiled (T, D) array (a slice along the
tiled sublanes must be whole tiles), so the accumulator lies as (T, L,
128), a row's D lanes in L lane tiles (the last padded with zeros where
128 does not divide D): a token's row is then whole tiles, an index along
the untiled first axis. :func:`accumulator`, :func:`as_rows` and
:func:`as_array` lay it so and back.

A tile's real rows come first and name distinct tokens (one expert's
picks); the rows past them are padding and name T, past the last: they
are neither copied nor added, so the same token named by a padding row
and by a real one is added to once.

On CPU (tests) the kernel runs in interpreter mode automatically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# Mosaic's own temporaries beside the tile's rows and their float32 copy
_VMEM_HEADROOM = 4 * 1024 * 1024


def accumulator(tokens: int, d: int) -> jax.Array:
    """Zeros for ``tokens`` rows of ``d`` lanes: (tokens, L, 128) float32,
    L = ceil(d / 128)."""
    return jnp.zeros((tokens, -(-d // LANE), LANE), jnp.float32)


def as_rows(x: jax.Array) -> jax.Array:
    """(n, d) → (n, L, 128) as the accumulator lies, zeros past ``d``."""
    n, d = x.shape
    pad = -d % LANE
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(n, -1, LANE)


def as_array(acc: jax.Array, d: int) -> jax.Array:
    """The accumulator (T, L, 128) → (T, d)."""
    flat = acc.reshape(acc.shape[0], -1)
    return flat if flat.shape[1] == d else flat[:, :d]


def _add_rows_kernel(to_ref, n_ref, rows_ref, acc_ref, out_ref, buf, sems):
    def row_in(r):
        return pltpu.make_async_copy(acc_ref.at[to_ref[r]], buf.at[r],
                                     sems.at[0])

    def row_out(r):
        return pltpu.make_async_copy(buf.at[r], out_ref.at[to_ref[r]],
                                     sems.at[1])

    def each(fn):
        jax.lax.fori_loop(0, n_ref[0], lambda r, c: (fn(r), c)[1], 0)

    each(lambda r: row_in(r).start())
    each(lambda r: row_in(r).wait())
    buf[...] += rows_ref[...].astype(jnp.float32)
    each(lambda r: row_out(r).start())
    each(lambda r: row_out(r).wait())


# jitted so that a program's calls of one shape share one trace and one
# lowering of the kernel's body (PERF.md section 6, PR 40)
@jax.jit
def add_rows(acc, to, rows):
    """acc (T, L, lanes) float32 with ``rows`` (tile, L, lanes) added, as
    float32, at its rows ``to`` (tile,) int32: the real rows first, each
    naming another token, then padding rows that name T or more and add
    nothing. → the accumulator, in ``acc``'s place. The tile's rows and
    their float32 copy lie in VMEM whole: the kernel asks for their bytes
    (16.5 MB at a tile of 384 rows × 7,168 lanes)."""
    t, length, width = acc.shape
    tile = to.shape[0]
    row_bytes = length * width * 4
    return pl.pallas_call(
        _add_rows_kernel,
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tile, length, width), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tile * row_bytes + rows.nbytes
            + _VMEM_HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=tile * length * width, transcendentals=0,
            bytes_accessed=2 * tile * row_bytes + rows.nbytes),
        interpret=jax.default_backend() == "cpu",
        name="add_rows",
    )(to, jnp.sum(to < t, dtype=jnp.int32)[None], rows, acc)
