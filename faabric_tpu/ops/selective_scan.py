"""Mamba-1's recurrence over a chunk of positions as one Pallas TPU kernel.

What a prefill chunk runs in every "mamba1" layer (models/ssm.py:
``mixer1``): at each position p of each row, every lane e keeps a state of
N numbers, ``S[n, e] = exp(dt[p, e] · A[n, e]) · S[n, e] + dt[p, e] ·
x[p, e] · B[p, n]``, and gives ``y[p, e] = Σ_n S[n, e] · C[p, n] + D[e] ·
x[p, e]``. A ``lax.scan`` along the positions keeps S in HBM and reads and
writes all of it at every position; here S stays on the chip. One kernel,
``selective_scan``, over a grid of tiles of rows, blocks of positions and
tiles of lanes: a row's S, ``(N, E)`` float32, lives in VMEM scratch from
the chunk's first block of positions to its last, is initialised from
``state`` at the first and is what the state's output holds after the
last; inside a step a loop over the block's positions carries a tile's S,
``(N, lanes)``, in registers. ``x``, ``dt`` and ``y`` are read and written
in the ``(B, L, E)`` layout they have, so only they and the chunk's first
and last S touch HBM.

Every lane of every row is its own recurrence, so nothing crosses a tile.
The rows are a parallel axis; the positions are sequential, and so are
the tiles of lanes inside a block of positions, because they share one
thing: a position's ``B`` and ``C`` are N numbers that every lane
multiplies by, sublane vectors spread along the lanes. That spreading is
done once a row a block of positions, at the first tile of lanes, into
VMEM scratch ``(positions, N, 128)``, and the tiles of lanes that follow
read it back.

The arithmetic and its rounding points are ``models/ssm.py:_step1``'s and
``mixer1``'s: ``dt · x``, the decay ``exp(dt · A)``, S, the push, the
read-out and ``y + D · x`` in float32, one cast of ``y`` to the inputs'
type at the write. ``A`` is data: each state index has its own
exponential.

:func:`plan` gives the tile of rows and lanes, the block of positions, the
grid, the VMEM the call asks for and the bytes it streams, from the
call's shape alone; the kernel takes its blocks from it. It is None where
the kernel does not apply (lanes that 128 does not divide, a state size
that 8 does not divide, a single position), and the caller keeps its own
lines. A length the block of positions does not divide is served: the
last block's loop stops at the last position there is.

On CPU (tests) the kernel runs in interpreter mode automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE, SUBLANE = 128, 8
# The most a grid step takes of each: rows (a loop inside the step, so a
# step's fixed cost is shared), lanes (a tile's S, N × lanes float32, is
# carried in registers along the positions: 16 × 512 are 16 of the 64) and
# positions (their ``B`` and ``C`` spread along the lanes are 16 KB a row a
# position of scratch at a state of 16). On the chip, 64 rows × 256 × 5120
# with a state of 16 take 3.3 to 3.7 ms from 256 to 1280 lanes, 2 to 8
# rows and 32 to 256 positions a step (PERF.md section 6, PR 40): the
# vector unit's work on S decides, not the tile.
MAX_ROWS, MAX_LANES, BLOCK = 8, 512, 128
# Mosaic's own temporaries beside what :func:`plan` counts
_VMEM_HEADROOM = 4 * 1024 * 1024


def _largest_divisor(n: int, most: int) -> int:
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


def plan(rows: int, positions: int, inner: int, d_state: int,
         dtype=jnp.bfloat16, tile: tuple | None = None):
    """How :func:`selective_scan` runs a call of these shapes, or None
    where it does not: ``rows`` and ``lanes`` (the tile a grid step
    takes), ``positions`` (its block of them), ``grid`` (tiles of rows,
    blocks of positions, tiles of lanes), ``vmem_bytes`` (what the call
    asks for: a step's ``x``, ``dt``, ``y``, ``B`` and ``C`` (a position's
    N padded to a lane tile), ``A``, ``D`` and the state in and out
    twice, as Pallas double-buffers every block;
    the rows' whole S, their ``B`` and ``C`` spread along the lanes, and
    one row's ``dt · x`` and ``y`` in float32) and ``streamed_bytes``
    (``x`` and ``dt`` in, ``y`` out, the state in and out once). A pure
    function of its arguments. ``tile`` = (rows, lanes, positions)
    overrides the choice (sweeps and tests)."""
    if positions < 2 or inner % LANE or d_state % SUBLANE:
        return None
    if tile is None:
        tile = (_largest_divisor(rows, MAX_ROWS),
                LANE * _largest_divisor(inner // LANE, MAX_LANES // LANE),
                positions if positions <= BLOCK else BLOCK)
    r, lanes, block = tile
    if (rows % r or inner % lanes or lanes % LANE
            or (block != positions and block % (2 * SUBLANE))):
        return None
    item = jnp.dtype(dtype).itemsize
    blocks = (r * block * lanes * (2 * item + 4)       # x, y; dt
              + 2 * r * block * LANE * item            # B, C, padded
              + (d_state + 1) * lanes * 4              # A, D
              + r * d_state * lanes * (item + 4))      # the state in, out
    scratch = (r * d_state * inner * 4                 # S
               + 2 * r * block * d_state * LANE * 4    # B, C spread
               + 2 * block * lanes * 4)                # dt · x, y
    return {"rows": r, "lanes": lanes, "positions": block,
            "grid": (rows // r, -(-positions // block), inner // lanes),
            "vmem_bytes": 2 * blocks + scratch,
            "streamed_bytes": rows * (positions * inner * (2 * item + 4)
                                      + d_state * inner * (item + 4))}


def _selective_scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s_ref,
                           y_ref, out_ref, state_scr, b_scr, c_scr, dtx_scr,
                           y_scr, *, length: int):
    """One grid step: a tile of rows, a block of positions, a tile of
    lanes. x, dt, y (rows, positions, lanes); b, c (rows, positions, N);
    a (N, lanes); d (1, lanes); s and out (rows, N, lanes), the state
    before the first position and after the last one so far. state_scr
    (rows, tiles of lanes, N, lanes) is S; b_scr and c_scr (rows,
    positions, N, 128) hold a position's ``B`` and ``C`` along the lanes."""
    at, tile = pl.program_id(1), pl.program_id(2)
    rows, block, lanes = x_ref.shape
    n = a_ref.shape[0]
    f32 = jnp.float32
    spans = [slice(g * LANE, (g + 1) * LANE) for g in range(lanes // LANE)]
    a = [a_ref[:, span] for span in spans]
    # positions go a sublane tile at a time: a dynamic index into a tile
    # is nothing Mosaic loads or stores
    whole, rest = divmod(block, SUBLANE)
    ragged = length % block != 0
    if ragged:
        # a short last block: its loop stops at the tile that holds the
        # last position, and S stays as it is behind that one
        last = length - at * block
        whole = jnp.minimum(whole, pl.cdiv(last, SUBLANE))

    def one_row(r, _):
        @pl.when(at == 0)
        def _():
            state_scr[r, tile] = s_ref[r].astype(f32)

        @pl.when(tile == 0)
        def _():
            for ref, scr in ((b_ref, b_scr), (c_ref, c_scr)):
                scr[r] = jnp.broadcast_to(ref[r].astype(f32)[:, :, None],
                                          (block, n, LANE))

        dtx_scr[...] = dt_ref[r] * x_ref[r].astype(f32)

        def some(first, count, state):
            """``count`` positions from ``first``, which a sublane tile
            starts at."""
            here = pl.ds(first, count)
            dts, dtxs = dt_ref[r, here, :], dtx_scr[here, :]
            place = jax.lax.broadcasted_iota(jnp.int32, (count, LANE), 0)
            ys = [jnp.zeros((count, LANE), f32) for _ in spans]
            for p in range(count):
                pushed_by, read_by = b_scr[r, first + p], c_scr[r, first + p]
                new = []
                for g, (span, a_g, s_g) in enumerate(zip(spans, a, state)):
                    dt = dts[p:p + 1, span]                     # (1, 128)
                    moved = jnp.exp(dt * a_g) * s_g \
                        + dtxs[p:p + 1, span] * pushed_by
                    if ragged:
                        moved = jnp.where(first + p < last, moved, s_g)
                    ys[g] = jnp.where(
                        place == p, jnp.sum(moved * read_by, axis=0,
                                            keepdims=True), ys[g])
                    new.append(moved)
                state = tuple(new)
            for span, y in zip(spans, ys):
                y_scr[here, span] = y
            return state

        state = tuple(state_scr[r, tile, :, span] for span in spans)
        if block >= SUBLANE:
            state = jax.lax.fori_loop(
                0, whole,
                lambda i, state: some(pl.multiple_of(i * SUBLANE, SUBLANE),
                                      SUBLANE, state), state)
        if rest:
            state = some(block - rest, rest, state)
        for span, s_g in zip(spans, state):
            state_scr[r, tile, :, span] = s_g
            out_ref[r, :, span] = s_g
        y_ref[r] = (y_scr[...] + d_ref[...] * x_ref[r].astype(f32)
                    ).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, rows, one_row, 0)


# jitted so that a program's calls of one shape share one trace and one
# lowering of the kernel's body: traced a call, the cell's 18 were 6.6 s
# of every set-up, a warm compile cache or not (PERF.md section 6, PR 40)
@functools.partial(jax.jit, static_argnames=("tile",))
def selective_scan(x, dt, b, c, a, d, state, tile: tuple | None = None):
    """x (B, L, E) in the compute type, dt (B, L, E) float32, b and c
    (B, L, N), a (N, E) float32, d (E,), state (B, N, E) → (y (B, L, E)
    of x's type, ``D · x`` added; the state after the last position,
    float32). The caller asks :func:`plan` first: a shape it refuses is an
    error here."""
    rows, length, inner = x.shape
    n = a.shape[0]
    how = plan(rows, length, inner, n, x.dtype, tile)
    if how is None:
        raise ValueError(f"selective_scan does not take {rows} rows of "
                         f"{length} positions × {inner} lanes with a state "
                         f"of {n} (tile {tile})")
    r, lanes, block = how["rows"], how["lanes"], how["positions"]
    f32 = jnp.float32
    y, state = pl.pallas_call(
        functools.partial(_selective_scan_kernel, length=length),
        grid=how["grid"],
        in_specs=[
            pl.BlockSpec((r, block, lanes), lambda i, k, j: (i, k, j)),
            pl.BlockSpec((r, block, lanes), lambda i, k, j: (i, k, j)),
            pl.BlockSpec((r, block, n), lambda i, k, j: (i, k, 0)),
            pl.BlockSpec((r, block, n), lambda i, k, j: (i, k, 0)),
            pl.BlockSpec((n, lanes), lambda i, k, j: (0, j)),
            pl.BlockSpec((1, lanes), lambda i, k, j: (0, j)),
            pl.BlockSpec((r, n, lanes), lambda i, k, j: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((r, block, lanes), lambda i, k, j: (i, k, j)),
            pl.BlockSpec((r, n, lanes), lambda i, k, j: (i, 0, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        scratch_shapes=[
            pltpu.VMEM((r, inner // lanes, n, lanes), f32),
            pltpu.VMEM((r, block, n, LANE), f32),
            pltpu.VMEM((r, block, n, LANE), f32),
            pltpu.VMEM((block, lanes), f32),
            pltpu.VMEM((block, lanes), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=how["vmem_bytes"] + _VMEM_HEADROOM),
        cost_estimate=pl.CostEstimate(
            flops=7 * rows * length * n * inner,
            transcendentals=rows * length * n * inner,
            bytes_accessed=how["streamed_bytes"]),
        interpret=jax.default_backend() == "cpu",
        name="selective_scan",
    )(x, dt, b, c, a, d.astype(f32).reshape(1, inner), state)
    return y, state
