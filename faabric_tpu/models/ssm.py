"""A state-space mixer (Mamba-2, Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060): the sub-layer a "mamba" layer has where an "attention"
layer has attention, behind the same norm and residual
(``transformer._block``).

On a normed state ``h`` (B, S, D), with H heads of P lanes, G groups and a
state of N a lane (``cfg.ssm_heads``, ``ssm_head_dim``, ``ssm_groups``,
``ssm_d_state``):

    [z (H·P), xBC (H·P + 2·G·N), dt (H)] = h · ssm_in         (no bias)
    xBC = silu(conv(xBC))       depthwise, causal, ``ssm_d_conv`` taps, bias
    [x (H, P), B (G, N), C (G, N)] = xBC       a group's heads share B and C
    dt = softplus(dt + dt_bias);  A = −exp(A_log)                  (a head)
    S_t = exp(dt_t·A)·S_{t−1} + dt_t·x_t ⊗ B_t             S a head: (P, N)
    y_t = S_t·C_t + D·x_t
    out = RMSNorm(y · silu(z); ssm_norm) · ssm_out    the gate before the norm

A "mamba1" layer has the mixer of Mamba-1 (Gu and Dao, arXiv:2312.00752),
:func:`mixer1`, over the same convolution: E = ``cfg.ssm_inner`` lanes,
each with a state of N = ``cfg.ssm_d_state``, dt a lane through a
bottleneck of R = ``cfg.ssm_dt_rank``:

    [x (E), z (E)] = h · ssm_in;  x = silu(conv(x))
    [δ (R), B (N), C (N)] = x · ssm_x;  dt = softplus(δ · ssm_dt + dt_bias)
    A = −exp(A_log)  (E, N);  S_t = exp(dt_t ⊗ A) ⊙ S_{t−1} + (dt_t ⊙ x_t) ⊗ B_t
    y_t = S_t · C_t + D ⊙ x_t;  out = (y ⊙ silu(z)) · ssm_out

The decay differs by lane and state index, so there is no matrix form over
a chunk: a cached step is the recurrence, a longer input the same step
along its positions from the state the call before left; nothing of (B,
positions, E, N) is ever made. Where the call is a cached one on one chip
(a prefill chunk) and :func:`streams_scan` finds its shape, the positions
go through one kernel that keeps S, (B, N, E) float32, in VMEM from the
first to the last (ops/selective_scan.py); elsewhere they are a
``lax.scan`` whose carry S is. ``y`` before the gate is the layer's
memory, which "gated_memory"
layers (:func:`gated_memory`: ``(silu(h · gmu_in) ⊙ y) · gmu_out``) read at
the same position and keep nothing of.

Two forms over one set of weights. A cached step (S = 1) is the recurrence
itself. Longer inputs go in chunks of ``cfg.ssm_chunk`` positions: inside
a chunk the outputs are a masked product of C·Bᵀ with the decays between
the two positions (from the cumulative sums of dt·A), between chunks the
state is carried; the last chunk need not be full and the first starts
from whatever state it is given. The chunks of one call are unrolled: a
prefill holds no loop.

The state of a call (``cache``): ``conv``, the last ``ssm_d_conv − 1``
inputs of the convolution (B, d_conv − 1, H·P + 2·G·N), and ``state``,
S (B, H, P, N): their size does not depend on the call's reach. Both forms
leave the same two. Matrix products run in the compute type; dt, the
decays, their cumulative sums, the update of S and the norm's statistic
are float32; S and the window are stored in the compute type.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from faabric_tpu.models.transformer import _rms_norm

# the leaves a "mamba" layer holds beside its norms and feed-forward
MIXER_LEAVES = ("ssm_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "ssm_norm", "ssm_out")
# those of a "mamba1" layer, and of a "gated_memory" layer
MIXER1_LEAVES = ("ssm_in", "conv_w", "conv_b", "ssm_x", "ssm_dt", "dt_bias",
                 "A_log", "D", "ssm_out")
GATED_MEMORY_LEAVES = ("gmu_in", "gmu_out")


def widths(cfg) -> tuple:
    """(inner = H·P, B's and C's width G·N, the convolution's channels)."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = cfg.ssm_groups * cfg.ssm_d_state
    return inner, bc, inner + 2 * bc


def state_shapes(cfg, batch: int, kind: str = "mamba") -> dict:
    """The arrays one mixer of ``kind`` keeps through a call."""
    if kind == "mamba1":
        # S with the lanes last: a state index is a sublane, never a
        # 16th of a padded tile
        return {"conv": (batch, cfg.ssm_d_conv - 1, cfg.ssm_inner),
                "state": (batch, cfg.ssm_d_state, cfg.ssm_inner)}
    return {"conv": (batch, cfg.ssm_d_conv - 1, widths(cfg)[2]),
            "state": (batch, cfg.ssm_heads, cfg.ssm_head_dim,
                      cfg.ssm_d_state)}


def init_mixer(key: jax.Array, cfg, dense) -> dict:
    """The mixer's leaves as Mamba-2 initialises them: dt log-uniform in
    [0.001, 0.1] through the inverse of softplus, A uniform in [1, 16],
    D one."""
    inner, _, channels = widths(cfg)
    k = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(
        k[3], (cfg.ssm_heads,), jnp.float32,
        jnp.log(0.001), jnp.log(0.1)))
    return {
        "ssm_in": dense(k[0], (cfg.d_model, inner + channels
                               + cfg.ssm_heads), cfg.d_model),
        "conv_w": dense(k[1], (cfg.ssm_d_conv, channels), cfg.ssm_d_conv),
        "conv_b": jnp.zeros((channels,), cfg.param_dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.param_dtype),
        "A_log": jnp.log(jax.random.uniform(
            k[4], (cfg.ssm_heads,), jnp.float32, 1.0, 16.0)
        ).astype(cfg.param_dtype),
        "D": jnp.ones((cfg.ssm_heads,), cfg.param_dtype),
        "ssm_norm": jnp.ones((inner,), cfg.param_dtype),
        "ssm_out": dense(k[2], (inner, cfg.d_model), inner),
    }


def _convolve(window: jax.Array, xbc: jax.Array, blk: dict) -> tuple:
    """The causal depthwise convolution of xbc (B, S, C) behind the
    ``window`` of the inputs before it (B, taps − 1, C), as a sum of
    shifted products → (silu of it, the window the next call starts
    from)."""
    s = xbc.shape[1]
    taps = blk["conv_w"].astype(xbc.dtype)
    padded = jnp.concatenate([window, xbc], axis=1)
    out = blk["conv_b"].astype(xbc.dtype) + sum(
        taps[k] * padded[:, k:k + s] for k in range(taps.shape[0]))
    return jax.nn.silu(out), padded[:, s:]


def _step(x, b, c, dt, a, state):
    """The recurrence at one position: x (B, H, P), b and c (B, H, N)
    (each head its group's), dt (B, H) and a (H,) float32, ``state``
    (B, H, P, N) → (y (B, H, P) float32, the new state float32)."""
    decay = jnp.exp(dt * a)
    pushed = (dt[..., None] * x.astype(jnp.float32))[..., None] \
        * b.astype(jnp.float32)[:, :, None, :]
    state = decay[..., None, None] * state.astype(jnp.float32) + pushed
    y = jnp.einsum("bhpn,bhn->bhp", state, c.astype(jnp.float32))
    return y, state


def _chunk(x, b, c, dt, a, state):
    """The same recurrence over one chunk of L positions at once: x (B, L,
    H, P), b and c (B, L, G, N), dt (B, L, H) and a (H,) float32,
    ``state`` (B, H, P, N) float32, the state before the chunk's first
    position → (y (B, L, H, P) float32, the state after its last)."""
    bsz, length, heads, lanes = x.shape
    groups = b.shape[2]
    per = heads // groups
    dtype = x.dtype
    cum = jnp.cumsum(dt * a, axis=1)                       # (B, L, H) ≤ 0
    # position i reads what position j ≤ i pushed, decayed over (j, i]
    by_head = cum.transpose(0, 2, 1)                       # (B, H, L)
    causal = jnp.tril(jnp.ones((length, length), dtype=bool))
    between = jnp.where(causal, by_head[..., :, None] - by_head[..., None, :],
                        -jnp.inf)                          # (B, H, i, j)
    weight = jnp.exp(between) * dt.transpose(0, 2, 1)[..., None, :]
    cb = jnp.einsum("bign,bjgn->bgij", c, b,
                    preferred_element_type=jnp.float32)
    mixed = (weight.reshape(bsz, groups, per, length, length)
             * cb[:, :, None]).astype(dtype)
    inside = jnp.einsum("bgrij,bjgrp->bigrp", mixed,
                        x.reshape(bsz, length, groups, per, lanes),
                        preferred_element_type=jnp.float32)
    # what the state before the chunk still gives position i
    carried = jnp.einsum(
        "bign,bgrpn->bigrp", c,
        state.astype(dtype).reshape(bsz, groups, per, lanes, -1),
        preferred_element_type=jnp.float32) \
        * jnp.exp(cum).reshape(bsz, length, groups, per)[..., None]
    y = (inside + carried).reshape(bsz, length, heads, lanes)
    # the state after the chunk: the old one decayed over the whole chunk
    # and every position's push decayed over what follows it
    left = jnp.exp(cum[:, -1:, :] - cum) * dt               # (B, L, H)
    pushed = jnp.einsum(
        "bjgrp,bjgn->bgrpn",
        (x * left[..., None].astype(dtype)).reshape(
            bsz, length, groups, per, lanes), b,
        preferred_element_type=jnp.float32)
    state = jnp.exp(cum[:, -1, :])[..., None, None] * state \
        + pushed.reshape(state.shape)
    return y, state


def mixer(h: jax.Array, blk: dict, cfg, cache: Optional[dict] = None
          ) -> tuple:
    """The mixer on a normed state h (B, S, D) → (its output (B, S, D),
    the updated cache or None). Without ``cache`` the window and the state
    start from zero (a whole sequence, as ``transformer.forward`` runs
    it); with it they start from what the call before left."""
    dtype = cfg.compute_dtype
    bsz, s, _ = h.shape
    inner, bc, channels = widths(cfg)
    heads, lanes, groups = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups
    start = cache if cache is not None else {
        name: jnp.zeros(shape, dtype)
        for name, shape in state_shapes(cfg, bsz).items()}
    zxbcdt = h @ blk["ssm_in"].astype(dtype)
    z = zxbcdt[..., :inner]
    xbc, window = _convolve(start["conv"].astype(dtype),
                            zxbcdt[..., inner:inner + channels], blk)
    dt = jax.nn.softplus(zxbcdt[..., inner + channels:].astype(
        jnp.float32) + blk["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(blk["A_log"].astype(jnp.float32))
    x = xbc[..., :inner].reshape(bsz, s, heads, lanes)
    b = xbc[..., inner:inner + bc].reshape(bsz, s, groups, -1)
    c = xbc[..., inner + bc:].reshape(bsz, s, groups, -1)
    if s == 1:
        per_head = [jnp.repeat(m[:, 0], heads // groups, axis=1)
                    for m in (b, c)]
        y, state = _step(x[:, 0], *per_head, dt[:, 0], a, start["state"])
        y = y[:, None]
    else:
        state = start["state"].astype(jnp.float32)
        ys = []
        for at in range(0, s, cfg.ssm_chunk):
            span = slice(at, at + cfg.ssm_chunk)
            y, state = _chunk(x[:, span], b[:, span], c[:, span],
                              dt[:, span], a, state)
            ys.append(y)
        y = jnp.concatenate(ys, axis=1)
    y = y + blk["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    gated = y.reshape(bsz, s, inner).astype(dtype) * jax.nn.silu(z)
    out = _rms_norm(gated, blk["ssm_norm"], cfg.norm_eps) \
        @ blk["ssm_out"].astype(dtype)
    if cache is not None:
        cache = {"conv": window, "state": state.astype(dtype)}
    return out, cache


def init_mixer1(key: jax.Array, cfg, dense) -> dict:
    """A "mamba1" layer's leaves as Mamba-1 initialises them: dt
    log-uniform in [0.001, 0.1] through the inverse of softplus, A_log =
    log(1..N) in every lane, D one."""
    inner, n, rank = cfg.ssm_inner, cfg.ssm_d_state, cfg.ssm_dt_rank
    k = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(k[4], (inner,), jnp.float32,
                                    jnp.log(0.001), jnp.log(0.1)))
    return {
        "ssm_in": dense(k[0], (cfg.d_model, 2 * inner), cfg.d_model),
        "conv_w": dense(k[1], (cfg.ssm_d_conv, inner), cfg.ssm_d_conv),
        "conv_b": jnp.zeros((inner,), cfg.param_dtype),
        "ssm_x": dense(k[2], (inner, rank + 2 * n), inner),
        "ssm_dt": dense(k[3], (rank, inner), rank),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.param_dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
            (inner, n)).astype(cfg.param_dtype),
        "D": jnp.ones((inner,), cfg.param_dtype),
        "ssm_out": dense(k[5], (inner, cfg.d_model), inner),
    }


def init_gated_memory(key: jax.Array, cfg, dense) -> dict:
    k = jax.random.split(key, 2)
    return {"gmu_in": dense(k[0], (cfg.d_model, cfg.ssm_inner), cfg.d_model),
            "gmu_out": dense(k[1], (cfg.ssm_inner, cfg.d_model),
                             cfg.ssm_inner)}


def _step1(state, at, a):
    """Mamba-1's recurrence at one position: ``state`` (B, N, E) float32,
    ``at`` = (x (B, E), b and c (B, N), dt (B, E) float32), a (N, E)
    float32 → (the new state, y (B, E) float32)."""
    x, b, c, dt = at
    f32 = jnp.float32
    pushed = (dt * x.astype(f32))[:, None, :] * b.astype(f32)[:, :, None]
    state = jnp.exp(dt[:, None, :] * a) * state + pushed
    return state, jnp.sum(state * c.astype(f32)[:, :, None], axis=1)


def _scan1(state, x, b, c, dt, a):
    """:func:`_step1` along the positions: ``state`` (B, N, E) float32, x
    and dt (B, S, E), b and c (B, S, N) → (the state after the last, y
    (B, S, E) float32). The carry S lies in HBM."""
    along = tuple(m.swapaxes(0, 1) for m in (x, b, c, dt))
    state, y = jax.lax.scan(lambda st, at: _step1(st, at, a), state, along)
    return state, y.swapaxes(0, 1)


def streams_scan(cfg, rows: int, positions: int):
    """How a cached call on one chip runs a "mamba1" layer's recurrence
    over ``rows`` rows × ``positions`` through the one kernel that keeps S
    on the chip (ops/selective_scan.py: ``selective_scan.plan``), or None
    where it runs :func:`mixer1`'s own lines: more than one position,
    lanes and a state size the chip's tiles divide. ``call_sizes`` counts
    by it. Shapes alone decide; nothing names a model."""
    from faabric_tpu.ops import selective_scan

    return selective_scan.plan(rows, positions, cfg.ssm_inner,
                               cfg.ssm_d_state, cfg.compute_dtype)


def mixer1(h: jax.Array, blk: dict, cfg, cache: Optional[dict] = None,
           streamed: bool = False) -> tuple:
    """The Mamba-1 mixer on a normed state h (B, S, D) → (its output
    (B, S, D), the updated cache or None, the memory y (B, S, E) in the
    compute type: the recurrence's output before the gate). ``streamed``
    says that the call may go through the kernel (a cached call on one
    chip: no gradient is ever taken there) where :func:`streams_scan`
    finds its shape."""
    dtype = cfg.compute_dtype
    bsz, s, _ = h.shape
    inner, n, rank = cfg.ssm_inner, cfg.ssm_d_state, cfg.ssm_dt_rank
    start = cache if cache is not None else {
        name: jnp.zeros(shape, dtype)
        for name, shape in state_shapes(cfg, bsz, "mamba1").items()}
    xz = h @ blk["ssm_in"].astype(dtype)
    z = xz[..., inner:]
    x, window = _convolve(start["conv"].astype(dtype), xz[..., :inner], blk)
    dbc = x @ blk["ssm_x"].astype(dtype)
    b, c = dbc[..., rank:rank + n], dbc[..., rank + n:]
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dbc[..., :rank],
                   blk["ssm_dt"].astype(dtype),
                   preferred_element_type=jnp.float32)
        + blk["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(blk["A_log"].astype(jnp.float32)).T           # (N, E)
    if streamed and streams_scan(cfg, bsz, s) is not None:
        from faabric_tpu.ops.selective_scan import selective_scan

        y, state = selective_scan(x, dt, b, c, a, blk["D"], start["state"])
    else:
        state = start["state"].astype(jnp.float32)
        if s == 1:
            state, y = _step1(state, (x[:, 0], b[:, 0], c[:, 0], dt[:, 0]),
                              a)
            y = y[:, None]
        else:
            state, y = _scan1(state, x, b, c, dt, a)
        y = (y + blk["D"].astype(jnp.float32) * x.astype(jnp.float32)
             ).astype(dtype)
    out = (y * jax.nn.silu(z)) @ blk["ssm_out"].astype(dtype)
    if cache is not None:
        cache = {"conv": window, "state": state.astype(dtype)}
    return out, cache, y


def gated_memory(h: jax.Array, blk: dict, cfg, memory: jax.Array
                 ) -> jax.Array:
    """A gated memory unit on a normed state h (B, S, D) and the memory
    (B, S, E) another layer's recurrence gave at the same positions."""
    dtype = cfg.compute_dtype
    gate = jax.nn.silu(h @ blk["gmu_in"].astype(dtype))
    return (gate * memory.astype(dtype)) @ blk["gmu_out"].astype(dtype)
