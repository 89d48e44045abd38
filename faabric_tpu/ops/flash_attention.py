"""Fused causal flash attention as Pallas TPU kernels (fwd + bwd).

The hot op of the flagship model, written for the memory hierarchy: per
(batch·head, q-block) grid step the Q tile sits in VMEM while the kernel
streams K/V blocks with the online-softmax recurrence — no (S, S) score
matrix ever materialises in HBM. fp32 running max/sum/accumulator, compute
in the input dtype on the MXU.

Training runs the standard two-pass flash backward: the forward kernel
additionally emits the per-row log-sum-exp, and two backward kernels
recompute probabilities in-block from (Q, K, LSE) — one gridded over
q-blocks producing dQ, one over k-blocks producing dK/dV. Peak memory
stays O(S·D) in both directions.

Which path runs is a pure function of the shapes and the backend:
:func:`uses_kernel` answers it, so a caller can assert the kernel was
taken. Shapes the tiling cannot take (ragged, sub-tile, causal with
s_q > s_k) run the jnp reference; every other shape runs the kernels,
and a shape the Mosaic compiler refuses is an error, never the
reference. The kernels hold whole-sequence blocks in VMEM (K/V in the
forward and dQ passes; Q, dO and the lane-broadcast statistics in the
dK/dV pass), so libtpu rejects long sequences with RESOURCE_EXHAUSTED
at compile time: on a v5e the backward compiles up to S = 8192 and the
forward up to S = 12288 at head_dim 128.

On CPU (tests) the kernels run in interpreter mode automatically.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# Per-row softmax statistics (lse, delta) ride through Pallas with a
# broadcast 128-lane trailing dim: Mosaic requires the last two block
# dims to be (8k, 128k)-tileable, so a (1, block_q) block of a 2-D
# (B·H, S) array cannot lower on real TPU hardware (the official TPU
# flash kernel uses the same layout for its m/l statistics).
LANE = 128


def _stat_cols(stat, n_cols: int):
    """Expand a (rows, LANE) lane-broadcast statistic to (rows, n_cols)
    (every lane holds the same per-row value; n_cols may be < LANE on the
    CPU interpret path)."""
    reps = max(1, -(-n_cols // LANE))
    return jnp.tile(stat, (1, reps))[:, :n_cols]


def _reference_attention(q, k, v, causal: bool = True):
    """Plain jnp attention (the model's _attention twin) — used for the
    backward pass and for numerics tests."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, causal_offset: int):
    """One grid step: one (batch·head, q-block). Refs (leading singleton is
    the folded batch·head block): q (1, block_q, d), k/v (1, s_k, d).
    ``causal_offset`` end-aligns the mask when s_k > s_q (query row i may
    see keys up to i + offset) — matching the reference's tril(k=s_k-s_q).

    Matmul operands stay in the input dtype (bf16 rides the MXU at full
    rate, accumulating in fp32 via preferred_element_type); only the
    softmax statistics and the accumulator live in fp32."""
    _, block_q, d = q_ref.shape
    s_k = k_ref.shape[1]
    n_k_blocks = s_k // block_k

    q_idx = pl.program_id(1)
    q_off = q_idx * block_q

    q = q_ref[0]
    scale = 1.0 / np.sqrt(d)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]

        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)
        if causal:
            q_pos = q_off + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)

        m_cur = jnp.max(scores, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new[:, None])
        l_new = l_prev * correction + jnp.sum(p, axis=1)
        acc = acc * correction[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q,), dtype=jnp.float32)
    acc0 = jnp.zeros((block_q, d), dtype=jnp.float32)

    if causal:
        # Blocks strictly above the (offset) diagonal contribute nothing
        n_blocks = jnp.minimum(
            n_k_blocks,
            (q_off + causal_offset + block_q + block_k - 1) // block_k)
    else:
        n_blocks = n_k_blocks
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    # Per-row log-sum-exp: the only softmax statistic the backward needs
    # (broadcast across the LANE dim — see LANE comment above)
    lse_ref[0] = jnp.broadcast_to((m + jnp.log(l))[:, None],
                                  (block_q, LANE))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         causal_offset: int):
    """dQ pass: one grid step per (batch·head, q-block). Streams K/V blocks,
    recomputing P from (Q, K, LSE) — the (S, S) matrix never exists."""
    _, block_q, d = q_ref.shape
    s_k = k_ref.shape[1]
    n_k_blocks = s_k // block_k
    q_off = pl.program_id(1) * block_q
    scale = 1.0 / np.sqrt(d)

    q = q_ref[0]
    do = do_ref[0].astype(jnp.float32)
    # (block_q, LANE) lane-broadcast stats → expand across the k lanes
    lse = _stat_cols(lse_ref[0], block_k)
    delta = _stat_cols(delta_ref[0], block_k)

    def body(i, dq_acc):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :]

        scores = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_off + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)

        p = jnp.exp(scores - lse)  # masked entries underflow to 0
        dp = jax.lax.dot_general(
            do.astype(v_blk.dtype), v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq_acc + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        n_blocks = jnp.minimum(
            n_k_blocks,
            (q_off + causal_offset + block_q + block_k - 1) // block_k)
    else:
        n_blocks = n_k_blocks
    dq = jax.lax.fori_loop(0, n_blocks, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          causal_offset: int):
    """dK/dV pass: one grid step per (batch·head, k-block), streaming
    q-blocks from the first causally-visible one."""
    _, block_k, d = k_ref.shape
    s_q = q_ref.shape[1]
    n_q_blocks = s_q // block_q
    k_off = pl.program_id(1) * block_k
    scale = 1.0 / np.sqrt(d)

    k = k_ref[0]
    v = v_ref[0]

    def body(j, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, pl.ds(j * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(j * block_q, block_q), :]
        lse_blk = _stat_cols(lse_ref[0, pl.ds(j * block_q, block_q), :],
                             block_k)
        delta_blk = _stat_cols(delta_ref[0, pl.ds(j * block_q, block_q), :],
                               block_k)

        scores = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = j * block_q + causal_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)

        p = jnp.exp(scores - lse_blk)
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk) * scale
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    if causal:
        # First q-block whose last row (j·bq + bq − 1 + offset) reaches this
        # k-block: ceil((k_off − offset − bq + 1) / bq) = floor((k_off − offset) / bq)
        j_start = jnp.maximum(0, (k_off - causal_offset) // block_q)
    else:
        j_start = 0
    zeros = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(j_start, n_q_blocks, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def uses_kernel(q_shape, k_shape, causal: bool = True,
                block_q: int = DEFAULT_BLOCK_Q,
                block_k: int = DEFAULT_BLOCK_K) -> bool:
    """True when :func:`flash_attention` on (B, S, H, D) shapes runs the
    Pallas kernels on the current backend, False when it runs the jnp
    reference."""
    s_q, s_k = q_shape[1], k_shape[1]
    d = q_shape[-1]
    # On real TPU hardware, sub-tile shapes (short sequences / narrow
    # heads vs the 128-lane register tiling) stay on the reference path —
    # Mosaic lowering of tiny blocks is at best wasteful padding. CPU
    # interpret mode has no tiling, so tests exercise small shapes.
    if jax.default_backend() == "tpu" and (
            s_q < DEFAULT_BLOCK_Q or s_k < DEFAULT_BLOCK_K or d < 64):
        return False
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    # The lane-broadcast stats layout needs Mosaic-tileable blocks
    if jax.default_backend() == "tpu" and (block_q % 8 or block_k % LANE):
        return False
    # Ragged shapes — and the degenerate causal s_q > s_k case, where
    # fully-masked query rows need the reference's uniform-softmax
    # treatment rather than a 0/0 accumulator — use the reference path
    return not (s_q % block_q or s_k % block_k or (causal and s_q > s_k))


def _fold_heads(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """Causal attention, (B, S, H, D) → (B, S, H, D)."""
    out, _ = _flash_forward(q, k, v, causal, block_q, block_k)
    return out


def _flash_forward(q, k, v, causal, block_q, block_k):
    """Returns (out, lse) — lse is None on the reference fallback path,
    (B·H, S_q, LANE) lane-broadcast fp32 otherwise (slice ``[:, :, 0]``
    for the per-row value; kept 3-D so the backward can feed it straight
    back into the kernels without re-materializing the broadcast)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if not uses_kernel(q.shape, k.shape, causal, block_q, block_k):
        return _reference_attention(q, k, v, causal), None
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)

    # Fold (B, H) into the grid's first axis; kernel sees 2-D tiles
    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)

    interpret = jax.default_backend() == "cpu"
    kernel = functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                               causal_offset=s_k - s_q)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, s_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s_k, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_q, LANE), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, s_q, d).transpose(0, 2, 1, 3), lse


def _flash_fwd(q, k, v, causal, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k)
    return out, (q, k, v, out, lse)


def _run_bwd_kernels(q, k, v, g_out, out, lse_l, causal, block_q, block_k,
                     g_lse=None):
    """Launch the two-pass backward kernels. ``lse_l`` is the forward
    kernel's (B·H, S_q, LANE) lane-broadcast statistic, fed back verbatim.
    ``g_lse`` (the lse output's cotangent, when the caller exposed lse)
    folds into the row correction: ds = p·(dp − (Δ − g_lse)), since
    ∂lse/∂s = p."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)

    qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    dof, of = _fold_heads(g_out), _fold_heads(out)
    # delta_i = Σ_d dO·O — the softmax-jacobian row correction, O(S·D)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    # Lane-broadcast layout for the in-kernel stats (see LANE comment)
    delta_l = jnp.broadcast_to(delta[..., None], (*delta.shape, LANE))

    interpret = jax.default_backend() == "cpu"
    offset = s_k - s_q

    dq_kernel = functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                                  causal=causal, causal_offset=offset)
    dqf = pl.pallas_call(
        dq_kernel,
        grid=(b * h, s_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s_k, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANE), lambda bh, qi: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, dof, lse_l, delta_l)

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                                   causal=causal, causal_offset=offset)
    dkf, dvf = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, s_k // block_k),
        in_specs=[
            pl.BlockSpec((1, s_q, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, s_q, d), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, s_q, LANE), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, s_q, LANE), lambda bh, ki: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, dof, lse_l, delta_l)

    def unfold(x, s):
        return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    return unfold(dqf, s_q), unfold(dkf, s_k), unfold(dvf, s_k)


def _flash_bwd(causal, block_q, block_k, res, g):
    q, k, v, out, lse = res
    if lse is None:
        # Forward fell back to reference numerics; match them in reverse
        _, vjp = jax.vjp(
            lambda q, k, v: _reference_attention(q, k, v, causal), q, k, v)
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
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    # (B, H, S_q) → fold to the kernel's (B·H, S_q) layout
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    b, h, s_q = lse.shape
    return lse.reshape(b * h, s_q)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal: bool = True,
                             block_q: int = DEFAULT_BLOCK_Q,
                             block_k: int = DEFAULT_BLOCK_K):
    """Attention plus the per-row log-sum-exp: (out (B,S,H,D),
    lse (B·H, S) fp32). Differentiable in BOTH outputs — the lse
    cotangent folds into the existing backward kernels as a delta
    adjustment (ds = p·(dp − (Δ − g_lse)), since ∂lse/∂s = p)."""
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k)
    if lse is None:  # reference fallback path
        return out, _reference_lse(q, k, causal)
    return out, lse[:, :, 0]


def _flash_lse_fwd(q, k, v, causal, block_q, block_k):
    out, kernel_lse = _flash_forward(q, k, v, causal, block_q, block_k)
    lse = (kernel_lse[:, :, 0] if kernel_lse is not None
           else _reference_lse(q, k, causal))
    return (out, lse), (q, k, v, out, kernel_lse)


def _flash_lse_bwd(causal, block_q, block_k, res, cotangents):
    g_out, g_lse = cotangents
    q, k, v, out, lse = res
    if lse is None:
        # Reference numerics in reverse for the fallback path
        def ref(q, k, v):
            return (_reference_attention(q, k, v, causal),
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
