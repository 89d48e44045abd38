"""Ring attention: causal attention over a sequence-sharded mesh axis.

Long-context first-class: for sequences too large for one chip's HBM, Q/K/V
shard along the sequence over the ``sp`` mesh axis. Each device keeps its Q
shard resident and the K/V shards rotate around the ring with
``jax.lax.ppermute`` — ICI neighbour hops. Each rotation step computes its
(Q-block, KV-block) attention through the **Pallas flash kernel**
(ops/flash_attention.py) and folds the partial result in with the
flash-decoding (out, lse) merge: the diagonal block runs the causal
kernel, fully-visible past blocks the non-causal kernel, and fully-masked
future blocks skip both matmuls entirely via ``lax.switch`` (the previous
jnp path materialized an (S_l, S_l) fp32 score block per step and spent
half the ring's FLOPs computing scores it then masked). Communication
overlaps compute in XLA's pipeline; the full (S, S) score matrix never
exists anywhere, and per-step peak memory is the kernel's O(S_l·D).

This is the sequence-parallel analog of the reference's "scale memory
beyond one host" capability (SURVEY §5.7); same recurrence as the Pallas
flash kernel, one level up the hierarchy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _mark_varying(x, axes: tuple[str, ...]):
    """Tag a locally-built array as device-varying over the given mesh
    axes (loop-carry / cond-branch types must match shard-derived
    values). Only the axes the value isn't already varying over are
    added — pcast rejects re-marking."""
    have = getattr(getattr(x, "aval", None), "vma", frozenset())
    missing = tuple(a for a in axes if a not in have)
    if not missing:
        return x
    return jax.lax.pcast(x, missing, to="varying")


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp",
                   causal: bool = True, batch_axis: str | None = None,
                   head_axis: str | None = None):
    """q/k/v (B, S, H, D) with S sharded over ``axis``; B and H may
    additionally shard over ``batch_axis``/``head_axis`` (attention is
    independent across batch and heads, so those axes never communicate).
    Returns the same sharding.

    Within each rotation step, device i holds Q block i and K/V block
    ((i - step) mod n); causal masking uses the blocks' global positions,
    so fully-masked future blocks contribute nothing.
    """
    n = mesh.shape[axis]
    if n == 1:
        from faabric_tpu.ops.attention import attend

        s = q.shape[1]
        return attend(q, k, v, jnp.tril(jnp.ones((s, s), dtype=bool))
                      if causal else None)
    return _compiled_ring(mesh, axis, causal, batch_axis, head_axis)(q, k, v)


@functools.lru_cache(maxsize=64)
def _compiled_ring(mesh: Mesh, axis: str, causal: bool,
                   batch_axis: str | None = None,
                   head_axis: str | None = None):
    """One jitted shard_map per signature — eager callers must hit the jit
    cache, not retrace per invocation."""
    n = mesh.shape[axis]
    perm = [(j, (j + 1) % n) for j in range(n)]

    def local_fn(q_blk, k_blk, v_blk):
        from faabric_tpu.ops.flash_attention import (
            flash_attention_with_lse,
            merge_attention_blocks,
        )

        # shapes (B, S_l, H, D)
        b, s_l, h, d = q_blk.shape
        my_idx = jax.lax.axis_index(axis)

        # (the shard_map runs with the varying check off, so fresh
        # constants need no pcast marking here)
        acc0 = jnp.zeros((b, s_l, h, d), jnp.float32)
        lse0 = jnp.full((b * h, s_l), NEG_INF, jnp.float32)

        # Per-block attention: each branch returns (out (B,S_l,H,D) in
        # the input dtype, lse (B·H, S_l) fp32)
        def diag_block(q, k, v):
            return flash_attention_with_lse(q, k, v, True)

        def full_block(q, k, v):
            return flash_attention_with_lse(q, k, v, False)

        def skip_block(q, k, v):
            # Fully-masked future block: neutral element of the merge
            return (jnp.zeros_like(q),
                    jnp.full((b * h, s_l), NEG_INF, jnp.float32))

        def fold(i, acc, lse_acc, k_cur, v_cur):
            kv_idx = (my_idx - i) % n
            if causal:
                # 0: diagonal (causal kernel), 1: past (full kernel),
                # 2: future (skip — no matmuls at all)
                rel = jnp.where(kv_idx == my_idx, 0,
                                jnp.where(kv_idx < my_idx, 1, 2))
                out_blk, lse_blk = jax.lax.switch(
                    rel, [diag_block, full_block, skip_block],
                    q_blk, k_cur, v_cur)
            else:
                out_blk, lse_blk = full_block(q_blk, k_cur, v_cur)
            # Flash-decoding combine (acc stays fp32: it's outs[0], and
            # merge_attention_blocks casts to the first operand's dtype)
            return merge_attention_blocks([acc, out_blk],
                                          [lse_acc, lse_blk])

        def step(i, carry):
            acc, lse_acc, k_cur, v_cur = carry
            acc, lse_acc = fold(i, acc, lse_acc, k_cur, v_cur)
            # Rotate K/V to the next ring neighbour (ICI hop)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return acc, lse_acc, k_nxt, v_nxt

        # Steps 0..n-2 fold-then-rotate; the final block folds outside the
        # loop so no rotation result is ever discarded (2 ICI hops saved)
        acc, lse_acc, k_last, v_last = jax.lax.fori_loop(
            0, n - 1, step, (acc0, lse0, k_blk, v_blk))
        acc, _ = fold(n - 1, acc, lse_acc, k_last, v_last)
        # acc is the normalized union already (merge of normalized
        # partials); causal rows always see their diagonal, so no
        # fully-masked rows exist
        return acc.astype(q_blk.dtype)

    spec = P(batch_axis, axis, head_axis, None)
    # Varying-check off: pallas_call's out_shape carries no varying-mesh-
    # axes annotation (same trade as the model's flash path,
    # models/transformer.py)
    return jax.jit(jax.shard_map(local_fn, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))


def shard_sequence(x, mesh: Mesh, axis: str = "sp"):
    """Place (B, S, ...) with S sharded over the axis."""
    spec = [None] * x.ndim
    spec[1] = axis
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))
