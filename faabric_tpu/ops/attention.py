"""Attention in the ``jnp`` lines: the one function every path that does not
take a kernel goes through, and the reference the kernels are held to
(``flash_attention``'s fallback, ``parallel/ring_attention.py`` on one
device, the pipeline's stages, the model's whole sequences and caches).

The caller lays out what is attended and says which keys a query sees:
causal, causal from a query offset, causal over a cache's written slots, a
window's band, a ring's written slots. Scores accumulate in float32 times
the scale, keys a query may not see go to −1e30, the softmax is float32,
and the probabilities are cast to the inputs' type before the product with
the values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# What the float32 scores of one call may take: more rows than fit go
# through it a block of rows at a time (at 64 rows × 40 heads × 256 × 768
# the scores alone are 2 GB, their exponentials and mask as much again).
SCORE_BYTES = 512 * 1024 * 1024


def attend(q, keys, values, mask=None, written=None, scale=None,
           pairs: bool = False, head_major: bool = False) -> jax.Array:
    """q (B, S, H, D) on keys and values (B, K, KV, D), or with
    ``head_major`` (B, KV, K, D), under ``mask`` (S, K) (None: every key);
    ``written`` (K,) says which of the K hold something (None: all): a slot
    never written may hold NaN, and 0 × NaN is NaN, so its values are
    zeroed. ``scale`` defaults to 1 / sqrt(D). Plain heads where KV == H,
    else the H / KV query heads of a group attend the group's one key/value
    head as it lies, no copy a head → (B, S, H, D). With ``pairs`` head
    (g, p, c) attends key head (g, c) and sums the group's two value heads
    side by side → (B, S, groups, pairs a group, 2, 2·D): the two maps of
    every pair (position-major keys only). Rows whose scores pass
    ``SCORE_BYTES`` go in equal blocks, one after the other."""
    b, s, h, d = q.shape
    kv, reach = (keys.shape[1:3] if head_major
                 else (keys.shape[2], keys.shape[1]))
    fit = max(1, SCORE_BYTES // (4 * h * s * reach))
    block = max(r for r in range(1, b + 1) if b % r == 0 and r <= fit)
    if block < b:
        def blocks(x):
            return x.reshape(b // block, block, *x.shape[1:])

        out = jax.lax.map(
            lambda qkv: attend(*qkv, mask, written, scale, pairs,
                               head_major),
            (blocks(q), blocks(keys), blocks(values)))
        return out.reshape(b, *out.shape[2:])
    scale = 1.0 / np.sqrt(d) if scale is None else scale
    if written is not None:
        values = jnp.where(written[None, None, :, None] if head_major
                           else written[None, :, None, None], values, 0)
    if pairs:
        groups = kv // 2
        q = q.reshape(b, s, groups, h // kv, 2, d)
        keys = keys.reshape(b, reach, groups, 2, d)
        values = values.reshape(b, reach, groups, 2 * d)
        scores, weighted = "bqgpcd,bkgcd->bgpcqk", "bgpcqk,bkge->bqgpce"
    elif kv != h:
        q = q.reshape(b, s, kv, h // kv, d)
        at = "bksd" if head_major else "bskd"
        scores, weighted = f"bqkgd,{at}->bkgqs", f"bkgqs,{at}->bqkgd"
    else:
        at = "bhkd" if head_major else "bkhd"
        scores, weighted = f"bqhd,{at}->bhqk", f"bhqk,{at}->bqhd"
    logits = jnp.einsum(scores, q, keys).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum(weighted, probs, values)
    return out if pairs else out.reshape(b, s, h, d)
