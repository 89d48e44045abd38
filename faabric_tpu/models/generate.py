"""Autoregressive decoding with a KV cache.

Static shapes end-to-end: the cache is pre-allocated with as many slots
as the call can fill (prompt length + new tokens, both static, rounded up
to ``_SLOT_MULTIPLE`` and never above ``max_seq``), so a decode step
attends over the call's own reach and not over ``max_seq``. It is laid
head-major, ``(batch, heads, slots, head_dim)``, and pinned so in
memory: one head's keys are one contiguous ``(slots, head_dim)`` tile
array. It is filled with ``lax.dynamic_update_slice``; attention masks by
position, so
prefill and every decode step compile once each. The whole greedy loop is
one ``lax.scan`` under jit — no host round-trips between tokens, which is
what keeps a TPU busy at small batch.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from faabric_tpu.models.transformer import (
    ModelConfig,
    _norm,
    _rope,
)


# Cache lengths are rounded up to this many slots: the TPU's lane width,
# so the scores' last dimension fills whole tiles.
_SLOT_MULTIPLE = 128


def _head_major(cache: jax.Array) -> jax.Array:
    """Pin a (batch, heads, slots, head_dim) cache row-major in memory.
    Left to itself XLA:TPU lays the scan's carried cache heads-minor
    whatever the logical order, the heads padded to a tile's 128 lanes:
    with 16 heads, eight times the bytes at every step."""
    return with_layout_constraint(cache, Layout((0, 1, 2, 3)))


def _cache_slots(cfg: ModelConfig, reach: int) -> int:
    """Slots for a call whose last write is position ``reach - 1``."""
    rounded = -(-reach // _SLOT_MULTIPLE) * _SLOT_MULTIPLE
    return min(rounded, cfg.max_seq)


def init_kv_cache(cfg: ModelConfig, batch: int,
                  slots: int | None = None) -> list[dict]:
    """Zeroed per-layer caches, head-major: (batch, heads, slots,
    head_dim). ``slots`` defaults to ``cfg.max_seq``."""
    shape = (batch, cfg.n_heads, cfg.max_seq if slots is None else slots,
             cfg.head_dim)
    return [{"k": jnp.zeros(shape, cfg.compute_dtype),
             "v": jnp.zeros(shape, cfg.compute_dtype)}
            for _ in range(cfg.n_layers)]


def _cached_attention(q, cache_k, cache_v, length):
    """q (B, S_q, H, D) against the first ``length`` positions of a
    head-major cache (B, H, slots, D); q's last position is length-1."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bhkd->bhqk", q, cache_k
                        ).astype(jnp.float32) * scale
    s_q = q.shape[1]
    slots = cache_k.shape[2]
    q_pos = (length - s_q) + jnp.arange(s_q)
    k_pos = jnp.arange(slots)
    mask = q_pos[:, None] >= k_pos[None, :]
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bqhd", probs, cache_v)


def _block_with_cache(x, blk, cache, start, length, cfg: ModelConfig):
    """One transformer block over tokens at positions [start, start+S);
    updates the cache in place (functionally) and attends over
    [0, length)."""
    b, s, _ = x.shape
    h = _norm(x, blk["ln1"], cfg)
    qkv = jnp.einsum("bsd,dthe->tbshe", h,
                     blk["wqkv"].astype(cfg.compute_dtype))
    q, k, v = qkv[0], qkv[1], qkv[2]
    positions = jnp.broadcast_to(start + jnp.arange(s)[None], (b, s))
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)

    cache_k = _head_major(jax.lax.dynamic_update_slice(
        cache["k"], k.transpose(0, 2, 1, 3), (0, 0, start, 0)))
    cache_v = _head_major(jax.lax.dynamic_update_slice(
        cache["v"], v.transpose(0, 2, 1, 3), (0, 0, start, 0)))

    attn = _cached_attention(q, cache_k, cache_v, length)
    x = x + jnp.einsum("bshe,hed->bsd", attn,
                       blk["wo"].astype(cfg.compute_dtype))
    h = _norm(x, blk["ln2"], cfg)
    ff = jax.nn.gelu(h @ blk["w1"].astype(cfg.compute_dtype))
    x = x + ff @ blk["w2"].astype(cfg.compute_dtype)
    return x, {"k": cache_k, "v": cache_v}


def forward_with_cache(params, tokens, cache, start, cfg: ModelConfig):
    """tokens (B, S) entering at position ``start`` → (logits (B, S, V),
    new cache). length = start + S."""
    from faabric_tpu.models.transformer import resolve_impls

    cfg = resolve_impls(cfg)
    b, s = tokens.shape
    length = start + s
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    new_cache = []
    for blk, layer_cache in zip(params["blocks"], cache):
        x, updated = _block_with_cache(x, blk, layer_cache, start, length,
                                       cfg)
        new_cache.append(updated)
    x = _norm(x, params["ln_f"], cfg)
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)
              ).astype(jnp.float32)
    return logits, new_cache


def _pick_token(logits, key, greedy: bool, temperature, top_k: int,
                use_top_p: bool, top_p) -> jax.Array:
    """One sampling step over (B, V) logits. Static structure (greedy vs
    sample, top-k size, top-p enabled) picks the program; temperature and
    top_p themselves are TRACED operands, so a serving loop varying them
    per request reuses one compiled decode."""
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if use_top_p:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep the smallest prefix with cumulative mass >= top_p (the
        # first token always survives)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnums=(2, 3, 6, 7, 9, 10, 11))
def _generate_impl(params, prompt, cfg: ModelConfig, n_tokens: int,
                   key, temperature, greedy: bool, top_k: int, top_p,
                   use_top_p: bool, mesh, prefill_chunk: int = 0):
    from jax.sharding import NamedSharding, PartitionSpec as P

    b, s_p = prompt.shape
    cache = init_kv_cache(cfg, b, _cache_slots(cfg, s_p + n_tokens))
    if mesh is not None:
        kv_sharding = NamedSharding(mesh, P("dp", "tp", None, None))
        cache = [{k: jax.lax.with_sharding_constraint(v, kv_sharding)
                  for k, v in layer.items()} for layer in cache]

    if prefill_chunk and prefill_chunk < s_p:
        # Chunked prefill: attention during prefill peaks at
        # (chunk × slots) scores instead of (S_p × slots) — the
        # long-prompt memory bound. Chunk boundaries are static.
        pos = 0
        logits = None
        while pos < s_p:
            hi = min(pos + prefill_chunk, s_p)
            logits, cache = forward_with_cache(
                params, prompt[:, pos:hi], cache, pos, cfg)
            pos = hi
    else:
        logits, cache = forward_with_cache(params, prompt, cache, 0, cfg)
    key, sub = jax.random.split(key)
    next_tok = _pick_token(logits[:, -1], sub, greedy, temperature,
                           top_k, use_top_p, top_p)

    def step(carry, _):
        tok, pos, cache, key = carry
        logits, cache = forward_with_cache(params, tok[:, None], cache,
                                           pos, cfg)
        key, sub = jax.random.split(key)
        nxt = _pick_token(logits[:, -1], sub, greedy, temperature,
                          top_k, use_top_p, top_p)
        return (nxt, pos + 1, cache, key), tok

    (_, _, _, _), toks = jax.lax.scan(step, (next_tok, s_p, cache, key),
                                      None, length=n_tokens)
    return toks.T  # (B, n_tokens)


def generate(params, prompt, cfg: ModelConfig, n_tokens: int,
             key: jax.Array | None = None, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 1.0, mesh=None,
             prefill_chunk: int = 0):
    """Decode: prompt (B, S_p) int32 → (B, n_tokens) int32. Prefill + a
    scanned single-token decode loop, all one program. Default is greedy
    (temperature 0); pass a PRNG ``key`` with ``temperature``/``top_k``/
    ``top_p`` for sampling (varying temperature/top_p does NOT
    recompile; varying top_k does — it's a shape). The KV cache holds
    the ``S_p + n_tokens`` positions this call can write (rounded up to
    a multiple of 128), so each (prompt length, ``n_tokens``) pair is
    its own program and a step attends over no more than that;
    ``S_p + n_tokens`` above ``cfg.max_seq`` raises ``ValueError``. With
    ``mesh``, the KV cache shards batch over ``dp`` and heads over ``tp``
    (matching tp-sharded params), so decode runs tensor-parallel with
    XLA inserting the activation collectives. ``prefill_chunk``
    processes long prompts in fixed-size chunks, bounding prefill
    attention memory."""
    reach = prompt.shape[1] + n_tokens
    if reach > cfg.max_seq:
        raise ValueError(
            f"prompt of {prompt.shape[1]} tokens + {n_tokens} new needs "
            f"{reach} cache slots; cfg.max_seq is {cfg.max_seq}")
    greedy = temperature == 0.0
    if key is None:
        key = jax.random.PRNGKey(0)
    return _generate_impl(
        params, prompt, cfg, n_tokens, key,
        jnp.float32(temperature if not greedy else 1.0), greedy,
        int(top_k), jnp.float32(top_p), top_p < 1.0, mesh,
        int(prefill_chunk))
