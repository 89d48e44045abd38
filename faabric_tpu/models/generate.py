"""Autoregressive decoding with a KV cache.

Static shapes end-to-end: the cache is pre-allocated with as many slots
as the call can fill (prompt length + new tokens, both static, rounded up
to ``_SLOT_MULTIPLE`` and never above ``max_seq``), so a decode step
attends over the call's own reach and not over ``max_seq``. Each layer
has one cache a pass of the stack, ``(passes, batch, heads, slots,
head_dim)``, head-major and pinned so in memory: one head's keys are one
contiguous ``(slots, head_dim)`` tile array. Where a call's steps take
many rows, or the configuration names a ring, a lent cache or
differential pairs, the cache lies dense instead, ``(passes, batch,
slots, heads · head_dim)``, a position's keys one row of whole lanes
whatever the head's width (``transformer.cache_layout`` decides), and a
step of many rows attends it through one kernel a layer
(ops/cached_attention.py), a prompt's chunk through another that keeps
its scores on the chip (ops/prefill_attention.py) where
``transformer.streams_prefill_attention`` finds its shape; any other call
attends the cache as it lies (``ops/attention.py``). It is filled with
``lax.dynamic_update_slice``; attention masks by position, so prefill
and every decode step compile once each. The whole greedy loop is one
``lax.scan`` under jit — no host round-trips between tokens, which is
what keeps a TPU busy at small batch; a looped stack's passes are a
rolled loop inside it, over the same weights.

Caches go by the attention's kind. Latent attention keeps one latent and
its turned rotary lanes a position, ``(passes, batch, slots, kv_lora_rank
+ qk_rope_dim)``, row-major and once an attention, whatever the number of
heads; a prompt's chunk expands its reach's keys and values from it and
attends them through one kernel (ops/latent_attention.py) where
``transformer.streams_latent_prefill`` finds its shape; else a prompt, or
its first chunk, expands keys and values from it, a later chunk attends
over it as it lies, the scores in blocks of rows and queries
(``transformer._latent_attention``, ``score_blocks``); a cached step
attends over it as it lies. A "shortcut"
layer's state is its two attentions' caches and its expert layer's
counters, summed on the device over the call; a "single" layer whose
feed-forward is an expert layer (``ModelConfig.ffn_types``) keeps the
same counters beside its mixer's state.

State goes by the layer's kind too (``ModelConfig.layer_types``). An
"attention" layer keeps keys and values over its key/value heads, fewer
than the query heads under grouped-query attention; a "mamba" or
"mamba1" layer (models/ssm.py) keeps a convolution window and a
recurrent state whose size does not depend on the reach; a
"window_attention" layer keeps a ring of the window's length whatever
the reach, position p in slot p % slots. All kinds live in one call's
list. With ``prefill_chunk`` a chunk of the prompt starts from the state
the chunk before left (a ring is attended before the chunk overwrites
it).

A layer may read state another layer owns. A "cross_attention" layer
attends the cache of layer ``cfg.cache_source`` and writes nothing; a
"gated_memory" layer reads, at the same positions, what the recurrence of
layer ``cfg.memory_source`` gave. Neither has an entry of its own in the
call's list (``None``): the step hands the owner's updated cache, and the
step's memory, along the layers. From ``cfg.stateless_from`` on no layer
keeps anything, so a prefill runs those layers, the final norm and the
head on the position it serves alone.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from faabric_tpu.models import scopes, ssm
from faabric_tpu.models.moe import COUNTERS, _row_tile, prefill_plan
from faabric_tpu.models.transformer import (
    ATTENDING,
    ModelConfig,
    _block,
    cache_layout,
    embed,
    feed_forward_widths,
    head,
    lender_of,
    resolve_impls,
    run_passes,
    refuse_served_only,
    score_blocks,
    streams_attention,
    streams_feed_forward,
    streams_latent_prefill,
    streams_prefill_attention,
)


# Cache lengths are rounded up to this many slots: the TPU's lane width,
# so the scores' last dimension fills whole tiles.
_SLOT_MULTIPLE = 128


def _cache_slots(cfg: ModelConfig, reach: int) -> int:
    """Slots for a call whose last write is position ``reach - 1``."""
    rounded = -(-reach // _SLOT_MULTIPLE) * _SLOT_MULTIPLE
    return min(rounded, cfg.max_seq)


def _attention_cache_shapes(cfg: ModelConfig, batch: int, slots: int,
                            mesh=None) -> dict:
    """The arrays one attention's cache is made of, by its kind. Keys and
    values lie as ``transformer.cache_layout`` says: head-major, or dense
    (a position's key/value heads one row)."""
    if cfg.attention == "latent":
        return {"latent": (cfg.n_passes, batch, slots,
                           cfg.kv_lora_rank + cfg.qk_rope_dim)}
    shape = (cfg.n_passes, batch, cfg.kv_heads, slots, cfg.head_dim)
    if cache_layout(cfg, batch, slots, mesh) == "dense":
        shape = (cfg.n_passes, batch, slots, cfg.kv_heads * cfg.head_dim)
    return {"k": shape, "v": shape}


def _ring_slots(cfg: ModelConfig, slots: int) -> int:
    """Slots of a "window_attention" layer's ring in a call whose full
    caches have ``slots``: the window, whatever the reach beyond it."""
    return min(cfg.sliding_window, slots)


def _state_shapes(cfg: ModelConfig, kind: str, batch: int, slots: int,
                  mesh=None) -> dict:
    """The arrays a layer of ``kind`` keeps through a call; none for a
    layer that reads what another lends."""
    if kind in ("mamba", "mamba1"):
        return ssm.state_shapes(cfg, batch, kind)
    if kind == "window_attention":
        slots = _ring_slots(cfg, slots)
    elif kind != "attention":
        return {}
    return _attention_cache_shapes(cfg, batch, slots, mesh)


def _prefill_chunks(prompt_len: int, prefill_chunk: int) -> list:
    """The static (start, length) of the prompt's chunks."""
    chunk = prefill_chunk if 0 < prefill_chunk < prompt_len else prompt_len
    return [(pos, min(chunk, prompt_len - pos))
            for pos in range(0, prompt_len, chunk)]


def call_sizes(cfg: ModelConfig, batch: int, prompt_len: int,
               n_tokens: int, prefill_chunk: int = 0) -> dict:
    """What one ``generate`` call of these static shapes allocates and
    runs: ``cache_slots`` a cache, ``cache_bytes`` of all the caches (what
    the attention's kind keeps a position, every attention of every
    attention layer, every pass), ``ut_passes`` of the stack (prefill and
    each decode step pass it ``cfg.n_passes`` times); where the layers
    have an expert layer also ``experts_held`` here, the
    ``router_width`` and ``prefill_expert_tile``, the rows of a tile of
    the held experts' grouped product in the prompt's first chunk
    (``moe.prefill_plan``); where the configuration names its layers'
    feed-forwards (``ffn_types``) also ``shared_experts``,
    ``dense_layers`` and ``expert_layers``, ``prefill_chunks``,
    ``score_blocks`` (the blocks of float32 scores that one layer's
    prefill sends through HBM, summed over the prompt's chunks that the
    kernel does not take), ``expanded_bytes`` (what the prefill keeps of
    expanded keys and values from chunk to chunk: nothing),
    ``latent_streamed_layers`` (the attentions whose prefill chunks keep
    their scores on the chip, ops/latent_attention.py),
    ``latent_streamed_chunks`` (the chunks a layer that do, by
    ``transformer.streams_latent_prefill``) and ``latent_streamed_bytes``
    (what the kernel streams for them, all layers); where the
    configuration names its layers' kinds
    also ``attention_layers``, ``ssm_layers``, ``state_bytes`` (the
    windows and states of all state-space layers: the same at any reach)
    and ``scan_chunks``, the chunks of the state-space scan a row a layer
    in prefill; where a layer keeps a ring or lends its state also
    ``window_layers``, ``window_slots`` (a ring's), ``window_cache_bytes``
    and ``shared_cache_bytes`` (the lent cache, once; both are part of
    ``cache_bytes``), ``cross_layers`` and ``memory_layers`` (the layers
    that borrow) and ``prefill_skipped_layers`` (those a prefill runs at
    the served position alone). ``ffn_streamed_layers`` is the
    feed-forwards whose cached
    step goes through the streaming kernel (ops/gated_ffn.py) and
    ``ffn_streamed_bytes`` what they stream a step, every pass, for a call
    without a mesh on parameters of ``cfg.param_dtype``
    (``transformer.streams_feed_forward``, each feed-forward planned at
    its own width: an expert layer's shared experts count as one); 0
    where none does.
    ``attention_streamed_layers`` is the attentions whose cached step
    reads a dense cache through its kernel (ops/cached_attention.py), its
    own, a ring or a lent one, and ``attention_streamed_bytes`` the keys
    and values they stream a step as the kernel reads them (a cache's
    allocated slots, a lent cache once a reader), every pass, for a call
    without a mesh
    (``transformer.streams_attention``); 0 where none does. Beside the
    rings' and the lent state's counters, ``scan_streamed_layers`` is the
    "mamba1" layers whose prefill chunks run their recurrence through the
    kernel that keeps S on the chip (ops/selective_scan.py) and
    ``scan_streamed_bytes`` what they stream a call's prefill, for a call
    without a mesh (``ssm.streams_scan``); 0 where none does.
    Where a prefill chunk attends a dense cache through the kernel that
    keeps its scores on the chip (ops/prefill_attention.py, by
    ``transformer.streams_prefill_attention``), and only there,
    ``prefill_attention_streamed_layers`` is the attending layers whose
    chunks do, ``prefill_attention_streamed_chunks`` the chunks a layer
    that do (the most over those layers) and
    ``prefill_attention_streamed_bytes`` what the kernel streams for
    them, all layers and passes (``prefill_attention.plan``).
    ``generate`` sizes its cache from this; a server reports it beside
    its answers."""
    slots = _cache_slots(cfg, prompt_len + n_tokens)
    itemsize = jnp.dtype(cfg.compute_dtype).itemsize
    shortcut = cfg.layer == "shortcut"

    def kept(kind):
        return sum(math.prod(shape) for shape in
                   _state_shapes(cfg, kind, batch, slots).values()) * itemsize

    count = cfg.mixers.count
    attention_layers = count("attention")
    # every feed-forward by its own width: an "experts" layer's shared
    # experts stream as one, at theirs
    streamed = [plan for plan in (
        streams_feed_forward(cfg, batch, 1, cfg.param_dtype, d_ff=width)
        for width in feed_forward_widths(cfg)) if plan]
    # every attending layer reads a cache through the kernel, its own, a
    # ring or a lent one, where a step of these rows over its slots does
    attended = {kind: streams_attention(
        cfg, batch, 1, _ring_slots(cfg, slots)
        if kind == "window_attention" else slots, cfg.compute_dtype)
        for kind in ATTENDING if count(kind)}
    windows = count("window_attention") * kept("window_attention")
    sizes = {
        "cache_slots": slots,
        "cache_bytes": (1 + shortcut) * attention_layers * kept("attention")
        + windows,
        "ut_passes": cfg.n_passes * (1 + n_tokens),
        "ffn_streamed_layers": len(streamed),
        "ffn_streamed_bytes": cfg.n_passes * sum(
            plan["streamed_bytes"] for plan in streamed),
        "attention_streamed_layers": sum(
            (1 + shortcut) * count(kind)
            for kind, plan in attended.items() if plan),
        "attention_streamed_bytes": sum(
            (1 + shortcut) * count(kind) * cfg.n_passes
            * plan["streamed_bytes"]
            for kind, plan in attended.items() if plan),
    }
    # the attending layers that prefill runs at more than one position,
    # by kind and chunk: the chunks whose scores stay on the chip
    prefilled = cfg.mixers[:cfg.stateless_from]
    on_chip = {kind: [plan for plan in (streams_prefill_attention(
        cfg, batch, length, _ring_slots(cfg, slots)
        if kind == "window_attention" else slots, cfg.compute_dtype, pos,
        cfg.sliding_window if kind == "window_attention" else 0)
        for pos, length in _prefill_chunks(prompt_len, prefill_chunk))
        if plan] for kind in ATTENDING if kind in prefilled}
    on_chip = {kind: plans for kind, plans in on_chip.items() if plans}
    if on_chip:
        sizes.update(
            prefill_attention_streamed_layers=sum(
                prefilled.count(kind) for kind in on_chip),
            prefill_attention_streamed_chunks=max(map(len, on_chip.values())),
            prefill_attention_streamed_bytes=cfg.n_passes * sum(
                prefilled.count(kind) * plan["streamed_bytes"]
                for kind, plans in on_chip.items() for plan in plans))
    if shortcut or cfg.ffn_types:
        width = cfg.routed_experts + cfg.zero_experts
        (_, length), *_ = _prefill_chunks(prompt_len, prefill_chunk)
        sizes.update(experts_held=cfg.experts_held[1], router_width=width,
                     prefill_expert_tile=prefill_plan(
                         batch * length, cfg.experts_per_token, width,
                         cfg.d_model)["tile"] if length > 1
                     else _row_tile(batch))
    if cfg.ffn_types:
        chunks = _prefill_chunks(prompt_len, prefill_chunk)
        # a chunk the kernel takes sends no score through HBM
        kept_on_chip = [streams_latent_prefill(cfg, batch, length,
                                               pos + length)
                        for pos, length in chunks]
        taken = [plan for plan in kept_on_chip if plan]
        sizes.update(
            shared_experts=cfg.shared_experts,
            dense_layers=cfg.ffns.count("dense"),
            expert_layers=cfg.ffns.count("experts"),
            prefill_chunks=len(chunks),
            score_blocks=sum(
                _score_blocks(cfg, batch, length, pos + length)
                for (pos, length), plan in zip(chunks, kept_on_chip)
                if not plan),
            # every chunk expands what it attends again, or attends it
            # absorbed: nothing stays expanded from chunk to chunk
            expanded_bytes=0,
            latent_streamed_layers=attention_layers if taken else 0,
            latent_streamed_chunks=len(taken),
            latent_streamed_bytes=attention_layers * sum(
                plan["streamed_bytes"] for plan in taken))
    if cfg.layer_types:
        chunks = _prefill_chunks(prompt_len, prefill_chunk)
        sizes.update(
            attention_layers=attention_layers,
            ssm_layers=count("mamba") + count("mamba1"),
            state_bytes=count("mamba") * kept("mamba")
            + count("mamba1") * kept("mamba1"),
            # Mamba-2 scans a chunk of ssm_chunk at once, Mamba-1 along
            # the positions of one prefill chunk
            scan_chunks=(sum(-(-length // cfg.ssm_chunk)
                             for _, length in chunks) if count("mamba")
                         else len(chunks) if count("mamba1") else 0))
    if cfg.differential or any(count(kind) for kind in (
            "window_attention", "cross_attention", "mamba1")):
        scans = [ssm.streams_scan(cfg, batch, length) for _, length in
                 _prefill_chunks(prompt_len, prefill_chunk)
                 ] if count("mamba1") else []
        sizes.update(
            scan_streamed_layers=count("mamba1") if any(scans) else 0,
            scan_streamed_bytes=count("mamba1") * sum(
                plan["streamed_bytes"] for plan in scans if plan),
            window_layers=count("window_attention"),
            window_slots=_ring_slots(cfg, slots)
            if count("window_attention") else 0,
            window_cache_bytes=windows,
            shared_cache_bytes=kept("attention")
            if count("cross_attention") else 0,
            cross_layers=count("cross_attention"),
            memory_layers=count("gated_memory"),
            prefill_skipped_layers=cfg.n_layers - cfg.stateless_from)
    return sizes


def _score_blocks(cfg: ModelConfig, batch: int, queries: int,
                  reach: int) -> int:
    """The blocks one attention's prefill scores of ``queries`` positions
    a row over ``reach`` go in (``transformer.score_blocks``: latent
    attention's, expanded or absorbed; 1 for any other kind, whose blocks
    are of rows alone and its own business)."""
    if cfg.attention != "latent":
        return 1
    rows, in_queries = score_blocks(batch, cfg.n_heads, queries, reach)
    return (batch // rows) * (queries // in_queries)


def init_kv_cache(cfg: ModelConfig, batch: int, slots: int,
                  mesh=None) -> list[dict]:
    """Zeroed per-layer state of a call, by the layer's kind. Per-head
    attention: keys and values, one cache a pass, as
    ``transformer.cache_layout`` lays them: head-major, (passes, batch,
    key/value heads, slots, head_dim), or dense, (passes, batch, slots,
    key/value heads · head_dim). Latent attention: (passes, batch, slots,
    kv_lora_rank + qk_rope_dim), once. A "shortcut" layer: its two
    attentions' caches and its expert layer's counters
    (``transformer._block``). A "mamba" or "mamba1" layer: its convolution
    window and its recurrent state (``ssm.state_shapes``), whatever
    ``slots``. A "window_attention" layer: a ring of ``_ring_slots``. A
    layer that reads what another lends: None. A layer whose feed-forward
    is an expert layer: its ``counters`` beside its mixer's state."""
    def zeros(shapes: dict):
        return {name: jnp.zeros(shape, cfg.compute_dtype)
                for name, shape in shapes.items()}

    def attention():
        return zeros(_attention_cache_shapes(cfg, batch, slots, mesh))

    if cfg.layer == "shortcut":
        return [{"attn": [attention(), attention()],
                 "counters": jnp.zeros((len(COUNTERS),), jnp.int32)}
                for _ in range(cfg.n_layers)]
    # a layer that reads what another lends keeps nothing: None; one
    # whose feed-forward is an expert layer keeps its counters too
    counters = {"counters": jnp.zeros((len(COUNTERS),), jnp.int32)}
    return [dict(zeros(_state_shapes(cfg, kind, batch, slots, mesh)),
                 **(counters if ffn == "experts" else {})) or None
            for kind, ffn in zip(cfg.mixers, cfg.ffns)]


def forward_with_cache(params, tokens, cache, start, cfg: ModelConfig,
                       last_only: bool = False, mesh=None):
    """tokens (B, S) entering at position ``start`` → (logits (B, S, V),
    new cache); with ``last_only`` the head reads the last position alone,
    logits (B, 1, V): what a server samples from (at a vocabulary of 100k
    the logits of a 64 × 512 prompt are 13 GB), and so do the layers from
    ``cfg.stateless_from`` on, which keep no state: exact, since such a
    layer at a position reads that position alone and what was lent.
    Pass ``t`` of the stack writes and attends ``cache[...][t]`` alone; a state-space layer starts
    from the window and state its cache holds. ``mesh`` is the one the
    parameters are laid over, if any: the blocks take their one-chip
    kernels only without it. A step whose depth depends
    on the data (an exit threshold below 1.0) has no cached path."""
    if cfg.exit_threshold < 1.0:
        raise ValueError(
            f"exit_threshold {cfg.exit_threshold} is below 1.0: cached "
            "decoding runs every pass and serves the last")
    cfg = resolve_impls(cfg)
    b, s = tokens.shape
    positions = jnp.broadcast_to(start + jnp.arange(s)[None], (b, s))
    x = embed(params, tokens, cfg)

    def stack(x, cache, t):
        new_cache, lent, at, where = [], {}, start, positions
        for i, (blk, layer_cache, kind) in enumerate(zip(
                params["blocks"], cache, cfg.mixers)):
            if last_only and i == cfg.stateless_from and x.shape[1] > 1:
                # from here on a position's way reads nothing of the
                # positions before it but what was lent: the one that is
                # served goes on alone
                x, where, at = x[:, -1:], where[:, -1:], start + s - 1
                lent = {kind: lends[:, -1:] if kind == "gated_memory"
                        else lends for kind, lends in lent.items()}
            x, updated, lends = _block(
                x, blk, where, cfg, mesh, cache=layer_cache, slot=(t, at),
                kind=kind, layer=i, lent=lent.get(kind))
            lent.update(lender_of(cfg, i, updated if lends is None
                                  else lends))
            new_cache.append(updated)
        return x, new_cache

    x, cache = run_passes(x, cache, params, cfg, stack)
    if last_only:
        with jax.named_scope(scopes.HEAD):
            x = x[:, -1:]
    return head(params, x, cfg), cache


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
    cache = init_kv_cache(
        cfg, b, call_sizes(cfg, b, s_p, n_tokens)["cache_slots"], mesh)
    if mesh is not None:
        kv_sharding = NamedSharding(mesh, P(None, "dp", "tp", None, None))
        cache = [{k: jax.lax.with_sharding_constraint(v, kv_sharding)
                  for k, v in layer.items()} for layer in cache]

    def pick(logits, key):
        with jax.named_scope(scopes.SAMPLE):
            key, sub = jax.random.split(key)
            return key, _pick_token(logits[:, -1], sub, greedy, temperature,
                                    top_k, use_top_p, top_p)

    # Chunked prefill: attention during prefill peaks at (chunk × slots)
    # scores instead of (S_p × slots) — the long-prompt memory bound —
    # and a state-space layer's chunk starts from the state the one
    # before left. Chunk boundaries are static; the head reads the one
    # position that is sampled from.
    with jax.named_scope(scopes.PREFILL):
        for pos, length in _prefill_chunks(s_p, prefill_chunk):
            with jax.named_scope(scopes.EMBED):
                chunk = prompt[:, pos:pos + length]
            logits, cache = forward_with_cache(
                params, chunk, cache, pos, cfg, last_only=True, mesh=mesh)
        key, next_tok = pick(logits, key)
    counted_in_prefill = _counted(cfg, cache)

    def step(carry, _):
        tok, pos, cache, key = carry
        with jax.named_scope(scopes.DECODE_STEP):
            logits, cache = forward_with_cache(params, tok[:, None], cache,
                                               pos, cfg, mesh=mesh)
            key, nxt = pick(logits, key)
        return (nxt, pos + 1, cache, key), tok

    (_, _, cache, _), toks = jax.lax.scan(step, (next_tok, s_p, cache, key),
                                          None, length=n_tokens)
    counters = {}
    if counted_in_prefill is not None:
        counters = dict(zip(COUNTERS, _counted(cfg, cache)))
        # over the decode steps and layers, and over prefill's chunks and
        # layers, the held experts that got a token (what a call has to
        # read of the experts' weights) and the tiles it ran (what it read
        # of them)
        in_prefill = dict(zip(COUNTERS, counted_in_prefill))
        for name in ("experts_hit", "tiles"):
            counters[name + "_decode"] = (counters.pop(name)
                                          - in_prefill[name])
            counters[name + "_prefill"] = in_prefill[name]
    return toks.T, counters  # (B, n_tokens)


def _counted(cfg: ModelConfig, cache: list):
    """What the expert layers have counted in this call so far, summed
    over the layers (``moe.COUNTERS``); None where no layer counts."""
    if cfg.layer != "shortcut" and "experts" not in cfg.ffn_types:
        return None
    return sum(layer["counters"] for layer in cache
               if layer is not None and "counters" in layer)


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
    attention memory. A looped stack (``cfg.n_passes`` above 1) keeps
    one cache a pass a layer; :func:`call_sizes` says what a call of
    these shapes allocates. The other kinds of attention and layer
    (latent attention, shortcut layers, expert layers as a feed-forward,
    state-space layers, windows, lent caches and memories, differential
    pairs, grouped key/value heads, the multipliers, a rotary scaling, a
    tied head) are single-chip so far: with
    ``mesh`` they raise ``ValueError``."""
    return generate_with_counters(params, prompt, cfg, n_tokens, key,
                                  temperature, top_k, top_p, mesh,
                                  prefill_chunk)[0]


def generate_with_counters(params, prompt, cfg: ModelConfig, n_tokens: int,
                           key: jax.Array | None = None,
                           temperature: float = 0.0, top_k: int = 0,
                           top_p: float = 1.0, mesh=None,
                           prefill_chunk: int = 0):
    """:func:`generate`, with what the call counted on the device, from
    the same program: ((B, n_tokens) int32, counters). ``counters`` is
    empty but where layers have an expert layer; there the picks of
    the whole call (prefill and every step, all layers) are
    ``picks_held`` (they fell on experts held here), ``picks_zero``
    (zero-compute experts) and ``picks_absent`` (experts held elsewhere);
    ``experts_hit_decode`` is the held experts that got at least one
    token and ``tiles_decode`` the row tiles the grouped products ran
    (each reads one expert's matrices; an expert nobody picked has
    none), both summed over the decode steps and layers;
    ``experts_hit_prefill`` and ``tiles_prefill`` the same over the
    prompt's chunks and layers."""
    if mesh is not None and (cfg.attention, cfg.layer) != ("heads", "single"):
        raise ValueError(
            f"generate under a mesh implements attention='heads' and "
            f"layer='single' only, not {cfg.attention!r} and {cfg.layer!r}")
    if mesh is not None:
        refuse_served_only(cfg, "generate under a mesh")
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
