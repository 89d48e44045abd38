"""Flagship model: decoder-only transformer, TPU-first.

Pure-JAX pytree params (no framework indirection between the model and
XLA), written for the MXU and the mesh:
- matmuls stay large and batched, activations compute in bfloat16 while
  params/optimizer stay float32 (classic mixed precision);
- every weight has an explicit PartitionSpec: attention heads and MLP
  hidden shard over ``tp``, batch over ``dp``, sequence over ``sp``
  (Megatron-style sequence parallelism on the norm/MLP path — XLA inserts
  the gathers around attention);
- blocks are ``jax.checkpoint``-wrapped so long-context activations
  rematerialise instead of living in HBM;
- static shapes and a Python-unrolled layer loop: everything under jit
  traces once.

The reference is a serverless runtime with no models; this is the
framework's own flagship workload (SURVEY §5.7: the deliverable substrate
must carry DP/TP/SP strategies), exercised by __graft_entry__ and bench.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048
    max_seq: int = 2048
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "reference" = plain jnp attention; "flash" = the Pallas fused kernel
    # (ops/flash_attention.py) — identical numerics, no (S, S) scores in
    # HBM; "ring" = sequence-parallel ring attention over the sp axis
    # (parallel/ring_attention.py) — the long-context path that never
    # gathers the sequence; "auto" (default) = flash on TPU, reference on
    # CPU (interpret-mode Pallas is for tests, not speed)
    attention_impl: str = "auto"
    # "reference" = inline jnp RMS norm; "fused" = the Pallas kernel
    # (ops/rms_norm.py); "auto" = fused on TPU, reference on CPU
    norm_impl: str = "auto"
    # The block's kinds. The defaults are this repository's first block;
    # a configuration file names others (benchmarks/configs/).
    # "gelu": gelu(h·w1)·w2 | "swiglu": (silu(h·wg) ⊙ h·w1)·w2
    ffn: str = "gelu"
    # "pre": a norm on each sub-layer's input | "sandwich": one on its
    # output too, before the residual (scales ln1_post, ln2_post)
    norm_placement: str = "pre"
    # rotary lanes paired: "neighbours" (2i, 2i+1) | "halves" (i, i+d/2)
    rope_pairing: str = "neighbours"
    norm_eps: float = 1e-6
    # A looped stack: every token passes the same blocks n_passes times,
    # the final norm closing each pass; an exit gate on the normed state
    # gives each pass its share of probability, and the head reads the
    # first pass whose cumulative share reaches exit_threshold (at 1.0
    # the last pass, for every token)
    n_passes: int = 1
    exit_threshold: float = 1.0

    def __post_init__(self):
        for field, kinds in (("ffn", ("gelu", "swiglu")),
                             ("norm_placement", ("pre", "sandwich")),
                             ("rope_pairing", ("neighbours", "halves"))):
            if getattr(self, field) not in kinds:
                raise ValueError(f"{field} {getattr(self, field)!r} is not "
                                 f"one of {kinds}")
        if self.n_passes < 1:
            raise ValueError(f"n_passes {self.n_passes} is below 1")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, cfg.param_dtype)
                / np.sqrt(fan_in))

    def ones():
        return jnp.ones((cfg.d_model,), cfg.param_dtype)

    blocks = []
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[i], 4)
        blk = {
            "ln1": ones(),
            "wqkv": dense(bk[0], (cfg.d_model, 3, cfg.n_heads, cfg.head_dim),
                          cfg.d_model),
            "wo": dense(bk[1], (cfg.n_heads, cfg.head_dim, cfg.d_model),
                        cfg.d_model),
            "ln2": ones(),
            "w1": dense(bk[2], (cfg.d_model, cfg.d_ff), cfg.d_model),
            "w2": dense(bk[3], (cfg.d_ff, cfg.d_model), cfg.d_ff),
        }
        if cfg.ffn == "swiglu":
            blk["wg"] = dense(jax.random.fold_in(keys[i], 4),
                              (cfg.d_model, cfg.d_ff), cfg.d_model)
        if cfg.norm_placement == "sandwich":
            blk["ln1_post"], blk["ln2_post"] = ones(), ones()
        blocks.append(blk)
    params = {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.d_model), cfg.d_model),
        "blocks": blocks,
        "ln_f": ones(),
        "lm_head": dense(keys[-1], (cfg.d_model, cfg.vocab_size), cfg.d_model),
    }
    if cfg.n_passes > 1:
        gk = jax.random.fold_in(key, cfg.n_layers)
        params["exit_gate"] = {"w": dense(gk, (cfg.d_model,), cfg.d_model),
                               "b": jnp.zeros((), cfg.param_dtype)}
    return params


def param_shardings(mesh: Mesh, cfg: ModelConfig) -> dict:
    """PartitionSpecs per weight: heads/hidden over tp, vocab over tp for
    the embedding table halves (keeps the biggest tables sharded)."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    block = {
        "ln1": ns(),
        "wqkv": ns(None, None, "tp", None),
        "wo": ns("tp", None, None),
        "ln2": ns(),
        "w1": ns(None, "tp"),
        "w2": ns("tp", None),
    }
    if cfg.ffn == "swiglu":
        block["wg"] = ns(None, "tp")
    if cfg.norm_placement == "sandwich":
        block["ln1_post"], block["ln2_post"] = ns(), ns()
    shardings = {
        "embed": ns("tp", None),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
        "ln_f": ns(),
        "lm_head": ns(None, "tp"),
    }
    if cfg.n_passes > 1:
        shardings["exit_gate"] = {"w": ns(), "b": ns()}
    return shardings


def shard_params(params: dict, mesh: Mesh, cfg: ModelConfig) -> dict:
    return jax.device_put(params, param_shardings(mesh, cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          pairing: str = "neighbours") -> jax.Array:
    """Rotary embeddings over the head dim: x (B, S, H, D). Lane i of a
    pair turns with lane i+1 ("neighbours") or with lane i+D/2
    ("halves", the rotate-half form)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None, None].astype(jnp.float32) \
        * freqs[None, None, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if pairing == "halves":
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
        return out.astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal attention, (B, S, H, D); fp32 softmax accumulators."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = q.shape[1]
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _head_major(cache: jax.Array) -> jax.Array:
    """Pin a (passes, batch, heads, slots, head_dim) cache row-major in
    memory. Left to itself XLA:TPU lays the decode loop's carried cache
    heads-minor whatever the logical order, the heads padded to a tile's
    128 lanes: with 16 heads, eight times the bytes at every step."""
    return with_layout_constraint(cache, Layout(tuple(range(cache.ndim))))


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
    # A slot the call has not written yet holds whatever its memory held:
    # XLA:TPU drops the zero fill of a cache that it sees written only
    # through a loop (``AllocateBuffer`` in the optimized HLO). Its weight
    # is 0, and 0 × NaN is NaN: such values never reach the sum.
    written = (k_pos < length)[None, None, :, None]
    return jnp.einsum("bhqk,bhkd->bqhd", probs,
                      jnp.where(written, cache_v, 0))


def _attend_through_cache(q, k, v, cache: dict, slot: tuple):
    """Write these tokens' keys and values into pass ``t``'s cache from
    position ``start`` on (``slot = (t, start)``), then attend over that
    pass's cache up to themselves: a pass reads no other pass's cache.
    Returns (attention, the updated cache)."""
    t, start = slot
    updated = {
        name: _head_major(jax.lax.dynamic_update_slice(
            cache[name], new.transpose(0, 2, 1, 3)[None],
            (t, 0, 0, start, 0)))
        for name, new in (("k", k), ("v", v))}
    attn = _cached_attention(
        q, *(jax.lax.dynamic_index_in_dim(updated[name], t, 0,
                                          keepdims=False)
             for name in ("k", "v")), start + q.shape[1])
    return attn, updated


def resolve_impls(cfg: ModelConfig, mesh: Optional[Mesh] = None) -> ModelConfig:
    """Resolve "auto" kernel choices for the current backend, and downgrade
    combinations the mesh can't run: flash under a mesh runs shard_mapped
    over (dp, tp), so a sequence-sharded model (sp > 1) routes to ring
    attention, which keeps the sequence distributed; the fused norm kernel
    stays single-stream."""
    att, norm = cfg.attention_impl, cfg.norm_impl
    on_tpu = jax.default_backend() == "tpu"
    if att == "auto":
        att = "flash" if on_tpu else "reference"
    if norm == "auto":
        norm = "fused" if on_tpu else "reference"
    if mesh is not None:
        if att == "flash" and mesh.shape.get("sp", 1) > 1:
            att = "ring"
        if norm == "fused":
            norm = "reference"
    if (att, norm) != (cfg.attention_impl, cfg.norm_impl):
        cfg = dataclasses.replace(cfg, attention_impl=att, norm_impl=norm)
    return cfg


def _norm(x: jax.Array, scale: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.norm_impl == "fused":
        from faabric_tpu.ops.rms_norm import rms_norm

        return rms_norm(x, scale, cfg.norm_eps)
    return _rms_norm(x, scale, cfg.norm_eps)


def _sharded_flash(q, k, v, mesh: Mesh):
    """Flash attention under a mesh: batch (dp) and heads (tp) are
    embarrassingly parallel for attention, so each shard runs the Pallas
    kernel on its local (B/dp, S, H/tp, D) slab — no collectives."""
    from faabric_tpu.ops.flash_attention import flash_attention

    spec = P("dp", None, "tp", None)
    # check off: pallas_call's out_shape carries no varying-mesh-axes
    # annotation, and this wrapper is trivially per-shard anyway
    return jax.shard_map(lambda q, k, v: flash_attention(q, k, v, True),
                         mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention_sublayer(x: jax.Array, blk: dict, positions: jax.Array,
                       cfg: ModelConfig, mesh: Optional[Mesh] = None,
                       cache: Optional[dict] = None,
                       slot: Optional[tuple] = None) -> tuple:
    """Norm, attention, (norm,) residual — shared by the dense and MoE
    families (honours cfg.attention_impl / norm_impl). Without ``cache``
    the tokens attend causally among themselves; with this layer's
    ``cache`` they go through it (:func:`_attend_through_cache`).
    Returns (x, the updated cache or None)."""
    h = _norm(x, blk["ln1"], cfg)
    qkv = jnp.einsum("bsd,dthe->tbshe", h,
                     blk["wqkv"].astype(cfg.compute_dtype))
    q, k, v = qkv[0], qkv[1], qkv[2]
    q = _rope(q, positions, cfg.rope_theta, cfg.rope_pairing)
    k = _rope(k, positions, cfg.rope_theta, cfg.rope_pairing)
    if cache is not None:
        attn, cache = _attend_through_cache(q, k, v, cache, slot)
    elif cfg.attention_impl == "flash":
        from faabric_tpu.ops.flash_attention import flash_attention

        if mesh is not None:
            attn = _sharded_flash(q, k, v, mesh)
        else:
            attn = flash_attention(q, k, v, True)
    elif cfg.attention_impl == "ring" and mesh is not None:
        from faabric_tpu.parallel.ring_attention import ring_attention

        attn = ring_attention(q, k, v, mesh, axis="sp",
                              batch_axis="dp", head_axis="tp")
    else:
        attn = _attention(q, k, v)
    out = jnp.einsum("bshe,hed->bsd", attn,
                     blk["wo"].astype(cfg.compute_dtype))
    if cfg.norm_placement == "sandwich":
        out = _norm(out, blk["ln1_post"], cfg)
    return x + out, cache


def _block(x: jax.Array, blk: dict, positions: jax.Array,
           cfg: ModelConfig, mesh: Optional[Mesh] = None,
           cache: Optional[dict] = None,
           slot: Optional[tuple] = None) -> tuple:
    """The one transformer block, with or without a KV cache, in the
    kinds the configuration names. Returns (x, the updated cache or
    None)."""
    x, cache = attention_sublayer(x, blk, positions, cfg, mesh, cache, slot)
    h = _norm(x, blk["ln2"], cfg)
    ff = h @ blk["w1"].astype(cfg.compute_dtype)
    if cfg.ffn == "swiglu":
        ff = jax.nn.silu(h @ blk["wg"].astype(cfg.compute_dtype)) * ff
    else:
        ff = jax.nn.gelu(ff)
    out = ff @ blk["w2"].astype(cfg.compute_dtype)
    if cfg.norm_placement == "sandwich":
        out = _norm(out, blk["ln2_post"], cfg)
    return x + out, cache


def run_passes(x: jax.Array, carry: Any, params: dict, cfg: ModelConfig,
               stack) -> tuple:
    """The stack ``cfg.n_passes`` times over the same weights, the final
    norm closing each pass: ``stack(x, carry, t) -> (x, carry)`` is all
    the blocks once, ``t`` the pass. Returns (the normed state the head
    reads, carry). More than one pass is a rolled loop, the stack traced
    once. Below an exit threshold of 1.0 each token's state is that of
    the first pass at which the exit gate's cumulative share reaches the
    threshold; at 1.0 every token takes the last pass and the gate
    decides nothing."""
    def one_pass(x, carry, t):
        with jax.named_scope("ut_pass"):
            x, carry = stack(x, carry, t)
            return _norm(x, params["ln_f"], cfg), carry

    if cfg.n_passes == 1:
        return one_pass(x, carry, 0)
    if cfg.exit_threshold >= 1.0:
        return jax.lax.fori_loop(
            0, cfg.n_passes, lambda t, xc: one_pass(*xc, t), (x, carry))

    gate = jax.tree.map(lambda w: w.astype(jnp.float32),
                        params["exit_gate"])
    last = cfg.n_passes - 1

    def gated(t, state):
        x, carry, chosen, remaining, reached, done = state
        x, carry = one_pass(x, carry, t)
        lam = jax.nn.sigmoid(x.astype(jnp.float32) @ gate["w"] + gate["b"])
        # p_t = lam_t · prod_{s<t}(1 - lam_s); the last pass takes the rest
        # of the probability, so whoever is still in leaves there
        reached = reached + lam * remaining
        exits = ((reached >= cfg.exit_threshold) | (t == last)) & ~done
        chosen = jnp.where(exits[..., None], x, chosen)
        return (x, carry, chosen, remaining * (1.0 - lam), reached,
                done | exits)

    zeros = jnp.zeros(x.shape[:-1], jnp.float32)
    state = (x, carry, jnp.zeros_like(x), zeros + 1.0, zeros,
             zeros.astype(bool))
    _, carry, chosen, *_ = jax.lax.fori_loop(0, cfg.n_passes, gated, state)
    return chosen, carry


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S, V)."""
    def maybe_constrain(x, *spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        return x

    cfg = resolve_impls(cfg, mesh)

    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x = maybe_constrain(x, "dp", "sp", None)

    block_fn = _block
    if cfg.remat:
        block_fn = jax.checkpoint(_block, static_argnums=(3, 4))

    def stack(x, carry, _t):
        for blk in params["blocks"]:
            x, _ = block_fn(x, blk, positions, cfg, mesh)
            x = maybe_constrain(x, "dp", "sp", None)
        return x, carry

    x, _ = run_passes(x, None, params, cfg, stack)
    logits = x @ params["lm_head"].astype(cfg.compute_dtype)
    return maybe_constrain(logits.astype(jnp.float32), "dp", "sp", None)


def token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token negative log-likelihood — THE loss definition, shared by
    training (dense + MoE) and evaluation so they can never diverge."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: ModelConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    return jnp.mean(token_nll(forward(params, tokens, cfg, mesh), targets))
