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

    blocks = []
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[i], 4)
        blocks.append({
            "ln1": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "wqkv": dense(bk[0], (cfg.d_model, 3, cfg.n_heads, cfg.head_dim),
                          cfg.d_model),
            "wo": dense(bk[1], (cfg.n_heads, cfg.head_dim, cfg.d_model),
                        cfg.d_model),
            "ln2": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "w1": dense(bk[2], (cfg.d_model, cfg.d_ff), cfg.d_model),
            "w2": dense(bk[3], (cfg.d_ff, cfg.d_model), cfg.d_ff),
        })
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.d_model), cfg.d_model),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "lm_head": dense(keys[-1], (cfg.d_model, cfg.vocab_size), cfg.d_model),
    }


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
    return {
        "embed": ns("tp", None),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
        "ln_f": ns(),
        "lm_head": ns(None, "tp"),
    }


def shard_params(params: dict, mesh: Mesh, cfg: ModelConfig) -> dict:
    return jax.device_put(params, param_shardings(mesh, cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + 1e-6).astype(x.dtype)) * scale.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings over the head dim: x (B, S, H, D)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, :, None, None].astype(jnp.float32) \
        * freqs[None, None, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
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

        return rms_norm(x, scale)
    return _rms_norm(x, scale)


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
                       cfg: ModelConfig,
                       mesh: Optional[Mesh] = None) -> jax.Array:
    """Pre-norm attention + residual — shared by the dense and MoE
    families (honours cfg.attention_impl / norm_impl)."""
    h = _norm(x, blk["ln1"], cfg)
    qkv = jnp.einsum("bsd,dthe->tbshe", h,
                     blk["wqkv"].astype(cfg.compute_dtype))
    q, k, v = qkv[0], qkv[1], qkv[2]
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    if cfg.attention_impl == "flash":
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
    return x + jnp.einsum("bshe,hed->bsd", attn,
                          blk["wo"].astype(cfg.compute_dtype))


def _block(x: jax.Array, blk: dict, positions: jax.Array,
           cfg: ModelConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    x = attention_sublayer(x, blk, positions, cfg, mesh)
    h = _norm(x, blk["ln2"], cfg)
    ff = jax.nn.gelu(h @ blk["w1"].astype(cfg.compute_dtype))
    return x + ff @ blk["w2"].astype(cfg.compute_dtype)


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
    for blk in params["blocks"]:
        x = block_fn(x, blk, positions, cfg, mesh)
        x = maybe_constrain(x, "dp", "sp", None)

    x = _norm(x, params["ln_f"], cfg)
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
