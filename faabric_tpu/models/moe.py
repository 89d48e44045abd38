"""Mixture-of-Experts transformer: the second model family, exercising
expert parallelism over the ``ep`` mesh axis.

Top-k routing (switch-style top-1 by default, GShard-style top-2+ via
``router_top_k``) with fixed expert capacity, in the einsum-dispatch
formulation: a one-hot dispatch tensor scatters tokens into per-expert
buffers, experts run as one batched matmul pair, and the combine einsum
gathers results weighted by the router gates (renormalized over the
selected experts for k > 1). Capacity is allocated slot-major — every
token's first choice outranks any token's second choice, the standard
priority rule. Experts shard over ``ep``; with the dispatch/combine
sharding constraints XLA inserts the token all_to_alls over ICI — the
MoE analog of the MPI world's alltoall (SURVEY §2.4), expressed entirely
through shardings.

Static shapes throughout: capacity is fixed, overflow tokens drop (their
residual passes through), standard for TPU switch routing.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from faabric_tpu.models.transformer import (
    ModelConfig,
    _rms_norm,
    attention_sublayer,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig(ModelConfig):
    n_experts: int = 4
    capacity_factor: float = 1.25
    # Experts per token: 1 = switch routing (gate = raw top prob),
    # >1 = GShard-style with gates renormalized over the selected experts
    router_top_k: int = 1
    # Auxiliary load-balancing loss weight (switch transformer)
    aux_loss_weight: float = 0.01


def _refuse_other_kinds(cfg: MoEConfig) -> None:
    """The expert layer is a two-matrix GELU behind one pre-norm, passed
    once: a configuration that names another kind would be computed as
    another network (the attention half honours ``rope_pairing``)."""
    for field, kind in (("ffn", "gelu"), ("norm_placement", "pre"),
                        ("n_passes", 1)):
        if getattr(cfg, field) != kind:
            raise ValueError(
                f"the MoE family implements {field}={kind!r} only, "
                f"not {field}={getattr(cfg, field)!r}")


def init_moe_params(key: jax.Array, cfg: MoEConfig) -> dict:
    _refuse_other_kinds(cfg)
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, cfg.param_dtype)
                / np.sqrt(fan_in))

    blocks = []
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[i], 5)
        blocks.append({
            "ln1": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "wqkv": dense(bk[0], (cfg.d_model, 3, cfg.n_heads, cfg.head_dim),
                          cfg.d_model),
            "wo": dense(bk[1], (cfg.n_heads, cfg.head_dim, cfg.d_model),
                        cfg.d_model),
            "ln2": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "router": dense(bk[2], (cfg.d_model, cfg.n_experts), cfg.d_model),
            "w1": dense(bk[3], (cfg.n_experts, cfg.d_model, cfg.d_ff),
                        cfg.d_model),
            "w2": dense(bk[4], (cfg.n_experts, cfg.d_ff, cfg.d_model),
                        cfg.d_ff),
        })
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.d_model), cfg.d_model),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "lm_head": dense(keys[-1], (cfg.d_model, cfg.vocab_size), cfg.d_model),
    }


def moe_param_shardings(mesh: Mesh, cfg: MoEConfig) -> dict:
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    block = {
        "ln1": ns(),
        "wqkv": ns(None, None, "tp", None),
        "wo": ns("tp", None, None),
        "ln2": ns(),
        "router": ns(),
        # Experts shard over ep; each expert's hidden over tp
        "w1": ns("ep", None, "tp"),
        "w2": ns("ep", "tp", None),
    }
    return {
        "embed": ns("tp", None),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
        "ln_f": ns(),
        "lm_head": ns(None, "tp"),
    }


def _capacity(cfg: MoEConfig, seq: int) -> int:
    return max(1, int(np.ceil(
        seq * cfg.router_top_k * cfg.capacity_factor / cfg.n_experts)))


def moe_dispatch_combine(x: jax.Array, router: jax.Array, cfg: MoEConfig
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Routing + slot-major capacity allocation shared by the single-mesh
    layer below and the pipeline's ep-local path
    (parallel/pipeline.py:_pp_moe_ffn): x (B, S, D) →
    (dispatch (B, S, E, C), combine_w (B, S, E, C), aux scalar).
    Pure jnp — identical results wherever it runs, which is what keeps
    the two paths loss-parity-exact."""
    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.router_top_k
    c = _capacity(cfg, s)

    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_probs, topk_idx = jax.lax.top_k(probs, k)   # (B, S, K)
    if k == 1:
        gates = topk_probs                           # switch: raw prob
    else:
        gates = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)

    # Switch load-balancing aux loss over FIRST choices: E · Σ_e f_e · p_e
    top1_hot = jax.nn.one_hot(topk_idx[..., 0], e, dtype=jnp.float32)
    density = top1_hot.mean(axis=1)                  # fraction per expert
    density_proxy = probs.mean(axis=1)
    aux = (density * density_proxy).sum(axis=-1).mean() * e

    # Capacity allocation, slot-major: flatten (K, S) assignments so all
    # first choices outrank any second choice, cumsum positions within
    # each expert's buffer, drop past capacity
    oh = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)      # (B, S, K, E)
    oh_flat = oh.transpose(0, 2, 1, 3).reshape(b, k * s, e)  # slot-major
    pos_flat = ((jnp.cumsum(oh_flat, axis=1) - 1.0) * oh_flat).sum(axis=-1)
    keep = (pos_flat < c).astype(jnp.float32)
    disp_flat = (oh_flat * keep[..., None])[..., None] \
        * jax.nn.one_hot(pos_flat.astype(jnp.int32), c,
                         dtype=jnp.float32)[:, :, None, :]
    disp = disp_flat.reshape(b, k, s, e, c)                  # per slot
    dispatch = disp.sum(axis=1)                              # (B, S, E, C)
    combine_w = (disp
                 * gates.transpose(0, 2, 1)[..., None, None]).sum(axis=1)
    return dispatch, combine_w, aux


def _moe_layer(x: jax.Array, blk: dict, cfg: MoEConfig,
               mesh: Optional[Mesh]) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) → (out, aux_loss)."""
    dispatch, combine_w, aux = moe_dispatch_combine(x, blk["router"], cfg)

    def constrain(arr, *spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                arr, NamedSharding(mesh, P(*spec)))
        return arr

    xf = x.astype(jnp.float32)
    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, xf)
    # Token buffers shard over ep with the experts → XLA all_to_alls the
    # tokens to their expert's chips
    expert_in = constrain(expert_in, "ep", "dp", None, None)

    w1 = blk["w1"].astype(jnp.float32)
    w2 = blk["w2"].astype(jnp.float32)
    h = jax.nn.gelu(jnp.einsum("ebcd,edf->ebcf", expert_in, w1))
    out_e = jnp.einsum("ebcf,efd->ebcd", h, w2)
    out_e = constrain(out_e, "ep", "dp", None, None)

    out = jnp.einsum("bsec,ebcd->bsd", combine_w, out_e)
    return out.astype(x.dtype), aux.astype(jnp.float32)


def moe_forward(params: dict, tokens: jax.Array, cfg: MoEConfig,
                mesh: Optional[Mesh] = None
                ) -> tuple[jax.Array, jax.Array]:
    """tokens (B, S) → (logits (B, S, V), aux_loss scalar)."""
    _refuse_other_kinds(cfg)

    def constrain(arr, *spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                arr, NamedSharding(mesh, P(*spec)))
        return arr

    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x = constrain(x, "dp", None, None)

    # Resolve "auto" kernels + mesh downgrades (flash shard_maps over
    # (dp, tp); the fused norm stays single-stream)
    from faabric_tpu.models.transformer import resolve_impls
    cfg = resolve_impls(cfg, mesh)

    aux_total = jnp.zeros((), jnp.float32)
    for blk in params["blocks"]:
        x, _ = attention_sublayer(x, blk, positions, cfg, mesh)
        h = _rms_norm(x, blk["ln2"], cfg.norm_eps)
        moe_out, aux = _moe_layer(h, blk, cfg, mesh)
        aux_total = aux_total + aux
        x = x + moe_out
        x = constrain(x, "dp", None, None)

    x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(cfg.compute_dtype)
              ).astype(jnp.float32)
    return logits, aux_total / max(1, cfg.n_layers)


def moe_loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
                cfg: MoEConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    from faabric_tpu.models.transformer import token_nll

    logits, aux = moe_forward(params, tokens, cfg, mesh)
    return jnp.mean(token_nll(logits, targets)) + cfg.aux_loss_weight * aux


def make_moe_train_step(cfg: MoEConfig, mesh: Optional[Mesh] = None,
                        optimizer=None):
    import optax

    from faabric_tpu.models.train import make_optimizer

    optimizer = optimizer or make_optimizer()

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(moe_loss_fn)(params, tokens,
                                                      targets, cfg, mesh)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))
