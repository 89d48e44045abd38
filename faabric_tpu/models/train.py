"""Training step over a device mesh.

The full step — forward, backward, optimizer — compiles as ONE XLA program
over the mesh: gradient allreduce over ``dp``, tensor-parallel collectives
over ``tp``, sequence gathers over ``sp``, all inserted by XLA from the
sharding annotations. Params are donated so the update is in-place in HBM.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from faabric_tpu.models import scopes
from faabric_tpu.models.transformer import (
    ModelConfig,
    init_params,
    loss_fn,
    param_shardings,
    refuse_served_only,
)


def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.01,
                   warmup_steps: int = 0, total_steps: int | None = None,
                   clip_norm: float | None = None):
    """AdamW with optional warmup-cosine schedule and global-norm
    gradient clipping — the standard large-model training recipe."""
    if total_steps:
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=lr, warmup_steps=max(1, warmup_steps),
            decay_steps=max(total_steps, warmup_steps + 1))
    elif warmup_steps:
        # No horizon given: warm up then HOLD at peak (never silently
        # decay to zero on an invented horizon)
        schedule = optax.join_schedules(
            [optax.linear_schedule(0.0, lr, warmup_steps),
             optax.constant_schedule(lr)], [warmup_steps])
    else:
        schedule = lr
    tx = optax.adamw(schedule, weight_decay=weight_decay)
    if clip_norm is not None:
        tx = optax.chain(optax.clip_by_global_norm(clip_norm), tx)
    return tx


def _build_step(cfg: ModelConfig, mesh: Optional[Mesh],
                optimizer, accum_steps: int):
    """The un-jitted step body shared by :func:`make_train_step` (one
    dispatch per step) and :func:`make_multi_step` (n steps per
    dispatch)."""
    for field, kind in (("attention", "heads"), ("layer", "single")):
        if getattr(cfg, field) != kind:
            # served so far, never trained: the remat plan and the
            # shardings of a step know per-head attention in single
            # layers, and an expert layer has no balance loss here
            raise ValueError(
                f"the train step implements {field}={kind!r} only, "
                f"not {field}={getattr(cfg, field)!r}")
    # the chunked scan has no backward pass here, and remat_plan and the
    # flash kernels know as many key/value heads as query heads
    refuse_served_only(cfg, "the train step")

    def grads_of(params, tokens, targets):
        with jax.named_scope(scopes.LOSS):
            return jax.value_and_grad(loss_fn)(params, tokens, targets,
                                               cfg, mesh)

    def step(params, opt_state, tokens, targets):
        if accum_steps > 1:
            b = tokens.shape[0]
            if b % accum_steps:
                raise ValueError(
                    f"batch {b} not divisible by accum_steps={accum_steps}")
            tok = tokens.reshape(accum_steps, b // accum_steps,
                                 *tokens.shape[1:])
            tgt = targets.reshape(accum_steps, b // accum_steps,
                                  *targets.shape[1:])
            if mesh is not None:
                # Each microbatch must stay dp-sharded (the contiguous
                # reshape would otherwise park whole microbatches on a
                # subset of dp shards, idling the rest of the mesh)
                mb_sharding = NamedSharding(mesh, P(None, "dp", "sp"))
                tok = jax.lax.with_sharding_constraint(tok, mb_sharding)
                tgt = jax.lax.with_sharding_constraint(tgt, mb_sharding)

            def acc(carry, mb):
                loss_sum, g_sum = carry
                loss, g = grads_of(params, mb[0], mb[1])
                return (loss_sum + loss,
                        jax.tree.map(jnp.add, g_sum, g)), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (loss_sum, g_sum), _ = jax.lax.scan(
                acc, (jnp.zeros(()), zeros), (tok, tgt))
            loss = loss_sum / accum_steps
        else:
            loss, grads = grads_of(params, tokens, targets)
        with jax.named_scope(scopes.OPTIMIZER):
            if accum_steps > 1:
                grads = jax.tree.map(lambda g: g / accum_steps, g_sum)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def make_train_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                    optimizer=None, accum_steps: int = 1):
    """Returns jitted ``step(params, opt_state, tokens, targets) →
    (params, opt_state, loss)``. ``accum_steps > 1`` splits the batch
    into that many microbatches and accumulates gradients with a
    ``lax.scan`` before the single optimizer update — big effective
    batches without the activation memory (means over equal microbatches
    equal the full-batch gradient exactly)."""
    optimizer = optimizer or make_optimizer()
    return jax.jit(_build_step(cfg, mesh, optimizer, accum_steps),
                   donate_argnums=(0, 1))


def make_multi_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                    optimizer=None, accum_steps: int = 1):
    """Returns jitted ``run(params, opt_state, tokens, targets, n) →
    (params, opt_state, last_loss)`` executing ``n`` whole train steps
    inside ONE compiled program (``lax.scan`` over the step body).

    This puts the training loop itself on the device: one dispatch —
    and, on a remote PJRT client, one network round-trip — per n steps
    instead of per step. ``tokens``/``targets`` carry a leading step
    axis of length n (a fresh batch per step), or the plain batch shape
    to reuse one batch every step (benchmarking)."""
    optimizer = optimizer or make_optimizer()
    step = _build_step(cfg, mesh, optimizer, accum_steps)

    @partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1))
    def run(params, opt_state, tokens, targets, n: int):
        per_step = tokens.ndim == 3
        if per_step and tokens.shape[0] != n:
            raise ValueError(
                f"tokens carry {tokens.shape[0]} per-step batches, n={n}")

        def body(carry, xs):
            p, o = carry
            tok, tgt = xs if per_step else (tokens, targets)
            p, o, loss = step(p, o, tok, tgt)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state),
            (tokens, targets) if per_step else None, length=n)
        return params, opt_state, losses[-1]

    return run


def init_train_state(key: jax.Array, cfg: ModelConfig,
                     mesh: Optional[Mesh] = None, optimizer=None):
    """Params + optimizer state, laid out over the mesh when given."""
    optimizer = optimizer or make_optimizer()
    params = init_params(key, cfg)
    if mesh is not None:
        params = jax.device_put(params, param_shardings(mesh, cfg))
    opt_state = optimizer.init(params)
    return params, opt_state


def data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp", "sp"))
