"""The plain reference of the looped decoder (Ouro, ByteDance, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741): its
forward pass in straightforward float32 ``jax.numpy``, every product at
``highest``, plain Python loops over passes and layers, no cache, no
kernels. It imports nothing of ``faabric_tpu`` and takes nothing the
program has made; weights and tokens come from ``benchmarks/weights_ouro.py``
and the seed, in whatever type they were made and upcast here, layer by
layer, so that program and reference start from the same numbers and the
reference holds no float32 copy of the whole model.

With ``x`` the embedded tokens, for pass t = 0..passes-1, for every layer,
the same weights in every pass:

    a = RMSNorm(x; ln1)            q, k, v = a·Wq, a·Wk, a·Wv   (no bias)
    q, k = RoPE(q), RoPE(k)        lanes i and i + D/2 paired (rotate-half)
    o = softmax(q·kᵀ/√D + causal)·v        keys and values of pass t only
    x = x + RMSNorm(o·Wo; ln1_post)        the "sandwich"
    m = RMSNorm(x; ln2)
    x = x + RMSNorm((silu(m·Wg) ⊙ (m·W1))·W2; ln2_post)
    after the last layer: x = RMSNorm(x; ln_f); λ_t = sigmoid(x·w_e + b_e);
    x goes on into pass t+1

Exit: p_t = λ_t·Π_{s<t}(1 − λ_s) for t < passes-1, the last pass takes the
rest; the logits are the head on the first pass whose cumulative p reaches
``exit_threshold``. At 1.0 that is the last pass, for every token.

``sizes`` is ``weights_ouro.sizes_of(config)``: of it the reference reads
``rope_theta``, ``norm_eps``, ``passes`` and ``exit_threshold``.
``precision`` is "float32" or "fp8", the control of the correctness check
one step below bfloat16: both operands of every matrix product rounded to
float8_e4m3 under a per-tensor scale, the products themselves float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# the products, and the fp8 control's rounding of their operands, are the
# first reference's: one definition of "one precision below bfloat16"
from benchmarks.reference.transformer import _mm


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta: float):
    """x (S, H, D); position i is row i; lane j turns with lane j + D/2."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def layer(x, blk, theta: float, eps: float, precision: str):
    """One decoder layer over one sequence: x (S, D) → (S, D)."""
    blk = jax.tree.map(lambda w: w.astype(jnp.float32), blk)
    s = x.shape[0]
    a = rms_norm(x, blk["ln1"], eps)
    qkv = _mm("sd,dthe->tshe", a, blk["wqkv"], precision)
    q, k, v = rope(qkv[0], theta), rope(qkv[1], theta), qkv[2]
    scores = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -1e30), axis=-1)
    o = _mm("hqk,khd->qhd", probs, v, precision)
    x = x + rms_norm(_mm("she,hed->sd", o, blk["wo"], precision),
                     blk["ln1_post"], eps)
    m = rms_norm(x, blk["ln2"], eps)
    gated = jax.nn.silu(_mm("sd,df->sf", m, blk["wg"], precision)) \
        * _mm("sd,df->sf", m, blk["w1"], precision)
    return x + rms_norm(_mm("sf,fd->sd", gated, blk["w2"], precision),
                        blk["ln2_post"], eps)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(x, blk, theta, eps, precision):
    return layer(x, blk, theta, eps, precision)


@functools.partial(jax.jit, static_argnums=(2,))
def _close_pass(x, ln_f, eps, gate):
    """The final norm that closes a pass, and the exit gate's λ on it."""
    x = rms_norm(x, ln_f.astype(jnp.float32), eps)
    lam = jax.nn.sigmoid(
        jnp.einsum("sd,d->s", x, gate["w"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
        + gate["b"].astype(jnp.float32))
    return x, lam


@functools.partial(jax.jit, static_argnums=(2,))
def _head_jit(x, lm_head, precision):
    return _mm("sd,dv->sv", x, lm_head.astype(jnp.float32), precision)


def exit_passes(lams: list, threshold: float):
    """The pass each position is read from: the first whose cumulative
    exit probability reaches ``threshold``; the last pass takes what is
    left, so at 1.0 every position reads the last."""
    last = len(lams) - 1
    if threshold >= 1.0:
        return jnp.full(lams[0].shape, last, jnp.int32)
    remaining = jnp.ones_like(lams[0])
    reached = jnp.zeros_like(lams[0])
    chosen = jnp.full(lams[0].shape, last, jnp.int32)
    done = jnp.zeros(lams[0].shape, bool)
    for t, lam in enumerate(lams):
        reached = reached + (remaining if t == last else lam * remaining)
        exits = (reached >= threshold) & ~done
        chosen = jnp.where(exits, t, chosen)
        done = done | exits
        remaining = remaining * (1.0 - lam)
    return chosen


def states_of(params: dict, tokens, sizes: dict,
              precision: str = "float32"):
    """tokens (S,) → (the normed state after every pass, each (S, D); the
    pass every position is read from)."""
    theta, eps = float(sizes["rope_theta"]), float(sizes["norm_eps"])
    x = params["embed"][tokens].astype(jnp.float32)
    states, lams = [], []
    for _ in range(int(sizes["passes"])):
        for blk in params["blocks"]:
            x = _layer_jit(x, blk, theta, eps, precision)
        x, lam = _close_pass(x, params["ln_f"], eps, params["exit_gate"])
        states.append(x)
        lams.append(lam)
    return states, exit_passes(lams, float(sizes["exit_threshold"]))


def logits_of(params: dict, tokens, sizes: dict,
              precision: str = "float32", at: slice = slice(None)):
    """tokens (S,) int32 → logits at the positions ``at`` (all of them by
    default), one sequence."""
    states, chosen = states_of(params, tokens, sizes, precision)
    picked = jnp.take_along_axis(jnp.stack(states), chosen[None, :, None],
                                 axis=0)[0]
    return _head_jit(picked[at], params["lm_head"], precision)
