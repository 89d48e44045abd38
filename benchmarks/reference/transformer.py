"""The plain reference: the block this repository's model code implements,
in straightforward float32 ``jax.numpy``, with its loss, its gradients and
AdamW. It imports nothing of ``faabric_tpu`` and takes nothing the program
has made; weights and tokens come from ``benchmarks/weights.py`` and the
seed.

The block (one decoder layer, as ``PERF.md`` section 4 states it): pre-norm
RMSNorm (eps 1e-6, no bias), multi-head causal attention with rotary
embeddings over the whole head (pairs of neighbouring lanes), output
projection, residual; RMSNorm, a two-matrix MLP with tanh-GELU, residual.
A final RMSNorm and an untied output head. Loss: mean next-token negative
log-likelihood.

``precision`` is "float32" (every product at ``highest``) or "fp8": the
control of the correctness check, one step below the bfloat16 the
configurations compute in. There both operands of every matrix product
are rounded to float8_e4m3 under a per-tensor scale (amax → 448), with a
straight-through gradient, and the products themselves stay float32.

Memory: the forward runs layer by layer through one jitted layer
function; gradients are taken a row at a time with each layer
rematerialised, and summed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-6
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
PRECISIONS = ("float32", "fp8")


def _q8(x):
    """Round to float8_e4m3 under a per-tensor scale; gradient passes
    straight through."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec: str, a, b, precision: str):
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    elif precision != "float32":
        raise ValueError(f"precision {precision!r} is not one of {PRECISIONS}")
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * scale


def rope(x, theta: float):
    """x (S, H, D); position i is row i."""
    s, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def layer(x, blk, theta: float, precision: str):
    """One decoder layer over one sequence: x (S, D) → (S, D)."""
    s = x.shape[0]
    h = rms_norm(x, blk["ln1"])
    qkv = _mm("sd,dthe->tshe", h, blk["wqkv"], precision)
    q, k, v = rope(qkv[0], theta), rope(qkv[1], theta), qkv[2]
    scores = _mm("qhd,khd->hqk", q, k, precision) / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -1e30), axis=-1)
    attn = _mm("hqk,khd->qhd", probs, v, precision)
    x = x + _mm("she,hed->sd", attn, blk["wo"], precision)
    h = rms_norm(x, blk["ln2"])
    ff = gelu(_mm("sd,df->sf", h, blk["w1"], precision))
    return x + _mm("sf,fd->sd", ff, blk["w2"], precision)


def head(x, ln_f, lm_head, precision: str):
    return _mm("sd,dv->sv", rms_norm(x, ln_f), lm_head, precision)


# ---------------------------------------------------------------------------
# Forward, layer by layer (the serving check)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_jit(x, blk, theta, precision):
    return layer(x, blk, theta, precision)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_jit(x, ln_f, lm_head, precision):
    return head(x, ln_f, lm_head, precision)


def logits_of(params: dict, tokens, theta: float,
              precision: str = "float32", at: slice = slice(None)):
    """tokens (S,) int32 → logits at the positions ``at`` (all of them by
    default), one sequence."""
    x = params["embed"][tokens]
    for blk in params["blocks"]:
        x = _layer_jit(x, blk, theta, precision)
    return _head_jit(x[at], params["ln_f"], params["lm_head"], precision)


# ---------------------------------------------------------------------------
# Loss, gradients and AdamW (the training check)
# ---------------------------------------------------------------------------

def row_loss(params: dict, tokens, targets, theta: float, precision: str):
    """Sum (not mean) of the next-token negative log-likelihood over one
    row, each layer rematerialised."""
    x = params["embed"][tokens]
    one = jax.checkpoint(layer, static_argnums=(2, 3))
    for blk in params["blocks"]:
        x = one(x, blk, theta, precision)
    logp = jax.nn.log_softmax(
        head(x, params["ln_f"], params["lm_head"], precision), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))


@functools.partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0, 1))
def _add_row(loss_sum, grad_sum, params, tokens, targets, theta, precision):
    loss, grads = jax.value_and_grad(row_loss)(params, tokens, targets,
                                               theta, precision)
    return loss_sum + loss, jax.tree.map(jnp.add, grad_sum, grads)


def loss_and_grads(params: dict, tokens, targets, theta: float,
                   precision: str = "float32", rows=None):
    """Mean loss over the rows given (all of them by default) and its
    gradient: tokens, targets (B, S). ``rows`` lets a test leave rows out
    the way a faulty step would."""
    rows = range(tokens.shape[0]) if rows is None else list(rows)
    loss = jnp.zeros((), jnp.float32)
    grads = jax.tree.map(jnp.zeros_like, params)
    for r in rows:
        loss, grads = _add_row(loss, grads, params, tokens[r], targets[r],
                               theta, precision)
    return _mean(loss, grads, jnp.float32(len(rows) * tokens.shape[1]))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _mean(loss_sum, grad_sum, n):
    """In place: a second copy of the gradients, asked for while the last
    row's activations are still held, does not fit beside a large model."""
    return loss_sum / n, jax.tree.map(lambda g: g / n, grad_sum)


def adamw_init(params: dict) -> dict:
    return {"count": 0, "mu": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.map(jnp.zeros_like, params)}


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw_leaves(params, mu, nu, grads, count, lr, weight_decay):
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1.0 - ADAM_B1) * g,
                      mu, grads)
    nu = jax.tree.map(
        lambda v, g: ADAM_B2 * v + (1.0 - ADAM_B2) * jnp.square(g),
        nu, grads)

    def one(p, m, v):
        m_hat = m / (1.0 - ADAM_B1 ** count)
        v_hat = v / (1.0 - ADAM_B2 ** count)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + ADAM_EPS)
                         + weight_decay * p)

    return jax.tree.map(one, params, mu, nu), mu, nu


def adamw_update(params: dict, state: dict, grads: dict, lr: float,
                 weight_decay: float):
    """One AdamW step (decoupled decay, bias-corrected moments, no
    clipping, constant rate): returns (params, state)."""
    count = state["count"] + 1
    params, mu, nu = _adamw_leaves(
        params, state["mu"], state["nu"], grads, jnp.float32(count),
        jnp.float32(lr), jnp.float32(weight_decay))
    return params, {"count": count, "mu": mu, "nu": nu}


@jax.jit
def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]
