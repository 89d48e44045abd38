"""The plain reference of a decoder whose layers are of two kinds, Mamba-2
state-space layers and grouped-query attention layers without rotary
embeddings (IBM Granite 4.0-H, ``model_type`` ``granitemoehybrid``, dense:
no experts; the state-space layer is Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060): its forward pass in straightforward float32
``jax.numpy``, every product at ``highest``, a plain Python loop over the
layers, no cache, no chunks, no kernels. It imports nothing of
``faabric_tpu`` and takes nothing the program has made; weights and tokens
come from ``benchmarks/weights_granite.py`` and the seed, in whatever type
they were made and upcast here, a layer at a time, so that program and
reference start from the same numbers and the reference holds no float32
copy of the whole model.

    x = embed[tokens] · embedding_multiplier
    for every layer, of kind layer_types[l]:
        h = RMSNorm(x; ln1);  m = Mamba2(h) | Attention(h)
        x = x + residual_multiplier · m
        h = RMSNorm(x; ln2);  x = x + residual_multiplier ·
                                      (silu(h·Wg) ⊙ (h·W1))·W2
    logits = RMSNorm(x; ln_f) · embedᵀ / logits_scaling         (tied head)

    Attention(h): q = h·Wq (H heads), k, v = h·Wkv (KV heads), no bias, no
        rotary turn; every key/value head repeated for its H / KV query
        heads; softmax(q·kᵀ · attention_multiplier + causal) · v · Wo

    Mamba2(h): [z, xBC, dt] = h·W_in
        xBC = silu(conv(xBC))      depthwise, causal, d_conv taps, a bias
        [x (heads, lanes), B (groups, state), C (groups, state)] = xBC
        dt = softplus(dt + dt_bias);  A = −exp(A_log)              (a head)
        S_t = exp(dt_t·A)·S_{t−1} + dt_t·x_t ⊗ B_t   position by position
        y_t = S_t·C_t + D·x_t
        out = RMSNorm(y · silu(z); ssm_norm) · W_out

The recurrence is a ``lax.scan`` over time that carries the convolution's
window and S: the form a cached step has, never the chunked one.

``sizes`` is ``weights_granite.sizes_of(config)``. ``precision`` is
"float32" or "fp8", the control of the correctness check one step below
bfloat16: both operands of every matrix product rounded to float8_e4m3
under a per-tensor scale, the products themselves float32. ``fault``
plants what a broken hand-over from prefill to decoding would do, at
position ``handover`` (the first position a cached step computes):
``state_dropped`` zeroes S before it, ``window_dropped`` the convolution's
window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the products, and the fp8 control's rounding of their operands, are the
# first reference's: one definition of "one precision below bfloat16"
from benchmarks.reference.transformer import _mm

FAULTS = ("state_dropped", "window_dropped")


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def feed_forward(x, blk, sizes: dict, precision: str):
    m = rms_norm(x, blk["ln2"], sizes["norm_eps"])
    gated = jax.nn.silu(_mm("sd,df->sf", m, blk["wg"], precision)) \
        * _mm("sd,df->sf", m, blk["w1"], precision)
    return x + sizes["residual_multiplier"] * _mm(
        "sf,fd->sd", gated, blk["w2"], precision)


def attention(h, blk, sizes: dict, precision: str):
    """Grouped-query attention over one sequence: h (S, D) → (S, D)."""
    s = h.shape[0]
    per = sizes["n_heads"] // sizes["n_kv_heads"]
    q = _mm("sd,dhe->she", h, blk["wq"], precision)
    k, v = _mm("sd,dtke->tske", h, blk["wkv"], precision)
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    scores = _mm("qhe,khe->hqk", q, k, precision) \
        * sizes["attention_multiplier"]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -1e30), axis=-1)
    o = _mm("hqk,khe->qhe", probs, v, precision)
    return _mm("she,hed->sd", o, blk["wo"], precision)


def mamba(h, blk, sizes: dict, precision: str, fault=None, handover=None):
    """The state-space mixer over one sequence: h (S, D) → (S, D)."""
    heads, lanes = sizes["ssm_heads"], sizes["ssm_head_dim"]
    groups, n = sizes["ssm_groups"], sizes["ssm_d_state"]
    inner, bc = heads * lanes, groups * n
    taps = sizes["ssm_d_conv"]
    s = h.shape[0]
    zxbcdt = _mm("sd,de->se", h, blk["ssm_in"], precision)
    z = zxbcdt[:, :inner]
    xbc_in = zxbcdt[:, inner:2 * inner + 2 * bc]
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * bc:] + blk["dt_bias"])
    a = -jnp.exp(blk["A_log"])

    def position(carry, at):
        window, state = carry
        t, row, dt_t = at
        if fault == "window_dropped":
            window = jnp.where(t == handover, 0.0, window)
        if fault == "state_dropped":
            state = jnp.where(t == handover, 0.0, state)
        window = jnp.concatenate([window, row[None]], axis=0)   # (taps, C)
        xbc = jax.nn.silu(jnp.sum(blk["conv_w"] * window, axis=0)
                          + blk["conv_b"])
        x = xbc[:inner].reshape(heads, lanes)
        # a group's heads share its B and its C
        b = jnp.repeat(xbc[inner:inner + bc].reshape(groups, n),
                       heads // groups, axis=0)
        c = jnp.repeat(xbc[inner + bc:].reshape(groups, n),
                       heads // groups, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x)[:, :, None] * b[:, None, :]
        y = jnp.sum(state * c[:, None, :], axis=-1) + blk["D"][:, None] * x
        return (window[1:], state), y.reshape(inner)

    start = (jnp.zeros((taps - 1, xbc_in.shape[1]), jnp.float32),
             jnp.zeros((heads, lanes, n), jnp.float32))
    _, y = jax.lax.scan(position, start, (jnp.arange(s), xbc_in, dt))
    gated = rms_norm(y * jax.nn.silu(z), blk["ssm_norm"], sizes["norm_eps"])
    return _mm("se,ed->sd", gated, blk["ssm_out"], precision)


def layer(x, blk, kind: str, sizes: dict, precision: str, fault=None,
          handover=None):
    """One decoder layer over one sequence: x (S, D) → (S, D)."""
    blk = jax.tree.map(lambda w: w.astype(jnp.float32), blk)
    h = rms_norm(x, blk["ln1"], sizes["norm_eps"])
    if kind == "mamba":
        m = mamba(h, blk, sizes, precision, fault, handover)
    else:
        m = attention(h, blk, sizes, precision)
    x = x + sizes["residual_multiplier"] * m
    return feed_forward(x, blk, sizes, precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _layers_jit(x, blk, kind, frozen, precision, fault, handover):
    """Rows (R, S, D) through one layer, every row a sequence of its own."""
    one = functools.partial(layer, kind=kind, sizes=dict(frozen),
                            precision=precision, fault=fault,
                            handover=handover)
    return jax.vmap(one, in_axes=(0, None))(x, blk)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_jit(x, ln_f, table, frozen, precision):
    sizes = dict(frozen)
    x = rms_norm(x, ln_f.astype(jnp.float32), sizes["norm_eps"])
    return _mm("rsd,vd->rsv", x, table.astype(jnp.float32), precision) \
        / sizes["logits_scaling"]


def logits_of_rows(params: dict, tokens, sizes: dict,
                   precision: str = "float32", at: slice = slice(None),
                   fault=None, handover=None):
    """tokens (R, S) int32 → logits (R, positions ``at``, V): every row a
    sequence of its own, the rows in one block."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
    frozen = tuple(sorted(sizes.items()))
    x = params["embed"][tokens].astype(jnp.float32) \
        * sizes["embedding_multiplier"]
    for blk, kind in zip(params["blocks"], sizes["layer_types"]):
        x = _layers_jit(x, blk, kind, frozen, precision,
                        fault if kind == "mamba" else None,
                        handover if kind == "mamba" else None)
    return _head_jit(x[:, at], params["ln_f"], params["embed"], frozen,
                     precision)


def logits_of(params: dict, tokens, sizes: dict,
              precision: str = "float32", at: slice = slice(None),
              fault=None, handover=None):
    """tokens (S,) int32 → logits at the positions ``at`` (all of them by
    default), one sequence."""
    return logits_of_rows(params, tokens[None], sizes, precision, at, fault,
                          handover)[0]
