"""The plain reference of LongCat-Flash's language model (Meituan,
"LongCat-Flash Technical Report", arXiv:2509.01322; the published
``modeling_longcat_flash.py``), as one chip of an expert-parallel
deployment holds it: its forward pass in straightforward float32
``jax.numpy``, every product at ``highest``, plain Python loops over
layers and over the experts held, no cache, no absorbed projections, no
grouped product. It imports nothing of ``faabric_tpu`` and takes nothing
the program has made; weights and tokens come from
``benchmarks/weights_longcat.py`` and the seed, in whatever type they were
made, and are upcast here one sub-layer or one expert at a time, so that
the reference never holds a float32 copy of a layer.

A layer (shortcut-connected), all norms RMSNorm, ``x`` its input:

    a = x + MLA_1(n1(x))         u = n2(a)          m = MoE(u)
    b = a + FFN_1(u)             c = b + MLA_2(n3(b))
    y = c + FFN_2(n4(c)) + m     FFN(h) = (silu(h·Wg) ⊙ h·W1)·W2

Latent attention on a normed state h, H heads, D = hidden size:

    cq = RMSNorm(h·Wqa)·sqrt(D / q_rank)
    q = cq·Wqb → H × (nope ‖ rope)
    [ckv ‖ kr] = h·Wkva
    ckv = RMSNorm(ckv)·sqrt(D / kv_rank)
    kr = RoPE(kr), one for all heads
    [k ‖ v] = ckv·Wkvb → H × (nope ‖ v)
    q_rope = RoPE(q_rope); neighbouring lanes (2i, 2i+1) are a pair
    scores = (q_nope·k + q_rope·kr) / sqrt(nope + rope), causal softmax
    out = (softmax · v) → H × v, through Wo

The expert layer on u, router width = routed + zero-compute experts:

    s = softmax(u·Wr)            picks = the top_k largest of s + bias
    w_e = scaling · s_e at the picks, not renormalised
    m = Σ_{picked e held here} w_e · Expert_e(u)
      + Σ_{picked e zero-compute} w_e · u

with Expert_e a FFN at the experts' width. Picks of routed experts that
other chips hold add nothing, here as in the program.

``sizes`` is ``weights_longcat.sizes_of(config)``. ``precision`` is
"float32" or "fp8", the control of the correctness check one step below
bfloat16 (both operands of every matrix product rounded to float8_e4m3
under a per-tensor scale); the router's product stays float32 in both, as
the program keeps it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# the norm is the looped decoder's; the products, the fp8 control's
# rounding of their operands and the rotary turn over neighbouring lanes
# are the first reference's
from benchmarks.reference.ouro import rms_norm
from benchmarks.reference.transformer import _mm, rope


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def latent_attention(h, blk, sizes: dict, precision: str):
    """h (S, D), one sequence, normed → (S, D)."""
    blk = _f32(blk)
    s, d = h.shape
    rank, nope = sizes["kv_rank"], sizes["qk_nope"]
    theta, eps = sizes["rope_theta"], sizes["norm_eps"]
    cq = rms_norm(_mm("sd,dr->sr", h, blk["wqa"], precision),
                  blk["q_norm"], eps) * math.sqrt(d / sizes["q_rank"])
    q = _mm("sr,rhe->she", cq, blk["wqb"], precision)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], theta)
    kv = _mm("sd,dc->sc", h, blk["wkva"], precision)
    ckv = rms_norm(kv[:, :rank], blk["kv_norm"], eps) * math.sqrt(d / rank)
    kr = rope(kv[:, None, rank:], theta)[:, 0]
    keys_values = _mm("sc,che->she", ckv, blk["wkvb"], precision)
    k, v = keys_values[..., :nope], keys_values[..., nope:]
    scores = (_mm("qhe,khe->hqk", q_nope, k, precision)
              + _mm("qhe,ke->hqk", q_rope, kr, precision)
              ) / math.sqrt(nope + sizes["qk_rope"])
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -1e30), axis=-1)
    out = _mm("hqk,khe->qhe", probs, v, precision)
    return _mm("she,hed->sd", out, blk["wo"], precision)


def feed_forward(h, wg, w1, w2, precision: str):
    wg, w1, w2 = _f32((wg, w1, w2))
    gated = jax.nn.silu(_mm("sd,df->sf", h, wg, precision)) \
        * _mm("sd,df->sf", h, w1, precision)
    return _mm("sf,fd->sd", gated, w2, precision)


def route(u, router, sizes: dict):
    """u (S, D) → (picks (S, K) over the router's whole width, their
    weights (S, K)); float32 whatever the precision."""
    router = _f32(router)
    scores = jax.nn.softmax(jnp.einsum(
        "sd,de->se", u, router["w"], precision=jax.lax.Precision.HIGHEST),
        axis=-1)
    _, picks = jax.lax.top_k(scores + router["bias"], sizes["top_k"])
    return picks, sizes["routed_scaling"] * jnp.take_along_axis(
        scores, picks, axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention_sublayer(x, half, sizes, precision):
    sizes = dict(sizes)
    return x + latent_attention(rms_norm(x, half["ln1"].astype(jnp.float32),
                                         sizes["norm_eps"]),
                                {k: half[k] for k in (
                                    "wqa", "q_norm", "wqb", "wkva",
                                    "kv_norm", "wkvb", "wo")},
                                sizes, precision)


@functools.partial(jax.jit, static_argnums=(2,))
def _normed(x, scale, eps):
    return rms_norm(x, scale.astype(jnp.float32), eps)


_feed_forward_jit = jax.jit(feed_forward, static_argnums=(4,))


@functools.partial(jax.jit, static_argnums=(2,))
def _route_jit(u, router, sizes):
    return route(u, router, dict(sizes))


@functools.partial(jax.jit, static_argnums=(6,))
def _expert_jit(u, weight, wg, w1, w2, m, precision):
    """m + weight ⊙ Expert(u): every token goes through the expert, and
    the mask in ``weight`` (0 where the token did not pick it) decides."""
    return m + weight[:, None] * feed_forward(u, wg, w1, w2, precision)


def expert_layer(u, blk, sizes: dict, precision: str,
                 held: tuple | None = None):
    """u (S, D) → (m (S, D), picks (S, K)): the part of the expert layer
    that the routed experts ``held = (first, count)`` give (``blk``'s
    ``experts`` hold their weights; default: the share the sizes state),
    with the zero-compute part."""
    first, count = held if held is not None else sizes["experts_held"]
    picks, weights = _route_jit(u, blk["router"], _frozen(sizes))
    zero = jnp.sum(jnp.where(picks >= sizes["routed_experts"], weights, 0.0),
                   axis=-1)
    m = zero[:, None] * u
    for e in range(count):
        weight = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        m = _expert_jit(u, weight, blk["experts"]["wg"][e],
                        blk["experts"]["w1"][e], blk["experts"]["w2"][e], m,
                        precision)
    return m, picks


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def layer(x, blk, sizes: dict, precision: str = "float32"):
    """One shortcut-connected layer over one sequence: x (S, D) → (y (S,
    D), the expert layer's picks (S, K))."""
    eps, frozen = sizes["norm_eps"], _frozen(sizes)
    first, second = blk["halves"]
    a = _attention_sublayer(x, first, frozen, precision)
    u = _normed(a, first["ln2"], eps)
    m, picks = expert_layer(u, blk, sizes, precision)
    b = a + _feed_forward_jit(u, first["wg"], first["w1"], first["w2"],
                              precision)
    c = _attention_sublayer(b, second, frozen, precision)
    return c + _feed_forward_jit(_normed(c, second["ln2"], eps),
                                 second["wg"], second["w1"], second["w2"],
                                 precision) + m, picks


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_jit(x, ln_f, lm_head, precision, eps):
    x = rms_norm(x, ln_f.astype(jnp.float32), eps)
    return _mm("sd,dv->sv", x, lm_head.astype(jnp.float32), precision)


def logits_of(params: dict, tokens, sizes: dict, precision: str = "float32",
              at: slice = slice(None), with_picks: bool = False):
    """tokens (S,) int32 → logits at the positions ``at`` (all of them by
    default), one sequence; with ``with_picks`` also every layer's picks,
    (layers, S, K)."""
    x = params["embed"][tokens].astype(jnp.float32)
    picked = []
    for blk in params["blocks"]:
        x, picks = layer(x, blk, sizes, precision)
        picked.append(picks)
    logits = _head_jit(x[at], params["ln_f"], params["lm_head"], precision,
                       sizes["norm_eps"])
    return (logits, jnp.stack(picked)) if with_picks else logits
