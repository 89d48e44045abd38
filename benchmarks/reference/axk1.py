"""The plain reference of A.X-K1 (SK Telecom; ``model_type`` ``axk1``, the
published DeepSeek-V3 block key for key), as one chip of an
expert-parallel deployment holds it: its forward pass in straightforward
float32 ``jax.numpy``, every product at ``highest``, every position
through every layer, keys and values expanded for every head, no cache,
no chunk, no absorbed projection, a masked loop over the experts held, the
shared expert once. It imports nothing of ``faabric_tpu`` and takes
nothing the program has made; weights and tokens come from
``benchmarks/weights_axk1.py`` and the seed, in whatever type they were
made, and are upcast here one sub-layer or one expert at a time, so that
the reference never holds a float32 copy of a layer.

Every layer, all norms RMSNorm, ``x`` its input, D the hidden size:

    a = x + MLA(n1(x))           y = a + F(n2(a))
    F = FFN at the dense width in the leading ``dense_layers`` layers
    F(h) = Shared(h) + Σ_{e ∈ picks} w_e · Expert_e(h) in every other
    FFN(h) = (silu(h·Wg) ⊙ h·W1)·W2, Shared and Expert_e at the experts'

Latent attention on a normed state h, H heads, no factor on either
bottleneck:

    cq = RMSNorm(h·Wqa)          q = cq·Wqb → H × (nope ‖ rope)
    [ckv ‖ kr] = h·Wkva          ckv = RMSNorm(ckv)
    kr = RoPE(kr), one for all heads
    [k ‖ v] = ckv·Wkvb → H × (nope ‖ v)
    q_rope = RoPE(q_rope); neighbouring lanes (2i, 2i+1) are a pair
    scores = (q_nope·k + q_rope·kr) · s, causal softmax
    out = (softmax · v) → H × v, through Wo

RoPE under YaRN (``sizes["yarn"]`` = factor, original reach, beta_fast,
beta_slow, mscale, mscale_all_dim), rope lanes d, theta the base:

    f_i = theta^(−2i/d)          pair(t) = d·ln(reach / (2π t)) / (2 ln theta)
    low = ⌊pair(beta_fast)⌋      high = ⌈pair(beta_slow)⌉
    r_i = clip((i − low) / (high − low), 0, 1)
    pair i turns at f_i (1 − r_i) + (f_i / factor) r_i
    m(x) = 0.1 · x · ln(factor) + 1
    cosines and sines times m(mscale) / m(mscale_all_dim)
    s = m(mscale_all_dim)² / sqrt(nope + rope)

The router, float32 whatever the precision:

    σ = sigmoid(h·Wr)            picks = the top_k largest of σ
    w_e = scaling · σ_e / (Σ_{picks} σ + 1e-20)

Picks of routed experts that other chips hold add nothing, here as in the
program.

``sizes`` is ``weights_axk1.sizes_of(config)``. ``precision`` is "float32"
or "fp8", the control of the correctness check one step below bfloat16
(both operands of every matrix product rounded to float8_e4m3 under a
per-tensor scale); the router's product stays float32 in both, as the
program keeps it. ``fault`` plants what this model's own parts make
possible (:data:`FAULTS`). At a long reach a whole layer at once would
not fit the chip beside the weights: a layer goes a block of positions at
a time (``BLOCK``) and attention a block of heads (``SCORE_BYTES``), the
same lines for each.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# the norm is the looped decoder's; the products and the fp8 control's
# rounding of their operands are the first reference's
from benchmarks.reference.ouro import rms_norm
from benchmarks.reference.transformer import _mm

# shared_dropped: the shared expert left out. weights_unnormalised: a pick
# weighs scaling times its score, not divided by the picks' sum.
# yarn_dropped: every pair at theta^(−2i/d) and the scores' scale 1 /
# sqrt(nope + rope). chunk_carry_dropped: a position of the prompt
# (before ``handover``) attends the positions of its own prefill chunk
# alone, as a chunk would that did not read the cache the chunks before
# it wrote; the positions decoded attend everything.
FAULTS = ("shared_dropped", "weights_unnormalised", "yarn_dropped",
          "chunk_carry_dropped")

# On the chip the reference runs beside 9.77 GB of weights and the loaded
# program of the requests, with 3 GB to spare: every jitted piece takes a
# block of positions (its temporaries a fraction of a GB; the chip keeps
# every loaded program's room), attention the scores of a block of
# queries and a block of heads, and the host waits for each layer (a
# result's room is taken when its program is enqueued, and the host runs
# ahead by seconds). The lines are the same for every block.
BLOCK = 1024
SCORE_BYTES = 64 * 1024 * 1024


def _f32(tree):
    return jax.tree.map(lambda w: w.astype(jnp.float32), tree)


def _m(factor: float, x: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * x * math.log(factor) + 1.0


def yarn_range(sizes: dict) -> tuple:
    """(low, high), the pairs between which the ramp rises."""
    _, reach, fast, slow, _, _ = sizes["yarn"]
    d, theta = sizes["qk_rope"], sizes["rope_theta"]

    def pair(turns):
        return d * math.log(reach / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return max(math.floor(pair(fast)), 0), min(math.ceil(pair(slow)), d - 1)


def frequencies(sizes: dict, fault=None):
    """A pair's turn a position, (rope / 2,) float32."""
    d = sizes["qk_rope"]
    f = 1.0 / (sizes["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if fault == "yarn_dropped":
        return f
    low, high = yarn_range(sizes)
    r = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                 / max(high - low, 0.001), 0.0, 1.0)
    return f * (1.0 - r) + f / sizes["yarn"][0] * r


def score_scale(sizes: dict, fault=None) -> float:
    factor, _, _, _, _, all_dim = sizes["yarn"]
    plain = 1.0 / math.sqrt(sizes["qk_nope"] + sizes["qk_rope"])
    if fault == "yarn_dropped" or not all_dim:
        return plain
    return plain * _m(factor, all_dim) ** 2


def rope(x, positions, sizes: dict, fault=None):
    """x (P, H, D) at ``positions`` (P,); lanes (2j, 2j+1) are pair j."""
    factor, _, _, _, mscale, all_dim = sizes["yarn"]
    angles = positions.astype(jnp.float32)[:, None, None] \
        * frequencies(sizes, fault)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if fault != "yarn_dropped":
        size = _m(factor, mscale) / _m(factor, all_dim)
        cos, sin = cos * size, sin * size
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def latents(x, blk, first, sizes: dict, precision: str, fault=None):
    """A block of the layer's input x (P, D) at positions ``first`` on →
    its normed latents (P, rank) and turned rotary lanes (P, rope)."""
    rank, eps = sizes["kv_rank"], sizes["norm_eps"]
    h = rms_norm(x, blk["ln1"].astype(jnp.float32), eps)
    kv = _mm("sd,dc->sc", h, blk["wkva"].astype(jnp.float32), precision)
    ckv = rms_norm(kv[:, :rank], blk["kv_norm"].astype(jnp.float32), eps)
    at = first + jnp.arange(x.shape[0])
    return ckv, rope(kv[:, None, rank:], at, sizes, fault)[:, 0]


def attention_sublayer(x, blk, ckv, kr, first, sizes: dict, precision: str,
                       fault=None, handover=None, chunk=None):
    """A block of the layer's input x (P, D) at positions ``first`` on,
    against the latents ``ckv`` (S, rank) and rotary lanes ``kr`` (S,
    rope) of the whole sequence → x + MLA(n1(x)) (P, D). Every head's
    keys and values are expanded from the latents, a block of heads at a
    time."""
    blk = _f32(blk)
    p, s = x.shape[0], ckv.shape[0]
    nope, heads = sizes["qk_nope"], sizes["n_heads"]
    eps = sizes["norm_eps"]
    h = rms_norm(x, blk["ln1"], eps)
    cq = rms_norm(_mm("sd,dr->sr", h, blk["wqa"], precision),
                  blk["q_norm"], eps)
    q = _mm("sr,rhe->she", cq, blk["wqb"], precision)
    q_pos, k_pos = first + jnp.arange(p), jnp.arange(s)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], q_pos, sizes, fault)], axis=-1)
    mask = k_pos[None, :] <= q_pos[:, None]
    if fault == "chunk_carry_dropped":
        mask &= (k_pos[None, :] // chunk == q_pos[:, None] // chunk) \
            | (q_pos[:, None] >= handover)
    scale = score_scale(sizes, fault)

    def some_heads(block):
        q, wkvb = block  # (P, hb, nope + rope), (rank, hb, nope + v)
        keys_values = _mm("sc,che->she", ckv, wkvb, precision)
        k, v = keys_values[..., :nope], keys_values[..., nope:]
        scores = (_mm("qhe,khe->hqk", q[..., :nope], k, precision)
                  + _mm("qhe,ke->hqk", q[..., nope:], kr, precision)) * scale
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), axis=-1)
        return _mm("hqk,khe->qhe", probs, v, precision)

    fit = max(1, SCORE_BYTES // (4 * p * s))
    hb = max(n for n in range(1, heads + 1) if heads % n == 0 and n <= fit)
    if hb == heads:
        out = some_heads((q, blk["wkvb"]))
    else:
        def blocks(a):
            return jnp.moveaxis(a.reshape(
                a.shape[0], heads // hb, hb, a.shape[2]), 1, 0)

        out = jax.lax.map(some_heads, (blocks(q), blocks(blk["wkvb"])))
        out = jnp.moveaxis(out, 0, 1).reshape(p, heads, -1)
    return x + _mm("she,hed->sd", out, blk["wo"], precision)


def feed_forward(h, wg, w1, w2, precision: str):
    wg, w1, w2 = _f32((wg, w1, w2))
    gated = jax.nn.silu(_mm("sd,df->sf", h, wg, precision)) \
        * _mm("sd,df->sf", h, w1, precision)
    return _mm("sf,fd->sd", gated, w2, precision)


def route(u, router, sizes: dict, fault=None):
    """u (S, D) → (picks (S, K) over the router's whole width, their
    weights (S, K)); float32 whatever the precision."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "sd,de->se", u, router["w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    weights, picks = jax.lax.top_k(scores, sizes["top_k"])
    if fault != "weights_unnormalised":
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return picks, sizes["routed_scaling"] * weights


ATTENTION_LEAVES = ("ln1", "wqa", "q_norm", "wqb", "wkva", "kv_norm",
                    "wkvb", "wo")


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _latents_jit(x, blk, first, sizes, precision, fault):
    return latents(x, blk, first, dict(sizes), precision, fault)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _attention_jit(x, blk, ckv, kr, first, sizes, precision, fault, handover,
                   chunk):
    return attention_sublayer(x, blk, ckv, kr, first, dict(sizes), precision,
                              fault, handover, chunk)


@functools.partial(jax.jit, static_argnums=(2,))
def _normed(x, scale, eps):
    return rms_norm(x, scale.astype(jnp.float32), eps)


_feed_forward_jit = jax.jit(feed_forward, static_argnums=(4,))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _route_jit(u, router, sizes, fault):
    return route(u, router, dict(sizes), fault)


@functools.partial(jax.jit, static_argnums=(5,))
def _expert_jit(u, weight, experts, e, m, precision):
    """m + weight ⊙ Expert_e(u), ``e`` the expert's place among those
    held: every token goes through the expert, and the mask in ``weight``
    (0 where the token did not pick it) decides."""
    wg, w1, w2 = (jax.lax.dynamic_index_in_dim(experts[name], e,
                                               keepdims=False)
                  for name in ("wg", "w1", "w2"))
    return m + weight[:, None] * feed_forward(u, wg, w1, w2, precision)


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


def _blocks(length: int) -> list:
    """(first, positions) of the blocks a sequence goes in: equal ones
    where a divisor of the length lies between BLOCK / 2 and BLOCK (one
    shape, one set of programs), else BLOCK and a shorter last one."""
    most = min(BLOCK, length)
    size = max(d for d in range(1, most + 1) if length % d == 0)
    if 2 * size < most:
        size = most
    return [(at, min(size, length - at)) for at in range(0, length, size)]


def expert_layer(u, blk, sizes: dict, precision: str = "float32",
                 held: tuple | None = None, fault=None, shared: bool = True):
    """u (P, D) → (F(u) (P, D), picks (P, K)): the part of the expert layer
    that the routed experts ``held = (first, count)`` give (``blk``'s
    ``experts`` hold their weights; default: the share the sizes state)
    and, with ``shared``, the shared expert."""
    first, count = held if held is not None else sizes["experts_held"]
    picks, weights = _route_jit(u, blk["router"], _frozen(sizes), fault)
    m = jnp.zeros_like(u)
    for e in range(count):
        weight = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        m = _expert_jit(u, weight, blk["experts"], e, m, precision)
    if shared and fault != "shared_dropped":
        m = m + _feed_forward_jit(u, blk["shared"]["wg"], blk["shared"]["w1"],
                                  blk["shared"]["w2"], precision)
    return m, picks


def layer_in_blocks(xs: list, blk, sizes: dict, precision: str = "float32",
                    fault=None, handover=None, chunk=None):
    """One layer over one sequence given in blocks of positions: xs, a
    list of (P, D) → (the layer's output in the same blocks, the expert
    layer's picks a block, none for a dense layer). Every block attends
    the whole sequence's latents."""
    frozen, eps = _frozen(sizes), sizes["norm_eps"]
    attention = {name: blk[name] for name in ATTENTION_LEAVES}
    firsts = [sum(x.shape[0] for x in xs[:i]) for i in range(len(xs))]
    ckv, kr = (jnp.concatenate(part) for part in zip(*(
        _latents_jit(x, attention, at, frozen, precision, fault)
        for x, at in zip(xs, firsts))))
    out, picked = [], []
    for x, at in zip(xs, firsts):
        a = _attention_jit(x, attention, ckv, kr, at, frozen, precision,
                           fault, handover, chunk)
        u = _normed(a, blk["ln2"], eps)
        if "router" in blk:
            f, picks = expert_layer(u, blk, sizes, precision, fault=fault)
            picked.append(picks)
        else:
            f = _feed_forward_jit(u, blk["wg"], blk["w1"], blk["w2"],
                                  precision)
        out.append(jax.block_until_ready(a + f))
    return out, picked


def layer(x, blk, sizes: dict, precision: str = "float32", fault=None,
          handover=None, chunk=None):
    """One layer over one sequence: x (S, D) → (y (S, D), the expert
    layer's picks (S, K), None for a dense layer)."""
    out, picked = layer_in_blocks(
        [x[at:at + p] for at, p in _blocks(x.shape[0])], blk, sizes,
        precision, fault, handover, chunk)
    return jnp.concatenate(out), jnp.concatenate(picked) if picked else None


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_jit(x, ln_f, lm_head, precision, eps):
    x = rms_norm(x, ln_f.astype(jnp.float32), eps)
    return _mm("sd,dv->sv", x, lm_head.astype(jnp.float32), precision)


def logits_of(params: dict, tokens, sizes: dict, precision: str = "float32",
              at: slice = slice(None), with_picks: bool = False, fault=None,
              handover=None, chunk=None):
    """tokens (S,) int32 → logits at the positions ``at`` (all of them by
    default), one sequence; with ``with_picks`` also every expert layer's
    picks, (expert layers, S, K). ``fault`` is one of :data:`FAULTS`;
    "chunk_carry_dropped" needs the prompt's length (``handover``) and the
    prefill chunk (``chunk``)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is not one of {FAULTS}")
    if fault == "chunk_carry_dropped" and not (handover and chunk):
        raise ValueError("fault 'chunk_carry_dropped' needs the prompt's "
                         "length and the prefill chunk")
    xs = [params["embed"][tokens[first:first + p]].astype(jnp.float32)
          for first, p in _blocks(tokens.shape[0])]
    picked = []
    for blk in params["blocks"]:
        xs, picks = layer_in_blocks(xs, blk, sizes, precision, fault,
                                    handover, chunk)
        if picks:
            picked.append(jnp.concatenate(picks))
    logits = _head_jit(jnp.concatenate(xs)[at], params["ln_f"],
                       params["lm_head"], precision, sizes["norm_eps"])
    return (logits, jnp.stack(picked)) if with_picks else logits


def logits_of_rows(params: dict, ids, sizes: dict,
                   precision: str = "float32", at: slice = slice(None),
                   **how):
    """ids (R, S) → logits (R, positions, V), a row at a time."""
    return jnp.stack([logits_of(params, row, sizes, precision, at, **how)
                      for row in ids])
