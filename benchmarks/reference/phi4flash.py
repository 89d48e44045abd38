"""The plain reference of a decoder-hybrid-decoder (Microsoft
Phi-4-mini-flash-reasoning, ``model_type`` ``phi4flash``; "Decoder-Hybrid-
Decoder Architecture for Efficient Reasoning with Long Generation",
arXiv:2507.06607: SambaY with differential attention): its forward pass in
straightforward float32 ``jax.numpy``, every product at ``highest``, a
plain Python loop over the layers, every position through every layer, the
recurrence position by position, no cache, no chunk, no ring, no skip, no
kernels. It imports nothing of ``faabric_tpu`` and takes nothing the
program has made; weights and tokens come from
``benchmarks/weights_phi4flash.py`` and the seed, in whatever type they
were made and upcast here, a layer at a time.

    x = embed[tokens]
    for every layer l, of kind layer_kinds[l]:
        x = x + Mixer_l(LayerNorm(x; ln1, ln1_b))
        h = LayerNorm(x; ln2, ln2_b);  x = x + (silu(h·Wg) ⊙ (h·W1))·W2
    logits = LayerNorm(x; ln_f, ln_f_b) · embedᵀ                 (tied head)

    Mamba-1 (l even, l ≤ L/2): [x, z] = h·W_in;  x = silu(conv(x))
        (depthwise, causal, taps, a bias);  [δ, B, C] = x·W_x
        dt = softplus(δ·W_dt + dt_bias);  A = −exp(A_log)  (E, N)
        S_t = exp(dt_t ⊗ A) ⊙ S_{t−1} + (dt_t ⊙ x_t) ⊗ B_t  (S: (E, N))
        y_t = S_t·C_t + D ⊙ x_t;  out = (y ⊙ silu(z))·W_out
        layer L/2 publishes m = y, the memory
    Differential attention (l odd, l < L/2 over the last ``window``
        positions: i − window < j ≤ i; l = L/2 + 1 over all j ≤ i):
        q = h·Wq + bq (H heads), [k, v] = h·Wkv + bkv (KV heads); pairs
        (q1_j, q2_j) = heads (2j, 2j+1), (k1_g, k2_g) and (v1_g, v2_g) =
        key and value heads (2g, 2g+1), pair j on group g = j // (H / KV)
        a1 = softmax(q1·k1ᵀ / sqrt(hd)) [v1 | v2];  a2 likewise of q2, k2
        λ = exp(λq1·λk1) − exp(λq2·λk2) + λ_init,
        λ_init = 0.8 − 0.6·exp(−0.3·l)
        out = RMSNorm(a1 − λ·a2; sub_norm)·(1 − λ_init), a pair's 2·hd
        lanes as heads (2j, 2j+1), then ·Wo + bo
        layer L/2 + 1's k and v are the shared keys and values
    Gated memory unit (l even, l ≥ L/2 + 2): (silu(h·W_in) ⊙ m)·W_out
    Cross attention (l odd, l ≥ L/2 + 3): q = h·Wq + bq alone; the
        differential form over layer L/2 + 1's k and v; its own λ vectors,
        norm and output projection

``sizes`` is ``weights_phi4flash.sizes_of(config)``. ``precision`` is
"float32" or "fp8", the control of the correctness check one step below
bfloat16 (both operands of every matrix product rounded to float8_e4m3
under a per-tensor scale). ``fault`` plants what a broken hand-over of
this model's own would do: ``window_unbounded`` (the windowed layers attend
the whole reach), ``memory_stale`` (a gated memory unit reads the memory of
the position before), ``lambda_dropped`` (a1 alone: λ = 0),
``state_dropped`` (S zeroed before position ``handover``, the first a
cached step computes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# the products, and the fp8 control's rounding of their operands, are the
# first reference's: one definition of "one precision below bfloat16"
from benchmarks.reference.transformer import _mm

# a fault → the kinds of layer it is planted in (the others' compiled
# programs are the unaltered ones)
FAULTS = {"window_unbounded": ("window",), "memory_stale": ("memory",),
          "lambda_dropped": ("window", "full", "cross"),
          "state_dropped": ("mamba1",)}


def layer_norm(x, scale, bias, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def feed_forward(x, blk, sizes: dict, precision: str):
    h = layer_norm(x, blk["ln2"], blk["ln2_b"], sizes["norm_eps"])
    gated = jax.nn.silu(_mm("sd,df->sf", h, blk["wg"], precision)) \
        * _mm("sd,df->sf", h, blk["w1"], precision)
    return x + _mm("sf,fd->sd", gated, blk["w2"], precision)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def differential_attention(h, blk, keys_values, fixed, window: int,
                           sizes: dict, precision: str, fault=None):
    """One sequence: h (S, D) → (S, D), over ``keys_values`` (k, v), each
    (S, KV, hd); ``fixed`` is the layer's λ_init; ``window`` 0: every
    position up to the query's."""
    s = h.shape[0]
    heads, kv, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    k, v = keys_values
    q = _mm("sd,dhe->she", h, blk["wq"], precision) + blk["bq"]
    q1, q2 = q[:, 0::2], q[:, 1::2]                        # (S, H/2, hd)
    k1, k2 = k[:, 0::2], k[:, 1::2]                        # (S, KV/2, hd)
    # the group's two value heads side by side: (S, KV/2, 2·hd)
    both = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)
    per = heads // kv                                      # pairs a group
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window and fault != "window_unbounded":
        seen &= j > i - window

    def one_map(qm, km):
        km = jnp.repeat(km, per, axis=1)
        scores = _mm("qhe,khe->hqk", qm, km, precision) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), axis=-1)
        return _mm("hqk,khe->qhe", probs, jnp.repeat(both, per, axis=1),
                   precision)

    a1, a2 = one_map(q1, k1), one_map(q2, k2)              # (S, H/2, 2·hd)
    lam = jnp.exp(jnp.sum(blk["lambda_q1"] * blk["lambda_k1"])) \
        - jnp.exp(jnp.sum(blk["lambda_q2"] * blk["lambda_k2"])) + fixed
    if fault == "lambda_dropped":
        lam = 0.0
    out = rms_norm(a1 - lam * a2, blk["sub_norm"], sizes["norm_eps"]) \
        * (1.0 - fixed)
    # a pair's 2·hd lanes are its two heads of hd
    out = out.reshape(s, heads, hd)
    return _mm("she,hed->sd", out, blk["wo"], precision) + blk["bo"]


def keys_and_values(h, blk, precision: str):
    k, v = _mm("sd,dtke->tske", h, blk["wkv"], precision)
    return k + blk["bkv"][0], v + blk["bkv"][1]


def mamba1(h, blk, sizes: dict, precision: str, fault=None, handover=None):
    """The Mamba-1 mixer over one sequence: h (S, D) → (out (S, D), the
    memory y (S, E))."""
    e, n = sizes["ssm_inner"], sizes["ssm_d_state"]
    r, taps = sizes["ssm_dt_rank"], sizes["ssm_d_conv"]
    s = h.shape[0]
    xz = _mm("sd,de->se", h, blk["ssm_in"], precision)
    x_in, z = xz[:, :e], xz[:, e:]
    # the convolution: position t reads inputs t − taps + 1 … t
    padded = jnp.concatenate([jnp.zeros((taps - 1, e), jnp.float32), x_in])
    x = jax.nn.silu(blk["conv_b"] + sum(
        blk["conv_w"][tap] * padded[tap:tap + s] for tap in range(taps)))
    dbc = _mm("se,ef->sf", x, blk["ssm_x"], precision)
    b, c = dbc[:, r:r + n], dbc[:, r + n:]
    dt = jax.nn.softplus(_mm("sr,re->se", dbc[:, :r], blk["ssm_dt"],
                             precision) + blk["dt_bias"])
    a = -jnp.exp(blk["A_log"])                              # (E, N)

    def position(state, at):
        t, x_t, b_t, c_t, dt_t = at
        if fault == "state_dropped":
            state = jnp.where(t == handover, 0.0, state)
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(position, jnp.zeros((e, n), jnp.float32),
                        (jnp.arange(s), x, b, c, dt))
    y = y + blk["D"] * x
    return _mm("se,ed->sd", y * jax.nn.silu(z), blk["ssm_out"],
               precision), y


def gated_memory(h, blk, memory, precision: str, fault=None):
    if fault == "memory_stale":
        memory = jnp.concatenate([jnp.zeros_like(memory[:1]), memory[:-1]])
    gate = jax.nn.silu(_mm("sd,de->se", h, blk["gmu_in"], precision))
    return _mm("se,ed->sd", gate * memory, blk["gmu_out"], precision)


def layer(x, blk, lent, fixed, kind: str, sizes: dict, precision: str,
          fault=None, handover=None):
    """One decoder layer over one sequence: x (S, D), what an earlier
    layer lends (the memory (S, E) for "memory", the keys and values for
    "cross") and the layer's λ_init (a traced scalar: one compiled
    program a kind, not a depth) → (x (S, D), what this layer lends or
    None)."""
    blk = jax.tree.map(lambda w: w.astype(jnp.float32), blk)
    h = layer_norm(x, blk["ln1"], blk["ln1_b"], sizes["norm_eps"])
    lends = None
    if kind == "mamba1":
        out, lends = mamba1(h, blk, sizes, precision, fault, handover)
    elif kind == "memory":
        out = gated_memory(h, blk, lent, precision, fault)
    else:
        if kind != "cross":
            lent = lends = keys_and_values(h, blk, precision)
        out = differential_attention(
            h, blk, lent, fixed, sizes["window"] if kind == "window" else 0,
            sizes, precision, fault)
    return feed_forward(x + out, blk, sizes, precision), lends


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _layers_jit(x, blk, lent, fixed, kind, frozen, precision, fault,
                handover):
    """Rows (R, S, D) through one layer, every row a sequence of its own."""
    one = functools.partial(layer, kind=kind, sizes=dict(frozen),
                            precision=precision, fault=fault,
                            handover=handover)
    return jax.vmap(one, in_axes=(0, None, 0, None))(x, blk, lent, fixed)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_jit(x, ln_f, ln_f_b, table, frozen, precision):
    sizes = dict(frozen)
    x = layer_norm(x, ln_f.astype(jnp.float32), ln_f_b.astype(jnp.float32),
                   sizes["norm_eps"])
    return _mm("rsd,vd->rsv", x, table.astype(jnp.float32), precision)


def logits_of_rows(params: dict, tokens, sizes: dict,
                   precision: str = "float32", at: slice = slice(None),
                   fault=None, handover=None):
    """tokens (R, S) int32 → logits (R, positions ``at``, V): every row a
    sequence of its own, the rows in one block."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"fault {fault!r} is not one of {tuple(FAULTS)}")
    frozen = tuple(sorted(sizes.items()))
    kinds = sizes["layer_kinds"]
    x = params["embed"][tokens].astype(jnp.float32)
    memory = shared = None
    for index, (blk, kind) in enumerate(zip(params["blocks"], kinds)):
        lent = {"memory": memory, "cross": shared}.get(kind)
        planted = fault if kind in FAULTS.get(fault, ()) else None
        x, lends = _layers_jit(
            x, blk, lent, jnp.float32(lambda_init(index)), kind, frozen,
            precision, planted,
            handover if planted == "state_dropped" else None)
        if index == sizes["memory_source"]:
            memory = lends
        if kind == "full":
            shared = lends
    return _head_jit(x[:, at], params["ln_f"], params["ln_f_b"],
                     params["embed"], frozen, precision)


def logits_of(params: dict, tokens, sizes: dict,
              precision: str = "float32", at: slice = slice(None),
              fault=None, handover=None):
    """tokens (S,) int32 → logits at the positions ``at``, one sequence."""
    return logits_of_rows(params, tokens[None], sizes, precision, at, fault,
                          handover)[0]
