"""Weights and sizes of LongCat-Flash's language model as one chip of an
expert-parallel deployment holds it (``configs/longcat-flash-omni.json``)
from ``--seed``, beside ``weights.py`` and in its manner: on the device,
in the type asked for; matrices normal / sqrt(fan_in); norm scales 1 +
0.1·normal, so that a scale that is dropped shows; the router's columns
at norm 1 and its selection bias a thirty-second of a mean score,
non-zero (the third paragraph says what for). The two matrices that read
a scaled bottleneck (``wqb``, ``wkvb``) are normal / sqrt(hidden size): the
published factors sqrt(hidden / rank) on the bottlenecks make their
outputs as large as a hidden state's, which is what they were designed to
do for weights of one scale, and queries, keys and values then have unit
lanes and attention's scores unit spread. Drawn at 1 / sqrt(rank) the
scores spread by 5.7, every softmax is nearly one key, and two bfloat16
evaluations of the same network part by 40% at position 250 (my chip run,
PR 31).

The embedding table is 3 · normal(0, 1), so that a token's own row stays
the larger part of the residual stream at every router (the eight
sub-layers add about a unit each). How many picks fall on the 16 experts
held is a property of the seed's weights, and a request's time follows it
(0.11 ms a held expert hit a step), so the rate moves with the seed by as
much as the held experts' share of the picks does (a program that reads
only the experts hit: ``models/moe.py`` reads all it holds). Drawn at 1 /
sqrt(hidden size), as the other configurations' tables are, a row is a
hundredth of the stream: every position's state is its row's running
mean, a row's picks repeat from position to position, and the share moves
by 3.5% from seed to seed. Over six seeds, six requests each (my chip
runs, PR 31), relative standard deviations of the held picks in decode /
the held experts hit / a request's time, by a program that reads only the
experts hit: scale 1: 1.8% / 1.1% / 0.31%; 2: 1.0% /
0.66% / 0.23%; 3: 0.4% / 0.31% / 0.08%; 4: 0.8% / 0.40% / 0.11%. At 3
what is left is which of the 16,384 rows pick a held expert. For the
same reason the router's columns have one norm and the selection bias is
small (at a quarter of a mean score it alone moved the share by 1.5%):
the load is as balanced as the published correction bias is there to make
it, and a cell's metrics spread by less than they may.

The tree is the program's checkpoint format for these kinds: ``embed``
(V, D); ``blocks[i]`` with ``halves`` (two of: ``ln1``, ``wqa`` (D, rq),
``q_norm`` (rq,), ``wqb`` (rq, H, nope + rope), ``wkva`` (D, rkv + rope),
``kv_norm`` (rkv,), ``wkvb`` (rkv, H, nope + v), ``wo`` (H, v, D), ``ln2``,
``wg`` and ``w1`` (D, F: gate and up), ``w2`` (F, D: down)), ``router``
(``w`` (D, routed + zero), ``bias`` (routed + zero,)) and ``experts``
(``wg``, ``w1`` (held, D, Fe), ``w2`` (held, Fe, D): the routed experts
``experts_held`` of the published ``routed_experts``); ``ln_f``;
``lm_head`` (D, V).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key, token_rows  # noqa: F401


# The embedding table's scale (the docstring's last paragraphs say why).
EMBED_SCALE = 3.0


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file that keeps
    the published key names. ``n_routed_experts`` counts the experts held
    here; the published count, the router's, is under ``deployment``."""
    if config["attention_method"] != "MLA" or config["attention_bias"] \
            or config["zero_expert_type"] != "identity" \
            or not (config["mla_scale_q_lora"]
                    and config["mla_scale_kv_lora"]):
        raise ValueError(
            f"not the layer this file makes weights for: {config}")
    deployment = config["deployment"]
    first, count = (int(n) for n in deployment["experts_held"])
    routed = int(deployment["published"]["n_routed_experts"])
    if count != int(config["n_routed_experts"]) or first + count > routed \
            or routed != count * int(deployment["expert_parallel_chips"]) \
            or first != count * int(deployment["rank"]):
        raise ValueError(
            f"the experts held do not make the stated share: {deployment}")
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(config["num_layers"]),
        "n_heads": int(config["num_attention_heads"]),
        "d_ff": int(config["ffn_hidden_size"]),
        "expert_d_ff": int(config["expert_ffn_hidden_size"]),
        "max_seq": int(config["max_position_embeddings"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "qk_nope": int(config["qk_nope_head_dim"]),
        "qk_rope": int(config["qk_rope_head_dim"]),
        "v_head": int(config["v_head_dim"]),
        "routed_experts": routed,
        "zero_experts": int(config["zero_expert_num"]),
        "experts_held": (first, count),
        "top_k": int(config["moe_topk"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
    }


def _dense(key: jax.Array, shape: tuple, fan_in: int, dtype) -> jax.Array:
    return jax.random.normal(key, shape, dtype) / math.sqrt(fan_in)


def _scale(key: jax.Array, width: int, dtype) -> jax.Array:
    return 1.0 + 0.1 * jax.random.normal(key, (width,), dtype)


def _unit_columns(w: jax.Array) -> jax.Array:
    """Every column at norm 1, which normal / sqrt(rows) gives on
    average: every expert's score then spreads alike over the tokens."""
    norm = jnp.sqrt(jnp.sum(jnp.square(w.astype(jnp.float32)), axis=0))
    return (w.astype(jnp.float32) / norm).astype(w.dtype)


def _half(key: jax.Array, sizes: dict, dtype) -> dict:
    d, h, f = sizes["d_model"], sizes["n_heads"], sizes["d_ff"]
    rq, rkv = sizes["q_rank"], sizes["kv_rank"]
    nope, rope, v = sizes["qk_nope"], sizes["qk_rope"], sizes["v_head"]
    k = jax.random.split(key, 12)

    def dense(k, shape, fan_in):
        return _dense(k, shape, fan_in, dtype)

    def scale(k, width=d):
        return _scale(k, width, dtype)

    return {
        "ln1": scale(k[0]),
        "wqa": dense(k[1], (d, rq), d), "q_norm": scale(k[2], rq),
        "wqb": dense(k[3], (rq, h, nope + rope), d),
        "wkva": dense(k[4], (d, rkv + rope), d), "kv_norm": scale(k[5], rkv),
        "wkvb": dense(k[6], (rkv, h, nope + v), d),
        "wo": dense(k[7], (h, v, d), h * v),
        "ln2": scale(k[8]),
        "wg": dense(k[9], (d, f), d),
        "w1": dense(k[10], (d, f), d),
        "w2": dense(k[11], (f, d), f),
    }


def _layer(key: jax.Array, sizes: dict, dtype) -> dict:
    d, fe = sizes["d_model"], sizes["expert_d_ff"]
    held = sizes["experts_held"][1]
    width = sizes["routed_experts"] + sizes["zero_experts"]
    k = jax.random.split(key, 7)

    def dense(k, shape, fan_in):
        return _dense(k, shape, fan_in, dtype)

    return {
        "halves": [_half(k[0], sizes, dtype), _half(k[1], sizes, dtype)],
        "router": {"w": _unit_columns(dense(k[2], (d, width), d)),
                   "bias": jax.random.normal(k[3], (width,), dtype)
                   / (32 * width)},
        "experts": {"wg": dense(k[4], (held, d, fe), d),
                    "w1": dense(k[5], (held, d, fe), d),
                    "w2": dense(k[6], (held, fe, d), fe)},
    }


def _ends(key: jax.Array, sizes: dict, dtype) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    k = jax.random.split(key, 3)
    return {
        "embed": EMBED_SCALE * jax.random.normal(k[0], (v, d), dtype),
        "ln_f": _scale(k[1], d, dtype),
        "lm_head": _dense(k[2], (d, v), d, dtype),
    }


def layer_key(seed: int, layer: int) -> jax.Array:
    """The key of layer ``layer``'s leaves; ``-1`` for the embedding, the
    final norm and the head."""
    return jax.random.fold_in(seed_key(seed), layer + 1)


@functools.lru_cache(maxsize=None)
def _maker(part, frozen: tuple, dtype, device):
    sharding = None if device is None \
        else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(lambda key: part(key, dict(frozen), dtype),
                   out_shardings=sharding)


def make_layer(seed: int, layer: int, sizes: dict, dtype=jnp.bfloat16,
               device=None) -> dict:
    """One layer's weights alone, as ``make_weights`` makes them."""
    return _maker(_layer, tuple(sorted(sizes.items())), dtype, device)(
        layer_key(seed, layer))


def make_weights(seed: int, sizes: dict, dtype=jnp.bfloat16,
                 device=None) -> dict:
    """The whole tree on ``device``, a jitted call a layer."""
    ends = _maker(_ends, tuple(sorted(sizes.items())), dtype, device)(
        layer_key(seed, -1))
    return dict(ends, blocks=[make_layer(seed, i, sizes, dtype, device)
                              for i in range(sizes["n_layers"])])


def n_params(sizes: dict) -> dict:
    """Parameter counts from the sizes: one latent attention, one dense
    feed-forward, the router with its selection bias, a layer outside its
    experts, one routed expert, a layer as held here, embedding, head,
    all."""
    d, h = sizes["d_model"], sizes["n_heads"]
    rq, rkv, rope = sizes["q_rank"], sizes["kv_rank"], sizes["qk_rope"]
    nope, v = sizes["qk_nope"], sizes["v_head"]
    width = sizes["routed_experts"] + sizes["zero_experts"]
    attention = (d * rq + rq + rq * h * (nope + rope) + d * (rkv + rope)
                 + rkv + rkv * h * (nope + v) + h * v * d)
    dense_ffn = 3 * d * sizes["d_ff"]
    router = d * width + width
    outside = 2 * attention + 2 * dense_ffn + router + 4 * d
    expert = 3 * d * sizes["expert_d_ff"]
    layer = outside + sizes["experts_held"][1] * expert
    table = sizes["vocab"] * d
    return {"attention": attention, "dense_ffn": dense_ffn, "router": router,
            "layer_outside_experts": outside, "expert": expert,
            "layer": layer, "embed": table, "lm_head": table,
            "total": sizes["n_layers"] * layer + 2 * table + d}
