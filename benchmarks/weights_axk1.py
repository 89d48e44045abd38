"""Weights and sizes of A.X-K1 as one chip of an expert-parallel
deployment holds it (``configs/a.x-k1.json``) from ``--seed``, beside
``weights_longcat.py`` and in its manner: on the device, in the type asked
for, a jitted call a layer, keyed by layer and leaf; matrices normal /
sqrt(fan_in); norm scales 1 + 0.1·normal, so that a scale that is dropped
shows.

What each scale is for:

- No factor stands between a bottleneck and what reads it (the published
  block has none), so ``wqb`` and ``wkvb`` are drawn at the fan-in of
  their bottleneck: a normed bottleneck has unit lanes, and keys and
  values then have unit lanes too. A query of unit lanes on a key of 192
  spreads by sqrt(192), and the YaRN scale 0.13086 = 1.3466² / sqrt(192)
  would leave the scores a spread of 1.81; the queries are drawn a little
  larger, so that the scores spread by ``SCORE_SPREAD`` = 2.5 and a
  softmax over 8,192 keys rests on some tens of them, as a trained
  model's long-context attention does.
- The embedding table is ``EMBED_SCALE`` · normal(0, 1) = 3,
  ``weights_longcat.py``'s scale, found there by a sweep on the chip: a
  token's own row stays the larger part of the residual stream at every
  router (the fourteen sub-layers add under half a unit each), so a
  row's picks do not repeat from position to position. The head is
  untied and drawn apart, so a served logit is not the input token's.
  What the spread of 2.5 is for: a prefill chunk that attends its own
  positions alone, or frequencies that YaRN did not scale, change only
  attention's output, and of the positions the check compares prefill
  serves one a row. The plain reference at a toy width (hidden 256, 4
  heads, all 7 layers) and the cell's reach and vocabulary, on the CPU
  (PR 41), 65 positions of a prompt: at a table of 3 and a spread of 1.81
  such a chunk moves a logit by 0.11 of its spread of 1 and the first
  token at none of three positions; at 3 and 2.5 it changes 43% of first
  tokens, unscaled frequencies 31%; at 2 and 2.5 57% and 42%; at 1 and
  2.5 88% and 71%; at 1 and 3.0 92%. A table of 1 was tried on the chip
  and given up: every perturbation is as much larger as the table is
  smaller, the program's own among them.
- The router has no bias leaf (``topk_method`` "none"). Its columns are
  at norm 1: every expert's sigmoid score then spreads alike over the
  tokens (a logit of unit spread; the 8 picked score 0.85 to 0.94, a
  renormalised pick weighs about 2.5 / 8 = 0.31), and the load over the
  experts is as even as a trained router's auxiliary loss is there to
  make it, so that the held experts' share of the picks is steady from
  seed to seed (6.2% of the picks in every request of six seeds, my chip
  runs, PR 41).
- A routed expert's down-projection is drawn at ``EXPERT_GAIN`` = a
  quarter of 1 / sqrt(fan_in). A pick is a selection: where the 8th and
  the 9th score nearly tie, the program in bfloat16 picks another expert
  than the reference in float32 (6.8% of (token, layer) pairs on the
  chip), and at a weight of 0.31 a pick an expert drawn at 1 / sqrt(fan_in)
  moves a logit by up to 0.2: the program read 0.007 to 0.21 over nine
  seeds, four rows each, where the float8 control read 0.14 to 0.25 (my
  chip runs, PR 41): nothing lay between them. At a quarter a pick that
  differs moves a quarter as much, the shared expert and the dense layer
  as much as before: the program read 0.010 to 0.018 over three further
  seeds, the control 0.16 to 0.21, and ``weights_unnormalised`` (seven
  times a pick's weight) still 0.58 to 1.17.

The tree is the program's checkpoint format for these kinds: ``embed`` (V,
D); ``blocks[i]`` with ``ln1``, latent attention's leaves (``wqa`` (D,
rq), ``q_norm`` (rq,), ``wqb`` (rq, H, nope + rope), ``wkva`` (D, rkv +
rope), ``kv_norm`` (rkv,), ``wkvb`` (rkv, H, nope + v), ``wo`` (H, v, D)),
``ln2`` and, in a dense layer, ``wg`` and ``w1`` (D, F: gate and up),
``w2`` (F, D: down); in an expert layer ``router`` (``w`` (D, routed)),
``experts`` (``wg``, ``w1`` (held, D, Fe), ``w2`` (held, Fe, D): the routed
experts ``experts_held`` of the published ``routed_experts``) and ``shared``
(``wg``, ``w1`` (D, shared · Fe), ``w2`` (shared · Fe, D)); ``ln_f``;
``lm_head`` (D, V).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key, token_rows  # noqa: F401
from benchmarks.weights_longcat import _dense, _scale, _unit_columns

# The spread of attention's scores and the embedding table's scale (the
# docstring's first two points say why)
SCORE_SPREAD = 2.5
EMBED_SCALE = 3.0
# What a routed expert's down-projection is drawn at, of 1 / sqrt(fan_in)
# (the docstring's last point says why)
EXPERT_GAIN = 0.25

YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def sizes_of(config: dict, published: bool = False) -> dict:
    """The sizes the benchmark needs, from a configuration file that keeps
    the published key names. ``n_routed_experts`` counts the experts held
    here; the published count, the router's, is under ``deployment``. With
    ``published`` the sizes of the whole model: every layer, every expert
    held, the whole vocabulary."""
    scaling = config["rope_scaling"]
    if config["model_type"] != "axk1" or config["attention_bias"] \
            or config["hidden_act"] != "silu" \
            or config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "none" \
            or not config["norm_topk_prob"] \
            or config["moe_layer_freq"] != 1 \
            or config["tie_word_embeddings"] or scaling["type"] != "yarn" \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError(
            f"not the layer this file makes weights for: {config}")
    deployment = config["deployment"]
    whole = deployment["published"]
    first, count = (int(n) for n in deployment["experts_held"])
    routed = int(whole["n_routed_experts"])
    if count != int(config["n_routed_experts"]) or first + count > routed \
            or routed != count * int(deployment["expert_parallel_chips"]) \
            or first != count * int(deployment["rank"]) \
            or int(whole["vocab_size"]) != int(config["vocab_size"]) * int(
                deployment["vocabulary_chips"]):
        raise ValueError(
            f"the experts and rows held do not make the stated share: "
            f"{deployment}")
    held = whole if published else config
    return {
        "vocab": int(held["vocab_size"]),
        "d_model": int(config["hidden_size"]),
        "n_layers": int(held["num_hidden_layers"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "n_heads": int(config["num_attention_heads"]),
        "d_ff": int(config["intermediate_size"]),
        "expert_d_ff": int(config["moe_intermediate_size"]),
        "shared_experts": int(config["n_shared_experts"]),
        "max_seq": int(config["max_position_embeddings"]),
        "rope_theta": float(config["rope_theta"]),
        "yarn": tuple(float(scaling[key]) for key in YARN_KEYS),
        "norm_eps": float(config["rms_norm_eps"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "qk_nope": int(config["qk_nope_head_dim"]),
        "qk_rope": int(config["qk_rope_head_dim"]),
        "v_head": int(config["v_head_dim"]),
        "routed_experts": routed,
        "experts_held": (0, routed) if published else (first, count),
        "top_k": int(config["num_experts_per_tok"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
    }


def ffn_kinds(sizes: dict) -> tuple:
    """Every layer's feed-forward: the leading ones dense, the rest an
    expert layer."""
    dense = sizes["dense_layers"]
    return ("dense",) * dense + ("experts",) * (sizes["n_layers"] - dense)


def _gated(keys, leading: tuple, d: int, f: int, dtype) -> dict:
    return {"wg": _dense(keys[0], (*leading, d, f), d, dtype),
            "w1": _dense(keys[1], (*leading, d, f), d, dtype),
            "w2": _dense(keys[2], (*leading, f, d), f, dtype)}


def _yarn_m2(sizes: dict) -> float:
    """What the YaRN scaling multiplies the scores' scale by."""
    factor, _, _, _, _, all_dim = sizes["yarn"]
    return (0.1 * all_dim * math.log(factor) + 1.0) ** 2 \
        if factor > 1.0 and all_dim else 1.0


def _attention(keys, sizes: dict, dtype) -> dict:
    d, h = sizes["d_model"], sizes["n_heads"]
    rq, rkv = sizes["q_rank"], sizes["kv_rank"]
    nope, rope, v = sizes["qk_nope"], sizes["qk_rope"], sizes["v_head"]
    return {
        "ln1": _scale(keys[0], d, dtype),
        "wqa": _dense(keys[1], (d, rq), d, dtype),
        "q_norm": _scale(keys[2], rq, dtype),
        # unit lanes would spread the scores by m(mscale_all_dim)²
        "wqb": _dense(keys[3], (rq, h, nope + rope), rq, dtype)
        * (SCORE_SPREAD / _yarn_m2(sizes)),
        "wkva": _dense(keys[4], (d, rkv + rope), d, dtype),
        "kv_norm": _scale(keys[5], rkv, dtype),
        "wkvb": _dense(keys[6], (rkv, h, nope + v), rkv, dtype),
        "wo": _dense(keys[7], (h, v, d), h * v, dtype),
        "ln2": _scale(keys[8], d, dtype),
    }


def _dense_layer(key: jax.Array, sizes: dict, dtype) -> dict:
    k = jax.random.split(key, 12)
    return {**_attention(k, sizes, dtype),
            **_gated(k[9:], (), sizes["d_model"], sizes["d_ff"], dtype)}


def _expert_layer(key: jax.Array, sizes: dict, dtype) -> dict:
    d, fe = sizes["d_model"], sizes["expert_d_ff"]
    k = jax.random.split(key, 16)
    routed = _gated(k[10:13], (sizes["experts_held"][1],), d, fe, dtype)
    return {
        **_attention(k, sizes, dtype),
        "router": {"w": _unit_columns(_dense(
            k[9], (d, sizes["routed_experts"]), d, dtype))},
        "experts": dict(routed, w2=routed["w2"] * EXPERT_GAIN),
        "shared": _gated(k[13:], (), d, sizes["shared_experts"] * fe, dtype),
    }


def _ends(key: jax.Array, sizes: dict, dtype) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    k = jax.random.split(key, 3)
    return {
        "embed": EMBED_SCALE * jax.random.normal(k[0], (v, d), dtype),
        "ln_f": _scale(k[1], d, dtype),
        "lm_head": _dense(k[2], (d, v), d, dtype),
    }


_PARTS = {"dense": _dense_layer, "experts": _expert_layer}


def layer_key(seed: int, layer: int) -> jax.Array:
    """The key of layer ``layer``'s leaves; ``-1`` for the embedding, the
    final norm and the head."""
    return jax.random.fold_in(seed_key(seed), layer + 1)


def _frozen(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


@functools.lru_cache(maxsize=None)
def _maker(part, frozen: tuple, dtype, device):
    sharding = None if device is None \
        else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(lambda key: part(key, dict(frozen), dtype),
                   out_shardings=sharding)


def make_layer(seed: int, layer: int, sizes: dict, dtype=jnp.bfloat16,
               device=None) -> dict:
    """One layer's weights alone, as ``make_weights`` makes them."""
    return _maker(_PARTS[ffn_kinds(sizes)[layer]], _frozen(sizes), dtype,
                  device)(layer_key(seed, layer))


def make_weights(seed: int, sizes: dict, dtype=jnp.bfloat16,
                 device=None) -> dict:
    """The whole tree on ``device``, a jitted call a layer."""
    ends = _maker(_ends, _frozen(sizes), dtype, device)(layer_key(seed, -1))
    return dict(ends, blocks=[make_layer(seed, i, sizes, dtype, device)
                              for i in range(sizes["n_layers"])])


def n_params(sizes: dict) -> dict:
    """Parameter counts by ``jax.eval_shape`` of the makers above: nothing
    is allocated, at the published sizes neither. ``attention`` (its five
    matrices and two norms), ``dense_ffn``, ``router``, ``expert`` (one
    routed expert), ``shared``, a ``dense_layer`` and an ``expert_layer``
    as held here (their two norms in), ``embed``, ``lm_head``, ``total``;
    ``matrices_a_token``: the matrix parameters a token multiplies through
    whatever it picks (every layer's attention, the dense feed-forwards,
    the routers and the shared experts)."""
    return dict(_n_params(_frozen(sizes)))


@functools.lru_cache(maxsize=None)
def _n_params(frozen: tuple) -> dict:
    sizes = dict(frozen)

    def count(tree) -> int:
        return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    shapes = {name: jax.eval_shape(
        lambda k, part=part: part(k, sizes, jnp.bfloat16), key)
        for name, part in dict(_PARTS, ends=_ends).items()}
    of = {
        "attention": count({name: shapes["dense"][name] for name in (
            "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb", "wo")}),
        "dense_ffn": count({name: shapes["dense"][name]
                            for name in ("wg", "w1", "w2")}),
        "router": count(shapes["experts"]["router"]),
        "expert": count(shapes["experts"]["experts"])
        // sizes["experts_held"][1],
        "shared": count(shapes["experts"]["shared"]),
        "dense_layer": count(shapes["dense"]),
        "expert_layer": count(shapes["experts"]),
        "embed": count(shapes["ends"]["embed"]),
        "lm_head": count(shapes["ends"]["lm_head"]),
    }
    kinds = ffn_kinds(sizes)
    dense, experts = kinds.count("dense"), kinds.count("experts")
    scales = sizes["q_rank"] + sizes["kv_rank"]
    of["matrices_a_token"] = (
        len(kinds) * (of["attention"] - scales) + dense * of["dense_ffn"]
        + experts * (of["router"] + of["shared"]))
    of["total"] = (dense * of["dense_layer"] + experts * of["expert_layer"]
                   + count(shapes["ends"]))
    return of
