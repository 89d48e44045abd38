"""Weights and sizes of a decoder-hybrid-decoder
(``configs/phi-4-mini-flash-reasoning.json``) from ``--seed``, beside
``weights_granite.py`` and in its manner: on the device, in the type asked
for, keyed by layer and leaf, a jitted call a layer. Token ids are
``weights.token_rows``.

What the draw has to give:

- *A head that does not answer with its own input.* The head is the table
  transposed and the residual stream carries the token's own row to it. The
  table is normal · ``EMBED_LANES`` (1/32): the first LayerNorm brings a
  token to unit size for the first mixer, the layers' own outputs are the
  stream from there on, and the input token's own logit is a fraction of a
  spread above the mean, one candidate among 200,064. Logits spread by
  about sqrt(2560) / 32 = 1.6: every number of the check is of that size.
- *Sub-layers of unit size.* Every matrix that reads a normed state is
  normal / sqrt(fan_in); q and k have unit lanes, so scores spread by 1
  under the scale 1/8. Norm scales 1 + 0.1·normal, norm biases and the
  attention's biases 0.1·normal: one that is dropped shows.
- *A difference that matters.* The four λ vectors are 0.1·normal (the
  published initialisation), so λ is λ_init ± 0.1 and a2 takes a quarter
  to three quarters of a1 away, by depth.
- *A state that matters, with decays from forgetting at once to barely at
  all.* ``dt_bias``, ``A_log`` and ``D`` as Mamba-1 initialises them: dt
  log-uniform in [0.001, 0.1] through the inverse of softplus, A_log =
  log(1..16) in every lane, D 1 (here 1 + 0.1·normal: a D left out shows).
  With 16 states a lane S·C is smaller than D·x at unit draws, so B's and
  C's columns of the x-projection are drawn at twice the size
  (``BC_GAIN``); the convolution's taps are normal / 2, its bias
  0.1·normal.

The tree is the program's checkpoint format for these kinds: ``embed``
(V, D), also the head; ``ln_f``, ``ln_f_b``; ``blocks[i]``, every kind:
``ln1``, ``ln1_b``, ``ln2``, ``ln2_b``, ``wg`` and ``w1`` (D, F), ``w2``
(F, D); "mamba1": ``ssm_in`` (D, 2E: x then z), ``conv_w`` (taps, E),
``conv_b``, ``ssm_x`` (E, R + 2N: δ, B, C), ``ssm_dt`` (R, E),
``dt_bias``, ``A_log`` (E, N), ``D``, ``ssm_out`` (E, D); "window" and
"full": ``wq`` (D, H, hd), ``bq``, ``wkv`` (D, 2, KV, hd), ``bkv``, ``wo``
(H, hd, D), ``bo``, ``lambda_q1`` … ``lambda_k2`` (hd,), ``sub_norm``
(2·hd,); "cross": the same without ``wkv`` and ``bkv``; "memory":
``gmu_in`` (D, E), ``gmu_out`` (E, D).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key, token_rows  # noqa: F401

EMBED_LANES = 1.0 / 32.0
BC_GAIN = 2.0
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")


def layer_kinds(n_layers: int, mb_per_layer: int) -> tuple:
    """Every layer's kind by its index (the configuration file's
    ``assumed.layer_kinds``): a self-decoder of Mamba-1 and windowed
    attention up to the middle, one full attention whose cache is shared,
    then a cross-decoder of gated memory units and cross attention."""
    half = n_layers // 2
    kinds = []
    for l in range(n_layers):
        recurrent = l % mb_per_layer == 0
        if l <= half:
            kinds.append("mamba1" if recurrent else "window")
        elif l == half + 1:
            kinds.append("full")
        else:
            kinds.append("memory" if recurrent else "cross")
    return tuple(kinds)


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file that keeps
    the published key names; the sizes the source leaves to its class's
    defaults may be given (the tests' toy file does), else they are the
    file's ``assumed``."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    kv, n_layers = int(config["num_key_value_heads"]), \
        int(config["num_hidden_layers"])
    per = int(config["mb_per_layer"])
    if config["hidden_act"] != "silu" or config["mlp_bias"] \
            or config["lm_head_bias"] or not config["tie_word_embeddings"] \
            or config["model_type"] != "phi4flash":
        raise ValueError(f"not the layers this file makes weights for: "
                         f"{config}")
    if d % h or h % 2 or kv % 2 or h % kv or per != 2 or n_layers % 2 \
            or n_layers < 8:
        raise ValueError(f"the sizes do not fit each other: {config}")
    kinds = layer_kinds(n_layers, per)
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": d,
        "n_layers": n_layers,
        "layer_kinds": kinds,
        "memory_source": n_layers // 2,
        "n_heads": h,
        "n_kv_heads": kv,
        "head_dim": d // h,
        "d_ff": int(config["intermediate_size"]),
        "max_seq": int(config["max_position_embeddings"]),
        "norm_eps": float(config["layer_norm_eps"]),
        "window": int(config["sliding_window"]),
        "ssm_inner": int(config.get("mamba_expand", 2)) * d,
        "ssm_d_state": int(config.get("mamba_d_state", 16)),
        "ssm_d_conv": int(config.get("mamba_d_conv", 4)),
        "ssm_dt_rank": int(config.get("mamba_dt_rank", -(-d // 16))),
    }


def _dense(key, shape, fan_in, dtype, gain=1.0):
    return jax.random.normal(key, shape, dtype) * (gain / math.sqrt(fan_in))


def _scale(key, width, dtype):
    return 1.0 + 0.1 * jax.random.normal(key, (width,), dtype)


def _small(key, shape, dtype):
    return 0.1 * jax.random.normal(key, shape, dtype)


def _norms_and_feed_forward(keys, sizes: dict, dtype) -> dict:
    d, f = sizes["d_model"], sizes["d_ff"]
    return {"ln1": _scale(keys[0], d, dtype),
            "ln1_b": _small(keys[1], (d,), dtype),
            "ln2": _scale(keys[2], d, dtype),
            "ln2_b": _small(keys[3], (d,), dtype),
            "wg": _dense(keys[4], (d, f), d, dtype),
            "w1": _dense(keys[5], (d, f), d, dtype),
            "w2": _dense(keys[6], (f, d), f, dtype)}


def _mamba1_layer(key, sizes: dict, dtype) -> dict:
    d, e = sizes["d_model"], sizes["ssm_inner"]
    n, r, taps = sizes["ssm_d_state"], sizes["ssm_dt_rank"], \
        sizes["ssm_d_conv"]
    k = jax.random.split(key, 16)
    dt = jnp.exp(jax.random.uniform(k[5], (e,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    return {
        "ssm_in": _dense(k[0], (d, 2 * e), d, dtype),
        "conv_w": _dense(k[1], (taps, e), taps, dtype),
        "conv_b": _small(k[2], (e,), dtype),
        "ssm_x": jnp.concatenate(
            [_dense(k[3], (e, r), e, dtype),
             _dense(k[4], (e, 2 * n), e, dtype, BC_GAIN)], axis=1),
        "ssm_dt": _dense(k[6], (r, e), r, dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
            (e, n)).astype(dtype),
        "D": _scale(k[7], e, dtype),
        "ssm_out": _dense(k[8], (e, d), e, dtype),
        **_norms_and_feed_forward(k[9:], sizes, dtype),
    }


def _attention_layer(key, sizes: dict, dtype, cross: bool = False) -> dict:
    d, h, kv = sizes["d_model"], sizes["n_heads"], sizes["n_kv_heads"]
    hd = sizes["head_dim"]
    k = jax.random.split(key, 18)
    blk = {
        "wq": _dense(k[0], (d, h, hd), d, dtype),
        "bq": _small(k[1], (h, hd), dtype),
        "wo": _dense(k[2], (h, hd, d), h * hd, dtype),
        "bo": _small(k[3], (d,), dtype),
        "sub_norm": _scale(k[4], 2 * hd, dtype),
        **{name: _small(k[5 + i], (hd,), dtype)
           for i, name in enumerate(LAMBDAS)},
        **_norms_and_feed_forward(k[11:], sizes, dtype),
    }
    if not cross:
        blk["wkv"] = _dense(k[9], (d, 2, kv, hd), d, dtype)
        blk["bkv"] = _small(k[10], (2, kv, hd), dtype)
    return blk


def _cross_layer(key, sizes: dict, dtype) -> dict:
    return _attention_layer(key, sizes, dtype, cross=True)


def _memory_layer(key, sizes: dict, dtype) -> dict:
    d, e = sizes["d_model"], sizes["ssm_inner"]
    k = jax.random.split(key, 9)
    return {"gmu_in": _dense(k[0], (d, e), d, dtype),
            "gmu_out": _dense(k[1], (e, d), e, dtype),
            **_norms_and_feed_forward(k[2:], sizes, dtype)}


def _ends(key, sizes: dict, dtype) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    k = jax.random.split(key, 3)
    return {"embed": jax.random.normal(k[0], (v, d), dtype) * EMBED_LANES,
            "ln_f": _scale(k[1], d, dtype),
            "ln_f_b": _small(k[2], (d,), dtype)}


_PARTS = {"mamba1": _mamba1_layer, "window": _attention_layer,
          "full": _attention_layer, "cross": _cross_layer,
          "memory": _memory_layer}


def layer_key(seed: int, layer: int) -> jax.Array:
    """The key of layer ``layer``'s leaves; ``-1`` for the table and the
    final norm."""
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
    return _maker(_PARTS[sizes["layer_kinds"][layer]],
                  tuple(sorted(sizes.items())), dtype, device)(
        layer_key(seed, layer))


def make_weights(seed: int, sizes: dict, dtype=jnp.bfloat16,
                 device=None) -> dict:
    """The whole tree on ``device``, a jitted call a layer."""
    ends = _maker(_ends, tuple(sorted(sizes.items())), dtype, device)(
        layer_key(seed, -1))
    return dict(ends, blocks=[make_layer(seed, i, sizes, dtype, device)
                              for i in range(sizes["n_layers"])])


def n_params(sizes: dict) -> dict:
    """Parameter counts from the sizes. ``mixer``: a kind's mixer with its
    biases and vectors; ``mixer_matrices``: the matrices a token
    multiplies through in it; ``ffn``; ``layers``: how many of each kind;
    ``matmul``: all matrices of all layers; ``embed``; ``total``."""
    d, f, v = sizes["d_model"], sizes["d_ff"], sizes["vocab"]
    h, kv, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    e, n = sizes["ssm_inner"], sizes["ssm_d_state"]
    r, taps = sizes["ssm_dt_rank"], sizes["ssm_d_conv"]
    vectors = 4 * hd + 2 * hd
    matrices = {
        "mamba1": d * 2 * e + e * (r + 2 * n) + r * e + e * d,
        "window": d * (h + 2 * kv) * hd + h * hd * d,
        "cross": 2 * d * h * hd,
        "memory": 2 * d * e,
    }
    matrices["full"] = matrices["window"]
    mixer = {
        "mamba1": matrices["mamba1"] + taps * e + e + e + e * n + e,
        "window": matrices["window"] + (h + 2 * kv) * hd + d + vectors,
        "cross": matrices["cross"] + h * hd + d + vectors,
        "memory": matrices["memory"],
    }
    mixer["full"] = mixer["window"]
    kinds = sizes["layer_kinds"]
    layers = {kind: kinds.count(kind) for kind in _PARTS}
    ffn = 3 * d * f
    return {
        "mixer": mixer, "mixer_matrices": matrices, "ffn": ffn,
        "layers": layers,
        "matmul": sum(layers[kind] * (matrices[kind] + ffn)
                      for kind in layers),
        "embed": v * d,
        "total": sum(layers[kind] * (mixer[kind] + ffn + 4 * d)
                     for kind in layers) + v * d + 2 * d,
    }
