"""Weights and sizes of a decoder of Mamba-2 state-space layers and
grouped-query attention layers (``configs/granite-4.0-h-micro.json``) from
``--seed``, beside ``weights.py`` and in its manner: on the device, in the
type asked for, keyed by layer and leaf, a jitted call a layer; norm
scales 1 + 0.1·normal, so that a scale that is dropped shows. Token ids
are ``weights.token_rows``.

What the draw has to give, and what was tried (on the CPU: the plain
reference at the published widths over six layers of both kinds, 260
positions, a vocabulary of 8,192; the chip's readings are in ``PERF.md``):

- *A head that does not answer with its own input.* The head is the table
  transposed, and the residual stream carries the token's own row to it.
  With the table at normal / 12, so that ``embedding_multiplier`` 12 gives
  unit lanes, that row is the larger part of the final state: the input
  token's own logit lay 16.5 above the next, every served token was the
  token before it, and ``served_logit_gap`` read 0.0 for the program, the
  fp8 control and both planted faults alike. The table is therefore
  normal · ``EMBED_LANES`` / 12 with ``EMBED_LANES`` = 1/32: the first
  norm brings a token to unit size for the first mixer, the layers' own
  outputs are the stream from there on (0.26 after one layer, 0.6 after
  six, about 1.2 after forty), and the input token's logit is 1.2 spreads
  above the mean, one candidate among 100,352. Logits then spread by 0.015
  (sqrt(2048) · EMBED_LANES / 12 / 8): every number of the check is of
  that size. At 1/16 the input token still won at most positions.
- *Sub-layers of unit size.* Every matrix that reads a normed state is
  normal / sqrt(fan_in): a sub-layer's output has lanes of 0.5 to 1 and
  joins the stream at ``residual_multiplier`` 0.22 of that.
- *Scores of unit spread.* ``attention_multiplier`` is 1/64, not 1/8:
  queries and keys of unit lanes would give scores of spread 0.125 and
  every softmax a flat mean. ``wq`` and the keys' half of ``wkv`` are
  normal · sqrt(8 / hidden): lanes of 2.83, scores of spread 1.
- *A state that matters, with decays from forgetting at once to barely at
  all.* ``dt_bias``, ``A_log`` and ``D`` as Mamba-2 initialises them: dt
  log-uniform in [0.001, 0.1] through the inverse of softplus, A uniform
  in [1, 16], D 1 (here 1 + 0.1·normal: a D left out shows). The
  in-projection's dt columns are normal / sqrt(hidden) like the rest, so a
  position's dt spreads by a factor e around its head's: a step's decay
  exp(dt·A) read 0.034 / 0.74 / 0.92 / 0.98 / 0.998 at the 1st / 25th /
  50th / 75th / 99th percentile. The convolution's taps are normal / 2
  (four taps: unit lanes), its bias 0.1·normal; x, B and C leave its silu
  with lanes of 0.6, S has entries of 0.04 and S·C (0.55) is as large as
  D·x (0.61): no gain on B and C is needed. Both planted faults then move
  the logits by 32 to 40% in relative L2 and read 0.027 and 0.044 where
  the fp8 control reads 0.007.

The tree is the program's checkpoint format for these kinds: ``embed``
(V, D), also the head; ``blocks[i]``, a "mamba" layer: ``ln1``,
``ssm_in`` (D, 2·H·P + 2·G·N + H: z, x, B, C, dt in this order),
``conv_w`` (taps, H·P + 2·G·N), ``conv_b``, ``dt_bias``, ``A_log``, ``D``
(H,), ``ssm_norm`` (H·P,), ``ssm_out`` (H·P, D); an "attention" layer:
``ln1``, ``wq`` (D, heads, hd), ``wkv`` (D, 2, kv heads, hd), ``wo``
(heads, hd, D); both: ``ln2``, ``wg`` and ``w1`` (D, F: gate and up),
``w2`` (F, D: down); ``ln_f``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key, token_rows  # noqa: F401

# The size of a lane of embed[token] · embedding_multiplier (the
# docstring's first point says why it is not 1).
EMBED_LANES = 1.0 / 32.0


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file that keeps
    the published key names."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    heads, lanes = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    kinds = tuple(config["layer_types"])
    if config["hidden_act"] != "silu" or config["attention_bias"] \
            or config["position_embedding_type"] != "nope" \
            or config["normalization_function"] != "rmsnorm" \
            or not config["mamba_conv_bias"] or config["mamba_proj_bias"] \
            or config["num_local_experts"] or not config["tie_word_embeddings"] \
            or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"not the layers this file makes weights for: "
                         f"{config}")
    if d % h or len(kinds) != int(config["num_hidden_layers"]) \
            or heads * lanes != int(config["mamba_expand"]) * d \
            or heads % int(config["mamba_n_groups"]) \
            or h % int(config["num_key_value_heads"]):
        raise ValueError(f"the sizes do not fit each other: {config}")
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": d,
        "n_layers": len(kinds),
        "layer_types": kinds,
        "n_heads": h,
        "n_kv_heads": int(config["num_key_value_heads"]),
        "head_dim": d // h,
        "d_ff": int(config["shared_intermediate_size"]),
        "max_seq": int(config["max_position_embeddings"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "ssm_heads": heads,
        "ssm_head_dim": lanes,
        "ssm_d_state": int(config["mamba_d_state"]),
        "ssm_d_conv": int(config["mamba_d_conv"]),
        "ssm_groups": int(config["mamba_n_groups"]),
        "ssm_chunk": int(config["mamba_chunk_size"]),
    }


def ssm_widths(sizes: dict) -> tuple:
    """(inner = H·P, B's and C's width G·N, the convolution's channels,
    the in-projection's outputs)."""
    inner = sizes["ssm_heads"] * sizes["ssm_head_dim"]
    bc = sizes["ssm_groups"] * sizes["ssm_d_state"]
    return inner, bc, inner + 2 * bc, 2 * inner + 2 * bc + sizes["ssm_heads"]


def _dense(key, shape, fan_in, dtype, gain=1.0):
    return jax.random.normal(key, shape, dtype) * (gain / math.sqrt(fan_in))


def _scale(key, width, dtype):
    return 1.0 + 0.1 * jax.random.normal(key, (width,), dtype)


def _feed_forward(keys, sizes: dict, dtype) -> dict:
    d, f = sizes["d_model"], sizes["d_ff"]
    return {"ln2": _scale(keys[0], d, dtype),
            "wg": _dense(keys[1], (d, f), d, dtype),
            "w1": _dense(keys[2], (d, f), d, dtype),
            "w2": _dense(keys[3], (f, d), f, dtype)}


def _mamba_layer(key, sizes: dict, dtype) -> dict:
    d, heads = sizes["d_model"], sizes["ssm_heads"]
    inner, _bc, channels, out = ssm_widths(sizes)
    taps = sizes["ssm_d_conv"]
    k = jax.random.split(key, 13)
    dt = jnp.exp(jax.random.uniform(k[4], (heads,), jnp.float32,
                                    math.log(0.001), math.log(0.1)))
    return {
        "ln1": _scale(k[0], d, dtype),
        "ssm_in": _dense(k[1], (d, out), d, dtype),
        "conv_w": _dense(k[2], (taps, channels), taps, dtype),
        "conv_b": 0.1 * jax.random.normal(k[3], (channels,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            k[5], (heads,), jnp.float32, 1.0, 16.0)).astype(dtype),
        "D": _scale(k[6], heads, dtype),
        "ssm_norm": _scale(k[7], inner, dtype),
        "ssm_out": _dense(k[8], (inner, d), inner, dtype),
        **_feed_forward(k[9:], sizes, dtype),
    }


def _attention_layer(key, sizes: dict, dtype) -> dict:
    d, h, kv = sizes["d_model"], sizes["n_heads"], sizes["n_kv_heads"]
    hd = sizes["head_dim"]
    k = jax.random.split(key, 9)
    # scores of unit spread under the stated scale: q·k over hd lanes,
    # times attention_multiplier
    lanes = 1.0 / math.sqrt(sizes["attention_multiplier"] * math.sqrt(hd))
    keys = _dense(k[2], (d, 1, kv, hd), d, dtype, lanes)
    values = _dense(k[3], (d, 1, kv, hd), d, dtype)
    return {
        "ln1": _scale(k[0], d, dtype),
        "wq": _dense(k[1], (d, h, hd), d, dtype, lanes),
        "wkv": jnp.concatenate([keys, values], axis=1),
        "wo": _dense(k[4], (h, hd, d), h * hd, dtype),
        **_feed_forward(k[5:], sizes, dtype),
    }


def _ends(key, sizes: dict, dtype) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    k = jax.random.split(key, 2)
    return {
        "embed": jax.random.normal(k[0], (v, d), dtype)
        * (EMBED_LANES / sizes["embedding_multiplier"]),
        "ln_f": _scale(k[1], d, dtype),
    }


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
    part = _mamba_layer if sizes["layer_types"][layer] == "mamba" \
        else _attention_layer
    return _maker(part, tuple(sorted(sizes.items())), dtype, device)(
        layer_key(seed, layer))


def make_weights(seed: int, sizes: dict, dtype=jnp.bfloat16,
                 device=None) -> dict:
    """The whole tree on ``device``, a jitted call a layer."""
    ends = _maker(_ends, tuple(sorted(sizes.items())), dtype, device)(
        layer_key(seed, -1))
    return dict(ends, blocks=[make_layer(seed, i, sizes, dtype, device)
                              for i in range(sizes["n_layers"])])


def n_params(sizes: dict) -> dict:
    """Parameter counts from the sizes: a state-space mixer and its two
    projections, an attention, the feed-forward, a layer of each kind
    (with its two norms), the matrices a token multiplies through in a
    layer of each kind, the table, all."""
    d, f, v = sizes["d_model"], sizes["d_ff"], sizes["vocab"]
    h, kv, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    heads = sizes["ssm_heads"]
    inner, _bc, channels, out = ssm_widths(sizes)
    projections = d * out + inner * d
    mixer = projections + channels * sizes["ssm_d_conv"] + channels \
        + 3 * heads + inner
    attention = d * (h + 2 * kv) * hd + h * hd * d
    ffn = 3 * d * f
    n_mamba = sizes["layer_types"].count("mamba")
    n_attention = sizes["n_layers"] - n_mamba
    return {
        "mixer": mixer, "mixer_projections": projections,
        "attention": attention, "ffn": ffn,
        "mamba_layer": mixer + ffn + 2 * d,
        "attention_layer": attention + ffn + 2 * d,
        "mamba_layers": n_mamba, "attention_layers": n_attention,
        "matmul": n_mamba * (projections + ffn)
        + n_attention * (attention + ffn),
        "embed": v * d,
        "total": n_mamba * (mixer + ffn + 2 * d)
        + n_attention * (attention + ffn + 2 * d) + v * d + d,
    }
