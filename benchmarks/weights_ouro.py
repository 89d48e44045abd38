"""Weights and sizes of the looped decoder (``configs/ouro-2.6b.json``)
from ``--seed``, beside ``weights.py`` and in its manner: one jitted call,
on the device, in the type asked for; matrices normal / sqrt(fan_in); norm
scales 1 + 0.1·normal, so that a scale that is dropped shows; the exit
gate's weight and bias non-zero. Token ids are ``weights.token_rows``.

The tree is the program's checkpoint format for these kinds: ``embed``
(V, D); ``blocks[i]`` with ``ln1``, ``wqkv`` (D, 3, H, hd), ``wo``
(H, hd, D), ``ln1_post``, ``ln2``, ``wg`` and ``w1`` (D, F: gate and up),
``w2`` (F, D: down), ``ln2_post``; ``ln_f``; ``exit_gate`` (``w`` (D,),
``b`` ()); ``lm_head`` (D, V).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key, token_rows  # noqa: F401


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file that keeps
    the published key names."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if int(config["head_dim"]) * h != d \
            or int(config["num_key_value_heads"]) != h:
        raise ValueError(
            "the program's attention has hidden_size / heads lanes a head "
            f"and as many key/value heads as query heads; got {config}")
    if config["hidden_act"] != "silu" or config["rope_scaling"] is not None \
            or config["sliding_window"] is not None \
            or config["tie_word_embeddings"]:
        raise ValueError(f"not the block this file makes weights for: {config}")
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": d,
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": h,
        "head_dim": d // h,
        "d_ff": int(config["intermediate_size"]),
        "max_seq": int(config["max_position_embeddings"]),
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "passes": int(config["total_ut_steps"]),
        "exit_threshold": float(config["early_exit_threshold"]),
    }


def _tree(key: jax.Array, sizes: dict, dtype) -> dict:
    d, h, hd = sizes["d_model"], sizes["n_heads"], sizes["head_dim"]
    f, v = sizes["d_ff"], sizes["vocab"]

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, dtype) / math.sqrt(fan_in)

    def scale(k):
        return 1.0 + 0.1 * jax.random.normal(k, (d,), dtype)

    keys = jax.random.split(key, sizes["n_layers"] + 5)
    blocks = []
    for i in range(sizes["n_layers"]):
        k = jax.random.split(keys[i], 9)
        blocks.append({
            "ln1": scale(k[0]),
            "wqkv": dense(k[1], (d, 3, h, hd), d),
            "wo": dense(k[2], (h, hd, d), d),
            "ln1_post": scale(k[3]),
            "ln2": scale(k[4]),
            "wg": dense(k[5], (d, f), d),
            "w1": dense(k[6], (d, f), d),
            "w2": dense(k[7], (f, d), f),
            "ln2_post": scale(k[8]),
        })
    return {
        "embed": dense(keys[-5], (v, d), d),
        "blocks": blocks,
        "ln_f": scale(keys[-4]),
        # λ = sigmoid(x·w + b) on a normed state: x·w is about normal(0, 1)
        "exit_gate": {"w": dense(keys[-3], (d,), d),
                      "b": 0.5 * jax.random.normal(keys[-2], (), dtype) - 1.0},
        "lm_head": dense(keys[-1], (d, v), d),
    }


def make_weights(seed: int, sizes: dict, dtype=jnp.bfloat16,
                 device=None) -> dict:
    """The whole tree in one jitted call, on ``device``."""
    frozen = tuple(sorted(sizes.items()))

    def build(key):
        return _tree(key, dict(frozen), dtype)

    sharding = None if device is None \
        else jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=sharding)(seed_key(seed))


def n_params(sizes: dict) -> dict:
    """Parameter counts from the sizes: a block's matrices, a block, the
    embedding, the head, the gate, all."""
    d, f, v = sizes["d_model"], sizes["d_ff"], sizes["vocab"]
    block_matmul = 4 * d * d + 3 * d * f
    block = block_matmul + 4 * d
    gate = d + 1
    return {"block_matmul": block_matmul, "block": block, "embed": v * d,
            "lm_head": v * d, "exit_gate": gate,
            "total": sizes["n_layers"] * block + 2 * v * d + d + gate}
