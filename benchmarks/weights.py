"""Weights and token ids from ``--seed``: the benchmark's own, so that the
program under test and the plain reference start from the same numbers
without either handing anything to the other.

The tree has the layout the program's model code takes (its checkpoint
format, as it were): ``embed`` (V, D), ``blocks[i]`` with ``ln1``, ``wqkv``
(D, 3, H, hd), ``wo`` (H, hd, D), ``ln2``, ``w1`` (D, F), ``w2`` (F, D),
then ``ln_f`` and ``lm_head`` (D, V). Matrices are normal / sqrt(fan_in);
norm scales are 1 + 0.1·normal, so that a scale that is dropped shows.
Everything is made in one jitted call, on the device (or laid over the
mesh by ``out_shardings``), in the type asked for.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def sizes_of(config: dict) -> dict:
    """The sizes the benchmark needs, from a configuration file that keeps
    the published key names."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if d % h:
        raise ValueError(f"hidden_size {d} is not divisible by {h} heads")
    return {
        "vocab": int(config["vocab_size"]),
        "d_model": d,
        "n_layers": int(config["num_hidden_layers"]),
        "n_heads": h,
        "head_dim": d // h,
        "d_ff": int(config["intermediate_size"]),
        "max_seq": int(config["max_position_embeddings"]),
        "rope_theta": float(config["rotary_emb_base"]),
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low 32 bits make the key, what
    lies above them is folded in. The generator is ``rbg`` (XLA's
    RngBitGenerator, one operation a leaf): the same bits whatever the
    sharding, and on a TPU far cheaper than threefry's unrolled rounds. Its
    bits differ from one backend to another, which nothing here needs: the
    program's weights and the reference's are made on the same chips."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return jax.random.fold_in(jax.random.key(seed % 2**32, impl="rbg"),
                              seed >> 32)


def _tree(key: jax.Array, sizes: dict, dtype) -> dict:
    d, h, hd = sizes["d_model"], sizes["n_heads"], sizes["head_dim"]
    f, v = sizes["d_ff"], sizes["vocab"]

    def dense(k, shape, fan_in):
        return jax.random.normal(k, shape, dtype) / math.sqrt(fan_in)

    def scale(k):
        return 1.0 + 0.1 * jax.random.normal(k, (d,), dtype)

    keys = jax.random.split(key, sizes["n_layers"] + 3)
    blocks = []
    for i in range(sizes["n_layers"]):
        k = jax.random.split(keys[i], 6)
        blocks.append({
            "ln1": scale(k[0]),
            "wqkv": dense(k[1], (d, 3, h, hd), d),
            "wo": dense(k[2], (h, hd, d), d),
            "ln2": scale(k[3]),
            "w1": dense(k[4], (d, f), d),
            "w2": dense(k[5], (f, d), f),
        })
    return {
        "embed": dense(keys[-3], (v, d), d),
        "blocks": blocks,
        "ln_f": scale(keys[-2]),
        "lm_head": dense(keys[-1], (d, v), d),
    }


def make_weights(seed: int, sizes: dict, dtype=jnp.float32,
                 out_shardings=None, device=None) -> dict:
    """The whole tree in one jitted call. ``out_shardings`` lays it over a
    mesh as it is made; ``device`` puts it on one chip."""
    frozen = tuple(sorted(sizes.items()))

    def build(key):
        return _tree(key, dict(frozen), dtype)

    if device is not None:
        out_shardings = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))


def distance_from_seed(seed: int, sizes: dict, tree: dict) -> list:
    """Leaf by leaf, the norm of ``tree`` less the weights the seed gives
    (float32), in one jitted call laid out like ``tree``: the weights are
    made again inside it, so no second whole copy is held beside a training
    state that nearly fills the chips."""
    frozen = tuple(sorted(sizes.items()))
    shardings = jax.tree.map(lambda x: x.sharding, tree)

    def norms(key, now):
        start = _tree(key, dict(frozen), jnp.float32)
        start = jax.tree.map(jax.lax.with_sharding_constraint, start,
                             shardings)
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
                for a, b in zip(jax.tree.leaves(now),
                                jax.tree.leaves(start))]

    return jax.jit(norms)(seed_key(seed), tree)


def n_params(sizes: dict) -> dict:
    """Parameter counts from the sizes: per block, embedding, head, all."""
    d, f, v = sizes["d_model"], sizes["d_ff"], sizes["vocab"]
    block_matmul = 4 * d * d + 2 * d * f
    block = block_matmul + 2 * d
    total = sizes["n_layers"] * block + 2 * v * d + d
    return {"block": block, "block_matmul": block_matmul, "embed": v * d,
            "lm_head": v * d, "total": total,
            "matmul": sizes["n_layers"] * block_matmul + v * d}


def token_rows(seed: int, stream: int, index: int, rows: int, length: int,
               vocab: int) -> np.ndarray:
    """``rows`` × ``length`` token ids, uniform over the vocabulary, from
    (seed, stream, index) alone: the program's feed and the reference's are
    made by the same call and never passed between them."""
    rng = np.random.default_rng([int(seed), int(stream), int(index)])
    return rng.integers(0, vocab, size=(rows, length), dtype=np.int32)
