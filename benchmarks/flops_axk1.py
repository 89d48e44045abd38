"""Operations and bytes that latent attention under YaRN needs in single
layers whose feed-forward is dense in the leading layers and an expert
layer (a held share of the routed experts, shared experts beside them) in
the rest (``configs/a.x-k1.json``), from shapes and from the program's
counters; beside ``flops_longcat.py`` and under its conventions (a
multiply-add is two operations; causal attention counted once; nothing
recomputed is counted). The count is of the algorithm, whatever
implements it:

- a token multiplies through every matrix outside the routed experts (a
  layer's latent attention, the dense feed-forward or the router and the
  shared experts), and through one routed expert for each of its picks
  that fell on an expert held here (``picks_held``, counted by the
  program on the device); picks of experts held elsewhere cost nothing;
- at prefill a position's latent is expanded to every head's key and
  value once (the up-projection is among the matrices above), whatever
  the chunks: a chunk that expands the chunks before it again is charged
  nothing for it; attention is over keys of ``qk_nope + qk_rope`` lanes
  and values of ``v_head`` lanes, the causal half;
- a cached step attends over the latent cache as it lies: a position
  attended costs ``kv_rank + qk_rope`` lanes for the score and ``kv_rank``
  for the sum, a head;
- a cached step must read every matrix outside the routed experts once,
  the head's slice, the routed experts that got at least one token
  (``experts_hit``, counted by the program), and a latent and its rotary
  lanes a position attended a layer: nothing of an expert nobody picked.

``sizes`` is ``weights_axk1.sizes_of(config)``.
"""

from __future__ import annotations

from benchmarks.weights_axk1 import ffn_kinds, n_params

BF16 = 2


def cache_bytes_per_position(sizes: dict) -> int:
    """One latent and its rotary lanes, every layer's attention."""
    return (sizes["kv_rank"] + sizes["qk_rope"]) * BF16 * sizes["n_layers"]


def expert_flops(sizes: dict, picks_held: float) -> float:
    """The routed experts held here, for the picks that fell on them."""
    return 2.0 * n_params(sizes)["expert"] * picks_held


def prefill_flops(sizes: dict, rows: int, prompt: int) -> float:
    """``rows`` prompts of ``prompt`` tokens through every matrix outside
    the routed experts, causal attention within each prompt on expanded
    keys and values, and the head at each row's last position (the only
    logits a request needs). The routed experts' part is
    :func:`expert_flops`."""
    p = n_params(sizes)
    lanes = sizes["qk_nope"] + sizes["qk_rope"] + sizes["v_head"]
    attention = (2.0 * (prompt * prompt / 2.0) * sizes["n_heads"] * lanes
                 * sizes["n_layers"])
    return rows * (2.0 * p["matrices_a_token"] * prompt + attention
                   + 2.0 * p["lm_head"])


def decode_step_flops(sizes: dict, rows: int, context: int) -> float:
    """``rows`` new tokens, each attending ``context`` positions (itself
    among them) of its own latent caches, and the head for each."""
    p = n_params(sizes)
    lanes = 2 * sizes["kv_rank"] + sizes["qk_rope"]
    attention = 2.0 * context * sizes["n_heads"] * lanes * sizes["n_layers"]
    return rows * (2.0 * (p["matrices_a_token"] + p["lm_head"]) + attention)


def request_flops(sizes: dict, rows: int, prompt: int, new_tokens: int,
                  picks_held: float) -> float:
    """Prefill yields each row's first new token; each further one is a
    cached step: the request needs ``new_tokens - 1`` of them.
    ``generate`` runs one more, whose successors are never returned, and
    counts its picks too: ``picks_held`` is of ``prompt + new_tokens``
    positions a row, and the request is charged the share of the
    ``prompt + new_tokens - 1`` it needs."""
    steps = sum(decode_step_flops(sizes, rows, prompt + t)
                for t in range(1, new_tokens))
    needed = (prompt + new_tokens - 1.0) / (prompt + new_tokens)
    return (prefill_flops(sizes, rows, prompt) + steps
            + expert_flops(sizes, picks_held * needed))


def decode_step_bytes(sizes: dict, rows: int, context: float,
                      experts_hit: float) -> float:
    """What one cached step of ``rows`` tokens has to read: every layer
    outside its routed experts, the final norm and the head once,
    ``experts_hit`` routed experts (over all layers: those that got at
    least one token), and the latent caches over the positions attended,
    every row its own; all bfloat16."""
    p = n_params(sizes)
    kinds = ffn_kinds(sizes)
    held = sizes["experts_held"][1]
    outside = (kinds.count("dense") * p["dense_layer"]
               + kinds.count("experts")
               * (p["expert_layer"] - held * p["expert"]))
    weights = (outside + p["lm_head"] + sizes["d_model"]
               + experts_hit * p["expert"]) * BF16
    return float(weights) + float(
        cache_bytes_per_position(sizes)) * context * rows
