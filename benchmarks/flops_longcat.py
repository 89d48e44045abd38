"""Operations and bytes that latent attention in shortcut-connected double
layers with a held share of the experts needs, from shapes and from the
program's counters; beside ``flops.py`` and under its conventions (a
multiply-add is two operations; causal attention counted once; nothing
recomputed is counted). The count is of the algorithm, whatever
implements it:

- a token multiplies through every matrix of a layer outside its experts
  (two latent attentions, two dense feed-forwards, the router), and
  through one routed expert for each of its picks that fell on an expert
  held here (``picks_held``, counted by the program on the device);
  zero-compute experts and picks of experts held elsewhere cost nothing;
- at prefill a position's latent is expanded to every head's key and
  value once, and attention is over keys of ``qk_nope + qk_rope`` lanes
  and values of ``v_head`` lanes, the causal half;
- a cached step attends over the latent cache as it lies: the keys' half
  of the up-projection goes into the query and the values' half onto the
  weighted sum (both products of the same matrix, so a token multiplies
  through as many parameters as at prefill), and a position attended
  costs ``kv_rank + qk_rope`` lanes for the score and ``kv_rank`` for
  the sum, a head;
- a cached step must read every matrix outside the experts once, the
  head's slice, the experts that got at least one token
  (``experts_hit``, counted by the program), and a latent and its rotary
  lanes a position attended an attention: nothing of an expert nobody
  picked.

``sizes`` is ``weights_longcat.sizes_of(config)``.
"""

from __future__ import annotations

from benchmarks.weights_longcat import n_params

BF16 = 2


def attentions(sizes: dict) -> int:
    """Attentions a token passes: two a layer."""
    return 2 * sizes["n_layers"]


def matmul_params_outside_experts(sizes: dict) -> int:
    """Matrix parameters a token multiplies through in every layer,
    whatever it picks: the layers outside their experts, less the norms'
    scales and the selection bias."""
    p = n_params(sizes)
    width = sizes["routed_experts"] + sizes["zero_experts"]
    scales = 4 * sizes["d_model"] + 2 * (sizes["q_rank"] + sizes["kv_rank"])
    return sizes["n_layers"] * (p["layer_outside_experts"] - scales - width)


def cache_bytes_per_position(sizes: dict) -> int:
    """One latent and its rotary lanes, every attention of every layer."""
    return (sizes["kv_rank"] + sizes["qk_rope"]) * BF16 * attentions(sizes)


def expert_flops(sizes: dict, picks_held: float) -> float:
    """The routed experts held here, for the picks that fell on them."""
    return 2.0 * n_params(sizes)["expert"] * picks_held


def prefill_flops(sizes: dict, rows: int, prompt: int) -> float:
    """``rows`` prompts of ``prompt`` tokens through the layers outside
    their experts, causal attention within each prompt on expanded keys
    and values, and the head at each row's last position (the only logits
    a request needs). The experts' part is :func:`expert_flops`."""
    p = n_params(sizes)
    lanes = sizes["qk_nope"] + sizes["qk_rope"] + sizes["v_head"]
    attention = (2.0 * (prompt * prompt / 2.0) * sizes["n_heads"] * lanes
                 * attentions(sizes))
    return rows * (2.0 * matmul_params_outside_experts(sizes) * prompt
                   + attention + 2.0 * p["lm_head"])


def decode_step_flops(sizes: dict, rows: int, context: int) -> float:
    """``rows`` new tokens, each attending ``context`` positions (itself
    among them) of its own latent caches, and the head for each."""
    p = n_params(sizes)
    lanes = 2 * sizes["kv_rank"] + sizes["qk_rope"]
    attention = (2.0 * context * sizes["n_heads"] * lanes
                 * attentions(sizes))
    return rows * (2.0 * (matmul_params_outside_experts(sizes)
                          + p["lm_head"]) + attention)


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
    """What one cached step of ``rows`` tokens has to read: the layers
    outside their experts and the head once, ``experts_hit`` routed
    experts (over all layers: those that got at least one token), and the
    latent caches over the positions attended, every row its own; all
    bfloat16."""
    p = n_params(sizes)
    weights = (sizes["n_layers"] * p["layer_outside_experts"]
               + p["lm_head"] + sizes["d_model"]
               + experts_hit * p["expert"]) * BF16
    return float(weights) + float(
        cache_bytes_per_position(sizes)) * context * rows
