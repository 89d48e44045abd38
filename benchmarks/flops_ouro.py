"""Operations and bytes the looped decoder needs, from shapes alone; beside
``flops.py`` and under its conventions (a multiply-add is two operations;
causal attention counted once; nothing recomputed is counted). The count
is of the algorithm, whatever implements it: every token passes the whole
stack ``passes`` times over the same weights, each pass with a cache of
its own, and the head reads the last pass once.
``sizes`` is ``weights_ouro.sizes_of(config)``.
"""

from __future__ import annotations

from benchmarks.weights_ouro import n_params

BF16 = 2


def stack_params(sizes: dict) -> int:
    """Block matrices a token multiplies through in one step: every
    block's, once a pass."""
    return sizes["passes"] * sizes["n_layers"] * n_params(sizes)["block_matmul"]


def cache_bytes_per_position(sizes: dict) -> int:
    """Keys and values of one position: every layer, every pass."""
    return 2 * sizes["d_model"] * BF16 * sizes["n_layers"] * sizes["passes"]


def prefill_flops(sizes: dict, prompt: int) -> float:
    """One prompt through the stack ``passes`` times, causal attention
    within it once a pass a layer, and the head at its last position (the
    only logits a request needs). The exit gate is one dot product a
    position a pass."""
    p = n_params(sizes)
    attention = (2.0 * prompt * prompt * sizes["d_model"]
                 * sizes["n_layers"] * sizes["passes"])
    gate = 2.0 * sizes["d_model"] * prompt * sizes["passes"]
    return (2.0 * stack_params(sizes) * prompt + attention + gate
            + 2.0 * p["lm_head"])


def decode_step_flops(sizes: dict, context: int) -> float:
    """One new token that attends ``context`` positions (itself among
    them) in each pass's cache."""
    p = n_params(sizes)
    attention = (4.0 * context * sizes["d_model"] * sizes["n_layers"]
                 * sizes["passes"])
    gate = 2.0 * sizes["d_model"] * sizes["passes"]
    return 2.0 * (stack_params(sizes) + p["lm_head"]) + attention + gate


def request_flops(sizes: dict, prompt: int, new_tokens: int) -> float:
    """Prefill yields the first new token; each further one is a cached
    step: the request needs ``new_tokens - 1`` of them (``generate`` runs
    one more, whose successor is never returned)."""
    steps = sum(decode_step_flops(sizes, prompt + t)
                for t in range(1, new_tokens))
    return prefill_flops(sizes, prompt) + steps


def decode_step_bytes(sizes: dict, context: int) -> float:
    """What one cached step has to read: every block matrix once a pass
    (the stack's 4.9 GB cannot stay on the chip between passes), the head
    and the exit gate once, and the keys and values of the positions it
    attends, in every pass's cache; all bfloat16."""
    p = n_params(sizes)
    weights = (stack_params(sizes) + p["lm_head"] + p["exit_gate"]) * BF16
    return float(weights) + float(cache_bytes_per_position(sizes)) * context
