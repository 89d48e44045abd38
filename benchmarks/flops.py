"""Operations and bytes the algorithm needs, from shapes alone. Kept with
the benchmark so that no later change can move the yardstick.

Conventions: a multiply-add is two operations; causal attention is counted
once (the half of the square that is not masked); work a step recomputes
(rematerialisation, the backward kernels' second pass over the scores) is
not counted in a step's utilisation, and is counted in a kernel's own
roofline only where that kernel's algorithm needs it for the call.
``sizes`` is ``weights.sizes_of(config)``.
"""

from __future__ import annotations

from benchmarks.weights import n_params

BF16 = 2


def matmul_params(sizes: dict) -> int:
    """Parameters that take part in a matrix product for every token: the
    blocks' matrices and the output head. The embedding is a gather."""
    return n_params(sizes)["matmul"]


def train_flops_per_token(sizes: dict, seq: int) -> float:
    """Forward and backward: 6 per matmul parameter, and causal attention
    (scores and weighted sum: 2·S·d forward a token a layer, three times
    that with the backward)."""
    attention = 6.0 * seq * sizes["d_model"] * sizes["n_layers"]
    return 6.0 * matmul_params(sizes) + attention


def prefill_flops(sizes: dict, prompt: int) -> float:
    """One prompt through the blocks, causal attention within it, and the
    head at its last position (the only logits a request needs)."""
    p = n_params(sizes)
    blocks = 2.0 * sizes["n_layers"] * p["block_matmul"] * prompt
    attention = 2.0 * prompt * prompt * sizes["d_model"] * sizes["n_layers"]
    return blocks + attention + 2.0 * p["lm_head"]


def decode_step_flops(sizes: dict, context: int) -> float:
    """One new token that attends ``context`` positions (itself among
    them)."""
    attention = 4.0 * context * sizes["d_model"] * sizes["n_layers"]
    return 2.0 * matmul_params(sizes) + attention


def request_flops(sizes: dict, prompt: int, new_tokens: int) -> float:
    """Prefill yields the first new token; each further one is a cached
    step. ``generate`` runs ``new_tokens`` cached steps after the prefill
    (its last one feeds a token whose successor is never returned); the
    request needs ``new_tokens - 1`` of them."""
    steps = sum(decode_step_flops(sizes, prompt + t)
                for t in range(1, new_tokens))
    return prefill_flops(sizes, prompt) + steps


def decode_step_bytes(sizes: dict, context: int,
                      weight_bytes: int = BF16) -> float:
    """What one cached step has to read: every matmul weight once, in the
    type it is computed in, and the keys and values of the positions it
    attends."""
    cache = 2.0 * context * sizes["d_model"] * sizes["n_layers"] * BF16
    return float(matmul_params(sizes)) * weight_bytes + cache


def flash_fwd_call(batch: int, seq: int, heads: int, head_dim: int) -> dict:
    """One forward call on (batch, seq, heads, head_dim), causal: scores
    and weighted sum over the unmasked half; q, k, v read and o written in
    bf16, the row statistic written in float32."""
    pairs = batch * heads * seq * seq / 2.0
    return {"flops": 4.0 * pairs * head_dim,
            "bytes": 4.0 * batch * seq * heads * head_dim * BF16
            + 4.0 * batch * heads * seq}


def flash_bwd_dq_call(batch: int, seq: int, heads: int,
                      head_dim: int) -> dict:
    """dQ pass: scores, dP = dO·Vᵀ and dQ = dS·K over the unmasked half;
    reads q, k, v, dO and two row statistics, writes dQ."""
    pairs = batch * heads * seq * seq / 2.0
    return {"flops": 6.0 * pairs * head_dim,
            "bytes": 5.0 * batch * seq * heads * head_dim * BF16
            + 8.0 * batch * heads * seq}


def flash_bwd_dkv_call(batch: int, seq: int, heads: int,
                       head_dim: int) -> dict:
    """dK/dV pass: scores, dV = Pᵀ·dO, dP and dK = dSᵀ·Q; reads q, k, v,
    dO and two row statistics, writes dK and dV."""
    pairs = batch * heads * seq * seq / 2.0
    return {"flops": 8.0 * pairs * head_dim,
            "bytes": 6.0 * batch * seq * heads * head_dim * BF16
            + 8.0 * batch * heads * seq}


def least_seconds(call: dict, peaks: dict) -> dict:
    """The least time the chip could take for a call, and which peak
    bounds it."""
    by_flops = call["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = call["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "memory"}
