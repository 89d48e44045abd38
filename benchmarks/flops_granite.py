"""Operations and bytes that a decoder of Mamba-2 state-space layers and
grouped-query attention layers needs, from shapes; beside ``flops.py`` and
under its conventions (a multiply-add is two operations; causal attention
counted once; nothing recomputed is counted). The count is of the
algorithm, whatever implements it:

- a token multiplies through every matrix of a layer: a state-space
  mixer's two projections or an attention's four, and the gated
  feed-forward; the head, the table transposed, at the positions served;
- attention scores and sums over the positions attended, every query head
  (the key/value heads are fewer, the products are not);
- the recurrence a token a state-space layer, an element of S (heads ×
  lanes × state): decay, push and add (3) for the update, multiply and add
  (2) for the read-out, whether a cached step or the chunked form runs it;
  the convolution's taps (2 a tap a channel);
- a cached step must move: every matrix and the table once; S of every
  state-space layer read and written; the convolution windows read and one
  new row written; the keys and values of the positions attended read and
  one position written, over the key/value heads; all bfloat16.

``sizes`` is ``weights_granite.sizes_of(config)``.
"""

from __future__ import annotations

from benchmarks.weights_granite import n_params, ssm_widths

BF16 = 2
# operations an element of S a position: update (decay, push, add) and
# read-out (multiply, add)
UPDATE_OPS, READOUT_OPS = 3, 2


def state_elements(sizes: dict) -> int:
    """Elements of one row's S in one state-space layer."""
    return sizes["ssm_heads"] * sizes["ssm_head_dim"] * sizes["ssm_d_state"]


def recurrence_flops_per_token(sizes: dict) -> float:
    """One state-space layer's operations a token between its two
    projections that the roofline counts: the update and read-out of S
    and the convolution."""
    channels = ssm_widths(sizes)[2]
    return float((UPDATE_OPS + READOUT_OPS) * state_elements(sizes)
                 + 2 * sizes["ssm_d_conv"] * channels)


def kv_bytes_per_position(sizes: dict) -> int:
    """Keys and values of one position of one row, every attention layer."""
    p = n_params(sizes)
    return (2 * sizes["n_kv_heads"] * sizes["head_dim"] * BF16
            * p["attention_layers"])


def state_bytes(sizes: dict, rows: int) -> int:
    """S of every state-space layer, ``rows`` rows."""
    return (n_params(sizes)["mamba_layers"] * rows * state_elements(sizes)
            * BF16)


def window_bytes(sizes: dict, rows: int) -> int:
    """The convolution windows of every state-space layer."""
    return (n_params(sizes)["mamba_layers"] * rows
            * (sizes["ssm_d_conv"] - 1) * ssm_widths(sizes)[2] * BF16)


def prefill_flops(sizes: dict, rows: int, prompt: int) -> float:
    """``rows`` prompts of ``prompt`` tokens through every layer, causal
    attention within each prompt, the recurrence at every position, and
    the head at each row's last position (the only logits a request
    needs)."""
    p = n_params(sizes)
    attention = (2.0 * (prompt * prompt / 2.0) * sizes["n_heads"]
                 * 2 * sizes["head_dim"] * p["attention_layers"])
    recurrence = (recurrence_flops_per_token(sizes) * prompt
                  * p["mamba_layers"])
    return rows * (2.0 * p["matmul"] * prompt + attention + recurrence
                   + 2.0 * p["embed"])


def decode_step_flops(sizes: dict, rows: int, context: int) -> float:
    """``rows`` new tokens, each attending ``context`` positions (itself
    among them), one step of every recurrence, and the head for each."""
    p = n_params(sizes)
    attention = (2.0 * context * sizes["n_heads"] * 2 * sizes["head_dim"]
                 * p["attention_layers"])
    recurrence = recurrence_flops_per_token(sizes) * p["mamba_layers"]
    return rows * (2.0 * (p["matmul"] + p["embed"]) + attention + recurrence)


def request_flops(sizes: dict, rows: int, prompt: int,
                  new_tokens: int) -> float:
    """Prefill yields each row's first new token; each further one is a
    cached step: the request needs ``new_tokens - 1`` of them."""
    return prefill_flops(sizes, rows, prompt) + sum(
        decode_step_flops(sizes, rows, prompt + t)
        for t in range(1, new_tokens))


def decode_step_bytes(sizes: dict, rows: int, context: float) -> float:
    """What one cached step of ``rows`` tokens has to move."""
    weights = n_params(sizes)["total"] * BF16
    windows = window_bytes(sizes, rows) * sizes["ssm_d_conv"] \
        / (sizes["ssm_d_conv"] - 1)
    return (float(weights) + 2.0 * state_bytes(sizes, rows) + windows
            + float(kv_bytes_per_position(sizes)) * rows * (context + 1))


def state_step(sizes: dict, rows: int) -> dict:
    """The update and read-out of S in one cached step of ``rows`` rows,
    all state-space layers: operations, and the bytes of S read and
    written; ``flops.least_seconds`` takes these keys."""
    layers = n_params(sizes)["mamba_layers"]
    return {"flops": float((UPDATE_OPS + READOUT_OPS) * state_elements(sizes)
                           * rows * layers),
            "bytes": 2.0 * state_bytes(sizes, rows)}
