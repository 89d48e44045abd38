"""Operations and bytes that a decoder-hybrid-decoder needs
(``configs/phi-4-mini-flash-reasoning.json``), from shapes; beside
``flops_granite.py`` and under its conventions (a multiply-add is two
operations; causal attention counted once; nothing recomputed is counted).
The count is of the algorithm, whatever implements it:

- a token multiplies through every matrix of the layers it passes and the
  gated feed-forward of each; the head, the table transposed, at the
  positions served;
- a prompt passes the self-decoder (the Mamba-1 and window layers and the
  one full attention) at every position and the cross-decoder (gated
  memory units and cross attentions), the final norm and the head at its
  last position alone: a cross-decoder layer at a position reads that
  position's residual and memory and the shared cache, and only the last
  position is served;
- differential attention scores and sums twice over (two maps a pair: as
  many score products as heads) over the positions attended, a window's at
  most ``window`` of them, and each map sums a value of 2 · head_dim
  lanes;
- Mamba-1's recurrence a token a layer, an element of S (inner × state):
  the decay's product, exponential and product with S, the push and the
  add for the update, multiply and add for the read-out
  (``UPDATE_OPS`` + ``READOUT_OPS`` = 7); the convolution's taps;
- a cached step must move: every matrix and the table once; S of every
  Mamba-1 layer read and written; the convolution windows read and one row
  written; the keys and values of the *written* slots a window layer
  attends (at most ``window``) and of the positions the full layer and
  each cross layer attend (the one shared cache is read once by each of
  them), and one position written by the 9 layers that write; all
  bfloat16.

``sizes`` is ``weights_phi4flash.sizes_of(config)``.
"""

from __future__ import annotations

from benchmarks.weights_phi4flash import n_params

BF16 = 2
# operations an element of S a position: dt·A, its exponential, the decay
# times S, the push dt·x·B (one more product: dt·x is a lane's), the add;
# then the read-out's multiply and add
UPDATE_OPS, READOUT_OPS = 5, 2
SELF_DECODER = ("mamba1", "window", "full")
CROSS_DECODER = ("memory", "cross")


def _matrices(sizes: dict, kinds) -> int:
    p = n_params(sizes)
    return sum(p["layers"][kind] * (p["mixer_matrices"][kind] + p["ffn"])
               for kind in kinds)


def state_elements(sizes: dict) -> int:
    """Elements of one row's S in one Mamba-1 layer."""
    return sizes["ssm_inner"] * sizes["ssm_d_state"]


def recurrence_flops_per_token(sizes: dict) -> float:
    """One Mamba-1 layer's operations a token between its projections:
    the update and read-out of S and the convolution."""
    return float((UPDATE_OPS + READOUT_OPS) * state_elements(sizes)
                 + 2 * sizes["ssm_d_conv"] * sizes["ssm_inner"])


def attended(sizes: dict, kind: str, context: float) -> float:
    """Positions a query whose own position is the ``context``-th attends
    in a layer of ``kind``."""
    return min(context, sizes["window"]) if kind == "window" else context


def attention_flops(sizes: dict, positions: float) -> float:
    """One differential attention layer, one query over ``positions``:
    scores of every head over head_dim lanes, sums of every head over its
    pair's 2 · head_dim value lanes."""
    return 2.0 * positions * sizes["n_heads"] * 3 * sizes["head_dim"]


def prefill_flops(sizes: dict, rows: int, prompt: int) -> float:
    """``rows`` prompts of ``prompt`` tokens: the self-decoder at every
    position, the cross-decoder and the head at the last alone."""
    layers = n_params(sizes)["layers"]
    own = sum(attention_flops(sizes, attended(sizes, "window", t + 1))
              for t in range(prompt)) * layers["window"] \
        + sum(attention_flops(sizes, t + 1) for t in range(prompt)) \
        * layers["full"]
    recurrence = recurrence_flops_per_token(sizes) * prompt * layers["mamba1"]
    last = (2.0 * (_matrices(sizes, CROSS_DECODER)
                   + n_params(sizes)["embed"])
            + attention_flops(sizes, prompt) * layers["cross"])
    return rows * (2.0 * _matrices(sizes, SELF_DECODER) * prompt + own
                   + recurrence + last)


def whole_stack_prefill_flops(sizes: dict, rows: int, prompt: int) -> float:
    """What prefill would need with every layer at every position (the
    head still at the last): what the skip is measured against."""
    layers = n_params(sizes)["layers"]
    skipped = (2.0 * _matrices(sizes, CROSS_DECODER) * (prompt - 1)
               + sum(attention_flops(sizes, t + 1)
                     for t in range(prompt - 1)) * layers["cross"])
    return prefill_flops(sizes, rows, prompt) + rows * skipped


def decode_step_flops(sizes: dict, rows: int, context: int) -> float:
    """``rows`` new tokens through all layers, each attending ``context``
    positions (itself among them; a window layer its window of them), one
    step of every recurrence, and the head for each."""
    p = n_params(sizes)
    attention = sum(
        p["layers"][kind] * attention_flops(sizes, attended(sizes, kind,
                                                            context))
        for kind in ("window", "full", "cross"))
    recurrence = recurrence_flops_per_token(sizes) * p["layers"]["mamba1"]
    return rows * (2.0 * (p["matmul"] + p["embed"]) + attention + recurrence)


def request_flops(sizes: dict, rows: int, prompt: int,
                  new_tokens: int) -> float:
    """Prefill yields each row's first new token; each further one is a
    cached step: the request needs ``new_tokens - 1`` of them."""
    return prefill_flops(sizes, rows, prompt) + sum(
        decode_step_flops(sizes, rows, prompt + t)
        for t in range(1, new_tokens))


def kv_bytes_per_position(sizes: dict) -> int:
    """Keys and values of one position of one row in one layer."""
    return 2 * sizes["n_kv_heads"] * sizes["head_dim"] * BF16


def state_bytes(sizes: dict, rows: int) -> int:
    """S of every Mamba-1 layer, ``rows`` rows."""
    return (n_params(sizes)["layers"]["mamba1"] * rows
            * state_elements(sizes) * BF16)


def window_bytes(sizes: dict, rows: int) -> int:
    """The convolution windows of every Mamba-1 layer."""
    return (n_params(sizes)["layers"]["mamba1"] * rows
            * (sizes["ssm_d_conv"] - 1) * sizes["ssm_inner"] * BF16)


def attended_bytes(sizes: dict, rows: int, context: float) -> float:
    """The keys and values a cached step's attending layers must read,
    the new position at ``context`` among them: the written slots of the
    window layers' rings, and the shared cache once for the full layer
    and once for each cross layer."""
    layers = n_params(sizes)["layers"]
    positions = sum(layers[kind] * attended(sizes, kind, context)
                    for kind in ("window", "full", "cross"))
    return float(kv_bytes_per_position(sizes)) * rows * positions


def decode_step_bytes(sizes: dict, rows: int, context: float) -> float:
    """What one cached step of ``rows`` tokens has to move; ``context``
    counts the new position."""
    layers = n_params(sizes)["layers"]
    weights = n_params(sizes)["total"] * BF16
    windows = window_bytes(sizes, rows) * sizes["ssm_d_conv"] \
        / (sizes["ssm_d_conv"] - 1)
    written = float(kv_bytes_per_position(sizes)) * rows \
        * (layers["window"] + layers["full"])
    return (float(weights) + 2.0 * state_bytes(sizes, rows) + windows
            + attended_bytes(sizes, rows, context) + written)
