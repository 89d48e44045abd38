"""Mixture-of-Experts transformer: the second model family, exercising
expert parallelism over the ``ep`` mesh axis.

Top-k routing (switch-style top-1 by default, GShard-style top-2+ via
``router_top_k``) with fixed expert capacity, in the einsum-dispatch
formulation: a one-hot dispatch tensor scatters tokens into per-expert
buffers, experts run as one batched matmul pair, and the combine einsum
gathers results weighted by the router gates (renormalized over the
selected experts for k > 1). Capacity is allocated slot-major — every
token's first choice outranks any token's second choice, the standard
priority rule. Experts shard over ``ep``; with the dispatch/combine
sharding constraints XLA inserts the token all_to_alls over ICI — the
MoE analog of the MPI world's alltoall (SURVEY §2.4), expressed entirely
through shardings.

Static shapes throughout: capacity is fixed, overflow tokens drop (their
residual passes through), standard for TPU switch routing.

Beside that family, and for ``models/transformer.py``'s one block where a
configuration names ``layer="shortcut"``: :func:`expert_layer`, the expert
layer as one chip of an expert-parallel deployment holds it. The router
has its published width; the chip is told which routed experts it holds,
sorts the picks that fall on them by expert and runs one grouped product
over them (a loop over the row tiles that hold a pick, each through its
expert's matrices: an expert that got no token has no tile and is not
read), so no token is dropped whatever the routing; gated (SwiGLU)
experts, a stored bias that corrects the selection, zero-compute experts
that return their input, and the picks of experts held elsewhere add
nothing here. Where the configuration gives an "experts" feed-forward to
a "single" layer (``ModelConfig.ffn_types``) the same function is that
layer's feed-forward: a sigmoid router whose picked weights are
renormalised, without a stored bias, and shared experts that every token
passes, are said by the configuration's fields.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from faabric_tpu.models import scopes
from faabric_tpu.models.transformer import (
    ModelConfig,
    _feed_forward,
    _rms_norm,
    attention_of_kind,
    refuse_served_only,
)
from faabric_tpu.ops import add_rows


@dataclasses.dataclass(frozen=True)
class MoEConfig(ModelConfig):
    n_experts: int = 4
    capacity_factor: float = 1.25
    # Experts per token: 1 = switch routing (gate = raw top prob),
    # >1 = GShard-style with gates renormalized over the selected experts
    router_top_k: int = 1
    # Auxiliary load-balancing loss weight (switch transformer)
    aux_loss_weight: float = 0.01


def _refuse_other_kinds(cfg: MoEConfig) -> None:
    """The expert layer is a two-matrix GELU behind one pre-norm and
    per-head attention, one to a layer, passed once: a configuration that
    names another kind would be computed as another network (the
    attention half honours ``rope_pairing``)."""
    for field, kind in (("ffn", "gelu"), ("norm_placement", "pre"),
                        ("n_passes", 1), ("attention", "heads"),
                        ("layer", "single")):
        if getattr(cfg, field) != kind:
            raise ValueError(
                f"the MoE family implements {field}={kind!r} only, "
                f"not {field}={getattr(cfg, field)!r}")
    refuse_served_only(cfg, "the MoE family")


def init_moe_params(key: jax.Array, cfg: MoEConfig) -> dict:
    _refuse_other_kinds(cfg)
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, cfg.param_dtype)
                / np.sqrt(fan_in))

    blocks = []
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[i], 5)
        blocks.append({
            "ln1": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "wqkv": dense(bk[0], (cfg.d_model, 3, cfg.n_heads, cfg.head_dim),
                          cfg.d_model),
            "wo": dense(bk[1], (cfg.n_heads, cfg.head_dim, cfg.d_model),
                        cfg.d_model),
            "ln2": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "router": dense(bk[2], (cfg.d_model, cfg.n_experts), cfg.d_model),
            "w1": dense(bk[3], (cfg.n_experts, cfg.d_model, cfg.d_ff),
                        cfg.d_model),
            "w2": dense(bk[4], (cfg.n_experts, cfg.d_ff, cfg.d_model),
                        cfg.d_ff),
        })
    return {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.d_model), cfg.d_model),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "lm_head": dense(keys[-1], (cfg.d_model, cfg.vocab_size), cfg.d_model),
    }


def moe_param_shardings(mesh: Mesh, cfg: MoEConfig) -> dict:
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    block = {
        "ln1": ns(),
        "wqkv": ns(None, None, "tp", None),
        "wo": ns("tp", None, None),
        "ln2": ns(),
        "router": ns(),
        # Experts shard over ep; each expert's hidden over tp
        "w1": ns("ep", None, "tp"),
        "w2": ns("ep", "tp", None),
    }
    return {
        "embed": ns("tp", None),
        "blocks": [dict(block) for _ in range(cfg.n_layers)],
        "ln_f": ns(),
        "lm_head": ns(None, "tp"),
    }


def _capacity(cfg: MoEConfig, seq: int) -> int:
    return max(1, int(np.ceil(
        seq * cfg.router_top_k * cfg.capacity_factor / cfg.n_experts)))


def moe_dispatch_combine(x: jax.Array, router: jax.Array, cfg: MoEConfig
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Routing + slot-major capacity allocation shared by the single-mesh
    layer below and the pipeline's ep-local path
    (parallel/pipeline.py:_pp_moe_ffn): x (B, S, D) →
    (dispatch (B, S, E, C), combine_w (B, S, E, C), aux scalar).
    Pure jnp — identical results wherever it runs, which is what keeps
    the two paths loss-parity-exact."""
    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.router_top_k
    c = _capacity(cfg, s)

    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_probs, topk_idx = jax.lax.top_k(probs, k)   # (B, S, K)
    if k == 1:
        gates = topk_probs                           # switch: raw prob
    else:
        gates = topk_probs / jnp.sum(topk_probs, axis=-1, keepdims=True)

    # Switch load-balancing aux loss over FIRST choices: E · Σ_e f_e · p_e
    top1_hot = jax.nn.one_hot(topk_idx[..., 0], e, dtype=jnp.float32)
    density = top1_hot.mean(axis=1)                  # fraction per expert
    density_proxy = probs.mean(axis=1)
    aux = (density * density_proxy).sum(axis=-1).mean() * e

    # Capacity allocation, slot-major: flatten (K, S) assignments so all
    # first choices outrank any second choice, cumsum positions within
    # each expert's buffer, drop past capacity
    oh = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)      # (B, S, K, E)
    oh_flat = oh.transpose(0, 2, 1, 3).reshape(b, k * s, e)  # slot-major
    pos_flat = ((jnp.cumsum(oh_flat, axis=1) - 1.0) * oh_flat).sum(axis=-1)
    keep = (pos_flat < c).astype(jnp.float32)
    disp_flat = (oh_flat * keep[..., None])[..., None] \
        * jax.nn.one_hot(pos_flat.astype(jnp.int32), c,
                         dtype=jnp.float32)[:, :, None, :]
    disp = disp_flat.reshape(b, k, s, e, c)                  # per slot
    dispatch = disp.sum(axis=1)                              # (B, S, E, C)
    combine_w = (disp
                 * gates.transpose(0, 2, 1)[..., None, None]).sum(axis=1)
    return dispatch, combine_w, aux


def _moe_layer(x: jax.Array, blk: dict, cfg: MoEConfig,
               mesh: Optional[Mesh]) -> tuple[jax.Array, jax.Array]:
    """x (B, S, D) → (out, aux_loss)."""
    with jax.named_scope(scopes.ROUTER):
        dispatch, combine_w, aux = moe_dispatch_combine(x, blk["router"],
                                                        cfg)

    def constrain(arr, *spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                arr, NamedSharding(mesh, P(*spec)))
        return arr

    with jax.named_scope(scopes.EXPERTS):
        xf = x.astype(jnp.float32)
        expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, xf)
        # Token buffers shard over ep with the experts → XLA all_to_alls
        # the tokens to their expert's chips
        expert_in = constrain(expert_in, "ep", "dp", None, None)

        w1 = blk["w1"].astype(jnp.float32)
        w2 = blk["w2"].astype(jnp.float32)
        h = jax.nn.gelu(jnp.einsum("ebcd,edf->ebcf", expert_in, w1))
        out_e = jnp.einsum("ebcf,efd->ebcd", h, w2)
        out_e = constrain(out_e, "ep", "dp", None, None)

        out = jnp.einsum("bsec,ebcd->bsd", combine_w, out_e)
        return out.astype(x.dtype), aux.astype(jnp.float32)


# A cached step's grouped product takes at most this many rows (picks) at
# once: a longer step goes through the experts in token chunks. It bounds
# the static room for every pick and the gather back from it, at d_model
# lanes.
_MAX_ROWS = 24576

# A prefill call's held experts run over all its tokens at once, in token
# pieces only where the tokens' float32 accumulator would pass this (224
# MiB at A.X-K1's chunk of 8,192 tokens × 7,168 lanes, 192 MiB at
# LongCat-Flash's 8,192 × 6,144): nothing else at prefill grows with the
# tokens at d_model lanes, and the sort and the tiles' bookkeeping take a
# few words a pick.
_MAX_ACCUMULATOR_BYTES = 256 << 20

# What expert_layer counts, in order: picks that fell on the experts held
# here, on zero-compute experts, on experts held elsewhere, the held
# experts that got at least one token, and the row tiles the grouped
# product ran (its loop's trip count: each reads one expert's matrices).
COUNTERS = ("picks_held", "picks_zero", "picks_absent", "experts_hit",
            "tiles")


def route(u: jax.Array, router: dict, cfg: ModelConfig) -> tuple:
    """u (T, D) → (picks (T, K) int32 over the router's whole width,
    weights (T, K) float32). The product, the scores and the selection
    are float32 whatever the compute type: scores = softmax(u·w), or
    sigmoid(u·w) an output (``cfg.router_score``); the K largest of the
    scores, plus the stored bias under ``cfg.router_bias``, are picked; a
    pick weighs routed_scaling times its score, under
    ``cfg.router_renormalise`` its score over the sum of the picks'."""
    logits = jnp.dot(u.astype(jnp.float32), router["w"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    chosen_by = scores + router["bias"].astype(jnp.float32) \
        if cfg.router_bias else scores
    _, picks = jax.lax.top_k(chosen_by, cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    if cfg.router_renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return picks, weights * cfg.routed_scaling


def _row_tile(tokens: int) -> int:
    """Rows of a tile of the grouped product: a multiple of 8 between 16
    and 128, about a sixteenth of the tokens. A cached step of 64 tokens
    gives an expert a row or two, and a tile is then mostly padding that
    the MXU multiplies all the same; a prefill chunk gives it dozens."""
    return min(128, max(16, tokens // 16 // 8 * 8))


def prefill_plan(tokens: int, k: int, width: int, d: int) -> dict:
    """How a prefill call of ``tokens`` runs its held experts, from shapes
    alone: in ``pieces`` of its tokens (one, but where their float32
    accumulator of ``d`` lanes passes ``_MAX_ACCUMULATOR_BYTES``), each
    one grouped product in tiles of ``tile`` rows, the multiple of 128 at
    or above the rows a held expert is expected to get in a piece (its
    tokens × ``k`` picks over the router's ``width`` outputs, the
    zero-compute ones counted). An expert's weights are then read about
    once a piece, for enough rows that the tile waits on the MXU and not
    on their stream (a tile of 128 rows did at A.X-K1's widths); an
    expert that gets more rows runs more tiles, and none is dropped."""
    row_bytes = -(-d // add_rows.LANE) * add_rows.LANE * 4
    pieces = -(-tokens // max(1, _MAX_ACCUMULATOR_BYTES // row_bytes))
    per_piece = -(-tokens // pieces)
    expected = -(-per_piece * k // width)
    return {"pieces": pieces, "tile": -(-expected // 128) * 128}


def _held_experts(u: jax.Array, local: jax.Array, weights: jax.Array,
                  experts: dict, dtype, tile: int, accumulate: bool) -> tuple:
    """The held experts' part for u (T, D): ``local`` (T, K) is a pick's
    index among the experts held, or their count where it fell elsewhere.
    A grouped product: the picks sorted by expert, every expert's rows
    padded to whole tiles of ``tile`` rows (none where it got no pick),
    and a loop over those tiles, each through its expert's three matrices
    (sliced in place, streamed once a tile): time follows the experts
    that got a pick here, not the static bound or the count held, and no
    token is dropped. The way back goes by the call's shape: where
    ``accumulate`` (more than one position a row: a prefill chunk), each
    tile adds its weighted rows into a float32 accumulator of the T
    tokens at their tokens' rows (``ops/add_rows.py``), so nothing grows
    with T · K at d_model lanes; else (a cached step, whose T · K rows
    are few) each tile writes them into a static room, gathered back to
    the picks' order and summed over a token's K. Both add the same rows,
    rounded to ``dtype``, in float32, in another order. Returns (the part
    (T, D), tokens an expert (held,), the tiles run)."""
    t, k = local.shape
    held, d, _ = experts["w1"].shape
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)  # the held picks first
    sizes = jnp.sum(flat[:, None] == jnp.arange(held)[None], axis=0,
                    dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes                   # in the sorted order
    # an expert nobody picked has no tile and is not read: it shares its
    # ``tile_ends`` with the expert before it, and a tile's expert (the
    # count of ends at or below the tile) steps over a run of them
    tiles = -(-sizes // tile)
    tile_ends = jnp.cumsum(tiles)
    tile_starts = tile_ends - tiles
    # a tile reads ``tile`` entries from its first row on: room past the end
    room = jnp.zeros((tile,), order.dtype)
    sorted_picks = jnp.concatenate([order, room])
    sorted_weights = jnp.concatenate(
        [weights.reshape(-1)[order], room.astype(weights.dtype)])

    def one_tile(i, out):
        e = jnp.sum(i >= tile_ends, dtype=jnp.int32)  # the tile's expert
        row = starts[e] + (i - tile_starts[e]) * tile
        picks = jax.lax.dynamic_slice(sorted_picks, (row,), (tile,))
        rows = u[picks // k]
        wg, w1, w2 = (jax.lax.dynamic_index_in_dim(
            experts[name], e, keepdims=False).astype(dtype)
            for name in ("wg", "w1", "w2"))
        got = (jax.nn.silu(rows @ wg) * (rows @ w1)) @ w2
        weight = jax.lax.dynamic_slice(sorted_weights, (row,), (tile,))
        # the rows past the expert's last are padding
        mine = (row + jnp.arange(tile) < ends[e])[:, None]
        if accumulate:
            # a padding row names token T, past the last: it adds nothing
            # (it may name a token the next expert's rows name too)
            got = got * weight.astype(dtype)[:, None]
            return add_rows.add_rows(out, jnp.where(mine[:, 0], picks // k, t),
                                     add_rows.as_rows(got))
        got = jnp.where(mine, got * weight.astype(dtype)[:, None], 0)
        return jax.lax.dynamic_update_slice(out, got, (i * tile, 0))

    # the tokens' float32 accumulator, or static room for every pick and
    # every expert's last, partial tile
    if accumulate:
        out = add_rows.accumulator(t, d)
    else:
        out = jnp.zeros(((held + t * k // tile) * tile, d), dtype)
    n_tiles = tile_ends[-1]
    out = jax.lax.fori_loop(0, n_tiles, one_tile, out)
    if accumulate:
        return add_rows.as_array(out, d).astype(dtype), sizes, n_tiles
    # back to the picks' order: where each held pick's row lies in ``out``
    here = flat < held
    e = jnp.minimum(flat, held - 1)
    at = tile_starts[e] * tile + jnp.argsort(order) - starts[e]
    part = jnp.where(here[:, None], out[jnp.where(here, at, 0)], 0)
    part = part.reshape(t, k, d).sum(axis=1, dtype=jnp.float32)
    return part.astype(dtype), sizes, n_tiles


def expert_layer(u: jax.Array, router: dict, experts: dict,
                 cfg: ModelConfig, shared: Optional[dict] = None,
                 streamed: bool = False) -> tuple:
    """One chip's share of the expert layer: u (B, S, D), a normed state →
    (m (B, S, D), counters int32 (5,) as :data:`COUNTERS`).

        m = Σ_{picked e held here} w_e · Expert_e(u)
          + Σ_{picked e zero-compute} w_e · u
          + Shared(u)

    with ``Expert_e(h) = (silu(h·wg_e) ⊙ h·w1_e)·w2_e``. Router outputs
    below ``cfg.routed_experts`` are routed experts, of which this chip
    holds ``cfg.experts_held = (first, count)`` (``experts`` has their
    weights, ``count`` on the leading axis); the rest are zero-compute.
    ``shared`` (``wg``, ``w1``, ``w2``; None: none) is the shared experts
    that every token passes, computed once and whole as one gated
    feed-forward (``transformer._feed_forward``'s lines, through the
    streaming kernel where ``streamed`` says the call is a cached one on
    one chip and its shape is one the kernel takes). Static shapes, and
    no token dropped whatever the routing."""
    b, s, d = u.shape
    first, count = cfg.experts_held
    k = cfg.experts_per_token
    with jax.named_scope(scopes.ROUTER):
        flat = u.reshape(b * s, d)
        picks, weights = route(flat, router, cfg)
        is_zero = picks >= cfg.routed_experts
        is_held = (picks >= first) & (picks < first + count)
        local = jnp.where(is_held, picks - first, count)
        zero_weight = jnp.sum(jnp.where(is_zero, weights, 0.0), axis=-1)
    with jax.named_scope(scopes.EXPERTS):
        tokens = b * s
        # a prefill chunk (more than one position a row) by its plan; a
        # cached step in pieces of _MAX_ROWS picks, tiles by _row_tile
        plan = prefill_plan(tokens, k, cfg.routed_experts + cfg.zero_experts,
                            d) if s > 1 else None
        pieces = plan["pieces"] if plan else -(-tokens * k // _MAX_ROWS)
        bounds = [tokens * i // pieces for i in range(pieces + 1)]
        parts, sizes, tiles = zip(*(
            _held_experts(flat[lo:hi], local[lo:hi], weights[lo:hi],
                          experts, u.dtype,
                          plan["tile"] if plan else _row_tile(hi - lo),
                          accumulate=s > 1)
            for lo, hi in zip(bounds, bounds[1:])))
        m = jnp.concatenate(parts) \
            + zero_weight.astype(u.dtype)[:, None] * flat
        if shared is not None:
            m = m + _feed_forward(u, shared, cfg, streamed).reshape(-1, d)
        n_held = jnp.sum(is_held, dtype=jnp.int32)
        n_zero = jnp.sum(is_zero, dtype=jnp.int32)
        counters = jnp.stack([n_held, n_zero, tokens * k - n_held - n_zero,
                              jnp.sum(sum(sizes) > 0, dtype=jnp.int32),
                              sum(tiles)])
        return m.reshape(b, s, d), counters


def moe_forward(params: dict, tokens: jax.Array, cfg: MoEConfig,
                mesh: Optional[Mesh] = None
                ) -> tuple[jax.Array, jax.Array]:
    """tokens (B, S) → (logits (B, S, V), aux_loss scalar)."""
    _refuse_other_kinds(cfg)

    def constrain(arr, *spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                arr, NamedSharding(mesh, P(*spec)))
        return arr

    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    with jax.named_scope(scopes.EMBED):
        x = params["embed"].astype(cfg.compute_dtype)[tokens]
    x = constrain(x, "dp", None, None)

    # Resolve "auto" kernels + mesh downgrades (flash shard_maps over
    # (dp, tp); the fused norm stays single-stream)
    from faabric_tpu.models.transformer import resolve_impls
    cfg = resolve_impls(cfg, mesh)

    aux_total = jnp.zeros((), jnp.float32)
    for blk in params["blocks"]:
        x, _, _ = attention_of_kind(x, blk, positions, cfg, mesh)
        with jax.named_scope(scopes.EXPERTS):
            h = _rms_norm(x, blk["ln2"], cfg.norm_eps)
        moe_out, aux = _moe_layer(h, blk, cfg, mesh)
        aux_total = aux_total + aux
        with jax.named_scope(scopes.EXPERTS):
            x = x + moe_out
        x = constrain(x, "dp", None, None)

    with jax.named_scope(scopes.FINAL_NORM):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
    with jax.named_scope(scopes.HEAD):
        logits = (x @ params["lm_head"].astype(cfg.compute_dtype)
                  ).astype(jnp.float32)
    return logits, aux_total / max(1, cfg.n_layers)


def moe_loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
                cfg: MoEConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    from faabric_tpu.models.transformer import token_nll

    logits, aux = moe_forward(params, tokens, cfg, mesh)
    with jax.named_scope(scopes.HEAD):
        return (jnp.mean(token_nll(logits, targets))
                + cfg.aux_loss_weight * aux)


def make_moe_train_step(cfg: MoEConfig, mesh: Optional[Mesh] = None,
                        optimizer=None):
    import optax

    from faabric_tpu.models.train import make_optimizer

    optimizer = optimizer or make_optimizer()

    def step(params, opt_state, tokens, targets):
        with jax.named_scope(scopes.LOSS):
            loss, grads = jax.value_and_grad(moe_loss_fn)(
                params, tokens, targets, cfg, mesh)
        with jax.named_scope(scopes.OPTIMIZER):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))
