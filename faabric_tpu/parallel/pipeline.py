"""Pipeline parallelism over the ``pp`` mesh axis.

TPU-first design — no per-stage processes, no host-driven schedule. The
whole pipeline is ONE compiled SPMD program:

- Block params stack into leading-``n_layers`` arrays sharded over ``pp``
  (each stage holds a contiguous slab of ``n_layers / pp`` layers and
  runs them with ``lax.scan``).
- The GPipe microbatch schedule is a differentiable ``lax.scan`` over
  ``M + S − 1`` ticks under ``shard_map``: at tick ``t`` stage ``s``
  processes microbatch ``t − s``; activations hop stage→stage+1 with a
  single ``lax.ppermute`` (one ICI neighbour transfer per tick).
- Reverse-mode AD through the scan + ppermute gives the backward
  pipeline for free — XLA schedules it as the mirrored permute chain,
  so ``jax.grad`` of the pipelined loss is itself pipelined.
- Within a stage, tensor parallelism is Megatron-style: heads/hidden
  shard over ``tp`` with an explicit ``psum`` after the attention output
  and MLP down projections (a size-1 ``tp`` axis makes them no-ops).
- Embedding / final norm / LM head are replicated over ``pp`` (they are
  small next to the blocks). The schedule is deliberately branch-free —
  collectives near device-varying ``lax.cond`` deadlock — so every stage
  embeds each tick (a cheap gather) and selects against the hopped-in
  activation; stage outputs stream out as scan ys (the last stage's
  microbatch m is the static slice at tick m + S − 1) and the
  LM-head/loss runs once after the loop, scanned one microbatch at a
  time, masked to the last stage by the final psum.

The reference has no pipeline concept — its "scale the big thing" analog
is gang-scheduled MPI worlds (SURVEY §5.7); this is the mesh-axis
incarnation the TPU build must carry.

Schedule math: ``n_ticks(S, M) = M + S − 1``; bubble fraction
``(S − 1) / (M + S − 1)`` — exposed for tests.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from faabric_tpu.models.transformer import (
    ModelConfig,
    _rms_norm,
    _rope,
    refuse_served_only,
)
from faabric_tpu.ops.attention import attend
from faabric_tpu.parallel.ring_attention import _mark_varying


# ---------------------------------------------------------------------------
# Schedule math (unit-testable without devices)
# ---------------------------------------------------------------------------

def n_ticks(n_stages: int, n_microbatches: int) -> int:
    """GPipe ticks to drain the pipeline."""
    return n_microbatches + n_stages - 1


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """Fraction of stage-ticks idle in the fill/drain bubble."""
    total = n_stages * n_ticks(n_stages, n_microbatches)
    useful = n_stages * n_microbatches
    return (total - useful) / total


def schedule(n_stages: int, n_microbatches: int) -> list[list[int | None]]:
    """``schedule(S, M)[t][s]`` = microbatch stage ``s`` works on at tick
    ``t`` (None = bubble). Mirrors the on-device arithmetic exactly."""
    out = []
    for t in range(n_ticks(n_stages, n_microbatches)):
        row = []
        for s in range(n_stages):
            m = t - s
            row.append(m if 0 <= m < n_microbatches else None)
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# Param layout: blocks stacked over a leading layer axis, sharded over pp
# ---------------------------------------------------------------------------

def stack_block_params(params: dict) -> dict:
    """Transformer param tree (blocks as a list of dicts) → pipeline tree
    with each block leaf stacked on a leading (n_layers,) axis."""
    blocks = params["blocks"]
    stacked = {k: jnp.stack([blk[k] for blk in blocks])
               for k in blocks[0]}
    return {"embed": params["embed"], "stacked": stacked,
            "ln_f": params["ln_f"], "lm_head": params["lm_head"]}


def unstack_block_params(pp_params: dict) -> dict:
    """Inverse of :func:`stack_block_params` (checkpoint interop)."""
    stacked = pp_params["stacked"]
    n_layers = next(iter(stacked.values())).shape[0]
    blocks = [{k: stacked[k][i] for k in stacked} for i in range(n_layers)]
    return {"embed": pp_params["embed"], "blocks": blocks,
            "ln_f": pp_params["ln_f"], "lm_head": pp_params["lm_head"]}


def pp_param_shardings(mesh: Mesh, cfg: ModelConfig) -> dict:
    """Layer axis over ``pp``; heads/hidden over ``tp``; embed/ln_f/
    lm_head replicated (small next to the blocks). MoE configs add the
    expert axis: router replicated, expert slabs over ``ep`` with each
    expert's hidden over ``tp``."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    if getattr(cfg, "n_experts", 0) > 0:
        stacked = {
            "ln1": ns("pp", None),
            "wqkv": ns("pp", None, None, "tp", None),
            "wo": ns("pp", "tp", None, None),
            "ln2": ns("pp", None),
            "router": ns("pp", None, None),
            "w1": ns("pp", "ep", None, "tp"),
            "w2": ns("pp", "ep", "tp", None),
        }
    else:
        stacked = {
            "ln1": ns("pp", None),
            "wqkv": ns("pp", None, None, "tp", None),
            "wo": ns("pp", "tp", None, None),
            "ln2": ns("pp", None),
            "w1": ns("pp", None, "tp"),
            "w2": ns("pp", "tp", None),
        }
    return {
        "embed": ns(),
        "stacked": stacked,
        "ln_f": ns(),
        "lm_head": ns(),
    }


def pp_data_sharding(mesh: Mesh) -> NamedSharding:
    """(M, B, S) microbatched tokens: batch over dp, sequence over sp
    (identical to the pre-sp layout when sp=1), microbatch axis
    replicated (every stage sees every microbatch's tokens; only stage 0
    embeds them)."""
    return NamedSharding(mesh, P(None, "dp", "sp"))


# ---------------------------------------------------------------------------
# In-stage compute (Megatron tp inside a pipeline stage)
# ---------------------------------------------------------------------------

def _head_nll(y, ln_f, lm_head, targets_m, cfg: ModelConfig):
    """LM-head NLL for one microbatch — the single definition both
    schedules (GPipe's loss_one, 1F1B's head) differentiate. The local
    token mean is pmean'd over sp (equal shard sizes; no-op at sp=1) so
    a sequence-sharded pipeline reports the global mean."""
    h = _rms_norm(y, ln_f)
    logits = (h @ lm_head.astype(cfg.compute_dtype)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets_m[..., None], axis=-1)[..., 0]
    return jax.lax.pmean(jnp.mean(nll), "sp")


def _global_positions(b_local: int, seq: int):
    """GLOBAL row ids for this device's sequence shard — the single
    definition of 'global row = axis_index(sp) · seq_local + local'
    shared by both schedule bodies (and consistent with the rows a shard's
    queries cover in _pp_attention_sublayer). A no-op offset at sp=1."""
    return jnp.broadcast_to(
        jax.lax.axis_index("sp") * seq + jnp.arange(seq)[None],
        (b_local, seq))


# The kinds of block that _pp_block and _pp_moe_block implement: handed a
# configuration that names another, they would train another network
_PP_KINDS = {"ffn": "gelu", "norm_placement": "pre",
             "rope_pairing": "neighbours", "norm_eps": 1e-6, "n_passes": 1,
             "attention": "heads", "layer": "single"}


def _validate_pp_mesh(cfg: ModelConfig, mesh: Mesh) -> int:
    for field, kind in _PP_KINDS.items():
        if getattr(cfg, field) != kind:
            raise ValueError(
                f"pipeline stages implement {field}={kind!r} only, "
                f"not {field}={getattr(cfg, field)!r}")
    refuse_served_only(cfg, "the pipeline")
    n_stages = mesh.shape["pp"]
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={n_stages}")
    if mesh.shape.get("sp", 1) > 1 and getattr(cfg, "n_experts", 0):
        raise ValueError(
            "MoE pipeline stages don't compose with sp (per-shard "
            "capacity would diverge from the global routing)")
    ep = mesh.shape.get("ep", 1)
    if ep > 1:
        n_experts = getattr(cfg, "n_experts", 0)
        if not n_experts:
            raise ValueError("ep>1 needs a MoE config (n_experts)")
        if n_experts % ep:
            raise ValueError(
                f"n_experts={n_experts} not divisible by ep={ep}")
    return n_stages


def _pp_specs(cfg: ModelConfig, mesh: Mesh):
    param_specs = jax.tree.map(lambda s: s.spec,
                               pp_param_shardings(mesh, cfg))
    return param_specs, P(None, "dp", "sp")


def _pp_attention_sublayer(x, blk, positions, cfg: ModelConfig):
    """Megatron attention on tp-local shards (qkv column-parallel, wo
    row-parallel + psum) — shared by the dense and MoE pp blocks.

    Sequence parallelism composes here: activations/Q stay sharded over
    ``sp`` and K/V are (transiently) all-gathered for the causal
    offset-masked attention — the DeepSpeed-Ulysses-flavoured gather
    variant, chosen over the ring inside the pipeline because the tick
    scan already owns the ppermute schedule. Both collectives are
    no-ops at sp=1, so this is ONE code path, not a branch. (The
    dedicated non-pp sp path keeps full ring attention with flash
    kernels — parallel/ring_attention.py.)"""
    h = _rms_norm(x, blk["ln1"])
    qkv = jnp.einsum("bsd,dthe->tbshe", h,
                     blk["wqkv"].astype(cfg.compute_dtype))
    q, k, v = qkv[0], qkv[1], qkv[2]
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    k_full = jax.lax.all_gather(k, "sp", axis=1, tiled=True)
    v_full = jax.lax.all_gather(v, "sp", axis=1, tiled=True)
    # q covers this shard's GLOBAL rows of the whole K/V
    rows = jax.lax.axis_index("sp") * q.shape[1] + jnp.arange(q.shape[1])
    attn = attend(q, k_full, v_full,
                  jnp.arange(k_full.shape[1])[None, :] <= rows[:, None])
    attn_out = jnp.einsum("bshe,hed->bsd", attn,
                          blk["wo"].astype(cfg.compute_dtype))
    return x + jax.lax.psum(attn_out, "tp")


def _pp_block(x, blk, positions, cfg: ModelConfig):
    """One transformer block on tp-local shards: qkv/w1 column-parallel,
    wo/w2 row-parallel with a psum over ``tp`` after each."""
    x = _pp_attention_sublayer(x, blk, positions, cfg)
    h = _rms_norm(x, blk["ln2"])
    ff = jax.nn.gelu(h @ blk["w1"].astype(cfg.compute_dtype))
    ff_out = ff @ blk["w2"].astype(cfg.compute_dtype)
    return x + jax.lax.psum(ff_out, "tp")


# ---------------------------------------------------------------------------
# The pipelined loss
# ---------------------------------------------------------------------------

def _is_moe(cfg: ModelConfig) -> bool:
    return getattr(cfg, "n_experts", 0) > 0


def _pp_moe_ffn(h, blk, cfg):
    """Switch-MoE feed-forward on (tp, ep)-local shards: the routing +
    capacity math is replicated (every member computes the same
    dispatch/combine from the same activations, exactly the global
    formulation in models/moe.py:_moe_layer), the expert FFN runs only
    this member's experts (ep-local slab, tp-sharded hidden), and two
    psums reassemble: tp for the row-parallel expert matmul, ep to sum
    each member's contribution for its own experts' tokens. The switch
    aux load-balancing loss is NOT computed on the pipeline path (the
    head-anchored schedules carry one scalar loss; capacity dispatch
    still bounds imbalance) — train with aux via the single-mesh MoE
    step, or accept aux_loss_weight=0 semantics under pp."""
    from faabric_tpu.models.moe import moe_dispatch_combine

    e = cfg.n_experts
    h32 = h.astype(jnp.float32)
    # Routing + capacity allocation: the SHARED pure-jnp definition from
    # models/moe.py — one implementation is what keeps this path
    # loss-parity-exact with the single-mesh layer (aux is discarded
    # here; see docstring)
    dispatch, combine_w, _aux = moe_dispatch_combine(h, blk["router"], cfg)

    # This member's expert slab
    ep_size = jax.lax.psum(1, "ep")
    e_loc = e // ep_size
    lo = jax.lax.axis_index("ep") * e_loc
    disp_loc = jax.lax.dynamic_slice_in_dim(dispatch, lo, e_loc, axis=2)
    comb_loc = jax.lax.dynamic_slice_in_dim(combine_w, lo, e_loc, axis=2)

    expert_in = jnp.einsum("bsec,bsd->ebcd", disp_loc, h32)
    w1 = blk["w1"].astype(jnp.float32)                     # (E_loc, D, F_tp)
    w2 = blk["w2"].astype(jnp.float32)                     # (E_loc, F_tp, D)
    mid = jax.nn.gelu(jnp.einsum("ebcd,edf->ebcf", expert_in, w1))
    out_e = jax.lax.psum(jnp.einsum("ebcf,efd->ebcd", mid, w2), "tp")
    out = jnp.einsum("bsec,ebcd->bsd", comb_loc, out_e)
    return jax.lax.psum(out, "ep").astype(h.dtype)


def _pp_moe_block(x, blk, positions, cfg):
    """MoE transformer block on (tp, ep)-local shards: the shared
    Megatron attention sublayer + the ep-local switch-MoE FFN above."""
    x = _pp_attention_sublayer(x, blk, positions, cfg)
    h = _rms_norm(x, blk["ln2"])
    return x + _pp_moe_ffn(h, blk, cfg)


def _block_fn(cfg: ModelConfig):
    return _pp_moe_block if _is_moe(cfg) else _pp_block


def _pipeline_loss_local(pp_params, tokens_mb, targets_mb,
                         cfg: ModelConfig, n_stages: int):
    """Per-device body (under shard_map over dp/tp/pp). tokens_mb/
    targets_mb: (M, b_local, S)."""
    s_idx = jax.lax.axis_index("pp")
    m_count, b_local, seq = tokens_mb.shape
    d_model = cfg.d_model
    ticks = n_ticks(n_stages, m_count)

    positions = _global_positions(b_local, seq)
    embed = pp_params["embed"]
    stacked = pp_params["stacked"]

    def stage_fn(x):
        """Run my slab of layers (scan over the local layer axis)."""
        def body(h, blk):
            return _block_fn(cfg)(h, blk, positions, cfg), None

        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, stacked)
        return x

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    # Branch-free schedule (collectives under device-varying lax.cond
    # deadlock — every device must run the same collective sequence):
    # every stage embeds (a cheap gather) and selects between that and
    # the hopped-in activation. Stage outputs stream out as scan ys —
    # the last stage's microbatch m output is simply tick m + S − 1, a
    # STATIC slice after the loop — so the backward saves O(T) per-tick
    # activations, not the O(T·M) an in-carry output buffer would.
    def tick(x_in, t):
        m = jnp.clip(t - s_idx, 0, m_count - 1)
        tokens_m = tokens_mb[m]

        emb = _mark_varying(embed.astype(cfg.compute_dtype)[tokens_m],
                            ("dp", "pp", "sp"))
        x = jnp.where(s_idx == 0, emb, x_in)
        y = stage_fn(x)

        # One ICI neighbour hop moves every stage's output forward
        return jax.lax.ppermute(y, "pp", perm), y

    x0 = _mark_varying(jnp.zeros((b_local, seq, d_model), cfg.compute_dtype),
                       ("dp", "pp", "sp"))
    _, ys = jax.lax.scan(tick, x0, jnp.arange(ticks))
    # Last stage produced microbatch m at tick m + (S − 1); every other
    # stage's slice is garbage and is masked out by the final psum
    outputs = ys[n_stages - 1:n_stages - 1 + m_count]

    # Loss head scanned one microbatch at a time so peak logits memory
    # stays (b, S, V) — not M× that. Real data only on the last stage;
    # other stages' buffers are garbage and get masked out below.
    def loss_one(acc, y_t):
        y, targets_m = y_t
        return acc + _head_nll(y, pp_params["ln_f"], pp_params["lm_head"],
                               targets_m, cfg), None

    loss_sum, _ = jax.lax.scan(
        loss_one, _mark_varying(jnp.zeros((), jnp.float32), ("dp", "pp")),
        (outputs, targets_mb))
    local_loss = loss_sum / m_count

    loss = jax.lax.psum(
        jnp.where(s_idx == n_stages - 1, local_loss, 0.0), "pp")
    loss = jax.lax.pmean(loss, "dp")
    return jax.lax.pmean(loss, "tp")  # tp replicas agree; mark it so


def make_pp_loss(cfg: ModelConfig, mesh: Mesh):
    """Jittable ``loss(pp_params, tokens_mb, targets_mb)`` where tokens_mb
    is (n_microbatches, batch, seq)."""
    n_stages = _validate_pp_mesh(cfg, mesh)
    param_specs, data_spec = _pp_specs(cfg, mesh)

    local = partial(_pipeline_loss_local, cfg=cfg, n_stages=n_stages)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(param_specs, data_spec, data_spec),
                         out_specs=P())


# ---------------------------------------------------------------------------
# 1F1B: hand-scheduled interleaved forward/backward
# ---------------------------------------------------------------------------

def n_ticks_1f1b(n_stages: int, n_microbatches: int) -> int:
    """Wall ticks for the 1F1B schedule below (each tick = one fwd unit
    + one bwd unit per stage)."""
    return n_microbatches + 2 * (n_stages - 1)


def ring_slots(n_stages: int) -> int:
    """Saved-input slots a stage needs: in-flight microbatches are
    bounded by the schedule depth 2(S−1)+1 — NOT by M (the GPipe-by-grad
    path's backward holds O(M + S) per-tick activations)."""
    return 2 * (n_stages - 1) + 1


def _pipeline_1f1b_local(pp_params, tokens_mb, targets_mb,
                         cfg: ModelConfig, n_stages: int, dp_size: int):
    """Per-device 1F1B body: a FORWARD-ONLY scan that carries gradient
    accumulators — no outer jax.grad, so XLA never materialises per-tick
    saved activations. Schedule (branch-free, both units every tick):

    - fwd: stage ``s`` forwards microbatch ``mf = t − s`` (GPipe fill),
      saving its post-select INPUT in a ring buffer (recompute-style
      residual — the cheapest carryable VJP state).
    - bwd: stage ``s`` backwards ``mb = t − 2(S−1) + s``; for the last
      stage ``mb == mf``, so the loss head's dy feeds its own vjp the
      same tick. Invalid units run on clamped garbage with a ZERO dy —
      vjp is linear in the cotangent, so their grad contribution is
      exactly zero without a branch (collectives under device-varying
      lax.cond deadlock).
    - hops: activations ppermute forward, input-cotangents ppermute
      backward; one tick = one ICI hop each way.

    Returns (loss, grads) with grads in the pp-sharded param layout.
    """
    s_idx = jax.lax.axis_index("pp")
    m_count, b_local, seq = tokens_mb.shape
    d_model = cfg.d_model
    ticks = n_ticks_1f1b(n_stages, m_count)
    n_slots = ring_slots(n_stages)

    positions = _global_positions(b_local, seq)
    embed = pp_params["embed"]
    stacked = pp_params["stacked"]
    is_first = s_idx == 0
    is_last = s_idx == n_stages - 1

    def stage_fn(slab, x):
        def body(h, blk):
            return _block_fn(cfg)(h, blk, positions, cfg), None

        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, slab)
        return x

    perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    perm_bwd = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        x_hop, dy_hop, ring, g_stacked, g_embed, g_lnf, g_lmh, loss_acc = \
            carry

        # ---- forward unit -------------------------------------------
        mf = t - s_idx
        fwd_valid = (mf >= 0) & (mf < m_count)
        mf_c = jnp.clip(mf, 0, m_count - 1)
        tokens_f = tokens_mb[mf_c]
        emb = _mark_varying(embed.astype(cfg.compute_dtype)[tokens_f],
                            ("dp", "pp", "sp"))
        x_in = jnp.where(is_first, emb, x_hop)
        slot_f = mf_c % n_slots
        ring = ring.at[slot_f].set(
            jnp.where(fwd_valid, x_in, ring[slot_f]))
        y = stage_fn(stacked, x_in)

        # Loss head each tick. The validity mask is INSIDE the
        # differentiated function: ln_f/lm_head are invariant over dp AND
        # pp, so the in-body vjp auto-psums their cotangents over both
        # axes (transpose of the implicit invariant→varying casts) — an
        # outside-the-grad mask would let other stages' garbage heads
        # into that sum. Masked inside, the auto-psum delivers exactly
        # the valid last-stage contribution, Σ'd over dp shards.
        head_mask = fwd_valid & is_last
        hm = jnp.where(head_mask, 1.0, 0.0)
        (masked_loss, (dy_own, d_lnf, d_lmh)) = jax.value_and_grad(
            lambda y_, lnf_, lmh_: hm * _head_nll(y_, lnf_, lmh_,
                                                  targets_mb[mf_c], cfg),
            argnums=(0, 1, 2))(y, pp_params["ln_f"], pp_params["lm_head"])
        loss_acc = loss_acc + masked_loss
        g_lnf = g_lnf + d_lnf
        g_lmh = g_lmh + d_lmh

        # ---- backward unit ------------------------------------------
        mb = t - 2 * (n_stages - 1) + s_idx
        bwd_valid = (mb >= 0) & (mb < m_count)
        mb_c = jnp.clip(mb, 0, m_count - 1)
        x_saved = ring[mb_c % n_slots]
        dy_in = jnp.where(is_last, dy_own.astype(cfg.compute_dtype), dy_hop)
        dy_eff = jnp.where(bwd_valid, dy_in, jnp.zeros_like(dy_in))
        _, vjp = jax.vjp(stage_fn, stacked, x_saved)
        d_slab, dx = vjp(dy_eff)
        g_stacked = jax.tree.map(jnp.add, g_stacked, d_slab)
        # dx is already the FULL input cotangent: under the vma-checked
        # shard_map, transposing the invariant→tp-varying casts where x
        # meets the tp-sharded matmuls inserts the psum('tp') (the
        # Megatron f/g pattern) — an explicit psum here would double-
        # count the tp-invariant residual path
        # Stage 0's dx is the embedding-gather cotangent
        tokens_b = tokens_mb[mb_c]
        g_embed = g_embed.at[tokens_b].add(
            jnp.where(is_first, dx, jnp.zeros_like(dx)).astype(g_embed.dtype))

        # ---- hops ---------------------------------------------------
        x_hop = jax.lax.ppermute(y, "pp", perm_fwd)
        dy_hop = jax.lax.ppermute(dx, "pp", perm_bwd)
        return (x_hop, dy_hop, ring, g_stacked, g_embed, g_lnf, g_lmh,
                loss_acc), None

    zeros_act = _mark_varying(
        jnp.zeros((b_local, seq, d_model), cfg.compute_dtype),
        ("dp", "pp", "sp"))
    ring0 = _mark_varying(
        jnp.zeros((n_slots, b_local, seq, d_model), cfg.compute_dtype),
        ("dp", "pp", "sp"))
    # Accumulator vma types mirror what lands in them: g_stacked /
    # g_lnf / g_lmh receive vjp cotangents already auto-psum'd over the
    # axes their params are invariant on (zeros_like inherits the
    # param's own type); g_embed takes the dp-local dx scatter and the
    # loss the pp/dp-local masked head value
    g_stacked0 = jax.tree.map(jnp.zeros_like, stacked)
    g_embed0 = _mark_varying(jnp.zeros_like(embed), ("dp", "pp", "sp"))
    g_lnf0 = jnp.zeros_like(pp_params["ln_f"])
    g_lmh0 = jnp.zeros_like(pp_params["lm_head"])
    loss0 = _mark_varying(jnp.zeros((), jnp.float32), ("dp", "pp"))

    (x_hop, dy_hop, ring, g_stacked, g_embed, g_lnf, g_lmh,
     loss_acc), _ = jax.lax.scan(
        tick, (zeros_act, zeros_act, ring0, g_stacked0, g_embed0, g_lnf0,
               g_lmh0, loss0), jnp.arange(ticks))

    inv_m = 1.0 / m_count
    # Loss value lives on the last stage (values are device-local, only
    # cotangents of invariant leaves get auto-psum'd)
    loss = jax.lax.psum(loss_acc * inv_m, "pp")
    loss = jax.lax.pmean(loss, "dp")
    loss = jax.lax.pmean(loss, "tp")

    # Gradient normalization — two regimes:
    # - manually-accumulated g_embed (scatter of the dp-LOCAL dx): combine
    #   stages with psum('pp'), dp-average with pmean;
    # - vjp-produced g_stacked / g_lnf / g_lmh: the
    #   in-body vjp already psum'd them over every axis their param is
    #   invariant on (dp; pp too for the head leaves) — they arrive as
    #   Σ over dp shards, so the dp MEAN is a static division, and
    #   another psum/pmean would double-count.
    g_embed = jax.lax.pmean(
        jax.lax.psum(jax.lax.psum(g_embed * inv_m, "pp"), "sp"), "dp")
    scale = inv_m / dp_size
    g_stacked = jax.tree.map(lambda g: g * scale, g_stacked)
    g_lnf = g_lnf * scale
    g_lmh = g_lmh * scale

    grads = {"embed": g_embed, "stacked": g_stacked,
             "ln_f": g_lnf, "lm_head": g_lmh}
    return loss, grads


def make_pp_1f1b_value_and_grad(cfg: ModelConfig, mesh: Mesh):
    """Jittable ``fn(pp_params, tokens_mb, targets_mb) → (loss, grads)``
    — the 1F1B analog of ``jax.value_and_grad(make_pp_loss(...))``, with
    activation memory bounded by the schedule depth instead of the tick
    count."""
    n_stages = _validate_pp_mesh(cfg, mesh)
    param_specs, data_spec = _pp_specs(cfg, mesh)

    local = partial(_pipeline_1f1b_local, cfg=cfg, n_stages=n_stages,
                    dp_size=mesh.shape["dp"])
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(param_specs, data_spec, data_spec),
                         out_specs=(P(), param_specs))


def microbatch(tokens: jax.Array, n_microbatches: int) -> jax.Array:
    """(B, S) → (M, B/M, S)."""
    b, s = tokens.shape
    if b % n_microbatches:
        raise ValueError(
            f"batch {b} not divisible by n_microbatches={n_microbatches}")
    return tokens.reshape(n_microbatches, b // n_microbatches, s)


def make_pp_train_step(cfg: ModelConfig, mesh: Mesh, optimizer=None,
                       n_microbatches: int = 4,
                       schedule_name: str = "gpipe"):
    """Returns jitted ``step(pp_params, opt_state, tokens, targets) →
    (pp_params, opt_state, loss)``; tokens/targets are (B, S) and are
    microbatched internally. ``schedule_name``:

    - ``"gpipe"``: the scan-based forward with ``jax.value_and_grad``
      deriving the mirrored backward (activation memory O(M + S)
      per-tick outputs, remat inside stages).
    - ``"1f1b"``: the hand-scheduled interleaved forward/backward
      (activation memory O(S) ring of saved stage inputs).
    """
    from faabric_tpu.models.train import make_optimizer

    import optax

    optimizer = optimizer or make_optimizer()
    if schedule_name == "1f1b":
        value_and_grad = make_pp_1f1b_value_and_grad(cfg, mesh)
    elif schedule_name == "gpipe":
        loss_fn = make_pp_loss(cfg, mesh)

        def value_and_grad(pp_params, tok_mb, tgt_mb):
            return jax.value_and_grad(
                lambda p: loss_fn(p, tok_mb, tgt_mb))(pp_params)
    else:
        raise ValueError(f"Unknown pipeline schedule {schedule_name!r}")

    def step(pp_params, opt_state, tokens, targets):
        tok_mb = microbatch(tokens, n_microbatches)
        tgt_mb = microbatch(targets, n_microbatches)
        loss, grads = value_and_grad(pp_params, tok_mb, tgt_mb)
        updates, opt_state = optimizer.update(grads, opt_state, pp_params)
        pp_params = optax.apply_updates(pp_params, updates)
        return pp_params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


def init_pp_train_state(key: jax.Array, cfg: ModelConfig, mesh: Mesh,
                        optimizer=None):
    """Stacked params + optimizer state laid out over the pp mesh."""
    from faabric_tpu.models.train import make_optimizer
    from faabric_tpu.models.transformer import init_params

    optimizer = optimizer or make_optimizer()
    if _is_moe(cfg):
        from faabric_tpu.models.moe import init_moe_params

        raw = init_moe_params(key, cfg)
    else:
        raw = init_params(key, cfg)
    pp_params = stack_block_params(raw)
    pp_params = jax.device_put(pp_params, pp_param_shardings(mesh, cfg))
    opt_state = optimizer.init(pp_params)
    return pp_params, opt_state
