"""Flagship model: decoder-only transformer, TPU-first.

Pure-JAX pytree params (no framework indirection between the model and
XLA), written for the MXU and the mesh:
- matmuls stay large and batched, activations compute in bfloat16 while
  params/optimizer stay float32 (classic mixed precision);
- every weight has an explicit PartitionSpec: attention heads and MLP
  hidden shard over ``tp``, batch over ``dp``, sequence over ``sp``
  (Megatron-style sequence parallelism on the norm/MLP path — XLA inserts
  the gathers around attention);
- under ``remat`` a block is ``jax.checkpoint``-wrapped and the backward
  pass keeps what the chips can hold (:func:`remat_plan`, from the call's
  shapes and the memory the chips report when the step is traced): the
  first layers keep their matrix products and the attention kernel's
  output (:data:`KEPT`) and make only their element-wise operations
  again, the others keep their input alone and are recomputed whole, as
  every layer is where no memory can be read;
- static shapes and a Python-unrolled layer loop: everything under jit
  traces once.

The reference is a serverless runtime with no models; this is the
framework's own flagship workload (SURVEY §5.7: the deliverable substrate
must carry DP/TP/SP strategies), exercised by __graft_entry__ and bench.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from faabric_tpu.models import scopes


# The kinds of mixer a layer can have (``ModelConfig.layer_types``), and
# those of them that attend keys and values
MIXER_KINDS = ("attention", "mamba", "mamba1", "window_attention",
               "cross_attention", "gated_memory")
ATTENDING = ("attention", "window_attention", "cross_attention")
# the four vectors of head_dim that give a differential layer's weight
LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
# The kinds of feed-forward a "single" layer can have
# (``ModelConfig.ffn_types``)
FFN_KINDS = ("dense", "experts")


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """A rotary turn stretched past the reach it was trained at (YaRN):
    lane pair i of a head's d/2 turns at ``f_i (1 − r_i) + (f_i / factor)
    r_i``, ``f_i = theta^(−2i/d)``, with ``r`` a ramp from 0 to 1 between
    the pairs that make ``beta_fast`` and ``beta_slow`` turns over
    ``original_max_seq`` positions (:func:`yarn_range`): fast pairs turn
    as they did, slow ones ``factor`` times slower. Cosines and sines are
    multiplied by ``m(mscale) / m(mscale_all_dim)`` and the scores' scale
    by ``m(mscale_all_dim)²``, ``m(x) = 0.1 · x · ln(factor) + 1``
    (:func:`yarn_mscale`; ``m(0)`` = 1 and the scores' scale as it was)."""
    factor: float
    original_max_seq: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(scaling: RopeScaling, dim: int, theta: float) -> tuple:
    """(low, high): the lane pairs of a head of ``dim`` rotary lanes
    between which :class:`RopeScaling`'s ramp rises from 0 to 1."""
    def pair_of(turns: float) -> float:
        return dim * math.log(scaling.original_max_seq
                              / (turns * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))

    return (max(math.floor(pair_of(scaling.beta_fast)), 0),
            min(math.ceil(pair_of(scaling.beta_slow)), dim - 1))


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 2048
    max_seq: int = 2048
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # False: the backward pass keeps whatever autodiff saves. True: a
    # block is checkpointed and keeps its input, and its matrix products
    # too where remat_plan() finds room on the chips; the rest is recomputed
    remat: bool = True
    # "reference" = plain jnp attention; "flash" = the Pallas fused kernel
    # (ops/flash_attention.py) — identical numerics, no (S, S) scores in
    # HBM; "ring" = sequence-parallel ring attention over the sp axis
    # (parallel/ring_attention.py) — the long-context path that never
    # gathers the sequence; "auto" (default) = flash on TPU, reference on
    # CPU (interpret-mode Pallas is for tests, not speed)
    attention_impl: str = "auto"
    # "reference" = inline jnp RMS norm; "fused" = the Pallas kernel
    # (ops/rms_norm.py); "auto" = fused on TPU, reference on CPU
    norm_impl: str = "auto"
    # The block's kinds. The defaults are this repository's first block;
    # a configuration file names others (benchmarks/configs/).
    # "gelu": gelu(h·w1)·w2 | "swiglu": (silu(h·wg) ⊙ h·w1)·w2
    ffn: str = "gelu"
    # "pre": a norm on each sub-layer's input | "sandwich": one on its
    # output too, before the residual (scales ln1_post, ln2_post)
    norm_placement: str = "pre"
    # rotary lanes paired: "neighbours" (2i, 2i+1) | "halves" (i, i+d/2)
    rope_pairing: str = "neighbours"
    norm_eps: float = 1e-6
    # A looped stack: every token passes the same blocks n_passes times,
    # the final norm closing each pass; an exit gate on the normed state
    # gives each pass its share of probability, and the head reads the
    # first pass whose cumulative share reaches exit_threshold (at 1.0
    # the last pass, for every token)
    n_passes: int = 1
    exit_threshold: float = 1.0
    # "heads": a key and a value a head a position, one wqkv | "latent":
    # low-rank attention: queries through a q_lora_rank bottleneck, keys
    # and values of all heads from one kv_lora_rank latent a position and
    # qk_rope_dim rotary lanes shared by the heads (each bottleneck normed
    # and scaled by sqrt(d_model / rank)); a head's query and key have
    # qk_nope_dim + qk_rope_dim lanes, its value v_head_dim; the cache
    # holds the latent and the turned rotary lanes, not keys and values
    attention: str = "heads"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # "single": attention, then the feed-forward | "shortcut": two of
    # those in one layer, and an expert layer (models/moe.py:expert_layer)
    # that reads the first feed-forward's normed input and joins the
    # residual after the second. Its router has routed_experts +
    # zero_experts outputs (the latter return their input and hold no
    # weights) and picks experts_per_token of them; this chip holds the
    # routed experts experts_held = (first, count), of width expert_d_ff,
    # and computes their part and the zero-compute part only
    layer: str = "single"
    routed_experts: int = 0
    zero_experts: int = 0
    experts_held: tuple = (0, 0)
    experts_per_token: int = 0
    routed_scaling: float = 1.0
    expert_d_ff: int = 0
    # Each layer's mixer, the sub-layer before the feed-forward, one name
    # a layer; empty: attention in every layer. "attention" | "mamba" (a
    # Mamba-2 state-space mixer, models/ssm.py) | "mamba1" (a Mamba-1
    # mixer, there too) | "window_attention" (attention over the last
    # sliding_window positions, i − window < j ≤ i; its cache a ring) |
    # "cross_attention" (a query and an output projection; it attends the
    # keys and values of layer cache_source, an "attention" layer before
    # it, and writes nothing) | "gated_memory" (a gate on what the
    # recurrence of layer memory_source, a "mamba1" layer before it, gave
    # at the same position before its own gate; it keeps nothing)
    layer_types: tuple = ()
    sliding_window: int = 0
    cache_source: int = -1
    memory_source: int = -1
    # Differential attention: the heads come in neighbouring pairs (2j,
    # 2j+1), the key/value heads too; a pair is two softmax maps (head 2j
    # on key head 2g, head 2j+1 on key head 2g+1, g = j // (n_heads /
    # n_kv_heads)) over the one value of the group's two value heads side
    # by side, the second subtracted with a learned weight, the difference
    # normed over its 2·head_dim lanes (``sub_norm``) and scaled
    differential: bool = False
    # "rms": RMSNorm, a scale | "layer": LayerNorm, a scale and a bias
    # (leaves ``ln1_b``, ``ln2_b``, ``ln_f_b``)
    norm: str = "rms"
    # biases on attention's projections (``bq``, ``bkv``, ``bo``)
    attention_bias: bool = False
    # key/value heads; 0: as many as query heads. Fewer: grouped-query
    # attention, n_heads / n_kv_heads query heads a key/value head, the
    # cache over the key/value heads
    n_kv_heads: int = 0
    # "rope": queries and keys turn with their position | "none"
    position: str = "rope"
    # the scores' scale; 0: 1 / sqrt(head_dim)
    attention_scale: float = 0.0
    # x = embed[tokens] · embedding_multiplier; a sub-layer joins the
    # residual as x + residual_multiplier · out; logits / logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # the head is the embedding table transposed: no ``lm_head`` leaf
    tie_embeddings: bool = False
    # the state-space mixer's sizes: ssm_heads heads of ssm_head_dim
    # lanes, each with a state of ssm_head_dim × ssm_d_state; B and C
    # shared by the heads of one of ssm_groups groups; a causal depthwise
    # convolution of ssm_d_conv taps; prefill in chunks of ssm_chunk
    ssm_d_state: int = 0
    ssm_d_conv: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 256
    # a "mamba1" mixer's: ssm_inner lanes, each with a state of
    # ssm_d_state, dt through a bottleneck of ssm_dt_rank, ssm_d_conv taps
    ssm_inner: int = 0
    ssm_dt_rank: int = 0
    # Each "single" layer's feed-forward, one name a layer; empty: "dense"
    # in every layer. "dense": the feed-forward of kind ``ffn`` at d_ff |
    # "experts": an expert layer in its place (models/moe.py:expert_layer
    # under the routed_experts … expert_d_ff fields above), and beside the
    # routed experts shared_experts gated experts that every token
    # passes, as one feed-forward of shared_experts · expert_d_ff (leaf
    # ``shared``)
    ffn_types: tuple = ()
    shared_experts: int = 0
    # The router: its scores a "softmax" or a "sigmoid" of its outputs;
    # whether the picks' weights are divided by their sum (before
    # routed_scaling); whether a stored bias (leaf ``bias``) corrects the
    # selection
    router_score: str = "softmax"
    router_renormalise: bool = False
    router_bias: bool = True
    # latent attention: whether each normed bottleneck is scaled by
    # sqrt(d_model / its rank)
    latent_scale: bool = True
    # the rotary turn stretched past the reach it was trained at; None:
    # every pair at theta^(−2i/d)
    rope_scaling: Optional[RopeScaling] = None

    def __post_init__(self):
        for field, kinds in (("ffn", ("gelu", "swiglu")),
                             ("norm_placement", ("pre", "sandwich")),
                             ("rope_pairing", ("neighbours", "halves")),
                             ("attention", ("heads", "latent")),
                             ("layer", ("single", "shortcut")),
                             ("position", ("rope", "none")),
                             ("norm", ("rms", "layer")),
                             ("router_score", ("softmax", "sigmoid"))):
            if getattr(self, field) not in kinds:
                raise ValueError(f"{field} {getattr(self, field)!r} is not "
                                 f"one of {kinds}")
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types and (
                len(self.layer_types) != self.n_layers
                or set(self.layer_types) - set(MIXER_KINDS)):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers as "
                f"{sorted(set(self.layer_types))}; it takes one of "
                f"{MIXER_KINDS} for each of the {self.n_layers}")
        self._check_shared_state()
        self._check_experts()
        if self.n_heads % self.kv_heads or (
                self.kv_heads != self.n_heads and self.attention != "heads"):
            raise ValueError(
                f"n_kv_heads {self.n_kv_heads} does not group the "
                f"{self.n_heads} heads of attention {self.attention!r}")
        if "mamba" in self.layer_types and not (
                self.ssm_d_state > 0 and self.ssm_d_conv > 1
                and self.ssm_heads > 0 and self.ssm_head_dim > 0
                and self.ssm_chunk > 0 and self.ssm_groups > 0
                and self.ssm_heads % self.ssm_groups == 0
                and self.n_passes == 1
                and (self.attention, self.layer) == ("heads", "single")):
            raise ValueError(
                "a 'mamba' layer needs ssm_d_state, ssm_d_conv above 1, "
                "ssm_heads in ssm_groups groups, ssm_head_dim and "
                "ssm_chunk, in single layers of one pass beside per-head "
                f"attention; got {self}")
        if self.n_passes < 1:
            raise ValueError(f"n_passes {self.n_passes} is below 1")

    def _check_shared_state(self):
        """The kinds whose layers lend or borrow state, the window, the
        differential form and the Mamba-1 mixer's sizes."""
        kinds = self.layer_types
        named = set(kinds) & {"mamba1", "window_attention",
                              "cross_attention", "gated_memory"}
        if (named or self.differential) and (
                self.n_passes != 1
                or (self.attention, self.layer) != ("heads", "single")):
            raise ValueError(
                f"{sorted(named) or 'differential'} needs single layers of "
                "one pass and per-head attention")
        if "mamba1" in kinds and not (
                self.ssm_inner > 0 and self.ssm_d_state > 0
                and self.ssm_d_conv > 1 and self.ssm_dt_rank > 0):
            raise ValueError(
                "a 'mamba1' layer needs ssm_inner, ssm_d_state, "
                f"ssm_dt_rank and ssm_d_conv above 1; got {self}")
        if ("window_attention" in kinds) != (self.sliding_window > 0):
            raise ValueError(
                f"sliding_window {self.sliding_window} and the "
                "'window_attention' layers go together")
        for kind, field, lender in (
                ("cross_attention", "cache_source", "attention"),
                ("gated_memory", "memory_source", "mamba1")):
            source = getattr(self, field)
            borrowers = [i for i, k in enumerate(kinds) if k == kind]
            if not borrowers and source == -1:
                continue
            if not (borrowers and 0 <= source < borrowers[0]
                    and kinds[source] == lender):
                raise ValueError(
                    f"{field} {source} has to name a {lender!r} layer "
                    f"before every {kind!r} layer {borrowers}")
        if self.differential and (self.n_heads % 2 or self.kv_heads % 2):
            raise ValueError(
                f"differential attention pairs heads: {self.n_heads} on "
                f"{self.kv_heads} are not pairs")
        if self.attention == "latent" and not (
                self.q_lora_rank > 0 and self.kv_lora_rank > 0
                and self.qk_nope_dim > 0 and self.v_head_dim > 0
                and self.qk_rope_dim > 0 and self.qk_rope_dim % 2 == 0):
            raise ValueError(
                "attention 'latent' needs q_lora_rank, kv_lora_rank, "
                "qk_nope_dim, v_head_dim and an even qk_rope_dim")

    def _check_experts(self):
        """The feed-forward's kind a layer, the expert layer's sizes, the
        shared experts and the rotary scaling."""
        object.__setattr__(self, "ffn_types", tuple(self.ffn_types))
        kinds = self.ffn_types
        if kinds and (len(kinds) != self.n_layers
                      or set(kinds) - set(FFN_KINDS)
                      or self.layer != "single"):
            raise ValueError(
                f"ffn_types names {len(kinds)} layers as "
                f"{sorted(set(kinds))}; it takes one of {FFN_KINDS} for "
                f"each of the {self.n_layers} layers of kind 'single'")
        if "experts" in kinds and not (
                self.ffn == "swiglu" and self.norm_placement == "pre"
                and self.n_passes == 1 and self.shared_experts >= 0):
            raise ValueError(
                "an 'experts' feed-forward needs ffn 'swiglu' behind one "
                "pre-norm in a stack of one pass, and no negative "
                f"shared_experts; got {self}")
        if self.shared_experts and "experts" not in kinds:
            raise ValueError(
                f"shared_experts {self.shared_experts} stand beside the "
                "routed experts of an 'experts' feed-forward; ffn_types "
                "names none")
        if self.layer == "shortcut" or "experts" in kinds:
            first, count = self.experts_held
            if not (0 <= first and count > 0
                    and first + count <= self.routed_experts
                    and 0 < self.experts_per_token
                    <= self.routed_experts + self.zero_experts
                    and self.expert_d_ff > 0):
                raise ValueError(
                    "an expert layer needs routed_experts, expert_d_ff, "
                    "experts_per_token within the router's width, and "
                    f"experts_held within the routed ones; got {self}")
        scaling = self.rope_scaling
        if scaling is not None and not (
                scaling.factor >= 1.0 and scaling.original_max_seq > 0
                and scaling.beta_fast > 0 and scaling.beta_slow > 0
                and self.position == "rope"):
            raise ValueError(
                "rope_scaling needs a factor of 1 or more, the reach it "
                "stretches and positive beta_fast and beta_slow, under "
                f"position 'rope'; got {scaling}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def mixers(self) -> tuple:
        """Every layer's mixer kind."""
        return self.layer_types or ("attention",) * self.n_layers

    @property
    def score_scale(self) -> float:
        """What the scores are multiplied by: ``attention_scale``, or 1 /
        sqrt(a key's lanes), times the rotary scaling's ``m²``."""
        lanes = (self.qk_nope_dim + self.qk_rope_dim
                 if self.attention == "latent" else self.head_dim)
        scale = self.attention_scale or 1.0 / np.sqrt(lanes)
        if self.rope_scaling is not None and self.rope_scaling.mscale_all_dim:
            scale = scale * yarn_mscale(self.rope_scaling.factor,
                                        self.rope_scaling.mscale_all_dim) ** 2
        return scale

    @property
    def ffns(self) -> tuple:
        """Every layer's feed-forward kind."""
        return self.ffn_types or ("dense",) * self.n_layers

    @property
    def stateless_from(self) -> int:
        """The first layer from which on no layer keeps state of its own
        (cross attentions and gated memory units alone): a position's
        way through them reads that position's residual and memory and
        the lent cache, so a prefill runs them at the positions it serves
        only. ``n_layers`` where the last layer keeps state."""
        at = self.n_layers
        while at > 0 and self.mixers[at - 1] in ("cross_attention",
                                                 "gated_memory"):
            at -= 1
        return at


def served_only(cfg: ModelConfig) -> list:
    """What of ``cfg`` only the served path on one chip implements
    (:func:`forward`, ``generate()`` without a mesh), as ``field=value``:
    the train step, the pipeline's stages and the MoE family refuse a
    configuration that names one of these, by name."""
    plain = ModelConfig()
    fields = ("layer_types", "position", "attention_scale",
              "embedding_multiplier", "residual_multiplier",
              "logits_scaling", "tie_embeddings", "sliding_window",
              "cache_source", "memory_source", "differential", "norm",
              "attention_bias", "ssm_inner", "ssm_dt_rank", "ffn_types",
              "shared_experts", "router_score", "router_renormalise",
              "router_bias", "latent_scale", "rope_scaling")
    named = [f"{f}={getattr(cfg, f)!r}" for f in fields
             if getattr(cfg, f) != getattr(plain, f)]
    if cfg.kv_heads != cfg.n_heads:
        named.append(f"n_kv_heads={cfg.n_kv_heads!r}")
    return named


def refuse_served_only(cfg: ModelConfig, who: str) -> None:
    """Raise where ``cfg`` names a kind that ``who`` does not implement."""
    named = served_only(cfg)
    if named:
        raise ValueError(f"{who} cannot run a configuration that names "
                         + ", ".join(named))


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, cfg.n_layers + 2)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, cfg.param_dtype)
                / np.sqrt(fan_in))

    def ones(width=cfg.d_model):
        return jnp.ones((width,), cfg.param_dtype)

    def latent_attention(k):
        k = jax.random.split(k, 5)
        d, h = cfg.d_model, cfg.n_heads
        rq, rkv, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim
        return {
            "wqa": dense(k[0], (d, rq), d), "q_norm": ones(rq),
            # what reads a bottleneck scaled up to a hidden state's size
            # is drawn as if it read a hidden state
            "wqb": dense(k[1], (rq, h, cfg.qk_nope_dim + rope),
                         d if cfg.latent_scale else rq),
            "wkva": dense(k[2], (d, rkv + rope), d), "kv_norm": ones(rkv),
            "wkvb": dense(k[3], (rkv, h, cfg.qk_nope_dim + cfg.v_head_dim),
                          d if cfg.latent_scale else rkv),
            "wo": dense(k[4], (h, cfg.v_head_dim, d), h * cfg.v_head_dim),
        }

    def gated(key, leading, f):
        """The three matrices of a gated feed-forward of width ``f``,
        ``leading`` axes before them (an expert layer's experts held)."""
        k, d = jax.random.split(key, 3), cfg.d_model
        return {"wg": dense(k[0], (*leading, d, f), d),
                "w1": dense(k[1], (*leading, d, f), d),
                "w2": dense(k[2], (*leading, f, d), f)}

    def expert_leaves(key):
        """A router (its selection bias a stored correction, a fraction
        of a mean score: non-zero here, so that a selection that leaves
        it out shows), the routed experts held and the shared ones."""
        k = jax.random.split(key, 4)
        width = cfg.routed_experts + cfg.zero_experts
        leaves = {"router": {"w": dense(k[0], (cfg.d_model, width),
                                        cfg.d_model)},
                  "experts": gated(k[2], (cfg.experts_held[1],),
                                   cfg.expert_d_ff)}
        if cfg.router_bias:
            leaves["router"]["bias"] = jax.random.normal(
                k[1], (width,), cfg.param_dtype) / (4 * width)
        if cfg.shared_experts:
            leaves["shared"] = gated(
                k[3], (), cfg.shared_experts * cfg.expert_d_ff)
        return leaves

    def half(key, kind="attention", ffn_kind="dense"):
        """A mixer (attention of a kind, a state-space mixer, a gated
        memory unit) and its feed-forward, dense or an expert layer: a
        whole "single" layer."""
        bk = jax.random.split(key, 4)
        blk = {"ln1": ones(), "ln2": ones()}
        if ffn_kind == "experts":
            blk.update(expert_leaves(bk[2]))
        else:
            blk.update(
                w1=dense(bk[2], (cfg.d_model, cfg.d_ff), cfg.d_model),
                w2=dense(bk[3], (cfg.d_ff, cfg.d_model), cfg.d_ff))
        if kind in ("mamba", "mamba1", "gated_memory"):
            from faabric_tpu.models import ssm

            blk.update({"mamba": ssm.init_mixer, "mamba1": ssm.init_mixer1,
                        "gated_memory": ssm.init_gated_memory}[kind](
                            bk[0], cfg, dense))
        elif cfg.attention == "latent":
            blk.update(latent_attention(bk[0]))
        else:
            if cfg.kv_heads == cfg.n_heads and kind == "attention" \
                    and not cfg.differential:
                blk["wqkv"] = dense(
                    bk[0], (cfg.d_model, 3, cfg.n_heads, cfg.head_dim),
                    cfg.d_model)
            else:
                # queries and keys/values projected at their own widths;
                # a cross attention has no keys and values of its own
                qk, kvk = jax.random.split(bk[0])
                blk["wq"] = dense(qk, (cfg.d_model, cfg.n_heads,
                                       cfg.head_dim), cfg.d_model)
                if kind != "cross_attention":
                    blk["wkv"] = dense(kvk, (cfg.d_model, 2, cfg.kv_heads,
                                             cfg.head_dim), cfg.d_model)
            blk["wo"] = dense(bk[1], (cfg.n_heads, cfg.head_dim, cfg.d_model),
                              cfg.d_model)
            if cfg.attention_bias:
                zeros = partial(jnp.zeros, dtype=cfg.param_dtype)
                blk["bq"] = zeros((cfg.n_heads, cfg.head_dim))
                blk["bo"] = zeros((cfg.d_model,))
                if "wkv" in blk:
                    blk["bkv"] = zeros((2, cfg.kv_heads, cfg.head_dim))
            if cfg.differential:
                lk = jax.random.split(jax.random.fold_in(key, 5), 4)
                for name, k in zip(LAMBDAS, lk):
                    blk[name] = 0.1 * jax.random.normal(
                        k, (cfg.head_dim,), cfg.param_dtype)
                blk["sub_norm"] = ones(2 * cfg.head_dim)
        if cfg.ffn == "swiglu" and ffn_kind == "dense":
            blk["wg"] = dense(jax.random.fold_in(key, 4),
                              (cfg.d_model, cfg.d_ff), cfg.d_model)
        if cfg.norm_placement == "sandwich":
            blk["ln1_post"], blk["ln2_post"] = ones(), ones()
        if cfg.norm == "layer":
            blk["ln1_b"] = jnp.zeros((cfg.d_model,), cfg.param_dtype)
            blk["ln2_b"] = jnp.zeros((cfg.d_model,), cfg.param_dtype)
        return blk

    def shortcut(key):
        k = jax.random.split(key, 3)
        return {"halves": [half(k[0]), half(k[1])], **expert_leaves(k[2])}

    if cfg.layer == "shortcut":
        blocks = [shortcut(keys[i]) for i in range(cfg.n_layers)]
    else:
        blocks = [half(keys[i], kind, ffn_kind) for i, (kind, ffn_kind)
                  in enumerate(zip(cfg.mixers, cfg.ffns))]
    params = {
        "embed": dense(keys[-2], (cfg.vocab_size, cfg.d_model), cfg.d_model),
        "blocks": blocks,
        "ln_f": ones(),
    }
    if cfg.norm == "layer":
        params["ln_f_b"] = jnp.zeros((cfg.d_model,), cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[-1], (cfg.d_model, cfg.vocab_size),
                                  cfg.d_model)
    if cfg.n_passes > 1:
        gk = jax.random.fold_in(key, cfg.n_layers)
        params["exit_gate"] = {"w": dense(gk, (cfg.d_model,), cfg.d_model),
                               "b": jnp.zeros((), cfg.param_dtype)}
    return params


def param_shardings(mesh: Mesh, cfg: ModelConfig) -> dict:
    """PartitionSpecs per weight: heads/hidden over tp, vocab over tp for
    the embedding table halves (keeps the biggest tables sharded)."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    if (set(cfg.mixers) - {"attention", "mamba"} or cfg.differential
            or cfg.norm != "rms" or cfg.attention_bias or cfg.ffn_types):
        raise ValueError(
            "no layout over a mesh for layers of the kinds "
            f"{sorted(set(cfg.mixers))} with differential="
            f"{cfg.differential}, norm={cfg.norm!r}, attention_bias="
            f"{cfg.attention_bias}, ffn_types={cfg.ffn_types}")
    half = {
        "ln1": ns(),
        "wo": ns("tp", None, None),
        "ln2": ns(),
        "w1": ns(None, "tp"),
        "w2": ns("tp", None),
    }
    if cfg.attention == "latent":
        # the bottlenecks whole on every chip, what fans out of them by head
        half.update(wqa=ns(), q_norm=ns(), wqb=ns(None, "tp", None),
                    wkva=ns(), kv_norm=ns(), wkvb=ns(None, "tp", None))
    elif cfg.kv_heads == cfg.n_heads:
        half["wqkv"] = ns(None, None, "tp", None)
    else:
        half.update(wq=ns(None, "tp", None), wkv=ns(None, None, "tp", None))
    if cfg.ffn == "swiglu":
        half["wg"] = ns(None, "tp")
    if cfg.norm_placement == "sandwich":
        half["ln1_post"], half["ln2_post"] = ns(), ns()
    by_kind = {"attention": half}
    if "mamba" in cfg.layer_types:
        from faabric_tpu.models.ssm import MIXER_LEAVES

        # the mixer whole on every chip: its in-projection's columns are
        # five pieces of unlike widths, and nothing runs it under a mesh
        by_kind["mamba"] = {
            **{k: v for k, v in half.items()
               if k not in ("wq", "wkv", "wqkv", "wo")},
            **{name: ns() for name in MIXER_LEAVES}}
    if cfg.layer == "shortcut":
        by_kind["attention"] = {
            "halves": [dict(half), dict(half)],
            "router": {"w": ns(), "bias": ns()},
            "experts": {"wg": ns(None, None, "tp"),
                        "w1": ns(None, None, "tp"),
                        "w2": ns(None, "tp", None)}}
    shardings = {
        "embed": ns("tp", None),
        "blocks": [jax.tree.map(lambda x: x, by_kind[kind])
                   for kind in cfg.mixers],
        "ln_f": ns(),
    }
    if not cfg.tie_embeddings:
        shardings["lm_head"] = ns(None, "tp")
    if cfg.n_passes > 1:
        shardings["exit_gate"] = {"w": ns(), "b": ns()}
    return shardings


def shard_params(params: dict, mesh: Mesh, cfg: ModelConfig) -> dict:
    return jax.device_put(params, param_shardings(mesh, cfg))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale.astype(x.dtype)


def rope_frequencies(d: int, theta: float,
                     scaling: Optional[RopeScaling] = None) -> jax.Array:
    """The turn a position of each of a head's d/2 lane pairs, float32;
    under ``scaling`` the slow pairs slower (:class:`RopeScaling`)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is None:
        return freqs
    low, high = yarn_range(scaling, d, theta)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs * (1.0 - ramp) + freqs / scaling.factor * ramp


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          pairing: str = "neighbours",
          scaling: Optional[RopeScaling] = None) -> jax.Array:
    """Rotary embeddings over the head dim: x (B, S, H, D). Lane i of a
    pair turns with lane i+1 ("neighbours") or with lane i+D/2
    ("halves", the rotate-half form)."""
    d = x.shape[-1]
    angles = positions[:, :, None, None].astype(jnp.float32) \
        * rope_frequencies(d, theta, scaling)[None, None, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        size = yarn_mscale(scaling.factor, scaling.mscale) \
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim)
        if size != 1.0:
            cos, sin = cos * size, sin * size
    if pairing == "halves":
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
        return out.astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array,
               scale: Optional[float] = None) -> jax.Array:
    """Causal attention, q (B, S, H, D) on keys and values (B, S, KV, D),
    H / KV query heads a key/value head; fp32 softmax accumulators."""
    scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
    b, s, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, h // kv, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((s, s), dtype=bool))
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, s, h, d)


def _row_major(cache: jax.Array) -> jax.Array:
    """Pin a cache row-major in memory, its last axis contiguous."""
    return with_layout_constraint(cache, Layout(tuple(range(cache.ndim))))


def _head_major(cache: jax.Array) -> jax.Array:
    """Pin a (passes, batch, heads, slots, head_dim) cache row-major in
    memory. Left to itself XLA:TPU lays the decode loop's carried cache
    heads-minor whatever the logical order, the heads padded to a tile's
    128 lanes: with 16 heads, eight times the bytes at every step."""
    return _row_major(cache)


def _cached_attention(q, cache_k, cache_v, length, scale=None,
                      position_major: bool = False):
    """q (B, S_q, H, D) against the first ``length`` positions of a
    head-major cache (B, KV, slots, D), or with ``position_major`` of a
    (B, slots, KV, D) view of a dense one; q's last position is length-1.
    With fewer key/value heads than query heads (grouped-query attention)
    the H / KV query heads of a group attend the group's one cache as it
    lies: no copy of it a query head is made."""
    scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
    b, s_q, h, d = q.shape
    kv, slots = cache_k.shape[1:3]
    if position_major:
        kv, slots = slots, kv
    q_pos = (length - s_q) + jnp.arange(s_q)
    k_pos = jnp.arange(slots)
    mask = q_pos[:, None] >= k_pos[None, :]
    # A slot the call has not written yet holds whatever its memory held:
    # XLA:TPU drops the zero fill of a cache that it sees written only
    # through a loop (``AllocateBuffer`` in the optimized HLO). Its weight
    # is 0, and 0 × NaN is NaN: such values never reach the sum.
    written = ((k_pos < length)[None, :, None, None] if position_major
               else (k_pos < length)[None, None, :, None])
    if kv != h:
        cached = "bskd" if position_major else "bksd"
        q = q.reshape(b, s_q, kv, h // kv, d)
        logits = jnp.einsum(f"bqkgd,{cached}->bkgqs", q, cache_k
                            ).astype(jnp.float32) * scale
        logits = jnp.where(mask[None, None, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum(f"bkgqs,{cached}->bqkgd", probs,
                          jnp.where(written, cache_v, 0)
                          ).reshape(b, s_q, h, d)
    cached = "bkhd" if position_major else "bhkd"
    logits = jnp.einsum(f"bqhd,{cached}->bhqk", q, cache_k
                        ).astype(jnp.float32) * scale
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum(f"bhqk,{cached}->bqhd", probs,
                      jnp.where(written, cache_v, 0))


def streams_attention(cfg: ModelConfig, rows: int, positions: int,
                      slots: int, cache_dtype,
                      mesh: Optional[Mesh] = None):
    """How a cached call of ``rows`` rows × ``positions`` attends caches
    of ``slots`` slots through the one kernel over a dense cache
    (ops/cached_attention.py: ``cached_attention.plan``), or None where
    the cache lies head-major under :func:`_cached_attention`'s own
    lines: a decode step (one position a row) of 8 rows or more,
    attention over heads (in differential pairs or not), on one chip, the
    cache in the compute type, a position's keys whole lanes, a row's
    reach within the plan's VMEM. ``slots`` is what the layer's cache
    holds: the call's reach, or a window's ring.
    ``generate`` lays a call's caches by it (a call decodes one position
    a step), the block takes the kernel by it and ``call_sizes`` counts
    by it. Shapes and types alone decide; nothing names a model."""
    from faabric_tpu.ops import cached_attention

    if (positions != 1 or mesh is not None or cfg.attention != "heads"
            or jnp.dtype(cache_dtype) != jnp.dtype(cfg.compute_dtype)):
        return None
    return cached_attention.plan(rows, cfg.n_heads, cfg.kv_heads, slots,
                                 cfg.head_dim, cfg.compute_dtype,
                                 paired=cfg.differential)


def lays_dense(cfg: ModelConfig) -> bool:
    """Whether a configuration's caches lie dense, ``(passes, rows, slots,
    kv_heads · head_dim)``, at any number of rows: one that names a
    window, a lent cache or the differential form goes through
    :func:`_attend_lent_or_ring`, which knows that layout alone."""
    return cfg.differential or bool(
        {"window_attention", "cross_attention"} & set(cfg.layer_types))


def lambda_init(layer: int) -> float:
    """The fixed part of a differential layer's weight, by its depth."""
    return 0.8 - 0.6 * float(np.exp(-0.3 * layer))


# What the float32 scores of one :func:`_attend` may take: more rows than
# fit go through it a block of rows at a time (at 64 rows × 40 heads × 256
# × 768 the scores alone are 2 GB, their exponentials and mask as much
# again).
SCORE_BYTES = 512 * 1024 * 1024


def _attend(q, keys, values, mask, written, scale: float,
            differential: bool) -> jax.Array:
    """q (B, S, H, D) on keys and values (B, K, KV, D) under ``mask``
    (S, K); ``written`` (K,) says which of the K hold something, None:
    all. Softmax in float32. Plain or grouped heads → (B, S, H, D). With
    ``differential`` head (g, p, c) attends key head (g, c) and sums the
    group's two value heads side by side → (B, S, groups, pairs a group,
    2, 2·D): the two maps of every pair. Rows whose scores pass
    ``SCORE_BYTES`` go in equal blocks, one after the other."""
    b, s, h, d = q.shape
    reach, kv = keys.shape[1:3]
    fit = max(1, SCORE_BYTES // (4 * h * s * reach))
    block = max(r for r in range(1, b + 1) if b % r == 0 and r <= fit)
    if block < b:
        def blocks(x):
            return x.reshape(b // block, block, *x.shape[1:])

        out = jax.lax.map(
            lambda qkv: _attend(*qkv, mask, written, scale, differential),
            (blocks(q), blocks(keys), blocks(values)))
        return out.reshape(b, *out.shape[2:])
    if written is not None:
        # 0 × NaN is NaN: what was never written never reaches the sum
        values = jnp.where(written[None, :, None, None], values, 0)
    if differential:
        groups = kv // 2
        q = q.reshape(b, s, groups, h // kv, 2, d)
        logits = jnp.einsum("bqgpcd,bkgcd->bgpcqk", q,
                            keys.reshape(b, reach, groups, 2, d)
                            ).astype(jnp.float32) * scale
        probs = jax.nn.softmax(jnp.where(mask, logits, -1e30),
                               axis=-1).astype(q.dtype)
        return jnp.einsum("bgpcqk,bkge->bqgpce", probs,
                          values.reshape(b, reach, groups, 2 * d))
    q = q.reshape(b, s, kv, h // kv, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, keys
                        ).astype(jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, values
                      ).reshape(b, s, h, d)


def _differential(maps: jax.Array, blk: dict, cfg: ModelConfig,
                  layer: int) -> jax.Array:
    """The two maps of every pair (B, S, ..., 2, 2·D) → the heads'
    outputs (B, S, H, D): a1 − λ·a2, λ = exp(λq1·λk1) − exp(λq2·λk2) +
    λ_init, RMSNorm over the 2·D lanes, times 1 − λ_init, and a pair's
    2·D lanes as its two heads of D. λ and the difference in float32."""
    b, s = maps.shape[:2]
    lq1, lk1, lq2, lk2 = (blk[name].astype(jnp.float32) for name in LAMBDAS)
    fixed = lambda_init(layer)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + fixed
    maps = maps.astype(jnp.float32)
    diff = (maps[..., 0, :] - lam * maps[..., 1, :]).astype(cfg.compute_dtype)
    normed = _rms_norm(diff, blk["sub_norm"], cfg.norm_eps) * (1.0 - fixed)
    return normed.reshape(b, s, cfg.n_heads, cfg.head_dim)


def _ring_positions(ring: int, start) -> jax.Array:
    """The position each of a ring's slots holds before position
    ``start`` is written: the last one below ``start`` that falls on the
    slot (position p lies in slot p % ring); negative: never written."""
    slot = jnp.arange(ring)
    return start - 1 - ((start - 1 - slot) % ring)


def _attend_lent_or_ring(q, k, v, cache: dict, slot: tuple,
                         cfg: ModelConfig, kind: str,
                         mesh: Optional[Mesh]) -> tuple:
    """A cached call's attention over a dense cache (passes, B, slots,
    KV · D) by the layer's ``kind`` → (the heads' outputs, or with
    ``cfg.differential`` the pairs' two maps; the updated cache, None for
    a cache that was lent).

    "attention": the tokens' keys and values go in from position
    ``start`` on and the tokens attend all up to themselves.
    "cross_attention": ``cache`` is another layer's, which holds these
    positions already; it is read and not written. "window_attention":
    the cache is a ring, position p in slot p % slots. One position a
    row is written, then attends the ring's written slots: without a
    rotary turn at reading they are a set, and a full ring is the
    window. A longer input (``start`` static) attends the ring as the
    tokens before left it, beside its own keys, under the window's mask,
    and then writes its last positions over the oldest.

    A step :func:`streams_attention` finds goes through the kernel
    (ops/cached_attention.py), every kind alike."""
    t, start = slot
    b, s_q, h, d = q.shape
    slots = cache["k"].shape[2]
    kv = cfg.kv_heads
    window = cfg.sliding_window if kind == "window_attention" else 0
    scale = cfg.score_scale

    def write(old, new, at):
        return _row_major(jax.lax.dynamic_update_slice(
            old, new.reshape(1, b, new.shape[1], -1), (t, 0, at, 0)))

    def mine(name):
        return jax.lax.dynamic_index_in_dim(
            cache[name], t, 0, keepdims=False).reshape(b, slots, kv, d)

    q_pos = start + jnp.arange(s_q)
    if window and s_q > 1:
        if not isinstance(start, int):
            raise ValueError("a window's ring takes more than one position "
                             "a row only from a static start")
        held = _ring_positions(slots, start)
        k_pos = jnp.concatenate([held, q_pos])
        mask = (k_pos[None, :] <= q_pos[:, None]) \
            & (k_pos[None, :] > q_pos[:, None] - window)
        out = _attend(q, jnp.concatenate([mine("k"), k], axis=1),
                      jnp.concatenate([mine("v"), v], axis=1),
                      mask & (k_pos >= 0)[None, :], k_pos >= 0, scale,
                      cfg.differential)
        # the last positions over the oldest, in at most two pieces
        first = max(0, s_q - slots)
        at = (start + first) % slots
        wrap = first + min(slots - at, s_q - first)

        def over_the_oldest(old, new):
            old = write(old, new[:, first:wrap], at)
            return write(old, new[:, wrap:], 0) if wrap < s_q else old

        return out, {"k": over_the_oldest(cache["k"], k),
                     "v": over_the_oldest(cache["v"], v)}

    updated = None
    if kind != "cross_attention":
        at = start % slots if window else start
        updated = cache = {name: write(cache[name], new, at)
                           for name, new in (("k", k), ("v", v))}
    reach = start + s_q
    length = jnp.minimum(reach, slots) if window else reach
    if streams_attention(cfg, b, s_q, slots, cache["k"].dtype,
                         mesh) is not None:
        from faabric_tpu.ops.cached_attention import cached_attention

        out = cached_attention(q[:, 0], cache["k"], cache["v"], length,
                               scale, t, paired=cfg.differential)
        return (out.reshape(b, 1, kv // 2, h // kv, 2, 2 * d)
                if cfg.differential else out[:, None]), updated
    k_pos = jnp.arange(slots)
    # a ring's slots hold the last positions in no order; all of them
    # are within one position's window
    mask = (k_pos[None, :] < length) if window \
        else (k_pos[None, :] <= q_pos[:, None])
    return _attend(q, mine("k"), mine("v"), mask, k_pos < length, scale,
                   cfg.differential), updated


def _attend_through_cache(q, k, v, cache: dict, slot: tuple, scale=None,
                          streamed: bool = False):
    """Write these tokens' keys and values into pass ``t``'s cache from
    position ``start`` on (``slot = (t, start)``), then attend over that
    pass's cache up to themselves: a pass reads no other pass's cache.
    The cache's own shape says how it lies: head-major (passes, B, KV,
    slots, D), or dense (passes, B, slots, KV · D) where
    :func:`streams_attention` laid it so. ``streamed`` says that the call
    over a dense cache is a step :func:`streams_attention` finds: it goes
    through the kernel; any other call over a dense cache (prefill)
    through :func:`_cached_attention` over a (B, slots, KV, D) view.
    Returns (attention, the updated cache)."""
    t, start = slot
    b, s_q, _, d = q.shape
    dense = cache["k"].ndim == 4

    def write(old, new):
        if dense:
            return _row_major(jax.lax.dynamic_update_slice(
                old, new.reshape(1, b, s_q, -1), (t, 0, start, 0)))
        return _head_major(jax.lax.dynamic_update_slice(
            old, new.transpose(0, 2, 1, 3)[None], (t, 0, 0, start, 0)))

    updated = {name: write(cache[name], new)
               for name, new in (("k", k), ("v", v))}
    if streamed:
        from faabric_tpu.ops.cached_attention import cached_attention

        scale = 1.0 / np.sqrt(d) if scale is None else scale
        return cached_attention(q[:, 0], updated["k"], updated["v"],
                                start + 1, scale, t)[:, None], updated
    mine = [jax.lax.dynamic_index_in_dim(updated[name], t, 0, keepdims=False)
            for name in ("k", "v")]
    if dense:
        mine = [one.reshape(b, -1, k.shape[2], d) for one in mine]
    return _cached_attention(q, *mine, start + s_q, scale,
                             position_major=dense), updated


def _causal_softmax(logits: jax.Array, first: Any, dtype) -> jax.Array:
    """Softmax over keys of float32 logits (B, H, S_q, K), the keys
    positions 0..K-1, the queries positions ``first`` on."""
    s_q, keys = logits.shape[-2:]
    q_pos = first + jnp.arange(s_q)
    mask = q_pos[:, None] >= jnp.arange(keys)[None, :]
    logits = jnp.where(mask[None, None], logits, -1e30)
    return jax.nn.softmax(logits, axis=-1).astype(dtype)


def score_blocks(rows: int, heads: int, queries: int, keys: int) -> tuple:
    """(rows, queries) of a block of latent attention's scores: the most
    rows that divide ``rows`` whose float32 scores (rows, heads, queries,
    keys) stay within ``SCORE_BYTES``, and where one row's do not, one
    row and the most queries that divide ``queries`` and do (one query at
    the least: nothing cuts the keys)."""
    def most(n: int, fit: int) -> int:
        return max(r for r in range(1, n + 1) if n % r == 0 and r <= fit)

    a_query = 4 * heads * keys
    in_rows = most(rows, max(1, SCORE_BYTES // (a_query * queries)))
    in_queries = queries if SCORE_BYTES >= a_query * queries else most(
        queries, max(1, SCORE_BYTES // a_query))
    return in_rows, in_queries


def _by_score_blocks(q_nope, q_rope, latent, first, prepare, attend):
    """``attend(q_nope, q_rope, prepare(latent), first)`` over queries (B,
    S_q, H, ·) at positions ``first`` on and the latents (B, K, ·) they
    attend, so that no float32 scores pass ``SCORE_BYTES``
    (:func:`score_blocks`): rows in equal blocks one after the other,
    each preparing its own rows' keys, and where one row's scores are too
    many, its queries in equal blocks over that row's keys; a block's
    lines are the whole's."""
    b, s_q, h, _ = q_nope.shape
    rows, queries = score_blocks(b, h, s_q, latent.shape[1])
    if rows < b:
        def blocks(x):
            return x.reshape(b // rows, rows, *x.shape[1:])

        out = jax.lax.map(
            lambda block: _by_score_blocks(*block, first, prepare, attend),
            (blocks(q_nope), blocks(q_rope), blocks(latent)))
        return out.reshape(b, *out.shape[2:])
    keys = prepare(latent)
    if queries == s_q:
        return attend(q_nope, q_rope, keys, first)

    def blocks(x):
        return x.reshape(b, s_q // queries, queries, *x.shape[2:]
                         ).swapaxes(0, 1)

    firsts = first + queries * jnp.arange(s_q // queries)
    out = jax.lax.map(lambda block: attend(*block[:2], keys, block[2]),
                      (blocks(q_nope), blocks(q_rope), firsts))
    return out.swapaxes(0, 1).reshape(b, s_q, *out.shape[3:])


def _latent_expanded(q_nope, q_rope, latent, wkvb, cfg: ModelConfig):
    """Latent attention with every head's keys and values made from the
    latents: q_nope (B, S_q, H, nope) and q_rope (B, S_q, H, rope), the
    last S_q of the K positions whose ``latent`` (B, K, rank + rope) is
    given. The path of a call that starts at position 0: K is the static
    reach. → (B, S_q, H, v). In :func:`_by_score_blocks`'s blocks, a
    block of rows expanding its own keys and values."""
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_dim

    def expand(latent):
        return (jnp.einsum("bkc,che->bkhe", latent[..., :rank], wkvb),
                latent[..., rank:])

    def attend(q_nope, q_rope, keys, first):
        kv, kr = keys
        logits = (jnp.einsum("bqhe,bkhe->bhqk", q_nope, kv[..., :nope],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhe,bke->bhqk", q_rope, kr,
                               preferred_element_type=jnp.float32))
        probs = _causal_softmax(logits * cfg.score_scale, first,
                                q_nope.dtype)
        return jnp.einsum("bhqk,bkhe->bqhe", probs, kv[..., nope:])

    return _by_score_blocks(q_nope, q_rope, latent,
                            latent.shape[1] - q_nope.shape[1], expand, attend)


def _latent_absorbed(q_nope, q_rope, cache, length, wkvb, cfg: ModelConfig):
    """The same attention over the first ``length`` slots of a latent
    cache (B, slots, rank + rope) without expanding it, the queries the
    last S_q of those positions: the keys' half of ``wkvb`` goes into the
    query (nope → rank lanes a head), the values' half onto the weighted
    sum of latents. It reads rank + rope values a position, whatever the
    number of heads: a cached step's path, and that of a prefill chunk
    that attends the chunks before it (``cache`` then the cache's first
    ``length`` slots), in :func:`_by_score_blocks`'s blocks."""
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    # as in _cached_attention: a slot not written yet may hold anything,
    # and 0 × NaN is NaN
    written = (jnp.arange(cache.shape[1]) < length)[None, :, None]

    def attend(q_nope, q_rope, cache, first):
        q = jnp.concatenate(
            [jnp.einsum("bqhe,che->bqhc", q_nope, wkvb[..., :nope]),
             q_rope], axis=-1)
        logits = jnp.einsum("bqhc,bkc->bhqk", q, cache,
                            preferred_element_type=jnp.float32)
        probs = _causal_softmax(logits * cfg.score_scale, first, q.dtype)
        mixed = jnp.einsum("bhqk,bkc->bqhc", probs,
                           jnp.where(written, cache[..., :rank], 0))
        return jnp.einsum("bqhc,che->bqhe", mixed, wkvb[..., nope:])

    return _by_score_blocks(q_nope, q_rope, cache,
                            length - q_nope.shape[1], lambda c: c, attend)


def streams_latent_prefill(cfg: ModelConfig, rows: int, queries: int,
                           reach: int, mesh: Optional[Mesh] = None):
    """How a cached call of ``rows`` rows × ``queries`` positions, the
    last of ``reach`` and starting at a static position, attends through
    the one kernel that keeps latent attention's scores on the chip
    (ops/latent_attention.py: ``latent_attention.plan``), or None where
    its scores go through HBM in :func:`_by_score_blocks`'s blocks:
    latent attention on one chip, keys' and values' lanes whole tiles,
    both lengths whole tiles too, a reach of ``MIN_REACH`` at the least.
    The block takes the kernel by it and ``call_sizes`` counts by it.
    Shapes and types alone decide; nothing names a model."""
    from faabric_tpu.ops import latent_attention

    if mesh is not None or cfg.attention != "latent":
        return None
    return latent_attention.plan(
        rows, cfg.n_heads, queries, reach, cfg.qk_nope_dim, cfg.qk_rope_dim,
        cfg.v_head_dim, cfg.compute_dtype)


def _latent_streamed(q_nope, q_rope, latent, wkvb, cfg: ModelConfig,
                     rows: int):
    """:func:`_latent_expanded`'s attention through the kernel: every
    head's keys and values made from the latents (B, K, rank + rope), a
    position's heads side by side as the product leaves them, and the
    queries, the last S_q of the K positions, attended with no score in
    HBM. ``rows`` rows at a time (``plan["rows"]``), one after the other,
    so that what is expanded at once stays within the kernel's
    ``EXPANDED_BYTES``. → (B, S_q, H, v)."""
    from faabric_tpu.ops.latent_attention import latent_attention

    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    b = q_nope.shape[0]

    def attend(q_nope, q_rope, latent):
        normed = latent[..., :rank]
        keys, values = (normed @ w.reshape(rank, -1)
                        for w in (wkvb[..., :nope], wkvb[..., nope:]))
        return latent_attention(q_nope, q_rope, keys, latent[..., rank:],
                                values, scale=cfg.score_scale)

    if rows == b:
        return attend(q_nope, q_rope, latent)

    def blocks(x):
        return x.reshape(b // rows, rows, *x.shape[1:])

    out = jax.lax.map(lambda block: attend(*block),
                      (blocks(q_nope), blocks(q_rope), blocks(latent)))
    return out.reshape(b, *out.shape[2:])


def _latent_attention(h, blk: dict, positions, cfg: ModelConfig,
                      cache: Optional[dict], slot: Optional[tuple],
                      mesh: Optional[Mesh] = None) -> tuple:
    """Low-rank attention on a normed state h (B, S, D) → (heads' outputs
    (B, S, H, v), the updated cache or None). The query goes through a
    normed bottleneck; one latent a position (normed) carries every
    head's keys and values, and ``qk_rope_dim`` rotary lanes are shared
    by all heads; under ``cfg.latent_scale`` each normed bottleneck is
    scaled to a hidden state's size. With a cache the tokens' latents and
    turned rotary lanes are written into pass ``t``'s from position
    ``start`` on (``slot = (t, start)``). A call at a static ``start`` (a
    prompt or one of its chunks) whose shape :func:`streams_latent_prefill`
    finds expands the keys and values of its whole reach from the cache
    and attends them through the kernel (:func:`_latent_streamed`): no
    score reaches HBM. Where the plan refuses, a call that starts at
    position 0 expands keys and values from what it wrote
    (:func:`_latent_expanded`) and a later chunk attends over the latent
    cache as it lies (:func:`_latent_absorbed`), which in the ``jnp``
    lines takes half the time of expanding the chunks before it again
    (PERF.md section 5, PR 41); no float32 scores pass ``SCORE_BYTES``
    on either. A cached step (``start`` traced) attends absorbed over all
    its slots."""
    dt = cfg.compute_dtype
    rank, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    cq = _rms_norm(h @ blk["wqa"].astype(dt), blk["q_norm"], cfg.norm_eps)
    if cfg.latent_scale:
        cq = cq * float(np.sqrt(cfg.d_model / cfg.q_lora_rank))
    q = jnp.einsum("bsr,rhe->bshe", cq, blk["wqb"].astype(dt))
    q_nope = q[..., :nope]
    q_rope = _rope(q[..., nope:], positions, cfg.rope_theta,
                   cfg.rope_pairing, cfg.rope_scaling)
    kv = h @ blk["wkva"].astype(dt)
    ckv = _rms_norm(kv[..., :rank], blk["kv_norm"], cfg.norm_eps)
    if cfg.latent_scale:
        ckv = ckv * float(np.sqrt(cfg.d_model / rank))
    kr = _rope(kv[:, :, None, rank:], positions, cfg.rope_theta,
               cfg.rope_pairing, cfg.rope_scaling)[:, :, 0]
    latent = jnp.concatenate([ckv, kr], axis=-1)
    wkvb = blk["wkvb"].astype(dt)
    if cache is None:
        return _latent_expanded(q_nope, q_rope, latent, wkvb, cfg), None
    t, start = slot
    # a position's latent one contiguous row, as _head_major pins keys
    cache = {"latent": _row_major(jax.lax.dynamic_update_slice(
        cache["latent"], latent[None], (t, 0, start, 0)))}
    mine = jax.lax.dynamic_index_in_dim(cache["latent"], t, 0,
                                        keepdims=False)
    reach = start + h.shape[1]
    if not isinstance(start, int):
        return _latent_absorbed(q_nope, q_rope, mine, reach, wkvb,
                                cfg), cache
    how = streams_latent_prefill(cfg, *h.shape[:2], reach, mesh)
    if how is not None:
        return _latent_streamed(q_nope, q_rope, mine[:, :reach], wkvb, cfg,
                                how["rows"]), cache
    if start > 0:
        return _latent_absorbed(q_nope, q_rope, mine[:, :reach], reach,
                                wkvb, cfg), cache
    return _latent_expanded(q_nope, q_rope, mine[:, :reach], wkvb,
                            cfg), cache


def resolve_impls(cfg: ModelConfig, mesh: Optional[Mesh] = None) -> ModelConfig:
    """Resolve "auto" kernel choices for the current backend, and downgrade
    combinations the mesh can't run: flash under a mesh runs shard_mapped
    over (dp, tp), so a sequence-sharded model (sp > 1) routes to ring
    attention, which keeps the sequence distributed; the fused norm kernel
    stays single-stream."""
    att, norm = cfg.attention_impl, cfg.norm_impl
    on_tpu = jax.default_backend() == "tpu"
    if cfg.kv_heads != cfg.n_heads or cfg.attention_scale \
            or cfg.rope_scaling is not None:
        # the flash and ring kernels know as many key/value heads as
        # query heads and the scale 1 / sqrt(head_dim)
        att = "reference"
    if att == "auto":
        att = "flash" if on_tpu else "reference"
    if norm == "auto":
        norm = "fused" if on_tpu else "reference"
    if mesh is not None:
        if att == "flash" and mesh.shape.get("sp", 1) > 1:
            att = "ring"
        if norm == "fused":
            norm = "reference"
    if (att, norm) != (cfg.attention_impl, cfg.norm_impl):
        cfg = dataclasses.replace(cfg, attention_impl=att, norm_impl=norm)
    return cfg


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
                eps: float) -> jax.Array:
    """LayerNorm over the last axis, its statistics in float32."""
    wide = x.astype(jnp.float32)
    mean = jnp.mean(wide, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(wide - mean), axis=-1, keepdims=True)
    return ((wide - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * scale.astype(x.dtype) + bias.astype(x.dtype)


def _norm(x: jax.Array, scale: jax.Array, cfg: ModelConfig,
          bias: Optional[jax.Array] = None) -> jax.Array:
    """The configuration's norm: RMSNorm with ``scale``, or LayerNorm
    with ``scale`` and ``bias`` (``cfg.norm``)."""
    if cfg.norm == "layer":
        return _layer_norm(x, scale, bias, cfg.norm_eps)
    if cfg.norm_impl == "fused":
        from faabric_tpu.ops.rms_norm import rms_norm

        return rms_norm(x, scale, cfg.norm_eps)
    return _rms_norm(x, scale, cfg.norm_eps)


def _sharded_flash(q, k, v, mesh: Mesh):
    """Flash attention under a mesh: batch (dp) and heads (tp) are
    embarrassingly parallel for attention, so each shard runs the Pallas
    kernel on its local (B/dp, S, H/tp, D) slab — no collectives."""
    from faabric_tpu.ops.flash_attention import flash_attention

    spec = P("dp", None, "tp", None)
    # check off: pallas_call's out_shape carries no varying-mesh-axes
    # annotation, and this wrapper is trivially per-shard anyway
    return jax.shard_map(lambda q, k, v: flash_attention(q, k, v, True),
                         mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention_sublayer(x: jax.Array, blk: dict, positions: jax.Array,
                       cfg: ModelConfig, mesh: Optional[Mesh] = None,
                       cache: Optional[dict] = None,
                       slot: Optional[tuple] = None) -> tuple:
    """Norm, attention, (norm,) residual — shared by the dense and MoE
    families (honours cfg.attention_impl / norm_impl). Without ``cache``
    the tokens attend causally among themselves; with this layer's
    ``cache`` they go through it (:func:`_attend_through_cache`).
    ``cfg.attention`` "latent" is :func:`_latent_attention`, behind the
    same norms and residual. Returns (x, the updated cache or None)."""
    return attention_of_kind(x, blk, positions, cfg, mesh, cache, slot)[:2]


@jax.named_scope(scopes.ATTENTION)
def attention_of_kind(x: jax.Array, blk: dict, positions: jax.Array,
                      cfg: ModelConfig, mesh: Optional[Mesh] = None,
                      cache: Optional[dict] = None,
                      slot: Optional[tuple] = None, kind: str = "attention",
                      layer: int = 0, lent: Optional[dict] = None) -> tuple:
    """:func:`attention_sublayer` for a layer of ``kind`` at depth
    ``layer`` (``ModelConfig.layer_types``). A "cross_attention" layer
    projects queries alone and attends what ``lent`` holds: another
    layer's cache in a cached call, else that layer's keys and values
    ``{"k", "v"}`` (B, S, KV, D). Returns (x, the updated cache or None,
    what the layer lends: its keys and values where it ran without a
    cache, else None: a cached call lends the cache itself)."""
    h = _norm(x, blk["ln1"], cfg, blk.get("ln1_b"))
    if cfg.attention == "latent":
        attn, cache = _latent_attention(h, blk, positions, cfg, cache, slot,
                                        mesh)
        return _attention_residual(x, attn, blk, cfg), cache, None
    dt = cfg.compute_dtype
    if "wqkv" in blk:
        qkv = jnp.einsum("bsd,dthe->tbshe", h, blk["wqkv"].astype(dt))
        q, k, v = qkv[0], qkv[1], checkpoint_name(qkv[2], "v")
    else:
        q = jnp.einsum("bsd,dhe->bshe", h, blk["wq"].astype(dt))
        k = v = None
        if "wkv" in blk:
            k, v = jnp.einsum("bsd,dtke->tbske", h, blk["wkv"].astype(dt))
    if "bq" in blk:
        q = q + blk["bq"].astype(dt)
    if "bkv" in blk:
        k, v = k + blk["bkv"][0].astype(dt), v + blk["bkv"][1].astype(dt)
    if cfg.position == "rope":
        q = checkpoint_name(
            _rope(q, positions, cfg.rope_theta, cfg.rope_pairing,
                  cfg.rope_scaling), "q_rope")
        k = checkpoint_name(
            _rope(k, positions, cfg.rope_theta, cfg.rope_pairing,
                  cfg.rope_scaling), "k_rope")
    scale = cfg.score_scale
    lends = None
    if lays_dense(cfg):
        if slot is not None:        # a cached call: forward gives none
            attn, cache = _attend_lent_or_ring(
                q, k, v, lent if kind == "cross_attention" else cache,
                slot, cfg, kind, mesh)
        else:
            if kind == "cross_attention":
                k, v = lent["k"], lent["v"]
            lends = {"k": k, "v": v}
            at = jnp.arange(q.shape[1])
            mask = at[None, :] <= at[:, None]
            if kind == "window_attention":
                mask &= at[None, :] > at[:, None] - cfg.sliding_window
            attn = _attend(q, k, v, mask, None, scale, cfg.differential)
        if cfg.differential:
            attn = _differential(attn, blk, cfg, layer)
    elif cache is not None:
        streamed = cache["k"].ndim == 4 and streams_attention(
            cfg, *q.shape[:2], cache["k"].shape[2], cache["k"].dtype,
            mesh) is not None
        attn, cache = _attend_through_cache(q, k, v, cache, slot, scale,
                                            streamed)
    elif cfg.attention_impl == "flash":
        from faabric_tpu.ops.flash_attention import flash_attention

        if mesh is not None:
            attn = _sharded_flash(q, k, v, mesh)
        else:
            attn = flash_attention(q, k, v, True)
    elif cfg.attention_impl == "ring" and mesh is not None:
        from faabric_tpu.parallel.ring_attention import ring_attention

        attn = ring_attention(q, k, v, mesh, axis="sp",
                              batch_axis="dp", head_axis="tp")
    else:
        attn = _attention(q, k, v, scale)
    return _attention_residual(x, attn, blk, cfg), cache, lends


def _attention_residual(x, attn, blk: dict, cfg: ModelConfig) -> jax.Array:
    """The heads' outputs (B, S, H, e) through ``wo``, (a norm,) and onto
    the residual."""
    out = checkpoint_name(
        jnp.einsum("bshe,hed->bsd", attn,
                   blk["wo"].astype(cfg.compute_dtype)), "attn_proj")
    if "bo" in blk:
        out = out + blk["bo"].astype(cfg.compute_dtype)
    if cfg.norm_placement == "sandwich":
        out = _norm(out, blk["ln1_post"], cfg)
    return _join(x, out, cfg)


def _join(x: jax.Array, out: jax.Array, cfg: ModelConfig) -> jax.Array:
    """A sub-layer's output onto the residual."""
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out


def streams_feed_forward(cfg: ModelConfig, rows: int, positions: int,
                         weights_dtype, mesh: Optional[Mesh] = None,
                         d_ff: int = 0):
    """How a cached call's feed-forward of ``rows`` rows × ``positions``
    streams its matrices through the one kernel (ops/gated_ffn.py:
    ``gated_ffn.plan``), or None where it runs :func:`_feed_forward`'s own
    lines: a decode step (one position a row) at a few rows, gated, with
    no norm behind the down product, on one chip, the matrices lying in
    the compute type already (a cast would write them out again). ``d_ff``
    is the matrices' own width (0: ``cfg.d_ff``; an expert layer's shared
    experts have another). Shapes and types alone decide; nothing names a
    model."""
    from faabric_tpu.ops import gated_ffn

    if (positions != 1 or mesh is not None or cfg.ffn != "swiglu"
            or cfg.norm_placement == "sandwich"
            or jnp.dtype(weights_dtype) != jnp.dtype(cfg.compute_dtype)):
        return None
    return gated_ffn.plan(rows, cfg.d_model, d_ff or cfg.d_ff,
                          cfg.compute_dtype)


def feed_forward_widths(cfg: ModelConfig) -> list:
    """The width of every feed-forward a token passes whatever it picks,
    in the layers' order: the dense ones, a "shortcut" layer's two, an
    "experts" layer's shared experts (as one)."""
    if cfg.layer == "shortcut":
        return [cfg.d_ff] * (2 * cfg.n_layers)
    shared = cfg.shared_experts * cfg.expert_d_ff
    return [cfg.d_ff if kind == "dense" else shared
            for kind in cfg.ffns if kind == "dense" or shared]


def _feed_forward(h: jax.Array, blk: dict, cfg: ModelConfig,
                  streamed: bool = False) -> jax.Array:
    """The feed-forward on a normed state, up to the residual. ``streamed``
    says that the call may go through the streaming kernel (a cached call
    on one chip: no gradient is ever taken there) where
    :func:`streams_feed_forward` finds its shape."""
    if streamed:
        types = {blk[w].dtype for w in ("wg", "w1", "w2") if w in blk}
        if len(types) == 1 and streams_feed_forward(
                cfg, *h.shape[:2], types.pop(),
                d_ff=blk["w1"].shape[1]) is not None:
            from faabric_tpu.ops.gated_ffn import gated_ffn

            return gated_ffn(h[:, 0], blk["wg"], blk["w1"],
                             blk["w2"])[:, None]
    ff = checkpoint_name(h @ blk["w1"].astype(cfg.compute_dtype), "ffn_up")
    if cfg.ffn == "swiglu":
        gate = checkpoint_name(h @ blk["wg"].astype(cfg.compute_dtype),
                               "ffn_gate")
        ff = jax.nn.silu(gate) * ff
    else:
        ff = jax.nn.gelu(ff)
    ff = checkpoint_name(ff, "ffn_act")
    out = checkpoint_name(ff @ blk["w2"].astype(cfg.compute_dtype),
                          "ffn_down")
    if cfg.norm_placement == "sandwich":
        out = _norm(out, blk["ln2_post"], cfg)
    return out


def _block(x: jax.Array, blk: dict, positions: jax.Array,
           cfg: ModelConfig, mesh: Optional[Mesh] = None,
           cache: Optional[dict] = None,
           slot: Optional[tuple] = None, kind: str = "attention",
           layer: int = 0, lent: Any = None) -> tuple:
    """The one transformer block, with or without its state of a call, in
    the kinds the configuration names. A "single" layer is a mixer of its
    layer's ``kind`` and a feed-forward, ``blk`` their weights, behind
    the same norm and residual: attention of a kind (``cache`` its keys
    and values, a "window_attention"'s a ring), a state-space mixer
    (models/ssm.py: "mamba", "mamba1"; ``cache`` its convolution window
    and recurrent state), or a layer that keeps nothing and reads what
    another lends (``lent``): a "cross_attention" the keys and values of
    layer ``cfg.cache_source``, a "gated_memory" the memory of layer
    ``cfg.memory_source``. Where ``blk`` holds a ``router`` the
    feed-forward is an expert layer (models/moe.py:expert_layer: the
    routed experts held and the shared ones; ``ModelConfig.ffn_types``)
    and the cache carries its ``counters`` beside the mixer's state. A
    "shortcut" layer is two of those (``blk["halves"]``) and an expert
    layer that reads the first feed-forward's normed input and joins the
    residual after the second; its cache is ``{"attn": [the two
    attentions' caches], "counters": what its expert layer has counted
    so far in this call (models/moe.py:COUNTERS)}``. Returns (x, the
    updated cache or None, what the layer lends to later ones or None:
    a "mamba1" layer its memory (B, S, inner), an attention that ran
    without a cache its keys and values; ``layer`` is its depth)."""
    # a cached call on one chip (a layer that keeps nothing has no cache)
    streamed = slot is not None and mesh is None
    if cfg.layer == "single":
        lends = counters = None
        if cache is not None and "counters" in cache:
            # an "experts" feed-forward's, beside the mixer's state
            cache = dict(cache)
            counters = cache.pop("counters")
        if kind in ATTENDING:
            x, cache, lends = attention_of_kind(
                x, blk, positions, cfg, mesh, cache, slot, kind, layer, lent)
        else:
            from faabric_tpu.models import ssm

            with jax.named_scope(scopes.MIXER):
                h = _norm(x, blk["ln1"], cfg, blk.get("ln1_b"))
                if kind == "mamba":
                    out, cache = ssm.mixer(h, blk, cfg, cache)
                elif kind == "mamba1":
                    out, cache, lends = ssm.mixer1(h, blk, cfg, cache,
                                                   streamed)
                else:
                    out = ssm.gated_memory(h, blk, cfg, lent)
                x = _join(x, out, cfg)
        if "router" not in blk:
            with jax.named_scope(scopes.FEED_FORWARD):
                return _join(x, _feed_forward(
                    _norm(x, blk["ln2"], cfg, blk.get("ln2_b")), blk, cfg,
                    streamed), cfg), cache, lends
        from faabric_tpu.models.moe import expert_layer

        with jax.named_scope(scopes.EXPERTS):
            h = _norm(x, blk["ln2"], cfg, blk.get("ln2_b"))
        out, counted = expert_layer(h, blk["router"], blk["experts"], cfg,
                                    blk.get("shared"), streamed)
        with jax.named_scope(scopes.EXPERTS):
            if counters is not None:
                cache = dict(cache, counters=counters + counted)
            return _join(x, out, cfg), cache, lends

    from faabric_tpu.models.moe import expert_layer

    caches = [None, None] if cache is None else list(cache["attn"])
    for i, half in enumerate(blk["halves"]):
        x, caches[i] = attention_sublayer(x, half, positions, cfg, mesh,
                                          caches[i], slot)
        with jax.named_scope(scopes.FEED_FORWARD):
            h = _norm(x, half["ln2"], cfg)
        if i == 0:
            branch, counted = expert_layer(h, blk["router"], blk["experts"],
                                           cfg)
        with jax.named_scope(scopes.FEED_FORWARD):
            x = _join(x, _feed_forward(h, half, cfg, streamed), cfg)
    with jax.named_scope(scopes.EXPERTS):
        if cache is not None:
            cache = {"attn": caches,
                     "counters": cache["counters"] + counted}
        return _join(x, branch, cfg), cache, None


def lender_of(cfg: ModelConfig, layer: int, lends: Any) -> dict:
    """What layer ``layer`` lends, keyed by the kind that borrows it."""
    lent = {}
    if layer == cfg.cache_source:
        lent["cross_attention"] = lends
    if layer == cfg.memory_source:
        lent["gated_memory"] = lends
    return lent


def run_passes(x: jax.Array, carry: Any, params: dict, cfg: ModelConfig,
               stack) -> tuple:
    """The stack ``cfg.n_passes`` times over the same weights, the final
    norm closing each pass: ``stack(x, carry, t) -> (x, carry)`` is all
    the blocks once, ``t`` the pass. Returns (the normed state the head
    reads, carry). More than one pass is a rolled loop, the stack traced
    once. Below an exit threshold of 1.0 each token's state is that of
    the first pass at which the exit gate's cumulative share reaches the
    threshold; at 1.0 every token takes the last pass and the gate
    decides nothing."""
    def one_pass(x, carry, t):
        x, carry = stack(x, carry, t)
        with jax.named_scope(scopes.FINAL_NORM):
            return _norm(x, params["ln_f"], cfg,
                         params.get("ln_f_b")), carry

    if cfg.n_passes == 1:
        return one_pass(x, carry, 0)
    if cfg.exit_threshold >= 1.0:
        return jax.lax.fori_loop(
            0, cfg.n_passes, lambda t, xc: one_pass(*xc, t), (x, carry))

    gate = jax.tree.map(lambda w: w.astype(jnp.float32),
                        params["exit_gate"])
    last = cfg.n_passes - 1

    def gated(t, state):
        x, carry, chosen, remaining, reached, done = state
        x, carry = one_pass(x, carry, t)
        lam = jax.nn.sigmoid(x.astype(jnp.float32) @ gate["w"] + gate["b"])
        # p_t = lam_t · prod_{s<t}(1 - lam_s); the last pass takes the rest
        # of the probability, so whoever is still in leaves there
        reached = reached + lam * remaining
        exits = ((reached >= cfg.exit_threshold) | (t == last)) & ~done
        chosen = jnp.where(exits[..., None], x, chosen)
        return (x, carry, chosen, remaining * (1.0 - lam), reached,
                done | exits)

    zeros = jnp.zeros(x.shape[:-1], jnp.float32)
    state = (x, carry, jnp.zeros_like(x), zeros + 1.0, zeros,
             zeros.astype(bool))
    _, carry, chosen, *_ = jax.lax.fori_loop(0, cfg.n_passes, gated, state)
    return chosen, carry


# What a block's checkpoint keeps for the backward pass, by the names the
# values take where they are made (``checkpoint_name`` in
# :func:`attention_sublayer`, :func:`_block` and the flash kernel's forward
# rule): the outputs of the matrix products (of the QKV product the
# queries and keys after their rotary turn: the same bytes, and no turn to
# make again), the attention kernel's output and row statistic, and the
# activation the down-projection reads. The rest of a block (norms,
# residual adds) is made again. What each is worth on the chip: PERF.md
# section 6, PR 30.
KEPT = ("q_rope", "k_rope", "v", "attn_out", "attn_lse", "attn_proj",
        "ffn_up", "ffn_gate", "ffn_act", "ffn_down")


def _per_chip(cfg: ModelConfig, tokens_shape, mesh: Optional[Mesh]) -> tuple:
    """(tokens, heads, d_ff) of one chip's share, and the mesh's ways:
    batch over ``dp``, sequence over ``sp``, heads and the MLP's hidden
    over ``tp``."""
    ways = dict(mesh.shape) if mesh is not None else {}
    ways = {a: ways.get(a, 1) for a in ("dp", "tp", "sp")}
    b, s = tokens_shape
    return (-(-b // ways["dp"]) * -(-s // ways["sp"]),
            -(-cfg.n_heads // ways["tp"]), -(-cfg.d_ff // ways["tp"]), ways)


def _block_matrices(cfg: ModelConfig, heads: int, d_ff: int) -> int:
    """Parameters of one block's matrices (QKV, output, up, down and a
    gate) at these many heads and this MLP width."""
    return cfg.d_model * (4 * heads * cfg.head_dim
                          + (2 + (cfg.ffn == "swiglu")) * d_ff)


def remat_plan(cfg: ModelConfig, tokens_shape, mesh: Optional[Mesh] = None,
               free_bytes: Optional[int] = None) -> dict:
    """Which blocks keep their products for the backward pass, as
    ``flash_attention.block_plan`` sizes the kernels' blocks: a pure
    function of the call's per-chip shapes and of ``free_bytes``, what one
    chip has left once the state is resident and the step's own needs
    (:func:`step_bytes`) are taken off. The first ``layers_kept`` blocks
    are checkpointed under a policy that saves :data:`KEPT`, the others
    whole. With no reading (``None``: the CPU backend has none) no block
    keeps anything: nothing changes where nothing can be observed.
    Returns the layers kept,
    the bytes one layer's kept values take on one chip (``layer_bytes``),
    those of all kept layers (``kept_bytes``), and the forward operations
    the backward pass runs again for each token
    (``recomputed_flops_per_token``: the block matrices at 2 a parameter
    and causal attention's two products, of the blocks checkpointed
    whole)."""
    tokens, heads, d_ff, _ = _per_chip(cfg, tokens_shape, mesh)
    item = jnp.dtype(cfg.compute_dtype).itemsize
    swiglu, sandwich = cfg.ffn == "swiglu", cfg.norm_placement == "sandwich"
    # a token's kept elements: q, k, v, the output projection, up (and
    # gate) and the activation, and the down-projection where a norm reads
    # it before the residual
    width = (3 * heads * cfg.head_dim + cfg.d_model + (2 + swiglu) * d_ff
             + sandwich * cfg.d_model)
    layer_bytes = tokens * width * item
    if cfg.attention_impl == "flash":
        from faabric_tpu.ops.flash_attention import SUBLANE

        # the kernel's output, and its row statistic as float32 rows
        # (batch·heads, SUBLANE, sequence)
        layer_bytes += tokens * heads * (cfg.head_dim * item + SUBLANE * 4)
    layer_bytes *= cfg.n_passes
    kept = 0
    if free_bytes is not None:
        kept = int(min(cfg.n_layers, max(0, free_bytes) // layer_bytes))
    again = (2 * _block_matrices(cfg, cfg.n_heads, cfg.d_ff)
             + 2 * tokens_shape[1] * cfg.d_model)
    return {"layers_kept": kept, "layer_bytes": layer_bytes,
            "kept_bytes": kept * layer_bytes,
            "recomputed_flops_per_token":
                (cfg.n_layers - kept) * cfg.n_passes * again}


def step_bytes(cfg: ModelConfig, tokens_shape,
               mesh: Optional[Mesh] = None) -> int:
    """What a train step takes on one chip besides the state and what
    :func:`remat_plan` keeps, from shapes alone and on the safe side: the
    float32 logits and their gradient; the gradients' leaves (all of them
    where replicas exchange them, ``dp·sp > 1``: XLA holds every leaf for
    its combined all-reduce; on one replica a leaf goes into its AdamW
    update as it is made, and two of the largest are counted); every
    block's input; and one block's values with their cotangents while it
    is differentiated."""
    tokens, heads, d_ff, ways = _per_chip(cfg, tokens_shape, mesh)
    item = jnp.dtype(cfg.compute_dtype).itemsize
    swiglu = cfg.ffn == "swiglu"
    logits = 2 * tokens * cfg.vocab_size * 4
    table = cfg.vocab_size * cfg.d_model // ways["tp"]
    if ways["dp"] * ways["sp"] > 1:
        leaves = 2 * table + cfg.n_layers * _block_matrices(cfg, heads, d_ff)
    else:
        leaves = 2 * max(table, cfg.d_model * d_ff,
                         3 * cfg.d_model * heads * cfg.head_dim)
    grads = leaves * jnp.dtype(cfg.param_dtype).itemsize
    inputs = cfg.n_layers * cfg.n_passes * tokens * cfg.d_model * item
    block = 2 * tokens * item * (5 * heads * cfg.head_dim + 4 * cfg.d_model
                                 + (2 + 2 * swiglu) * d_ff)
    return logits + grads + inputs + block


def _free_bytes(mesh: Optional[Mesh]) -> Optional[int]:
    """What the least free of the step's chips has left, by the device's
    own reading (``bytes_limit`` less ``bytes_in_use``); ``None`` where a
    chip gives none (the CPU backend), or where other processes hold
    chips of the mesh whose reading this one cannot see."""
    if jax.process_count() > 1:
        return None
    devices = (mesh.devices.flat if mesh is not None
               else jax.local_devices()[:1])
    free = []
    for device in devices:
        stats = device.memory_stats() or {}
        if "bytes_limit" not in stats or "bytes_in_use" not in stats:
            return None
        free.append(stats["bytes_limit"] - stats["bytes_in_use"])
    return min(free)


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S, V). Under ``cfg.remat`` the
    blocks are checkpointed as :func:`remat_plan` says, read from the
    chips' memory when this is traced (the state resident by then)."""
    def maybe_constrain(x, *spec):
        if mesh is not None:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))
        return x

    cfg = resolve_impls(cfg, mesh)

    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = embed(params, tokens, cfg)
    x = maybe_constrain(x, "dp", "sp", None)

    # a layer's kind goes in by name, and its depth where a kind reads it
    of_layer = [(kind, i if cfg.differential else 0)
                for i, kind in enumerate(cfg.mixers)]
    fns = {key: partial(_block, kind=key[0], layer=key[1])
           for key in set(of_layer)}
    block_fns = [fns[key] for key in of_layer]
    if cfg.remat:
        free = _free_bytes(mesh)
        if free is not None:
            free -= step_bytes(cfg, tokens.shape, mesh)
        kept = remat_plan(cfg, tokens.shape, mesh, free)["layers_kept"]
        keeping = {key: jax.checkpoint(
            fn, static_argnums=(3, 4),
            policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
            for key, fn in fns.items()}
        whole = {key: jax.checkpoint(fn, static_argnums=(3, 4))
                 for key, fn in fns.items()}
        block_fns = [(keeping if i < kept else whole)[key]
                     for i, key in enumerate(of_layer)]

    def stack(x, carry, _t):
        lent = {}
        for i, (block_fn, blk) in enumerate(zip(block_fns, params["blocks"])):
            x, _, lends = block_fn(x, blk, positions, cfg, mesh,
                                   lent=lent.get(cfg.mixers[i]))
            lent.update(lender_of(cfg, i, lends))
            x = maybe_constrain(x, "dp", "sp", None)
        return x, carry

    x, _ = run_passes(x, None, params, cfg, stack)
    return maybe_constrain(head(params, x, cfg), "dp", "sp", None)


@jax.named_scope(scopes.EMBED)
def embed(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """tokens (B, S) → their rows of the table, in the compute type."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


@jax.named_scope(scopes.HEAD)
def head(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The normed state (B, S, D) → float32 logits (B, S, V): through
    ``lm_head``, or through the embedding table where the head is tied."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"].astype(cfg.compute_dtype))
    else:
        logits = x @ params["lm_head"].astype(cfg.compute_dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token negative log-likelihood — THE loss definition, shared by
    training (dense + MoE) and evaluation so they can never diverge."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def loss_fn(params: dict, tokens: jax.Array, targets: jax.Array,
            cfg: ModelConfig, mesh: Optional[Mesh] = None) -> jax.Array:
    logits = forward(params, tokens, cfg, mesh)
    with jax.named_scope(scopes.HEAD):
        return jnp.mean(token_nll(logits, targets))
