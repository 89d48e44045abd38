"""The program's ``ModelConfig`` for a configuration file of latent
attention under YaRN in single layers, the leading ones with a dense
feed-forward and the rest with an expert layer (a sigmoid router, a held
share of the routed experts, shared experts beside them):
``configs/a.x-k1.json``, as ``program_longcat.py`` builds LongCat's. A
program whose ``ModelConfig`` cannot say these kinds raises here, at
once."""

from __future__ import annotations

from benchmarks.weights_axk1 import ffn_kinds, sizes_of


def model_config(config: dict, sizes: dict | None = None):
    import jax.numpy as jnp

    from faabric_tpu.models import ModelConfig
    from faabric_tpu.models.transformer import RopeScaling

    sizes = sizes or sizes_of(config)
    factor, reach, fast, slow, mscale, all_dim = sizes["yarn"]
    return ModelConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        rope_theta=sizes["rope_theta"], ffn="swiglu",
        norm_eps=sizes["norm_eps"], rope_pairing="neighbours",
        rope_scaling=RopeScaling(
            factor=factor, original_max_seq=int(reach), beta_fast=fast,
            beta_slow=slow, mscale=mscale, mscale_all_dim=all_dim),
        attention="latent", latent_scale=False,
        q_lora_rank=sizes["q_rank"], kv_lora_rank=sizes["kv_rank"],
        qk_nope_dim=sizes["qk_nope"], qk_rope_dim=sizes["qk_rope"],
        v_head_dim=sizes["v_head"], layer="single",
        ffn_types=ffn_kinds(sizes), shared_experts=sizes["shared_experts"],
        routed_experts=sizes["routed_experts"],
        experts_held=sizes["experts_held"],
        experts_per_token=sizes["top_k"],
        routed_scaling=sizes["routed_scaling"],
        expert_d_ff=sizes["expert_d_ff"], router_score="sigmoid",
        router_renormalise=True, router_bias=False,
        compute_dtype=jnp.dtype(config["compute_dtype"]).type,
        param_dtype=jnp.dtype(config["param_dtype"]).type)
