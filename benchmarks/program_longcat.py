"""The program's ``ModelConfig`` for a configuration file of latent
attention in shortcut-connected double layers with a held share of the
experts (``configs/longcat-flash-omni.json``), as ``program.py`` builds
Pythia's. A program whose ``ModelConfig`` cannot say these kinds raises
here, at once."""

from __future__ import annotations

from benchmarks.weights_longcat import sizes_of


def model_config(config: dict):
    import jax.numpy as jnp

    from faabric_tpu.models import ModelConfig

    sizes = sizes_of(config)
    return ModelConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        rope_theta=sizes["rope_theta"], ffn="swiglu",
        norm_eps=sizes["norm_eps"], rope_pairing="neighbours",
        attention="latent", q_lora_rank=sizes["q_rank"],
        kv_lora_rank=sizes["kv_rank"], qk_nope_dim=sizes["qk_nope"],
        qk_rope_dim=sizes["qk_rope"], v_head_dim=sizes["v_head"],
        layer="shortcut", routed_experts=sizes["routed_experts"],
        zero_experts=sizes["zero_experts"],
        experts_held=sizes["experts_held"],
        experts_per_token=sizes["top_k"],
        routed_scaling=sizes["routed_scaling"],
        expert_d_ff=sizes["expert_d_ff"],
        compute_dtype=jnp.dtype(config["compute_dtype"]).type,
        param_dtype=jnp.dtype(config["param_dtype"]).type)
