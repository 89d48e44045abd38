"""The program's ``ModelConfig`` for a configuration file of Mamba-2
state-space layers beside grouped-query attention layers under a
per-layer kind (``configs/granite-4.0-h-micro.json``), as ``program.py``
builds Pythia's. A program whose ``ModelConfig`` cannot say these kinds
raises here, at once."""

from __future__ import annotations

from benchmarks.weights_granite import sizes_of


def model_config(config: dict):
    import jax.numpy as jnp

    from faabric_tpu.models import ModelConfig

    sizes = sizes_of(config)
    return ModelConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"], ffn="swiglu",
        norm_eps=sizes["norm_eps"], layer_types=sizes["layer_types"],
        n_kv_heads=sizes["n_kv_heads"], position="none",
        attention_scale=sizes["attention_multiplier"],
        embedding_multiplier=sizes["embedding_multiplier"],
        residual_multiplier=sizes["residual_multiplier"],
        logits_scaling=sizes["logits_scaling"], tie_embeddings=True,
        ssm_d_state=sizes["ssm_d_state"], ssm_d_conv=sizes["ssm_d_conv"],
        ssm_heads=sizes["ssm_heads"], ssm_head_dim=sizes["ssm_head_dim"],
        ssm_groups=sizes["ssm_groups"], ssm_chunk=sizes["ssm_chunk"],
        compute_dtype=jnp.dtype(config["compute_dtype"]).type,
        param_dtype=jnp.dtype(config["param_dtype"]).type)
