"""The program's ``ModelConfig`` for a configuration file of Mamba-1
layers, windowed and full differential attention, gated memory units and
cross attention over one shared cache
(``configs/phi-4-mini-flash-reasoning.json``), as ``program_granite.py``
builds Granite's. A program whose ``ModelConfig`` cannot say these kinds
raises here, at once."""

from __future__ import annotations

from benchmarks.weights_phi4flash import sizes_of

# the benchmark's names of the layers' kinds → the program's
KINDS = {"mamba1": "mamba1", "window": "window_attention",
         "full": "attention", "memory": "gated_memory",
         "cross": "cross_attention"}


def model_config(config: dict):
    import jax.numpy as jnp

    from faabric_tpu.models import ModelConfig

    sizes = sizes_of(config)
    kinds = sizes["layer_kinds"]
    return ModelConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"], ffn="swiglu",
        norm_eps=sizes["norm_eps"], norm="layer", attention_bias=True,
        layer_types=tuple(KINDS[kind] for kind in kinds),
        n_kv_heads=sizes["n_kv_heads"], position="none",
        differential=True, sliding_window=sizes["window"],
        cache_source=kinds.index("full"),
        memory_source=sizes["memory_source"], tie_embeddings=True,
        ssm_inner=sizes["ssm_inner"], ssm_d_state=sizes["ssm_d_state"],
        ssm_d_conv=sizes["ssm_d_conv"], ssm_dt_rank=sizes["ssm_dt_rank"],
        compute_dtype=jnp.dtype(config["compute_dtype"]).type,
        param_dtype=jnp.dtype(config["param_dtype"]).type)
