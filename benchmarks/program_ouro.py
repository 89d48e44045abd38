"""The program's ``ModelConfig`` for a looped-decoder configuration file
(``configs/ouro-2.6b.json``), as ``program.py`` builds Pythia's. A program
whose ``ModelConfig`` cannot say these kinds raises here, at once."""

from __future__ import annotations

from benchmarks.weights_ouro import sizes_of


def model_config(config: dict):
    import jax.numpy as jnp

    from faabric_tpu.models import ModelConfig

    sizes = sizes_of(config)
    return ModelConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        rope_theta=sizes["rope_theta"], ffn="swiglu",
        norm_placement="sandwich", rope_pairing="halves",
        norm_eps=sizes["norm_eps"], n_passes=sizes["passes"],
        exit_threshold=sizes["exit_threshold"],
        compute_dtype=jnp.dtype(config["compute_dtype"]).type,
        param_dtype=jnp.dtype(config["param_dtype"]).type)
