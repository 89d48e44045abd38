"""What both guests need of the program under test: its ``ModelConfig`` at
a configuration file's sizes, and its state off the chips before the
reference runs. Nothing of the reference imports this."""

from __future__ import annotations

import gc

from benchmarks.weights import sizes_of


def model_config(config: dict):
    import jax.numpy as jnp

    from faabric_tpu.models import ModelConfig

    sizes = sizes_of(config)
    return ModelConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        d_ff=sizes["d_ff"], max_seq=sizes["max_seq"],
        rope_theta=sizes["rope_theta"],
        compute_dtype=jnp.dtype(config["compute_dtype"]).type,
        param_dtype=jnp.dtype(config["param_dtype"]).type)


def free_the_chips(state: dict) -> None:
    """Before the reference runs: drop the guest's state and delete every
    array the finished job left on the chips. Dropping the references is
    not enough: what still refers to the state does so in a cycle, and one
    seed's job kept 7.4 GB alive through ``gc.collect()`` on both of its
    runs (my chip runs, PR 24), which the reference then could not fit
    beside. Nothing that ran before the check is needed after it."""
    import jax

    state.clear()
    gc.collect()
    for array in jax.live_arrays():
        array.delete()
