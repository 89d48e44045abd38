"""Sampling generation with the KV cache (greedy, temperature, nucleus).

Run (CPU): JAX_PLATFORMS=cpu python examples/serve.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from faabric_tpu.util.device_env import configure_compile_cache

configure_compile_cache()

import jax
import jax.numpy as jnp
import numpy as np

from faabric_tpu.models import ModelConfig, init_params
from faabric_tpu.models.generate import generate


def main() -> None:
    cfg = ModelConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                      d_ff=128, max_seq=128, compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (1, 16)), jnp.int32)

    greedy = generate(params, prompt, cfg, 16)
    print("greedy :", np.asarray(greedy)[0].tolist())

    # Varying temperature/top_p reuses ONE compiled decode program
    for t in (0.7, 1.0, 1.3):
        toks = generate(params, prompt, cfg, 16, jax.random.PRNGKey(1),
                        temperature=t, top_k=40, top_p=0.95,
                        prefill_chunk=8)
        print(f"t={t:<4}:", np.asarray(toks)[0].tolist())


if __name__ == "__main__":
    main()
