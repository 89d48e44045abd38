"""Data-parallel training on a device mesh — the 60-second tour.

Run (CPU mesh): JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/train_ddp.py

On TPU hardware drop the env vars; the same code lays the mesh over the
real chips and the Pallas kernels engage automatically
(attention_impl="auto").
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from faabric_tpu.util.device_env import configure_compile_cache

configure_compile_cache()

import jax
import numpy as np

from faabric_tpu.models import (
    ModelConfig,
    init_train_state,
    make_optimizer,
    make_train_step,
)
from faabric_tpu.parallel import MeshConfig, build_mesh


def main() -> None:
    devices = jax.devices()
    n = len(devices)
    tp = 2 if n % 2 == 0 else 1
    mesh = build_mesh(devices, MeshConfig(tp=tp))
    print(f"mesh: {dict(mesh.shape)} over {n} {devices[0].platform} device(s)")

    cfg = ModelConfig(vocab_size=512, d_model=128, n_layers=2, n_heads=8,
                      d_ff=256, max_seq=128)
    opt = make_optimizer(lr=1e-3)
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, mesh,
                                         opt)
    step = make_train_step(cfg, mesh, opt)

    # Input pipeline: deterministic shuffled windows, prefetched onto the
    # mesh one batch ahead (swap the array for TokenDataset.from_file to
    # stream a memmap'd corpus)
    from faabric_tpu.data import DataLoader, TokenDataset

    rng = np.random.RandomState(0)
    corpus = rng.randint(0, cfg.vocab_size, 50_000, dtype=np.int32)
    dp = mesh.shape["dp"]
    loader = DataLoader(TokenDataset(corpus, seq_len=64),
                        batch_size=dp * 4, mesh=mesh, seed=0)

    for i, (tokens, targets) in enumerate(loader):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        print(f"step {i}: loss {float(loss):.4f}")
        if i == 4:
            break


if __name__ == "__main__":
    main()
