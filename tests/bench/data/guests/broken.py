"""Tests only: a cell's guest with the timed path broken underneath. The
traffic file names the guest it wraps (``wraps``) and the fault planted in
the program (``fault``); the harness, the guest and the comparison run
unchanged on top, and ``correct`` has to come out false.

- ``token_altered``: the program's ``generate`` answers with one token
  altered where it is produced.
- ``state_unchanged``: the train step computes its loss and returns
  parameters and optimizer state as it got them.
- ``half_batch``: the train step leaves half of the batch out and takes
  the mean over the rest.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(HERE))))
sys.path.insert(0, ROOT)

from benchmarks import cells  # noqa: E402


def _wrapped(cell: dict):
    manifest = cells.load_manifest(
        os.path.join(os.path.dirname(HERE), "toy_manifest.json"))
    return cells.load_module(manifest, "guests",
                             cell["traffic_values"]["wraps"])


def _plant(fault: str, vocab: int) -> None:
    import importlib

    import jax

    import faabric_tpu.models as models
    from faabric_tpu.models.transformer import loss_fn

    if fault == "token_altered":
        # the package's attribute of that name is the function
        generate_module = importlib.import_module(
            "faabric_tpu.models.generate")
        real = generate_module.generate

        def generate(params, prompt, cfg, n_tokens, **kw):
            tokens = real(params, prompt, cfg, n_tokens, **kw)
            return tokens.at[:, 3].set((tokens[:, 3] + 1) % vocab)

        generate_module.generate = generate
    elif fault == "state_unchanged":

        def make_train_step(cfg, mesh=None, optimizer=None, accum_steps=1):
            loss_of = jax.jit(lambda p, t, y: loss_fn(p, t, y, cfg, mesh))
            return lambda p, o, t, y: (p, o, loss_of(p, t, y))

        models.make_train_step = make_train_step
    elif fault == "half_batch":
        real_make = models.make_train_step

        def make_train_step(cfg, mesh=None, optimizer=None, accum_steps=1):
            step = real_make(cfg, mesh, optimizer, accum_steps)
            return lambda p, o, t, y: step(p, o, t[:t.shape[0] // 2],
                                           y[:y.shape[0] // 2])

        models.make_train_step = make_train_step
    else:
        raise ValueError(f"no fault {fault!r}")


def make_guest(cell: dict):
    _plant(cell["traffic_values"]["fault"],
           int(cell["config_values"]["vocab_size"]))
    return _wrapped(cell).make_guest(cell)


def drive(cluster, cell: dict, args, deadline: float) -> dict:
    return _wrapped(cell).drive(cluster, cell, args, deadline)
