#!/usr/bin/env python3
"""How a prefill chunk of latent attention may attend the chunks before
it, timed on the chip at a configuration's widths (one attention, the
cell's rows and chunk): keys and values of every head expanded again from
the latent cache (``transformer._latent_expanded``, the path of a call
that starts at position 0, in its blocks); the expansion alone (what a
path that kept them expanded would save, at ``expanded_bytes`` of memory
a layer); the absorbed form over the latents as they lie
(``transformer._latent_absorbed``, in the same blocks of rows and
queries); and the kernel that keeps the scores on the chip
(``transformer._latent_streamed``, the program's path where
``streams_latent_prefill`` finds the shape: the reach's keys and values
expanded again a block of rows at a time and attended through
``ops/latent_attention.py``), with the kernel alone on keys and values
already expanded beside it.

    python3 tools/probe_latent_prefill.py [config.json] [rows] [chunk] [prompt]

A line of JSON a reach, then the sums over a request's chunks. A probe,
not a benchmark: ``PERF.md`` says what it read and when.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks import program_axk1
    from faabric_tpu.models import transformer
    from faabric_tpu.ops.latent_attention import latent_attention

    path = argv[0] if argv else os.path.join(
        ROOT, "benchmarks", "configs", "a.x-k1.json")
    rows, chunk, prompt = (int(a) for a in (argv[1:4] + [8, 1024, 8192][
        len(argv[1:4]):]))
    with open(path) as f:
        cfg = program_axk1.model_config(json.load(f))
    dt, h = cfg.compute_dtype, cfg.n_heads
    rank, nope, rope = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q_nope = jax.random.normal(k[0], (rows, chunk, h, nope), dt)
    q_rope = jax.random.normal(k[1], (rows, chunk, h, rope), dt)
    wkvb = jax.random.normal(k[2], (rank, h, nope + cfg.v_head_dim), dt) \
        / rank ** 0.5

    def timed(fn, *args) -> float:
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 3 * 1e3

    expanded = jax.jit(lambda qn, qr, lat: transformer._latent_expanded(
        qn, qr, lat, wkvb, cfg))
    expansion = jax.jit(lambda lat: jnp.einsum(
        "bkc,che->bkhe", lat[..., :rank], wkvb))

    absorbed = jax.jit(lambda qn, qr, lat: transformer._latent_absorbed(
        qn, qr, lat, lat.shape[1], wkvb, cfg))
    kernel = jax.jit(lambda qn, qr, keys, kr, values: latent_attention(
        qn, qr, keys, kr, values, scale=cfg.score_scale))
    sums = {"expanded_ms": 0.0, "expansion_ms": 0.0, "absorbed_ms": 0.0,
            "streamed_ms": 0.0, "kernel_ms": 0.0}
    for reach in range(chunk, prompt + 1, chunk):
        latent = jax.random.normal(k[3], (rows, reach, rank + rope), dt)
        line = {"reach": reach,
                "blocks": transformer.score_blocks(rows, h, chunk, reach),
                "expanded_ms": timed(expanded, q_nope, q_rope, latent),
                "expansion_ms": timed(expansion, latent),
                "absorbed_ms": timed(absorbed, q_nope, q_rope, latent)}
        how = transformer.streams_latent_prefill(cfg, rows, chunk, reach)
        if how is not None:
            streamed = jax.jit(
                lambda qn, qr, lat, how=how: transformer._latent_streamed(
                    qn, qr, lat, wkvb, cfg, how["rows"]))
            # as the expansion leaves them: a position's heads side by side
            keys = jax.random.normal(k[2], (rows, reach, h * nope), dt)
            values = jax.random.normal(
                k[0], (rows, reach, h * cfg.v_head_dim), dt)
            line.update(
                plan={name: how[name] for name in (
                    "block_q", "block_k", "rows", "streamed_bytes")},
                streamed_ms=timed(streamed, q_nope, q_rope, latent),
                kernel_ms=timed(kernel, q_nope, q_rope, keys,
                                latent[..., rank:], values))
            del keys, values
        for name in sums:
            sums[name] += line.get(name, float("nan"))
        print(json.dumps(line), flush=True)
    kept = rows * prompt * h * (nope + cfg.v_head_dim) \
        * jnp.dtype(dt).itemsize
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "rows": rows,
        "chunk": chunk, "prompt": prompt, "an_attention_a_request": sums,
        "expanded_bytes_a_layer_if_kept": kept}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
