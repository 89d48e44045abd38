#!/usr/bin/env python3
"""Prefill attention over a dense cache, timed on the chip at the cells'
widths: one layer's attention of one prefill chunk
(``transformer._attend_cached`` at a static start, the cache written
where the layer writes it) through ``ops/attention.py:attend`` (the
``jnp`` lines) and through ``ops/prefill_attention.py`` (the program's
path where ``streams_prefill_attention`` finds the shape), with the
largest difference of their outputs.

    python3 tools/probe_prefill_attention.py [rows] [chunk] [prompt]

A line of JSON a (cell, kind, chunk), then a line a cell: what a request's
prefill attends, summed over its attending layers and chunks. A probe,
not a benchmark: ``PERF.md`` says what it read and when.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELLS = {"serve_phi4flash_1chip": ("phi-4-mini-flash-reasoning.json",
                                   "program_phi4flash", 256),
         "serve_granite_1chip": ("granite-4.0-h-micro.json",
                                 "program_granite", 128)}


def main(argv) -> int:
    import importlib

    import jax
    import jax.numpy as jnp

    from faabric_tpu.models import transformer
    from faabric_tpu.models.generate import _ring_slots, call_sizes

    rows, chunk, prompt = (int(a) for a in (argv[0:3] + [64, 256, 512][
        len(argv[0:3]):]))
    found = transformer.streams_prefill_attention

    def timed(fn, *args) -> float:
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 5 * 1e3

    for cell, (name, module, new) in CELLS.items():
        with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
            cfg = importlib.import_module(f"benchmarks.{module}") \
                .model_config(json.load(f))
        dt, d = cfg.compute_dtype, cfg.head_dim
        slots = call_sizes(cfg, rows, prompt, new)["cache_slots"]
        kinds = [kind for kind in ("window_attention", "attention")
                 if kind in cfg.mixers[:cfg.stateless_from]]
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        sums = {"attend_ms": 0.0, "kernel_ms": 0.0}
        for kind in kinds:
            held = _ring_slots(cfg, slots) if kind == "window_attention" \
                else slots
            layers = cfg.mixers[:cfg.stateless_from].count(kind)
            shape = (1, rows, held, cfg.kv_heads * d)
            cache = {"k": jax.random.normal(keys[0], shape, dt),
                     "v": jax.random.normal(keys[1], shape, dt)}
            q = jax.random.normal(keys[2], (rows, chunk, cfg.n_heads, d), dt)
            k, v = (jax.random.normal(one, (rows, chunk, cfg.kv_heads, d), dt)
                    for one in keys[3:])
            for start in range(0, prompt, chunk):
                def attended(start=start, kind=kind):
                    # a function of its own for each: jit keeps one trace
                    # a function
                    return jax.jit(lambda q, k, v, cache: (
                        transformer._attend_cached(
                            q, k, v, cache, (0, start), cfg, kind,
                            None)[0]))

                how = found(cfg, rows, chunk, held, dt, start,
                            cfg.sliding_window
                            if kind == "window_attention" else 0)
                kernel = attended()
                transformer.streams_prefill_attention = \
                    lambda *a, **kw: None
                lines = attended()
                want = lines(q, k, v, cache)
                transformer.streams_prefill_attention = found
                got = kernel(q, k, v, cache)
                line = {"cell": cell, "kind": kind, "start": start,
                        "layers": layers,
                        "attend_ms": timed(lines, q, k, v, cache),
                        "kernel_ms": timed(kernel, q, k, v, cache),
                        "max_abs_diff": float(jnp.max(jnp.abs(
                            got.astype(jnp.float32)
                            - want.astype(jnp.float32)))),
                        "plan": how and {key: how[key] for key in (
                            "block_q", "block_k", "grid", "visited",
                            "masked", "streamed_bytes")}}
                for key in sums:
                    sums[key] += layers * line[key]
                print(json.dumps(line), flush=True)
        print(json.dumps({"cell": cell, "device": jax.devices()[0].device_kind,
                          "rows": rows, "chunk": chunk, "prompt": prompt,
                          "a_request": sums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
