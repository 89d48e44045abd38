"""Single-stream serving of the looped decoder: ``guests/serve.py``'s
protocol (every request one invocation of a warm guest that holds the
weights on the chip the planner pinned it to, and answers with the
program's ``generate()``) for a configuration whose weights, sizes and
reference are ``weights_ouro.py``, ``program_ouro.py`` and
``reference/ouro.py``.

- :func:`make_guest` runs in the worker. It builds the program's
  ``ModelConfig`` at once, so that a program that cannot express the
  configuration fails before it says READY. Its ops are ``serve.py``'s
  (``load``, ``generate``, ``trace_start`` / ``trace_stop``, ``stats``,
  ``check``) and ``extras``. Every ``generate`` reply carries the
  program's counters for the call (``ut_passes``, ``cache_slots``,
  ``cache_bytes``, from ``models/generate.py:call_sizes``), and
  ``trace_stop`` leaves ``trace_loops.py``'s reduction beside
  ``trace_reduce.py``'s.
- :func:`drive` runs in the benchmark's parent: ``serve.drive`` as it is
  (set-up, the closed-loop window, memory, check, trace), then ``extras``:
  the counters of every request served and the loops of the traced ones.
"""

from __future__ import annotations

import json
import os
import shutil
import time

from benchmarks import trace_loops, trace_reduce
from benchmarks.guests import serve


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

def make_guest(cell: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program, program_ouro, weights_ouro
    from faabric_tpu.models.generate import call_sizes
    from faabric_tpu.models.generate import generate as program_generate

    config, traffic = cell["config_values"], cell["traffic_values"]
    sizes = weights_ouro.sizes_of(config)
    cfg = program_ouro.model_config(config)
    run = {"seed": None}  # every request names its seed
    n_new = int(traffic["new_tokens"])
    trace_dir = os.path.join(cell["out_dir"], "trace")
    state: dict = {}
    kept: dict = {"counters": {}, "loops_file": None}

    def prompt_ids(index: int, length: int) -> np.ndarray:
        return weights_ouro.token_rows(run["seed"], serve.PROMPT_STREAM,
                                       index, 1, length, sizes["vocab"])

    def load(ctx, _req):
        t0 = time.time()
        # where one worker serves seed after seed (limits.py), the seed
        # before and its reference go before this one's weights come
        program.free_the_chips(state)
        kept.update(counters={}, loops_file=None)
        state["params"] = jax.block_until_ready(weights_ouro.make_weights(
            run["seed"], sizes, cfg.param_dtype, device=ctx.device))
        return {"device_id": int(ctx.device.id),
                "phases": {"load_weights_s": time.time() - t0},
                "n_params": sum(int(x.size) for x in
                                jax.tree.leaves(state["params"]))}

    def generate(ctx, req):
        t0 = time.time()
        index, length = int(req["index"]), int(req["prompt_len"])
        with jax.profiler.TraceAnnotation(f"bench:request#{index}"):
            prompt = jax.device_put(prompt_ids(index, length), ctx.device)
            tokens = np.asarray(program_generate(
                state["params"], prompt, cfg, n_new))
        counters = call_sizes(cfg, 1, length, n_new)
        kept["counters"][index] = counters
        return {"tokens": tokens[0].tolist(), "guest_start": t0,
                "guest_end": time.time(), **counters}

    def trace_start(_ctx, _req):
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        return {}

    def trace_stop(_ctx, _req):
        jax.profiler.stop_trace()
        t0 = time.time()
        slots = {call_sizes(cfg, 1, int(p["tokens"]), n_new)["cache_slots"]
                 for p in traffic["prompt_lengths"]}
        out = trace_loops.reduce_to_files(
            trace_dir, cell["out_dir"],
            trace_loops.cache_operations(sizes, slots))
        kept["loops_file"] = out.pop("loops_file")
        kept["reduce_s"] = time.time() - t0
        return out

    def stats(ctx, _req):
        mem = ctx.device.memory_stats() or {}
        return {"compiles": cell["compiles"].snapshot(),
                "memory_peak_bytes": mem.get("peak_bytes_in_use"),
                "memory_stats": mem}

    def check(ctx, req):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled requests; with ``control`` the
        same gap for the token a lower precision puts first. The
        reference takes the seed's weights as the program had them
        (bfloat16) and upcasts them layer by layer."""
        from benchmarks.reference import ouro as ref

        program.free_the_chips(state)
        params = weights_ouro.make_weights(run["seed"], sizes,
                                           cfg.param_dtype,
                                           device=ctx.device)
        control = req.get("control")
        worst, per_request = 0.0, []
        for item in req["sample"]:
            length = int(item["prompt_len"])
            served = np.asarray(item["tokens"], np.int32)
            ids = np.concatenate([prompt_ids(int(item["index"]), length)[0],
                                  served[:-1]])
            at = slice(length - 1, length - 1 + len(served))
            with jax.default_device(ctx.device):
                logits = ref.logits_of(params, jnp.asarray(ids), sizes,
                                       at=at)
                if control:
                    picked = jnp.argmax(ref.logits_of(
                        params, jnp.asarray(ids), sizes, control, at=at),
                        axis=-1)
                else:
                    picked = jnp.asarray(served)
                gaps = jnp.max(logits, axis=-1) - jnp.take_along_axis(
                    logits, picked[:, None], axis=-1)[:, 0]
            gap = float(jnp.max(gaps))
            per_request.append(gap)
            worst = max(worst, gap)
        return {"served_logit_gap": worst, "per_request": per_request,
                "tokens_compared": sum(len(i["tokens"])
                                       for i in req["sample"])}

    def extras(_ctx, _req):
        return {"counters": kept["counters"],
                "loops_file": kept["loops_file"],
                "reduce_s": kept.get("reduce_s")}

    ops = {"load": load, "generate": generate, "trace_start": trace_start,
           "trace_stop": trace_stop, "stats": stats, "check": check,
           "extras": extras}

    def guest(ctx):
        req = json.loads(ctx.message.input_data)
        run["seed"] = int(req["seed"])
        return json.dumps(ops[req["op"]](ctx, req)).encode()

    return guest


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------

def drive(cluster, cell: dict, args, deadline: float) -> dict:
    record = serve.drive(cluster, cell, args, deadline)
    extras = cluster.invoke(
        cell["guest"], [{"op": "extras", "seed": args.seed}], deadline,
        float(cell["traffic_values"]["poll_ms"]) / 1e3)["replies"][0]
    for r in record["requests"]:
        # set-up's warm requests have indexes of their own: not these
        r.update(extras["counters"].get(str(r["index"]), {}))
    record["trace_loops"] = trace_reduce.load_reduced(extras["loops_file"])
    record["trace_reduce_s"] = extras["reduce_s"]
    return record
