"""Questions over long documents through one warm guest:
``guests/serve_longcat.py``'s protocol and counters (every request one
invocation of a guest that holds the weights on the chip the planner
pinned it to, carries a bucket of rows and answers with the program's
``generate_with_counters()``), with ``guests/serve_granite.py``'s chunked
prefill and planted faults, for a configuration of latent attention under
YaRN, a leading dense layer and expert layers with a shared expert, whose
weights, sizes and reference are ``weights_axk1.py``, ``program_axk1.py``
and ``reference/axk1.py``.

- :func:`make_guest` runs in the worker. It builds the program's
  ``ModelConfig`` at once, so that a program that cannot express the
  configuration fails before it says READY. Its ops are
  ``serve_longcat.py``'s. A ``generate`` reply carries every row's tokens
  and the program's counters for the call: static ones from
  ``models/generate.py:call_sizes`` (``cache_slots``, ``cache_bytes``,
  ``experts_held``, ``router_width``, ``shared_experts``, ``dense_layers``,
  ``expert_layers``, ``prefill_chunks``, ``score_blocks``,
  ``expanded_bytes``, ``ffn_streamed_layers`` and ``ffn_streamed_bytes``
  among them) and those the call summed on the device (``picks_held``,
  ``picks_zero``, ``picks_absent``, ``experts_hit_decode``,
  ``tiles_decode``). ``check`` takes a ``control`` (a lower precision) or
  a ``fault`` (``reference/axk1.py:FAULTS``: what this model's own parts
  make possible) and then reads the gap of the token the altered
  reference puts first; a plain check also counts the (token, layer)
  pairs whose picks differ between program and reference over the first
  ``PICKS_POSITIONS`` of each sampled row, which ``extras`` hands on. ``trace_stop`` leaves, beside ``trace_reduce.py``'s
  reduction, the decode loops: the run's long ``while`` of 64 steps, told
  from prefill's short ones (the expert layers' loops over row tiles and
  attention's blocks, a layer a chunk) as
  ``guests/serve_phi4flash.py:decode_loops`` tells them.
- :func:`drive`, in the benchmark's parent, is ``serve_granite.drive``
  with ``routing_mismatch_share`` added to its record.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time

from benchmarks import trace_loops, trace_reduce
from benchmarks.guests import serve_granite
from benchmarks.guests.serve import PROMPT_STREAM
from benchmarks.guests.serve_phi4flash import decode_loops

# routing_mismatch_share compares the program's picks and the reference's
# over these many positions of each sampled row
PICKS_POSITIONS = 1024


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

def make_guest(cell: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program, program_axk1, weights_axk1
    from faabric_tpu.models.generate import call_sizes
    from faabric_tpu.models.generate import generate_with_counters

    config, traffic = cell["config_values"], cell["traffic_values"]
    sizes = weights_axk1.sizes_of(config)
    cfg = program_axk1.model_config(config)
    run = {"seed": None}  # every request names its seed
    n_new, rows = int(traffic["new_tokens"]), int(traffic["rows"])
    chunk = int(traffic["prefill_chunk"])
    trace_dir = os.path.join(cell["out_dir"], "trace")
    state: dict = {}
    kept: dict = {"loops_file": None, "routing_mismatch_share": None}

    def prompt_ids(index: int, length: int) -> np.ndarray:
        return weights_axk1.token_rows(run["seed"], PROMPT_STREAM, index,
                                       rows, length, sizes["vocab"])

    def load(ctx, _req):
        t0 = time.time()
        # where one worker serves seed after seed (limits.py), the seed
        # before and its reference go before this one's weights come
        program.free_the_chips(state)
        kept.update(loops_file=None, routing_mismatch_share=None)
        state["params"] = jax.block_until_ready(weights_axk1.make_weights(
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
            tokens, counted = jax.device_get(generate_with_counters(
                state["params"], prompt, cfg, n_new, prefill_chunk=chunk))
        return {"tokens": tokens.tolist(), "guest_start": t0,
                "guest_end": time.time(),
                **call_sizes(cfg, rows, length, n_new, chunk),
                **{name: int(n) for name, n in counted.items()}}

    def trace_start(_ctx, _req):
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        return {}

    def trace_stop(_ctx, _req):
        """One read of the trace: ``trace_reduce``'s reduction where
        ``reduce_to_file`` leaves it, and the decode loops beside it."""
        jax.profiler.stop_trace()
        t0 = time.time()
        compact = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        planes = compact.pop("planes")
        path = None
        if any(compact["devices"].values()):
            path = os.path.join(cell["out_dir"], "trace_reduced.json")
            with open(path, "w") as f:
                json.dump(trace_reduce.reduce(compact), f)
            kept["loops_file"] = os.path.join(cell["out_dir"],
                                              "trace_loops.json")
            with open(kept["loops_file"], "w") as f:
                json.dump(decode_loops(trace_loops.reduce_loops(compact)), f)
        kept["reduce_s"] = time.time() - t0
        return {"trace_file": path, "planes": planes}

    def stats(ctx, _req):
        mem = ctx.device.memory_stats() or {}
        return {"compiles": cell["compiles"].snapshot(),
                "memory_peak_bytes": mem.get("peak_bytes_in_use"),
                "memory_stats": mem}

    def picks_of(params, ids):
        """Every expert layer's picks as the program makes them for
        ``ids`` (S,), one row: its own ``forward`` with ``moe.route``
        recorded as it is traced. (expert layers, S, K)."""
        import dataclasses

        from faabric_tpu.models import moe, transformer

        seen, real = [], moe.route

        def recording(u, router, cfg):
            out = real(u, router, cfg)
            seen.append(out[0])
            return out

        moe.route = recording
        try:
            transformer.forward(params, ids[None],
                                dataclasses.replace(cfg, remat=False))
        finally:
            moe.route = real
        return jnp.stack(seen)

    program_picks = jax.jit(picks_of)  # one trace for every sample

    def check(ctx, req):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled rows of the sampled requests;
        with ``control`` or ``fault`` the same gap for the token that the
        reference in a lower precision, or with the fault planted, puts
        first. The reference takes the seed's weights as the program had
        them (bfloat16) and upcasts them a sub-layer or an expert at a
        time, a row at a time. A plain check also reads, and does not
        compare, the share of (token, layer) pairs whose picks differ
        between program and reference."""
        from benchmarks.reference import axk1 as ref

        # The weights stay where the window had them, and the request's
        # program stays loaded through the first check. The chip keeps the
        # lower 7.2 GB of its 16.9 for the temporaries of loaded programs;
        # a fresh process lays 9.77 GB of weights above the 4.1 GB that
        # the request's program takes there, and 3.0 GB stay free: the one
        # state in which the reference was found to fit, a block of
        # positions at a time (its programs take 0.2 GB each, its results
        # 0.5 GB). Freed and drawn again, as the other guests do it, the
        # weights left the lower part 0.7 GB and less (my chip runs, PR
        # 41; PERF.md section 7). Only what else the window left goes.
        params = state.get("params")
        if params is None:
            params = state["params"] = jax.block_until_ready(
                weights_axk1.make_weights(run["seed"], sizes,
                                          cfg.param_dtype,
                                          device=ctx.device))
        kept_leaves = {id(leaf) for leaf in jax.tree.leaves(params)}

        def drop_what_is_left():
            gc.collect()
            for array in jax.live_arrays():
                if id(array) not in kept_leaves:
                    array.delete()

        drop_what_is_left()
        control, fault = req.get("control"), req.get("fault")
        per_row, compared = [], 0
        differ = pairs = 0
        for item in req["sample"]:
            length = int(item["prompt_len"])
            prompts = prompt_ids(int(item["index"]), length)
            at = slice(length - 1, length - 1 + n_new)
            served = np.asarray(item["tokens"], np.int32)
            # the last served token too: it moves nothing that is
            # compared, and 8,256 positions go in equal blocks where
            # 8,255 do not
            ids = np.concatenate([prompts[item["rows"]], served], axis=1)
            with jax.default_device(ctx.device):
                for row, answer in zip(ids, served):
                    row = jnp.asarray(row)
                    logits, picks = ref.logits_of(params, row, sizes, at=at,
                                                  with_picks=True)
                    picked = jnp.asarray(answer)
                    if control or fault:
                        picked = jnp.argmax(ref.logits_of(
                            params, row, sizes, control or "float32", at=at,
                            fault=fault, handover=length, chunk=chunk),
                            axis=-1)
                    else:
                        # the program's picks over the row's first
                        # positions: its whole forward pass at the cell's
                        # reach would take 1.6 GB more of the chip
                        first = row[:PICKS_POSITIONS]
                        mine = np.sort(np.asarray(program_picks(
                            params, first)), axis=-1)
                        theirs = np.asarray(picks)[:, :first.shape[0]]
                        differ += int(np.sum(np.any(
                            mine != np.sort(theirs, axis=-1), axis=-1)))
                        pairs += mine.shape[0] * mine.shape[1]
                    gaps = jnp.max(logits, axis=-1) - jnp.take_along_axis(
                        logits, picked[:, None], axis=-1)[:, 0]
                    per_row.append(float(jnp.max(gaps)))
                    print(f"check: {control or fault or 'program'} row "
                          f"{len(per_row)} gap {per_row[-1]:.4f} at "
                          f"{int(jnp.argmax(gaps))}", file=sys.stderr,
                          flush=True)
                    # what a row's reference leaves on the chip (2 GB and
                    # more a row, my chip run, PR 41) goes before the next
                    del logits, picks, picked, gaps, row
                    drop_what_is_left()
            compared += served.size
        if pairs:
            kept["routing_mismatch_share"] = differ / pairs
        # a check behind this one (a control, a fault) loads programs of
        # its own: this one's go, and the request's with them
        jax.clear_caches()
        return {"served_logit_gap": max(per_row, default=0.0),
                "per_row": per_row, "tokens_compared": compared}

    def extras(_ctx, _req):
        return {"loops_file": kept["loops_file"],
                "reduce_s": kept.get("reduce_s"),
                "routing_mismatch_share": kept["routing_mismatch_share"]}

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
    record = serve_granite.drive(cluster, cell, args, deadline)
    extras = cluster.invoke(
        cell["guest"], [{"op": "extras", "seed": args.seed}], deadline,
        float(cell["traffic_values"]["poll_ms"]) / 1e3)["replies"][0]
    record["routing_mismatch_share"] = extras["routing_mismatch_share"]
    return record
