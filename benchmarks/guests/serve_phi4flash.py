"""Batched problem solving through one warm guest:
``guests/serve_granite.py``'s protocol (every request one invocation of a
guest that holds the weights on the chip the planner pinned it to, carries
a bucket of rows and answers with the program's ``generate()``), for a
configuration of Mamba-1 layers, windowed and full differential attention,
gated memory units and cross attention over one shared cache, whose
weights, sizes and reference are ``weights_phi4flash.py``,
``program_phi4flash.py`` and ``reference/phi4flash.py``.

- :func:`make_guest` runs in the worker. It builds the program's
  ``ModelConfig`` at once, so that a program that cannot express the
  configuration fails before it says READY. Its ops are
  ``serve_granite.py``'s. A ``generate`` reply carries every row's tokens
  and the program's counters for the call
  (``models/generate.py:call_sizes``: ``window_layers``, ``window_slots``,
  ``window_cache_bytes``, ``shared_cache_bytes``, ``cross_layers``,
  ``memory_layers``, ``ssm_layers``, ``state_bytes``, ``scan_chunks``,
  ``prefill_skipped_layers``, ``attention_streamed_layers`` and
  ``attention_streamed_bytes`` among them). ``check`` takes a ``control``
  (a lower precision) or a ``fault`` (``reference/phi4flash.py:FAULTS``:
  what this model's own hand-overs make possible) and then reads the gap
  of the token the altered reference puts first. ``trace_stop`` leaves,
  beside ``trace_reduce.py``'s reduction, the decode loops
  (:func:`decode_loops`).
- :func:`drive`, in the benchmark's parent, is ``serve_granite.drive``.
"""

from __future__ import annotations

import json
import os
import shutil
import time

from benchmarks import trace_loops, trace_reduce
from benchmarks.guests.serve import PROMPT_STREAM
from benchmarks.guests.serve_granite import drive  # noqa: F401


def decode_loops(loops: dict) -> dict:
    """``trace_loops.reduce_loops``'s outermost ``while`` spans, told
    apart: prefill's scans along positions (a Mamba-1 layer a chunk) are
    loops too, each a few milliseconds, and a run's decode loop is
    seconds. A decode loop is one that lasts at least half as long as the
    longest; the loops before it back to the decode loop before are its
    run's prefill, so their spans (``scan_s``) and the busy time before
    them join its ``before_s``."""
    found = loops["decode_loops"]
    if not found:
        return loops
    longest = max(loop["seconds"] for loop in found)
    kept, before, scans, n_scans = [], 0.0, 0.0, 0
    for loop in found:
        if 2 * loop["seconds"] < longest:
            before += loop["before_s"] + loop["seconds"]
            scans += loop["seconds"]
            n_scans += 1
            continue
        kept.append(dict(loop, before_s=loop["before_s"] + before,
                         scan_s=scans, scans=n_scans))
        before, scans, n_scans = 0.0, 0.0, 0
    return dict(loops, decode_loops=kept)


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

def make_guest(cell: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program, program_phi4flash, weights_phi4flash
    from faabric_tpu.models.generate import call_sizes
    from faabric_tpu.models.generate import generate as program_generate

    config, traffic = cell["config_values"], cell["traffic_values"]
    sizes = weights_phi4flash.sizes_of(config)
    cfg = program_phi4flash.model_config(config)
    run = {"seed": None}  # every request names its seed
    n_new, rows = int(traffic["new_tokens"]), int(traffic["rows"])
    chunk = int(traffic["prefill_chunk"])
    trace_dir = os.path.join(cell["out_dir"], "trace")
    state: dict = {}
    kept: dict = {"loops_file": None}

    def prompt_ids(index: int, length: int) -> np.ndarray:
        return weights_phi4flash.token_rows(
            run["seed"], PROMPT_STREAM, index, rows, length, sizes["vocab"])

    def load(ctx, _req):
        t0 = time.time()
        # where one worker serves seed after seed (limits.py), the seed
        # before and its reference go before this one's weights come
        program.free_the_chips(state)
        kept.update(loops_file=None)
        state["params"] = jax.block_until_ready(
            weights_phi4flash.make_weights(run["seed"], sizes,
                                           cfg.param_dtype,
                                           device=ctx.device))
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
                state["params"], prompt, cfg, n_new, prefill_chunk=chunk))
        return {"tokens": tokens.tolist(), "guest_start": t0,
                "guest_end": time.time(),
                **call_sizes(cfg, rows, length, n_new, chunk)}

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

    def check(ctx, req):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled rows of the sampled requests;
        with ``control`` or ``fault`` the same gap for the token that the
        reference in a lower precision, or with the fault planted, puts
        first. The reference takes the seed's weights as the program had
        them (bfloat16) and upcasts them a layer at a time; a request's
        sampled rows go through it in one block."""
        from benchmarks.reference import phi4flash as ref

        program.free_the_chips(state)
        params = weights_phi4flash.make_weights(
            run["seed"], sizes, cfg.param_dtype, device=ctx.device)
        control, fault = req.get("control"), req.get("fault")
        worst, per_row, compared = 0.0, [], 0
        for item in req["sample"]:
            length = int(item["prompt_len"])
            prompts = prompt_ids(int(item["index"]), length)
            at = slice(length - 1, length - 1 + n_new)
            served = np.asarray(item["tokens"], np.int32)
            ids = jnp.asarray(np.concatenate(
                [prompts[item["rows"]], served[:, :-1]], axis=1))
            with jax.default_device(ctx.device):
                logits = ref.logits_of_rows(params, ids, sizes, at=at)
                picked = jnp.asarray(served)
                if control or fault:
                    picked = jnp.argmax(ref.logits_of_rows(
                        params, ids, sizes, control or "float32", at=at,
                        fault=fault, handover=length), axis=-1)
                gaps = jnp.max(logits, axis=-1) - jnp.take_along_axis(
                    logits, picked[..., None], axis=-1)[..., 0]
                by_row = [float(g) for g in jnp.max(gaps, axis=-1)]
            per_row.extend(by_row)
            worst = max([worst] + by_row)
            compared += served.size
        return {"served_logit_gap": worst, "per_row": per_row,
                "tokens_compared": compared}

    def extras(_ctx, _req):
        return {"loops_file": kept["loops_file"],
                "reduce_s": kept.get("reduce_s")}

    ops = {"load": load, "generate": generate, "trace_start": trace_start,
           "trace_stop": trace_stop, "stats": stats, "check": check,
           "extras": extras}

    def guest(ctx):
        req = json.loads(ctx.message.input_data)
        run["seed"] = int(req["seed"])
        return json.dumps(ops[req["op"]](ctx, req)).encode()

    return guest
