"""Batched rollouts through one warm guest: ``guests/serve.py``'s protocol
(every request one invocation of a guest that holds the weights on the
chip the planner pinned it to, and answers with the program's
``generate()``), for requests that carry a bucket of rows, and for a
configuration whose weights, sizes and reference are
``weights_longcat.py``, ``program_longcat.py`` and
``reference/longcat.py``.

- :func:`make_guest` runs in the worker. It builds the program's
  ``ModelConfig`` at once, so that a program that cannot express the
  configuration fails before it says READY. Its ops are ``serve.py``'s
  (``load``, ``generate``, ``trace_start`` / ``trace_stop``, ``stats``,
  ``check``) and ``extras``. A ``generate`` request is ``rows`` prompts of
  one length, one ``generate()`` call; its reply carries every row's
  tokens and the program's counters for the call: static ones from
  ``models/generate.py:call_sizes`` (``cache_slots``, ``cache_bytes``,
  ``experts_held``, ``router_width``) and those the call summed on the
  device (``picks_held``, ``picks_zero``, ``picks_absent``,
  ``experts_hit_decode``). ``trace_stop`` leaves ``trace_loops.py``'s
  reduction of the decode loops (the outermost ``while`` that holds inner
  ones: the grouped products' loops over their row tiles), once for the
  operations that touch the latent caches and once for the expert
  layer's, beside ``trace_reduce.py``'s.
- :func:`drive` runs in the benchmark's parent, which never imports JAX:
  ``serve.drive``'s phases (set-up, the closed-loop window, memory, check,
  trace) for requests of ``rows`` rows. ``new_tokens`` of the record is
  what a completed request generated, rows × new tokens a row.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import time

from benchmarks import trace_loops, trace_reduce
from benchmarks.guests.serve import PROMPT_STREAM, schedule


class Operations:
    """Operations of the decode body by rule, for
    ``trace_loops.reduce_loops``, which asks ``"<kind> <type[shape]>" in
    ops`` of every device event: one is in if its result's type and shape
    is among ``shapes``, or the whole label among ``labels``."""

    def __init__(self, shapes, labels=()):
        self.shapes, self.labels = frozenset(shapes), frozenset(labels)

    def __contains__(self, label: str) -> bool:
        return label in self.labels \
            or label.partition(" ")[2] in self.shapes

    def __bool__(self) -> bool:
        return True


def decode_operations(sizes: dict, rows: int, slots: int) -> dict:
    """The decode body's operations that touch a latent cache, and the
    expert layer's, as the optimized HLO of a step at ``rows`` rows names
    them (the cell's program compiled for a described v5e, PR 31; the
    v5e's trace carries an instruction's name and result shape and no
    ``op_name``, so the program's ``mla_decode``, ``moe_route`` and
    ``moe_experts`` scopes name them in the HLO and shapes find them in
    the trace).

    The caches': the update of (1, rows, slots, rank + rope); the scores
    against the cache fused with their softmax, results (rows, heads)
    float32; the weighted sum over it, (rows, heads, rank); the mask of
    the slots written. The expert layer's: the router's product with its
    softmax, results (rows,) float32 of kind ``fusion``; everything of
    shape (rows, width), (rows, top_k, ...) and (picks, ...): selection,
    sorts, gathers, weighting; what the loop over row tiles holds, by the
    tile's rows (the program's ``moe._row_tile``): a tile's gathered rows,
    its three products, the buffer they land in (the loop's own ``while``
    is named like the decode loop's, by a counter, and its scalar
    bookkeeping is left out); and the sum over a token's picks."""
    from faabric_tpu.models.moe import _row_tile

    h, k = sizes["n_heads"], sizes["top_k"]
    rank, rope = sizes["kv_rank"], sizes["qk_rope"]
    d, fe = sizes["d_model"], sizes["expert_d_ff"]
    width = sizes["routed_experts"] + sizes["zero_experts"]
    held = sizes["experts_held"][1]
    picks, tile = rows * k, _row_tile(rows)
    room = (held + picks // tile) * tile
    cache = Operations(
        [f"bf16[1,{rows},{slots},{rank + rope}]", f"f32[{rows},{h}]",
         f"bf16[{rows},{h},{rank}]", f"pred[{slots}]"])
    every = ("f32", "s32", "bf16", "pred")
    experts = Operations(
        [f"{t}[{shape}]" for t in every for shape in (
            f"{rows},{width}", f"{picks}", f"{picks},1", f"{picks + tile}",
            f"{picks},{d}", f"{rows},{k}", f"{rows},{k},1",
            f"{rows},{k},{d}", f"{width}", f"{d},{width}", f"{held}",
            f"{held},1", f"{tile}", f"{tile},{d}", f"{tile},{fe}",
            f"{room},{d}")],
        [f"fusion f32[{rows}]", f"select_reduce_fusion bf16[{rows},{d}]"])
    return {"cache": cache, "experts": experts}


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

def make_guest(cell: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program, program_longcat, weights_longcat
    from faabric_tpu.models.generate import call_sizes
    from faabric_tpu.models.generate import generate_with_counters

    config, traffic = cell["config_values"], cell["traffic_values"]
    sizes = weights_longcat.sizes_of(config)
    cfg = program_longcat.model_config(config)
    run = {"seed": None}  # every request names its seed
    n_new, rows = int(traffic["new_tokens"]), int(traffic["rows"])
    trace_dir = os.path.join(cell["out_dir"], "trace")
    state: dict = {}
    kept: dict = {"loops_file": None}

    def prompt_ids(index: int, length: int) -> np.ndarray:
        return weights_longcat.token_rows(run["seed"], PROMPT_STREAM, index,
                                          rows, length, sizes["vocab"])

    def load(ctx, _req):
        t0 = time.time()
        # where one worker serves seed after seed (limits.py), the seed
        # before and its reference go before this one's weights come
        program.free_the_chips(state)
        kept.update(loops_file=None)
        state["params"] = jax.block_until_ready(weights_longcat.make_weights(
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
                state["params"], prompt, cfg, n_new))
        return {"tokens": tokens.tolist(), "guest_start": t0,
                "guest_end": time.time(),
                **call_sizes(cfg, rows, length, n_new),
                **{name: int(n) for name, n in counted.items()}}

    def trace_start(_ctx, _req):
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        return {}

    def trace_stop(_ctx, _req):
        """One read of the trace: ``trace_reduce``'s reduction where
        ``reduce_to_file`` leaves it, and the decode loops with the own
        time of the caches' operations (``cache_s``) and of the expert
        layer's (``expert_s``) beside it."""
        jax.profiler.stop_trace()
        t0 = time.time()
        compact = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        planes = compact.pop("planes")
        path = None
        if any(compact["devices"].values()):
            path = os.path.join(cell["out_dir"], "trace_reduced.json")
            with open(path, "w") as f:
                json.dump(trace_reduce.reduce(compact), f)
            slots = call_sizes(cfg, rows, int(
                traffic["prompt_lengths"][0]["tokens"]), n_new)["cache_slots"]
            ops = decode_operations(sizes, rows, slots)
            loops = trace_loops.reduce_loops(compact, ops["cache"])
            by_experts = trace_loops.reduce_loops(compact, ops["experts"])
            for loop, other in zip(loops["decode_loops"],
                                   by_experts["decode_loops"]):
                loop["expert_s"] = other["cache_s"]
            kept["loops_file"] = os.path.join(cell["out_dir"],
                                              "trace_loops.json")
            with open(kept["loops_file"], "w") as f:
                json.dump(loops, f)
        kept["reduce_s"] = time.time() - t0
        return {"trace_file": path, "planes": planes}

    def stats(ctx, _req):
        mem = ctx.device.memory_stats() or {}
        return {"compiles": cell["compiles"].snapshot(),
                "memory_peak_bytes": mem.get("peak_bytes_in_use"),
                "memory_stats": mem}

    def picks_of(params, ids):
        """Every layer's picks as the program makes them for ``ids`` (R,
        S): its own ``forward`` with ``moe.route`` recorded as it is
        traced. (layers, R·S, K)."""
        import dataclasses

        from faabric_tpu.models import moe, transformer

        seen, real = [], moe.route

        def recording(u, router, cfg):
            out = real(u, router, cfg)
            seen.append(out[0])
            return out

        moe.route = recording
        try:
            transformer.forward(params, ids,
                                dataclasses.replace(cfg, remat=False))
        finally:
            moe.route = real
        return jnp.stack(seen)

    program_picks = jax.jit(picks_of)  # one trace for every sample

    def check(ctx, req):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled rows of the sampled requests;
        with ``control`` the same gap for the token a lower precision puts
        first. The reference takes the seed's weights as the program had
        them (bfloat16) and upcasts them a sub-layer or an expert at a
        time. Beside it, reported and not compared: the share of (token,
        layer) pairs whose picks differ between program and reference."""
        from benchmarks.reference import longcat as ref

        program.free_the_chips(state)
        params = weights_longcat.make_weights(run["seed"], sizes,
                                              cfg.param_dtype,
                                              device=ctx.device)
        control = req.get("control")
        worst, per_row, compared = 0.0, [], 0
        differ = pairs = 0
        for item in req["sample"]:
            length = int(item["prompt_len"])
            prompts = prompt_ids(int(item["index"]), length)
            at = slice(length - 1, length - 1 + n_new)
            ids = np.stack([
                np.concatenate([prompts[row], np.asarray(served[:-1],
                                                         np.int32)])
                for row, served in zip(item["rows"], item["tokens"])])
            with jax.default_device(ctx.device):
                if not control:
                    mine = np.sort(np.asarray(program_picks(
                        params, jnp.asarray(ids))), axis=-1)
                for i, served in enumerate(item["tokens"]):
                    logits, picks = ref.logits_of(
                        params, jnp.asarray(ids[i]), sizes, at=at,
                        with_picks=True)
                    if control:
                        picked = jnp.argmax(ref.logits_of(
                            params, jnp.asarray(ids[i]), sizes, control,
                            at=at), axis=-1)
                    else:
                        picked = jnp.asarray(served, jnp.int32)
                        s = ids.shape[1]
                        theirs = np.sort(np.asarray(picks), axis=-1)
                        differ += int(np.sum(np.any(
                            mine[:, i * s:(i + 1) * s] != theirs, axis=-1)))
                        pairs += theirs.shape[0] * theirs.shape[1]
                    gaps = jnp.max(logits, axis=-1) - jnp.take_along_axis(
                        logits, picked[:, None], axis=-1)[:, 0]
                    gap = float(jnp.max(gaps))
                    per_row.append(gap)
                    worst = max(worst, gap)
                    compared += len(served)
        return {"served_logit_gap": worst, "per_row": per_row,
                "tokens_compared": compared,
                "routing_mismatch_share": differ / pairs if pairs else None}

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


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------

def drive(cluster, cell: dict, args, deadline: float) -> dict:
    traffic = cell["traffic_values"]
    poll_s = float(traffic["poll_ms"]) / 1e3
    n_new, rows = int(traffic["new_tokens"]), int(traffic["rows"])
    vocab = int(cell["config_values"]["vocab_size"])
    lengths = sorted({int(p["tokens"]) for p in traffic["prompt_lengths"]})

    def call(payload):
        return cluster.invoke(cell["guest"], [dict(payload, seed=args.seed)],
                              deadline, poll_s)

    def request(index: int, length: int) -> dict:
        r = call({"op": "generate", "index": index, "prompt_len": length})
        reply = r["replies"][0]
        return dict(reply, index=index, prompt_len=length, rows=rows,
                    posted=r["posted"], seen=r["seen"])

    # ---- set-up: weights, then every shape the window will use ---------
    t0 = time.time()
    loaded = call({"op": "load"})["replies"][0]
    phases = {"load_s": time.time() - t0, **loaded.pop("phases")}
    for i, length in enumerate(lengths):
        t0 = time.time()
        request(10**6 + i, length)
        phases[f"warm_{length}_s"] = time.time() - t0
    before = call({"op": "stats"})["replies"][0]["compiles"]

    # ---- the window ------------------------------------------------------
    # More requests than any window completes
    plan = schedule(traffic, args.seed, 200 * max(1, int(args.seconds)))
    log: list = []
    skip = int(traffic["trace"]["skip_requests"])
    traced = int(traffic["trace"]["requests"])
    trace_out: dict = {}
    tracing = False
    window_start = time.time()
    window_end_at = window_start + args.seconds
    while time.time() < window_end_at:
        index = len(log)
        try:
            log.append(request(index, plan[index]))
        except Exception as e:  # noqa: BLE001 — a failed request counts
            log.append({"index": index, "prompt_len": plan[index],
                        "rows": rows, "posted": time.time(),
                        "seen": time.time(), "failed": repr(e),
                        "tokens": []})
            break
        if args.trace and len(log) == skip:
            call({"op": "trace_start"})
            tracing = True
        elif tracing and len(log) == skip + traced:
            trace_out = call({"op": "trace_stop"})["replies"][0]
            tracing = False
    window_s = max(r["seen"] for r in log) - window_start
    if tracing:
        trace_out = call({"op": "trace_stop"})["replies"][0]

    after = call({"op": "stats"})["replies"][0]
    in_window = {k: after["compiles"][k] - before[k] for k in before}

    # ---- correctness: requests and rows drawn from the seed -------------
    done = [r for r in log if not r.get("failed")]
    rng = random.Random(int(args.seed) + 1)
    sample = [done[0]] + rng.sample(done[1:], min(
        int(traffic["check"]["sample_requests"]) - 1, len(done) - 1))
    bad_rows = sum(
        1 for r in done for row in r["tokens"]
        if len(row) != n_new or any(not 0 <= t < vocab for t in row))
    bad_rows += sum(1 for r in done if len(r["tokens"]) != rows)
    payload = {"op": "check", "sample": []}
    for r in sample:
        picked = sorted(rng.sample(range(rows), int(
            traffic["check"]["sample_rows"])))
        payload["sample"].append({
            "index": r["index"], "prompt_len": r["prompt_len"],
            "rows": picked, "tokens": [r["tokens"][i] for i in picked]})
    t0 = time.time()
    checked = call(payload)["replies"][0]
    check_s = time.time() - t0
    numbers = {"served_logit_gap": checked["served_logit_gap"],
               "malformed_answers": float(bad_rows)}
    extra = {}
    if getattr(args, "control", None):
        # the control need not decode: its answers are the program's
        lower = call(dict(payload, control=args.control))["replies"][0]
        extra["control"] = dict(
            numbers, served_logit_gap=lower["served_logit_gap"])

    extras = call({"op": "extras"})["replies"][0]
    return {
        "loaded": loaded,
        "setup_phases": phases,
        "window_start": window_start,
        "window_s": window_s,
        "requests": [{k: v for k, v in r.items() if k != "tokens"}
                     for r in log],
        "new_tokens": rows * n_new,
        "attempted": len(log),
        "failed": len(log) - len(done),
        "compiles_in_window": in_window,
        "memory_peak_bytes": after["memory_peak_bytes"],
        "memory_stats": after["memory_stats"],
        "check_s": check_s,
        "numbers": numbers,
        "tokens_compared": checked["tokens_compared"],
        "routing_mismatch_share": checked["routing_mismatch_share"],
        "per_row_gap": checked["per_row"],
        "trace": trace_reduce.load_reduced(trace_out.get("trace_file")),
        "trace_loops": trace_reduce.load_reduced(extras["loops_file"]),
        "trace_reduce_s": extras["reduce_s"],
        "planes": trace_out.get("planes"),
        **extra,
    }
