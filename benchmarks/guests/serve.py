"""Single-stream serving: every request is one invocation of a warm guest
that holds the weights on the chip the planner pinned it to, and answers
with the program's ``generate()``.

Two halves in one file, because they are one protocol:

- :func:`make_guest` runs in the worker (the process that holds the chip)
  and returns the guest function. It answers ``op``s: ``load`` (weights
  from the seed, on the pinned chip), ``generate`` (one request),
  ``trace_start`` / ``trace_stop``, ``stats`` (compile counts, peak
  memory), ``check`` (the plain reference over a sample of what was
  served, once the program's weights are freed).
- :func:`drive` runs in the benchmark's parent, which never imports JAX:
  set-up (load, one warm request for each prompt length of the mix), the
  measured window (one caller in a closed loop: post, poll for the
  answer, post the next), then memory, check and trace.

Traffic parameters (``traffic/<name>.json``): ``prompt_lengths`` — a list
of ``{"tokens", "count"}``: one block of the schedule holds ``count``
requests of each length, and every block is put in an order drawn from the
seed, so every seed sends the same mix; ``new_tokens``; ``poll_ms``;
``check`` and ``trace`` as read below.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import time

from benchmarks import trace_reduce

PROMPT_STREAM = 1  # weights.token_rows stream of the prompts


def schedule(traffic: dict, seed: int, n: int) -> list:
    """Prompt lengths of the first ``n`` requests."""
    block = [int(p["tokens"]) for p in traffic["prompt_lengths"]
             for _ in range(int(p["count"]))]
    rng = random.Random(int(seed))
    out: list = []
    while len(out) < n:
        rng.shuffle(block)
        out.extend(block)
    return out[:n]


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

def make_guest(cell: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program, weights

    config, traffic = cell["config_values"], cell["traffic_values"]
    sizes = weights.sizes_of(config)
    run = {"seed": None}  # every request names its seed
    n_new = int(traffic["new_tokens"])
    trace_dir = os.path.join(cell["out_dir"], "trace")
    state: dict = {}

    def prompt_ids(index: int, length: int) -> np.ndarray:
        return weights.token_rows(run["seed"], PROMPT_STREAM, index, 1,
                                  length, sizes["vocab"])

    def load(ctx, _req):
        t0 = time.time()
        dev = ctx.device
        # where one worker serves seed after seed (limits.py), the seed
        # before and its reference go before this one's weights come
        program.free_the_chips(state)
        state["cfg"] = program.model_config(config)
        t1 = time.time()
        state["params"] = jax.block_until_ready(weights.make_weights(
            run["seed"], sizes, state["cfg"].param_dtype, device=dev))
        return {"device_id": int(dev.id),
                "phases": {"load_program_s": t1 - t0,
                           "load_weights_s": time.time() - t1},
                "n_params": sum(int(x.size) for x in
                                jax.tree.leaves(state["params"]))}

    def generate(ctx, req):
        from faabric_tpu.models.generate import generate as program_generate

        t0 = time.time()
        index, length = int(req["index"]), int(req["prompt_len"])
        with jax.profiler.TraceAnnotation(f"bench:request#{index}"):
            prompt = jax.device_put(prompt_ids(index, length), ctx.device)
            tokens = np.asarray(program_generate(
                state["params"], prompt, state["cfg"], n_new))
        return {"tokens": tokens[0].tolist(), "guest_start": t0,
                "guest_end": time.time()}

    def trace_start(_ctx, _req):
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        return {}

    def trace_stop(_ctx, _req):
        jax.profiler.stop_trace()
        return trace_reduce.reduce_to_file(trace_dir, cell["out_dir"])

    def stats(ctx, _req):
        mem = ctx.device.memory_stats() or {}
        return {"compiles": cell["compiles"].snapshot(),
                "memory_peak_bytes": mem.get("peak_bytes_in_use"),
                "memory_stats": mem}

    def check(ctx, req):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled requests; with ``control`` the
        same gap for the token a lower precision puts first."""
        from benchmarks.reference import transformer as ref

        program.free_the_chips(state)
        params = weights.make_weights(run["seed"], sizes, jnp.float32,
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
                logits = ref.logits_of(params, jnp.asarray(ids),
                                       sizes["rope_theta"], at=at)
                if control:
                    picked = jnp.argmax(ref.logits_of(
                        params, jnp.asarray(ids), sizes["rope_theta"],
                        control, at=at), axis=-1)
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

    ops = {"load": load, "generate": generate, "trace_start": trace_start,
           "trace_stop": trace_stop, "stats": stats, "check": check}

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
    n_new = int(traffic["new_tokens"])
    lengths = sorted({int(p["tokens"]) for p in traffic["prompt_lengths"]})

    def call(payload):
        return cluster.invoke(cell["guest"], [dict(payload, seed=args.seed)],
                              deadline, poll_s)

    def request(index: int, length: int) -> dict:
        r = call({"op": "generate", "index": index, "prompt_len": length})
        reply = r["replies"][0]
        return {"index": index, "prompt_len": length, "posted": r["posted"],
                "seen": r["seen"], "guest_start": reply["guest_start"],
                "guest_end": reply["guest_end"], "tokens": reply["tokens"]}

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
                        "posted": time.time(), "seen": time.time(),
                        "failed": repr(e), "tokens": []})
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

    # ---- correctness: a sample drawn from the seed, the longest in it ---
    done = [r for r in log if not r.get("failed")]
    n_sample = int(traffic["check"]["sample_requests"])
    rng = random.Random(int(args.seed) + 1)
    longest = max(done, key=lambda r: (r["prompt_len"], -r["index"]))
    others = [r for r in done if r is not longest]
    sample = [longest] + rng.sample(others, min(n_sample - 1, len(others)))
    bad_tokens = sum(
        1 for r in done
        if len(r["tokens"]) != n_new
        or any(not 0 <= t < cell["config_values"]["vocab_size"]
               for t in r["tokens"]))
    payload = {"op": "check", "sample": [
        {k: r[k] for k in ("index", "prompt_len", "tokens")}
        for r in sample]}
    t0 = time.time()
    checked = call(payload)["replies"][0]
    check_s = time.time() - t0
    numbers = {"served_logit_gap": checked["served_logit_gap"],
               "malformed_answers": float(bad_tokens)}
    extra = {}
    if getattr(args, "control", None):
        # the control need not decode: its answers are the program's
        lower = call(dict(payload, control=args.control))["replies"][0]
        extra["control"] = dict(
            numbers, served_logit_gap=lower["served_logit_gap"])

    return {
        "loaded": loaded,
        "setup_phases": phases,
        "window_start": window_start,
        "window_s": window_s,
        "requests": [{k: v for k, v in r.items() if k != "tokens"}
                     for r in log],
        "new_tokens": n_new,
        "attempted": len(log),
        "failed": len(log) - len(done),
        "compiles_in_window": in_window,
        "memory_peak_bytes": after["memory_peak_bytes"],
        "memory_stats": after["memory_stats"],
        "check_s": check_s,
        "numbers": numbers,
        "tokens_compared": checked["tokens_compared"],
        "trace": trace_reduce.load_reduced(trace_out.get("trace_file")),
        "planes": trace_out.get("planes"),
        **extra,
    }
