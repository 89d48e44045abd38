"""Batched extraction through one warm guest: ``guests/serve.py``'s
protocol (every request one invocation of a guest that holds the weights
on the chip the planner pinned it to, and answers with the program's
``generate()``), for requests that carry a bucket of rows as
``guests/serve_longcat.py``'s do, and for a configuration of Mamba-2
state-space layers beside grouped-query attention layers whose weights,
sizes and reference are ``weights_granite.py``, ``program_granite.py`` and
``reference/granite.py``.

- :func:`make_guest` runs in the worker. It builds the program's
  ``ModelConfig`` at once, so that a program that cannot express the
  configuration fails before it says READY. Its ops are ``serve.py``'s
  (``load``, ``generate``, ``trace_start`` / ``trace_stop``, ``stats``,
  ``check``) and ``extras``. A ``generate`` request is ``rows`` prompts of
  one length, one ``generate()`` call whose prefill goes in chunks of the
  traffic's ``prefill_chunk`` positions; its reply carries every row's
  tokens and the program's counters for the call
  (``models/generate.py:call_sizes``: ``cache_slots``, ``cache_bytes`` of
  the attention layers, ``state_bytes`` of the state-space layers,
  ``attention_layers``, ``ssm_layers``, ``scan_chunks``). ``check`` takes
  a ``control`` (a lower precision) or a ``fault`` (``state_dropped``,
  ``window_dropped``: what a broken hand-over from prefill to decoding
  would do) and then reads the gap of the token the altered reference
  puts first. ``trace_stop`` leaves, beside ``trace_reduce.py``'s
  reduction, ``trace_loops.py``'s of the decode loop (the program's one
  ``while``: prefill's chunks are unrolled) with the own time inside it
  of the state-space layers' operations other than their projections
  (``cache_s``) and of the update and read-out of S (``state_s``), and
  the time of the chunked form before it (``scan_s``).
- :func:`drive` runs in the benchmark's parent, which never imports JAX:
  ``serve.drive``'s phases (set-up, the closed-loop window, memory, check,
  trace) for requests of ``rows`` rows. ``new_tokens`` of the record is
  what a completed request generated, rows × new tokens a row.
"""

from __future__ import annotations

import json
import os
import random
import re
import shutil
import time

from benchmarks import trace_loops, trace_reduce
from benchmarks.guests.serve import PROMPT_STREAM, schedule


class Shapes:
    """Operations by rule, for ``trace_loops.reduce_loops``, which asks
    ``"<kind> <type[shape]>" in ops`` of every device event: one is in if
    its whole label is among ``labels``, or its result's type and shape
    matches ``pattern``."""

    def __init__(self, pattern: str, labels=()):
        self.pattern, self.labels = re.compile(pattern), frozenset(labels)

    def __contains__(self, label: str) -> bool:
        return label in self.labels or bool(
            self.pattern.fullmatch(label.partition(" ")[2]))

    def __bool__(self) -> bool:
        return True


def mixer_operations(sizes: dict, rows: int) -> dict:
    """The operations of a state-space layer between its two projections,
    and among them the update and read-out of S, as the optimized HLO of
    the cell's program names them (compiled for a described v5e, PR 33;
    the v5e's trace carries an instruction's name and result shape and no
    ``op_name``, so the program's ``ssm_prefill`` and ``ssm_decode`` scopes
    name them in the HLO and shapes find them in the trace).

    ``ssm``: every bfloat16, float32 or predicate result of two
    dimensions or more whose every dimension is one of the mixer's own:
    the rows, a chunk's positions, the heads, a head's lanes, the state's
    width, the convolution's channels and inner width, the window's
    length, and the 1, 2 and 3 that a squeeze or a cumulative sum's
    reduction window leave. That is the window's shift and product, dt and
    the decays with their cumulative sums, C·Bᵀ and the masked product,
    what the carried state gives, the update of S, the read-out, the
    gate. Nothing else of the program has such a shape: the projections
    carry the model's width or the in-projection's, the feed-forward its
    own, attention its 8 key/value heads, its 4 query heads a group or its
    slots, the head the vocabulary. Beside them, by whole label, the gated
    norm's statistic over a row, which is one-dimensional.
    ``state``: what touches a whole S or a slice of its rows, (n, heads,
    lanes, state) bfloat16: the one fusion that updates S and reads y out
    of it, and the copies XLA makes to stream it."""
    own = {rows, sizes["ssm_chunk"], sizes["ssm_heads"],
           sizes["ssm_head_dim"], sizes["ssm_d_state"],
           sizes["ssm_heads"] * sizes["ssm_head_dim"],
           sizes["ssm_heads"] * sizes["ssm_head_dim"]
           + 2 * sizes["ssm_groups"] * sizes["ssm_d_state"],
           sizes["ssm_d_conv"] - 1, 1, 2, 3}
    dim = "(?:" + "|".join(str(n) for n in sorted(own)) + ")"
    state = (rf"bf16\[\d+,{sizes['ssm_heads']},{sizes['ssm_head_dim']},"
             rf"{sizes['ssm_d_state']}\]")
    return {
        "ssm": Shapes(rf"(?:bf16|f32|pred)\[{dim}(?:,{dim})+\]|{state}",
                      [f"multiply_reduce_fusion f32[{rows}]",
                       f"rsqrt_convert_fusion bf16[{rows}]"]),
        "state": Shapes(state),
    }


def time_before(compact: dict, loops: dict, ops) -> list:
    """For each decode loop, the seconds of the operations ``ops`` names
    between the loop before it and its own start: prefill's entry
    computation holds no loop, so an event there is nobody's child."""
    events = sorted(compact["devices"][loops["chip"]], key=lambda e: e[1])
    out, since = [], 0
    for loop in loops["decode_loops"]:
        start = loop["start_ns"]
        out.append(sum(
            dur for name, at, dur in events if since <= at < start
            and trace_loops._kind_and_shape(name) in ops) / 1e9)
        since = start + int(loop["seconds"] * 1e9)
    return out


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

def make_guest(cell: dict):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import program, program_granite, weights_granite
    from faabric_tpu.models.generate import call_sizes
    from faabric_tpu.models.generate import generate as program_generate

    config, traffic = cell["config_values"], cell["traffic_values"]
    sizes = weights_granite.sizes_of(config)
    cfg = program_granite.model_config(config)
    run = {"seed": None}  # every request names its seed
    n_new, rows = int(traffic["new_tokens"]), int(traffic["rows"])
    chunk = int(traffic["prefill_chunk"])
    trace_dir = os.path.join(cell["out_dir"], "trace")
    state: dict = {}
    kept: dict = {"loops_file": None}

    def prompt_ids(index: int, length: int) -> np.ndarray:
        return weights_granite.token_rows(run["seed"], PROMPT_STREAM, index,
                                          rows, length, sizes["vocab"])

    def load(ctx, _req):
        t0 = time.time()
        # where one worker serves seed after seed (limits.py), the seed
        # before and its reference go before this one's weights come
        program.free_the_chips(state)
        kept.update(loops_file=None)
        state["params"] = jax.block_until_ready(weights_granite.make_weights(
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
            ops = mixer_operations(sizes, rows)
            loops = trace_loops.reduce_loops(compact, ops["ssm"])
            of_state = trace_loops.reduce_loops(compact, ops["state"])
            scans = time_before(compact, loops, ops["ssm"])
            for loop, other, scan_s in zip(loops["decode_loops"],
                                           of_state["decode_loops"], scans):
                loop.update(state_s=other["cache_s"], scan_s=scan_s)
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

    def check(ctx, req):
        """The widest gap by which a served token's logit lies below the
        reference's best, over the sampled rows of the sampled requests;
        with ``control`` or ``fault`` the same gap for the token that the
        reference in a lower precision, or with the fault planted where
        decoding took over from prefill, puts first. The reference takes
        the seed's weights as the program had them (bfloat16) and upcasts
        them a layer at a time; a request's sampled rows go through it in
        one block."""
        from benchmarks.reference import granite as ref

        program.free_the_chips(state)
        params = weights_granite.make_weights(run["seed"], sizes,
                                              cfg.param_dtype,
                                              device=ctx.device)
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
    # neither the control nor a fault need decode: their answers are the
    # program's, and the reference is asked what it would have put first
    extra = {}
    planted = [("control", {"control": args.control})] \
        if getattr(args, "control", None) else []
    planted += [(f"fault_{fault}", {"fault": fault})
                for fault in getattr(args, "faults", None) or []]
    for key, how in planted:
        altered = call(dict(payload, **how))["replies"][0]
        extra[key] = dict(numbers,
                          served_logit_gap=altered["served_logit_gap"])

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
        "per_row_gap": checked["per_row"],
        "trace": trace_reduce.load_reduced(trace_out.get("trace_file")),
        "trace_loops": trace_reduce.load_reduced(extras["loops_file"]),
        "trace_reduce_s": extras["reduce_s"],
        "planes": trace_out.get("planes"),
        **extra,
    }
