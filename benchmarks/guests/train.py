"""A train job: one invocation is the job. A gang of one message per chip;
the leader lays the program's ``mesh_from_group`` over the gang's chips and
runs the program's ``make_train_step`` (forward, backward, AdamW — one
jitted program over the mesh) on a fresh seeded batch each step; the other
ranks hold their chips in the group barrier, as ``chip_smoke.py``'s train
guest does.

- :func:`make_guest` (worker side) answers ``job`` (build the state from
  the seed, take the first steps — whose readings the correctness check
  compares — and hand the same compiled step and state to the window,
  which runs until ``seconds`` have passed) and ``check`` (the
  plain reference follows the first steps, once the program's state is
  freed; with ``control`` or ``fault`` it is put in the program's place).
- :func:`drive` (parent side, no JAX) sends them and shapes the record.

Traffic parameters: ``batch`` (sequences a step, over all replicas),
``seq``, ``tp`` (tensor-parallel ways; the rest of the gang is data
parallel), ``lr``, ``weight_decay``, ``check.steps`` (first steps the
reference follows), ``trace.skip_steps`` / ``trace.steps``.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import time

from benchmarks import trace_reduce

BATCH_STREAM = 2  # weights.token_rows stream of the batches
NEGLIGIBLE_GRADIENT = 1e-3  # of the median leaf's, by the reference


def gaps(program: dict, reference: dict) -> dict:
    """The numbers compared, from the program's readings and the
    reference's. Norms are taken by the worst leaf: the gap between the two
    norms against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Leaves whose reference gradient is under a
    thousandth of the median leaf's move under Adam by round-off alone and
    are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss{i + 1}_gap"] = abs(a - b) / abs(b)

    def worst(prog, ref, keep):
        floor = statistics.median(ref)
        return max(abs(p - r) / max(r, floor)
                   for p, r, k in zip(prog, ref, keep) if k)

    g_ref = reference["grad_norms"]
    everything = [True] * len(g_ref)
    out["grad_norm_gap"] = worst(program["grad_norms"], g_ref, everything)
    moved = [g >= NEGLIGIBLE_GRADIENT * statistics.median(g_ref)
             for g in g_ref]
    out["change_norm_gap"] = worst(program["change_norms"],
                                   reference["change_norms"], moved)
    out["leaves_left_out"] = float(len(moved) - sum(moved))
    return out


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
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    lr, decay = float(traffic["lr"]), float(traffic["weight_decay"])
    check_steps = int(traffic["check"]["steps"])
    trace_dir = os.path.join(cell["out_dir"], "trace")
    state: dict = {}

    def rows(step: int):
        ids = weights.token_rows(run["seed"], BATCH_STREAM, step, batch,
                                 seq + 1, sizes["vocab"])
        return ids[:, :-1], ids[:, 1:]

    def job(ctx, req):
        msg = ctx.message
        ctx.broker.wait_for_mappings(msg.group_id)
        group = ctx.broker.get_group(msg.group_id)
        if msg.group_idx != 0:
            group.barrier(msg.group_idx)
            return {"rank": msg.group_idx, "device_id": ctx.device_id}
        try:
            return leader(ctx, req, ctx.request.n_messages())
        finally:
            group.barrier(0)

    def leader(ctx, req, n):
        from faabric_tpu.models import (
            data_sharding,
            make_optimizer,
            make_train_step,
            param_shardings,
        )
        from faabric_tpu.parallel import MeshConfig
        from faabric_tpu.parallel.mesh import mesh_from_group

        from benchmarks.reference import transformer as ref

        msg = ctx.message
        t_job = time.time()
        mesh = mesh_from_group(ctx.broker, msg.group_id, range(n),
                               MeshConfig(tp=int(traffic["tp"])))
        devices = list(mesh.devices.reshape(-1))
        cfg = program.model_config(config)
        shardings = param_shardings(mesh, cfg)
        opt = make_optimizer(lr=lr, weight_decay=decay)
        params = weights.make_weights(run["seed"], sizes, cfg.param_dtype,
                                      out_shardings=shardings)
        # Not under jit: XLA would put the moments, which depend on no
        # input, whole on the first chip; made leaf by leaf they lie like
        # their parameters
        opt_state = jax.block_until_ready(opt.init(params))
        phases = {"state_s": time.time() - t_job}
        step = make_train_step(cfg, mesh, opt)
        feed = data_sharding(mesh)

        def put(k):
            tokens, targets = rows(k)
            return jax.device_put(tokens, feed), jax.device_put(targets, feed)

        def adam_mu(tree):
            found = [s.mu for s in jax.tree.leaves(
                tree, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
            if len(found) != 1:
                raise RuntimeError(f"{len(found)} Adam states in the "
                                   "optimizer's state")
            return found[0]

        for moment, param in zip(jax.tree.leaves(adam_mu(opt_state)),
                                 jax.tree.leaves(params)):
            if not moment.sharding.is_equivalent_to(param.sharding,
                                                    param.ndim):
                raise RuntimeError(
                    f"a moment lies as {moment.sharding}, its parameter as "
                    f"{param.sharding}: the step would reshard it")

        # ---- the first steps, through the window's own call and feed ----
        losses, grad_norms = [], None
        nxt = put(0)
        for k in range(check_steps):
            params, opt_state, loss = step(params, opt_state, *nxt)
            nxt = put(k + 1)
            losses.append(float(loss))
            phases[f"step{k + 1}_s"] = time.time() - t_job
            if k == 0:
                # Adam's first moment after one step is (1 − b1)·g
                grad_norms = [float(x) / (1.0 - ref.ADAM_B1)
                              for x in ref.leaf_norms(adam_mu(opt_state))]
        change_norms = [float(x) for x in weights.distance_from_seed(
            run["seed"], sizes, params)]
        phases["readings_s"] = time.time() - t_job
        readings = {"losses": losses, "grad_norms": grad_norms,
                    "change_norms": change_norms}

        # ---- the window: the same step, the same state -------------------
        seconds = float(req["seconds"])
        skip, traced = (int(traffic["trace"]["skip_steps"]),
                        int(traffic["trace"]["steps"]))
        tracing = False
        before = cell["compiles"].snapshot()
        steps = []
        k = check_steps
        t_start = time.time()
        while time.time() - t_start < seconds:
            done = k - check_steps
            if req["trace"] and done == skip:
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(trace_dir)
                tracing = True
            t0 = time.time()
            with jax.profiler.TraceAnnotation(f"bench:step#{k}"):
                params, opt_state, loss = step(params, opt_state, *nxt)
                # the next batch is made while this step runs
                nxt = put(k + 1)
                loss = float(loss)  # waits for the device
            steps.append({"step": k, "start": t0, "end": time.time(),
                          "loss": loss})
            k += 1
            if tracing and k - check_steps == skip + traced:
                jax.profiler.stop_trace()
                tracing = False
        t_end = steps[-1]["end"]
        if tracing:
            jax.profiler.stop_trace()
        after = cell["compiles"].snapshot()
        memory = [d.memory_stats() or {} for d in devices]
        peak = [m.get("peak_bytes_in_use") for m in memory]
        state.update(params=params, opt_state=opt_state, step=step)
        traced = (trace_reduce.reduce_to_file(trace_dir, cell["out_dir"])
                  if req["trace"] else {"trace_file": None, "planes": None})
        return {
            "rank": 0,
            "mesh": {a: int(s) for a, s in mesh.shape.items() if s > 1},
            "device_ids": [int(d.id) for d in devices],
            "n_params": sum(int(x.size) for x in jax.tree.leaves(params)),
            "readings": readings, "setup_phases": phases,
            "window_start": t_start,
            "window_s": t_end - t_start, "steps": steps,
            "tokens_per_step": batch * seq,
            "compiles_in_window": {c: after[c] - before[c] for c in before},
            "memory_peak_bytes": max(p for p in peak if p is not None)
            if any(p is not None for p in peak) else None,
            "memory_stats": memory,
            **traced,
        }

    def check(ctx, req):
        """The reference through the same first steps: float32, its own
        AdamW, the same weights and batches from the seed. ``control``
        computes it one precision lower; ``fault`` plants one of the faults
        a step can have: ``state_unchanged``, ``half_batch`` (half of the
        rows left out, the mean over the rest). Runs on the caller's chip,
        once the program's state is freed."""
        from benchmarks.reference import transformer as ref

        program.free_the_chips(state)
        precision = req.get("control") or "float32"
        fault = req.get("fault")
        kept = {"half_batch": range(batch // 2)}
        with jax.default_device(ctx.device):
            params = weights.make_weights(run["seed"], sizes, jnp.float32,
                                          device=ctx.device)
            adam = ref.adamw_init(params)
            losses, grad_norms = [], None
            for k in range(check_steps):
                tokens, targets = (jnp.asarray(x) for x in rows(k))
                loss, grads = ref.loss_and_grads(
                    params, tokens, targets, sizes["rope_theta"], precision,
                    rows=kept.get(fault))
                losses.append(float(loss))
                if k == 0:
                    grad_norms = [float(x) for x in ref.leaf_norms(grads)]
                if fault != "state_unchanged":
                    params, adam = ref.adamw_update(params, adam, grads, lr,
                                                    decay)
                del grads
            del adam
            change = [float(x) for x in weights.distance_from_seed(
                run["seed"], sizes, params)]
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": change}

    ops = {"job": job, "check": check}

    def guest(ctx):
        req = json.loads(ctx.message.input_data)
        run["seed"] = int(req["seed"])
        return json.dumps(ops[req["op"]](ctx, req)).encode()

    return guest


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------

def drive(cluster, cell: dict, args, deadline: float) -> dict:
    poll_s = 0.05
    n = int(cell["chips"])
    payload = {"op": "job", "seed": args.seed, "seconds": args.seconds,
               "trace": bool(args.trace)}
    replies = cluster.invoke(cell["guest"], [payload] * n, deadline,
                             poll_s)["replies"]
    lead = next(r for r in replies if r["rank"] == 0)
    pinned = {r["device_id"] for r in replies if r["rank"] != 0}
    if len(pinned) != n - 1 or len(set(lead["device_ids"])) != n:
        raise RuntimeError(f"a gang of {n} ran on chips "
                           f"{lead['device_ids']} / {sorted(pinned)}")

    def check(**how):
        return cluster.invoke(
            cell["guest"], [dict(op="check", seed=args.seed, **how)],
            deadline, poll_s)["replies"][0]

    t0 = time.time()
    reference = check()
    check_s = time.time() - t0
    numbers = gaps(lead["readings"], reference)
    bad = [s for s in lead["steps"] if s["loss"] != s["loss"]
           or abs(s["loss"]) == float("inf")]
    extra = {}
    if getattr(args, "control", None):
        extra["control"] = gaps(check(control=args.control), reference)
    for fault in getattr(args, "faults", None) or []:
        extra[f"fault_{fault}"] = gaps(check(fault=fault), reference)
    return {
        "loaded": {"n_params": lead["n_params"], "mesh": lead["mesh"],
                   "device_ids": lead["device_ids"]},
        "setup_phases": lead["setup_phases"],
        "window_start": lead["window_start"],
        "window_s": lead["window_s"],
        "steps": lead["steps"],
        "tokens_per_step": lead["tokens_per_step"],
        "attempted": len(lead["steps"]),
        "failed": len(bad),
        "compiles_in_window": lead["compiles_in_window"],
        "memory_peak_bytes": lead["memory_peak_bytes"],
        "memory_stats": lead["memory_stats"],
        "check_s": check_s,
        "numbers": numbers,
        "readings": {"program": lead["readings"], "reference": reference},
        "trace": trace_reduce.load_reduced(lead["trace_file"]),
        "planes": lead["planes"],
        **extra,
    }
