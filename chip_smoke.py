#!/usr/bin/env python3
"""First proof that the system starts on the chip: invocation → planner →
executor → jitted train / decode at flagship width.

    python chip_smoke.py              # on a machine with TPU chips
    python chip_smoke.py --rehearse   # toy width, any backend: debugging only

The parent never initialises a JAX backend (a chip belongs to one
process). It starts the planner (``python -m faabric_tpu.runner planner``)
and ONE worker (this file with ``--worker``) that embeds a
``WorkerRuntime`` over every local chip and registers the guests below,
then drives everything over the planner's REST endpoint
(EXECUTE_BATCH / EXECUTE_BATCH_STATUS) and reads each guest's JSON report
from ``messageResults[*].output_data``.

Guests (user ``smoke``), all on the chips the planner pinned:

- ``kernels`` — Pallas flash attention fwd and fwd+bwd, and the fused RMS
  norm, against float32 ``jnp`` references at the shapes the model runs
  and, for flash, the benchmark's (4 × 2048 and 1 × 8192 at 16 heads of
  128), and a compile of both directions at S = 16384; the gated
  feed-forward kernel of a cached step (ops/gated_ffn.py) at the widths
  of ``benchmarks/configs/granite-4.0-h-micro.json``, 64 rows, against
  the float32 ``jnp`` lines; the cached step's attention over a dense
  cache (ops/cached_attention.py) at the same file's heads, 64 rows over
  640 slots, against the block's own lines in float32; a prefill chunk's
  Mamba-1 recurrence with S kept on the chip (ops/selective_scan.py) at
  the widths of ``benchmarks/configs/phi-4-mini-flash-reasoning.json``,
  64 rows × 256 positions from a carried state, against ``mixer1``'s own
  ``lax.scan`` lines;
  plus the whole forward (one layer, full width) with the kernels against
  the same forward with the ``jnp`` impls.
- ``train``   — a gang of one rank per chip; the leader lays the mesh over
  the gang's chips (1 chip: dp1; 4 chips: dp2×tp2) and takes TRAIN_STEPS
  steps of ``make_train_step`` on a fixed batch.
- ``decode``  — ``generate()`` answering three requests (greedy, the same
  greedy again, sampled): the only path on which the fused norm runs.
- ``latent_experts`` — the other kinds of block (latent attention in a
  shortcut-connected double layer with a held share of the experts) at
  the widths of ``benchmarks/configs/longcat-flash-omni.json``, one layer:
  prefill of a few rows and two cached steps over the latent caches,
  logits against ``benchmarks/reference/longcat.py``, so that a broken
  lowering shows before a 40 s window of the benchmark does.
- ``state_space`` — layers of two kinds in one model (a Mamba-2
  state-space layer and a grouped-query attention layer without rotary,
  the multipliers, a tied head) at the widths of
  ``benchmarks/configs/granite-4.0-h-micro.json``, one layer of each kind:
  prefill over a chunk and a quarter (the chunked form, its carry and a
  last chunk that is not full), then two cached steps (the recurrence,
  grouped heads over the cache), logits against
  ``benchmarks/reference/granite.py``.
- ``shared_state`` — layers that lend and borrow state (Mamba-1, windowed
  and full differential attention, a gated memory unit and a cross
  attention on the full layer's cache; LayerNorm, biases, a tied head) at
  the widths of ``benchmarks/configs/phi-4-mini-flash-reasoning.json``,
  the shallowest stack with every kind, 64 rows: prefill in two chunks
  whose second wraps the rings and starts the selective-scan kernel from
  the S the first left, the cross-decoder at the last position
  alone, then two cached steps through the cached-attention kernel in its
  differential, ring and read-only forms, logits of four rows against
  ``benchmarks/reference/phi4flash.py``.
- ``long_latent`` — a feed-forward kind a layer (a dense layer, then an
  expert layer with a shared expert under a sigmoid router) behind latent
  attention under YaRN at the widths of ``benchmarks/configs/a.x-k1.json``,
  the two leading layers, 8 rows: a prompt of 8,192 in chunks of 1,024
  (each expands its reach's keys and values and attends them through
  ops/latent_attention.py, the last a reach of 8,192 two rows at a time;
  the rehearsal's toy lanes keep the ``jnp`` lines in blocks), then
  two cached steps over the latent caches with the dense feed-forward
  and the shared expert through the streaming kernel, logits of two rows
  against ``benchmarks/reference/axk1.py``.
- ``gang``    — with ≥ 2 chips: an MPI world through ``ctx.mpi_world()``,
  one rank per chip, collectives on device-resident arrays through the
  activated device plane, and the Pallas ring-permute kernel.

Any failed guest, wrong platform, tripped fallback or non-zero child exit
makes the script exit non-zero without printing a result. Speed is not
what this measures: the seconds it reports are informational.

Exit codes: 0 passed on the chip; 1 a phase failed; 3 JAX found no TPU.
The full report is also written to ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import dataclasses
import importlib.metadata
import json
import math
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# The smoke's full width:
# ~134 M parameters, fp32 params + AdamW state ≈ 1.6 GB, bf16 compute.
LARGE = dict(vocab_size=16384, d_model=1024, n_layers=8, n_heads=16,
             d_ff=4096, max_seq=1024)
LARGE_RUN = dict(seq=1024, batch_per_chip=8, train_steps=8, prompt=128,
                 new_tokens=64)
# --rehearse: the same flow at a width a CPU finishes in a minute
TINY = dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4, d_ff=256,
            max_seq=256)
TINY_RUN = dict(seq=128, batch_per_chip=2, train_steps=6, prompt=32,
                new_tokens=8)

# bf16 tolerances, as relative Frobenius error ‖kernel − ref‖ / ‖ref‖
# against a float32 reference computed at "highest" matmul precision.
# One bf16 rounding is 2⁻⁹ ≈ 0.2 %; the kernels round the probabilities
# and the output (forward), and additionally dS (backward). The first
# v5e run measured 0.0020 / 0.0028 / 0.0017 and, for the logits of the
# one-layer model against its jnp impls (both bf16), 0.011: each bound
# leaves about a factor of four.
TOL_FLASH_FWD = 8e-3
TOL_FLASH_BWD = 1.2e-2
TOL_RMS_NORM = 8e-3
# The activation and the output are rounded to bfloat16 once each
TOL_GATED_FFN = 8e-3
# The probabilities and the output are rounded to bfloat16 once each
TOL_CACHED_ATTENTION = 8e-3
# Against ``mixer1``'s own lines at the same rounding points: another
# exponential's last bit and another order of the sum over the state
# move ``y`` by a bfloat16 rounding (2⁻⁹) at most; its first run on the
# v5e read 0 (my chip run, PR 40)
TOL_SELECTIVE_SCAN = 2e-3
TOL_MODEL_LOGITS = 4e-2
# One double layer in bfloat16 against the float32 reference: its first run
# on the v5e measured 0.012 (my chip run, PR 31); four layers read 0.02.
TOL_LATENT_LOGITS = 4e-2
LATENT_CONFIG = os.path.join(REPO, "benchmarks", "configs",
                             "longcat-flash-omni.json")
LATENT_CONFIG_TINY = os.path.join(REPO, "tests", "bench", "data", "configs",
                                  "toy_longcat.json")

# One layer of each kind in bfloat16 against the float32 reference: its
# first run on the v5e measured 0.0082 and 0.0084 (my chip run, PR 33).
TOL_STATE_SPACE_LOGITS = 4e-2
HYBRID_CONFIG = os.path.join(REPO, "benchmarks", "configs",
                             "granite-4.0-h-micro.json")
HYBRID_CONFIG_TINY = os.path.join(REPO, "tests", "bench", "data", "configs",
                                  "toy_granite.json")

# One layer of each kind of a decoder-hybrid-decoder in bfloat16 against
# the float32 reference, the same measure and room as the two above.
TOL_SHARED_STATE_LOGITS = 4e-2
SHARED_CONFIG = os.path.join(REPO, "benchmarks", "configs",
                             "phi-4-mini-flash-reasoning.json")
SHARED_CONFIG_TINY = os.path.join(REPO, "tests", "bench", "data", "configs",
                                  "toy_phi4flash.json")

# The dense layer and one expert layer in bfloat16 against the float32
# reference at a reach of 8,192, the same measure and room as the three
# above.
TOL_LONG_LATENT_LOGITS = 4e-2
LONG_CONFIG = os.path.join(REPO, "benchmarks", "configs", "a.x-k1.json")
LONG_CONFIG_TINY = os.path.join(REPO, "tests", "bench", "data", "configs",
                                "toy_axk1.json")

PLANNER_HOST = "smoke-planner"
WORKER_HOST = "smoke-worker"
DEADLINE_S = 1100  # the contract allows 1200 s, compilation included

EXIT_FAILED = 1
EXIT_NO_TPU = 3


# ---------------------------------------------------------------------------
# Worker side: the one process that touches JAX
# ---------------------------------------------------------------------------

def _rel_err(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class _CompileCounter:
    """Counts XLA compile requests and persistent-cache hits/writes through
    jax.monitoring (every jit cache miss that reaches the backend is one
    request, whether the persistent cache then answers it or not)."""

    def __init__(self) -> None:
        import jax

        self.requests = 0
        self.cache_hits = 0
        self.cache_writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}


def _kernel_calls(lowered_text: str) -> dict:
    """How many Pallas kernel calls a lowered program holds, by kernel
    name (the ``name=`` each pallas_call in ops/ gives)."""
    names = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rms_norm")
    return {n: lowered_text.count(f'kernel_name = "{n}"') for n in names}


def _device_report(device) -> dict:
    return {"platform": device.platform, "kind": device.device_kind,
            "id": int(device.id)}


def _register_guests(model: dict, run: dict, on_chip: bool,
                     compiles: _CompileCounter) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from faabric_tpu.executor import register_function
    from faabric_tpu.models import (
        ModelConfig,
        data_sharding,
        init_train_state,
        make_optimizer,
        make_train_step,
    )
    from faabric_tpu.models.generate import generate
    from faabric_tpu.models.transformer import (
        forward,
        init_params,
        resolve_impls,
    )
    from faabric_tpu.ops.flash_attention import (
        _reference_attention,
        flash_attention,
        uses_kernel as flash_uses_kernel,
    )
    from faabric_tpu.ops.cached_attention import (
        cached_attention,
        plan as attention_plan,
    )
    from faabric_tpu.ops.gated_ffn import gated_ffn, plan as ffn_plan
    from faabric_tpu.ops.selective_scan import (
        plan as scan_plan,
        selective_scan,
    )
    from faabric_tpu.ops.rms_norm import (
        _reference_rms_norm,
        rms_norm,
        uses_kernel as norm_uses_kernel,
    )

    cfg = ModelConfig(attention_impl="auto", norm_impl="auto", **model)
    seq = run["seq"]

    def reply(**fields) -> bytes:
        return json.dumps(fields).encode()

    # ---- kernels -----------------------------------------------------
    @register_function("smoke", "kernels")
    def kernels(ctx):
        dev = ctx.device
        out = {"device": _device_report(dev), "on_kernel_path": {}}
        rng = np.random.RandomState(0)
        b, h, d = run["batch_per_chip"], cfg.n_heads, cfg.head_dim

        def ref_attn(q, k, v):
            with jax.default_matmul_precision("highest"):
                return _reference_attention(
                    *(t.astype(jnp.float32) for t in (q, k, v)), True)

        def loss_of(attn):
            return lambda q, k, v, g: jnp.sum(
                attn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

        def causal_flash(q, k, v):
            return flash_attention(q, k, v, True)

        flash = jax.jit(causal_flash)
        flash_grads = jax.jit(jax.grad(loss_of(causal_flash),
                                       argnums=(0, 1, 2)))
        ref = jax.jit(ref_attn)
        ref_grads = jax.jit(jax.grad(loss_of(ref_attn), argnums=(0, 1, 2)))

        def flash_parity(shape) -> dict:
            """Forward and gradients at ``shape`` against the float32
            reference, which goes two heads at a time (heads do not mix,
            and its (S, S) logits of all heads at once would not fit at
            S = 8192)."""
            q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                          for _ in range(4))
            if on_chip:
                _require(flash_uses_kernel(q.shape, k.shape),
                         f"flash takes the reference at {q.shape}")
            got = (flash(q, k, v), *flash_grads(q, k, v, g))
            gap = np.zeros(4)
            norm = np.zeros(4)
            for h0 in range(0, shape[2], 2):
                some = [t[:, :, h0:h0 + 2] for t in (q, k, v, g)]
                want = (ref(*some[:3]), *ref_grads(*some))
                for i, (a, r) in enumerate(zip(got, want)):
                    a = np.asarray(a[:, :, h0:h0 + 2], np.float32)
                    r = np.asarray(r, np.float32)
                    gap[i] += np.sum((a - r) ** 2)
                    norm[i] += np.sum(r ** 2)
            err = np.sqrt(gap / np.maximum(norm, 1e-30))
            return {"fwd": float(err[0]), "bwd": float(max(err[1:]))}

        with jax.default_device(dev):
            # the model's own call, then the benchmark's: train_2k_1chip's
            # and a long one (tests: toy sizes of as many blocks)
            shapes = [(b, seq, h, d)] + (
                [(4, 2048, 16, 128), (1, 8192, 16, 128)] if on_chip
                else [(1, 4 * seq, h, d)])
            out["flash_shapes"] = {
                "x".join(map(str, shape)): flash_parity(shape)
                for shape in shapes}
            out["flash_fwd_rel_err"] = max(
                e["fwd"] for e in out["flash_shapes"].values())
            out["flash_bwd_rel_err"] = max(
                e["bwd"] for e in out["flash_shapes"].values())
            out["on_kernel_path"]["flash"] = flash_uses_kernel(
                (b, seq, h, d), (b, seq, h, d))
            # No block of the kernels is as long as the sequence, so a
            # long one compiles (forward and backward; nothing runs)
            long = jax.ShapeDtypeStruct(
                (1, 16384, 16, 128) if on_chip else (1, 16 * seq, h, d),
                jnp.bfloat16)
            flash.lower(long, long, long).compile()
            flash_grads.lower(long, long, long, long).compile()
            out["flash_long_compiled"] = list(long.shape)

            scale = jnp.asarray(1.0 + 0.1 * rng.randn(cfg.d_model),
                                jnp.float32)
            errs = []
            # the decode prefill's rows and the train step's
            for shape in ((1, run["prompt"], cfg.d_model),
                          (b, seq, cfg.d_model)):
                x = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                if on_chip:
                    _require(norm_uses_kernel(x.shape),
                             f"rms_norm takes the reference at {x.shape}")
                errs.append(_rel_err(
                    jax.jit(rms_norm)(x, scale),
                    _reference_rms_norm(x.astype(jnp.float32), scale)))
            out["rms_norm_rel_err"] = max(errs)
            out["on_kernel_path"]["rms_norm"] = norm_uses_kernel(
                (1, run["prompt"], cfg.d_model))

            # The feed-forward of a cached step at the widths of the
            # state-space cell (tests: its toy's), against the jnp lines
            with open(HYBRID_CONFIG if on_chip else HYBRID_CONFIG_TINY) as f:
                widths = json.load(f)
            d_model = int(widths["hidden_size"])
            d_ff = int(widths["shared_intermediate_size"])
            rows = 64 if on_chip else 8
            out["gated_ffn_plan"] = ffn_plan(rows, d_model, d_ff,
                                             jnp.bfloat16)
            out["on_kernel_path"]["gated_ffn"] = \
                out["gated_ffn_plan"] is not None
            _require(out["on_kernel_path"]["gated_ffn"],
                     f"gated_ffn refuses {rows} rows of {d_model} × {d_ff}")
            h, wg, w1, w2 = (
                jnp.asarray(rng.randn(*shape) / math.sqrt(fan_in),
                            jnp.bfloat16)
                for shape, fan_in in (((rows, d_model), 1),
                                      ((d_model, d_ff), d_model),
                                      ((d_model, d_ff), d_model),
                                      ((d_ff, d_model), d_ff)))

            def ref_ffn(h, wg, w1, w2):
                with jax.default_matmul_precision("highest"):
                    h, wg, w1, w2 = (t.astype(jnp.float32)
                                     for t in (h, wg, w1, w2))
                    return (jax.nn.silu(h @ wg) * (h @ w1)) @ w2

            out["gated_ffn_rel_err"] = _rel_err(
                jax.jit(gated_ffn)(h, wg, w1, w2),
                jax.jit(ref_ffn)(h, wg, w1, w2))

            # That cell's cached attention over a dense cache (tests: a
            # toy's), unwritten slots holding NaN, against the block's
            # own lines in float32 over the same values head-major
            heads, kv = ((int(widths["num_attention_heads"]),
                          int(widths["num_key_value_heads"]))
                         if on_chip else (4, 2))
            e, slots = (d_model // heads, 640) if on_chip else (64, 128)
            reach = slots - 40
            out["cached_attention_plan"] = attention_plan(
                rows, heads, kv, slots, e, jnp.bfloat16)
            out["on_kernel_path"]["cached_attention"] = \
                out["cached_attention_plan"] is not None
            _require(out["on_kernel_path"]["cached_attention"],
                     f"cached_attention refuses {rows} rows of {heads} on "
                     f"{kv} × {e} over {slots}")
            q1 = jnp.asarray(rng.randn(rows, heads, e), jnp.bfloat16)
            unwritten = (np.arange(slots) >= reach)[None, None, :, None]
            dense = [jnp.asarray(np.where(
                unwritten, np.nan, rng.randn(1, rows, slots, kv * e)),
                jnp.bfloat16) for _ in range(2)]

            def ref_cached(q1, keys, values):
                from faabric_tpu.models.transformer import _cached_attention

                with jax.default_matmul_precision("highest"):
                    head_major = [
                        c[0].reshape(rows, slots, kv, e).transpose(
                            0, 2, 1, 3).astype(jnp.float32)
                        for c in (keys, values)]
                    return _cached_attention(
                        q1[:, None].astype(jnp.float32), *head_major,
                        reach, 1.0 / e)[:, 0]

            out["cached_attention_rel_err"] = _rel_err(
                jax.jit(lambda q1, keys, values: cached_attention(
                    q1, keys, values, jnp.int32(reach), 1.0 / e))(
                    q1, *dense),
                jax.jit(ref_cached)(q1, *dense))

            # A prefill chunk's recurrence at the widths of the cell
            # whose layers lend and borrow state (tests: its toy's), from
            # a carried state, against ``mixer1``'s own lines
            from benchmarks import program_phi4flash
            from faabric_tpu.models.ssm import _scan1

            with open(SHARED_CONFIG if on_chip else SHARED_CONFIG_TINY) as f:
                kinds = program_phi4flash.model_config(json.load(f))
            lanes, n = kinds.ssm_inner, kinds.ssm_d_state
            length = 256 if on_chip else 24
            out["selective_scan_plan"] = scan_plan(rows, length, lanes, n,
                                                   jnp.bfloat16)
            out["on_kernel_path"]["selective_scan"] = \
                out["selective_scan_plan"] is not None
            _require(out["on_kernel_path"]["selective_scan"],
                     f"selective_scan refuses {rows} rows of {length} × "
                     f"{lanes} with a state of {n}")
            x, b_in, c_in, s0 = (
                jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                for shape in ((rows, length, lanes), (rows, length, n),
                              (rows, length, n), (rows, n, lanes)))
            dt = jax.nn.softplus(jnp.asarray(
                rng.randn(rows, length, lanes) - 2, jnp.float32))
            a = -jnp.exp(jnp.asarray(rng.randn(n, lanes), jnp.float32))
            d_skip = jnp.asarray(rng.randn(lanes), jnp.float32)

            def ref_scan(x, dt, b_in, c_in, s0):
                state, y = _scan1(s0.astype(jnp.float32), x, b_in, c_in, dt,
                                  a)
                return (y + d_skip * x.astype(jnp.float32)).astype(x.dtype), \
                    state

            got = jax.jit(lambda *call: selective_scan(
                *call[:4], a, d_skip, call[4]))(x, dt, b_in, c_in, s0)
            want = jax.jit(ref_scan)(x, dt, b_in, c_in, s0)
            out["selective_scan_rel_err"] = max(
                _rel_err(got[0], want[0]), _rel_err(got[1], want[1]))

            # The whole forward at full width, depth cut to one layer:
            # kernels against the jnp impls on the same weights
            one = dataclasses.replace(
                cfg, n_layers=1,
                attention_impl="flash" if on_chip else "reference",
                norm_impl="fused" if on_chip else "reference")
            plain = dataclasses.replace(one, attention_impl="reference",
                                        norm_impl="reference")
            params = init_params(jax.random.PRNGKey(3), one)
            tokens = jnp.asarray(
                rng.randint(0, cfg.vocab_size, (2, seq)), jnp.int32)
            with_kernels = jax.jit(lambda p, t: forward(p, t, one))
            out["model_kernel_calls"] = _kernel_calls(
                with_kernels.lower(params, tokens).as_text())
            out["model_logits_rel_err"] = _rel_err(
                with_kernels(params, tokens),
                jax.jit(lambda p, t: forward(p, t, plain))(params, tokens))

        _require(out["flash_fwd_rel_err"] <= TOL_FLASH_FWD, f"flash fwd {out}")
        _require(out["flash_bwd_rel_err"] <= TOL_FLASH_BWD, f"flash bwd {out}")
        _require(out["rms_norm_rel_err"] <= TOL_RMS_NORM, f"rms_norm {out}")
        _require(out["gated_ffn_rel_err"] <= TOL_GATED_FFN,
                 f"gated_ffn {out}")
        _require(out["cached_attention_rel_err"] <= TOL_CACHED_ATTENTION,
                 f"cached_attention {out}")
        _require(out["selective_scan_rel_err"] <= TOL_SELECTIVE_SCAN,
                 f"selective_scan {out}")
        _require(out["model_logits_rel_err"] <= TOL_MODEL_LOGITS,
                 f"model logits {out}")
        if on_chip:
            calls = out["model_kernel_calls"]
            # one attention; the two block norms and the final one
            _require(calls["flash_fwd"] >= 1 and calls["rms_norm"] >= 3,
                     f"one-layer forward holds kernel calls {calls}")
        return reply(**out)

    # ---- train -------------------------------------------------------
    @register_function("smoke", "train")
    def train(ctx):
        from faabric_tpu.parallel import MeshConfig
        from faabric_tpu.parallel.mesh import mesh_from_group

        msg, n = ctx.message, ctx.request.n_messages()
        ctx.broker.wait_for_mappings(msg.group_id)
        group = ctx.broker.get_group(msg.group_id)
        if msg.group_idx != 0:
            # The SPMD program is driven by the gang's leader; the other
            # ranks hold their chips' claims until it is done
            group.barrier(msg.group_idx)
            return reply(rank=msg.group_idx, device_id=ctx.device_id)
        try:
            mesh = mesh_from_group(ctx.broker, msg.group_id, range(n),
                                   MeshConfig(tp=2 if n % 2 == 0 else 1))
            devices = list(mesh.devices.reshape(-1))
            resolved = resolve_impls(cfg, mesh)
            opt = make_optimizer()
            params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg,
                                                 mesh, opt)
            batch = run["batch_per_chip"] * n
            rng = np.random.RandomState(0)
            tokens, targets = (jax.device_put(
                rng.randint(0, cfg.vocab_size, (batch, seq), dtype=np.int32),
                data_sharding(mesh)) for _ in range(2))
            step = make_train_step(cfg, mesh, opt)

            t0 = time.perf_counter()
            lowered = step.lower(params, opt_state, tokens, targets)
            calls = _kernel_calls(lowered.as_text())
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0

            losses, step_s = [], []
            for _ in range(run["train_steps"]):
                t0 = time.perf_counter()
                params, opt_state, loss = compiled(params, opt_state,
                                                   tokens, targets)
                losses.append(float(loss))  # waits for the device
                step_s.append(time.perf_counter() - t0)
            placed_on = sorted({int(d.id) for leaf in jax.tree.leaves(params)
                                for d in leaf.devices()})
            peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in devices]
        finally:
            group.barrier(0)

        out = dict(
            rank=0, mesh={k: v for k, v in mesh.shape.items() if v > 1},
            devices=[_device_report(d) for d in devices],
            params_on_device_ids=placed_on,
            attention_impl=resolved.attention_impl,
            norm_impl=resolved.norm_impl, kernel_calls=calls,
            n_params=sum(int(x.size) for x in jax.tree.leaves(params)),
            batch=batch, seq=seq, losses=losses,
            compile_s=round(compile_s, 2),
            steady_step_s=round(sorted(step_s[1:])[len(step_s[1:]) // 2], 4),
            peak_bytes_in_use=peak)
        _require(len({d["id"] for d in out["devices"]}) == n,
                 f"gang of {n} got chips {out['devices']}")
        _require(placed_on == sorted(d["id"] for d in out["devices"]),
                 f"params live on {placed_on}")
        _require(all(math.isfinite(x) for x in losses), f"losses {losses}")
        _require(losses[-1] < losses[0], f"loss did not fall: {losses}")
        # A random init predicts near-uniformly: the first loss is ln(V)
        # up to the logits' unit variance
        _require(abs(losses[0] - math.log(cfg.vocab_size)) < 1.5,
                 f"first loss {losses[0]} vs ln V")
        if on_chip:
            _require(all(d["platform"] == "tpu" for d in out["devices"]),
                     str(out["devices"]))
            _require(resolved.attention_impl == "flash",
                     f"attention resolved {resolved.attention_impl}")
            _require(min(calls[k] for k in ("flash_fwd", "flash_bwd_dq",
                                            "flash_bwd_dkv")) >= cfg.n_layers,
                     f"train step holds kernel calls {calls}")
        return reply(**out)

    # ---- decode ------------------------------------------------------
    @register_function("smoke", "decode")
    def decode(ctx):
        dev = ctx.device
        n_new, s_p = run["new_tokens"], run["prompt"]
        resolved = resolve_impls(cfg)
        rng = np.random.RandomState(1)
        with jax.default_device(dev):
            params = init_params(jax.random.PRNGKey(1), cfg)
            prompts = [jnp.asarray(rng.randint(0, cfg.vocab_size, (1, s_p)),
                                   jnp.int32) for _ in range(2)]
            calls = _kernel_calls(jax.jit(
                lambda p, t: generate(p, t, cfg, n_new)
            ).lower(params, prompts[0]).as_text())
            requests = [
                dict(prompt=prompts[0]),
                dict(prompt=prompts[0]),
                dict(prompt=prompts[1], key=jax.random.PRNGKey(2),
                     temperature=0.8, top_k=40),
            ]
            answers, wall_s, compiled = [], [], []
            for r in requests:
                before = compiles.requests
                t0 = time.perf_counter()
                toks = np.asarray(generate(params, r.pop("prompt"), cfg,
                                           n_new, **r))
                wall_s.append(round(time.perf_counter() - t0, 3))
                compiled.append(compiles.requests - before)
                answers.append(toks)
            on = sorted({int(d.id) for leaf in jax.tree.leaves(params)
                         for d in leaf.devices()})
        out = dict(
            device=_device_report(dev), params_on_device_ids=on,
            attention_impl=resolved.attention_impl,
            norm_impl=resolved.norm_impl, kernel_calls=calls,
            fused_norm_at_prefill=norm_uses_kernel((1, s_p, cfg.d_model)),
            fused_norm_at_decode_step=norm_uses_kernel((1, 1, cfg.d_model)),
            request_wall_s=wall_s, request_compiles=compiled,
            greedy_tokens=answers[0][0, :8].tolist(),
            sampled_tokens=answers[2][0, :8].tolist())
        _require(on == [int(dev.id)], f"params live on {on}, pinned {dev.id}")
        for toks in answers:
            _require(toks.shape == (1, n_new), f"tokens shape {toks.shape}")
            _require(((toks >= 0) & (toks < cfg.vocab_size)).all(),
                     "token out of the vocabulary")
        _require((answers[0] == answers[1]).all(),
                 "repeated greedy request answered differently")
        _require(compiled[1] == 0,
                 f"second request compiled {compiled[1]} programs")
        if on_chip:
            _require(dev.platform == "tpu", dev.platform)
            _require(resolved.norm_impl == "fused",
                     f"norm resolved {resolved.norm_impl}")
            _require(out["fused_norm_at_prefill"],
                     "fused norm takes the reference at prefill")
            # two norms a layer and the final one, all at prefill
            _require(calls["rms_norm"] >= 2 * cfg.n_layers + 1,
                     f"decode program holds kernel calls {calls}")
        return reply(**out)

    def cached_logits(kinds, params, ids, s_p: int):
        """Prefill of ``ids[:, :s_p]`` and one cached step for each further
        position through ``forward_with_cache`` → (float32 logits at every
        position, the call's state)."""
        from faabric_tpu.models.generate import (
            forward_with_cache,
            init_kv_cache,
        )

        cache = init_kv_cache(kinds, ids.shape[0],
                              128 * -(-ids.shape[1] // 128))
        prefill = jax.jit(lambda p, t, c: forward_with_cache(
            p, t, c, 0, kinds))
        step = jax.jit(lambda p, t, c, pos: forward_with_cache(
            p, t, c, pos, kinds))
        logits, cache = prefill(params, jnp.asarray(ids[:, :s_p]), cache)
        got = [np.asarray(logits, np.float32)]
        for pos in range(s_p, ids.shape[1]):
            logits, cache = step(params, jnp.asarray(ids[:, pos:pos + 1]),
                                 cache, jnp.int32(pos))
            got.append(np.asarray(logits, np.float32))
        return np.concatenate(got, axis=1), cache

    # ---- latent attention, shortcut layers, a held share of experts ---
    @register_function("smoke", "latent_experts")
    def latent_experts(ctx):
        from benchmarks import program_longcat, weights_longcat
        from benchmarks.reference import longcat as reference

        dev = ctx.device
        with open(LATENT_CONFIG if on_chip else LATENT_CONFIG_TINY) as f:
            config = dict(json.load(f), num_layers=1)
        sizes = weights_longcat.sizes_of(config)
        kinds = program_longcat.model_config(config)
        rows, s_p, steps = 4, run["prompt"], 2
        ids = weights_longcat.token_rows(7, 1, 0, rows, s_p + steps,
                                         sizes["vocab"])
        with jax.default_device(dev):
            params = weights_longcat.make_weights(7, sizes,
                                                  kinds.param_dtype, dev)
            got, cache = cached_logits(kinds, params, ids, s_p)
            want = np.stack([np.asarray(reference.logits_of(
                params, jnp.asarray(row), sizes)) for row in ids])
            counted = np.asarray(cache[0]["counters"]).tolist()
        out = dict(
            device=_device_report(dev), n_params=sum(
                int(x.size) for x in jax.tree.leaves(params)),
            prefill_rel_err=_rel_err(got[:, :s_p], want[:, :s_p]),
            cached_steps_rel_err=_rel_err(got[:, s_p:], want[:, s_p:]),
            picks_held_zero_absent_experts_hit_tiles=counted)
        _require(np.isfinite(got).all(), "a logit is not finite")
        _require(sum(counted[:3]) == rows * (s_p + steps) * sizes["top_k"],
                 f"the picks do not add up: {counted}")
        for name in ("prefill_rel_err", "cached_steps_rel_err"):
            _require(out[name] < TOL_LATENT_LOGITS, f"{name} {out[name]}")
        if on_chip:
            _require(dev.platform == "tpu", dev.platform)
        return reply(**out)

    # ---- state-space layers beside grouped-query attention ------------
    @register_function("smoke", "state_space")
    def state_space(ctx):
        from benchmarks import program_granite, weights_granite
        from benchmarks.reference import granite as reference
        from faabric_tpu.models.generate import call_sizes

        dev = ctx.device
        with open(HYBRID_CONFIG if on_chip else HYBRID_CONFIG_TINY) as f:
            config = dict(json.load(f), num_hidden_layers=2,
                          layer_types=["mamba", "attention"])
        sizes = weights_granite.sizes_of(config)
        kinds = program_granite.model_config(config)
        rows, steps = 4, 2
        s_p = kinds.ssm_chunk + kinds.ssm_chunk // 4
        ids = weights_granite.token_rows(7, 1, 0, rows, s_p + steps,
                                         sizes["vocab"])
        with jax.default_device(dev):
            params = weights_granite.make_weights(7, sizes,
                                                  kinds.param_dtype, dev)
            got, cache = cached_logits(kinds, params, ids, s_p)
            want = np.asarray(reference.logits_of_rows(
                params, jnp.asarray(ids), sizes))
        counted = call_sizes(kinds, rows, s_p, steps)
        out = dict(
            device=_device_report(dev), n_params=sum(
                int(x.size) for x in jax.tree.leaves(params)),
            prefill_rel_err=_rel_err(got[:, :s_p], want[:, :s_p]),
            cached_steps_rel_err=_rel_err(got[:, s_p:], want[:, s_p:]),
            **{name: counted[name] for name in (
                "cache_bytes", "state_bytes", "scan_chunks")})
        _require(np.isfinite(got).all(), "a logit is not finite")
        _require(sorted(cache[0]) == ["conv", "state"]
                 and sorted(cache[1]) == ["k", "v"],
                 f"the layers' state is {[sorted(c) for c in cache]}")
        for name in ("prefill_rel_err", "cached_steps_rel_err"):
            _require(out[name] < TOL_STATE_SPACE_LOGITS,
                     f"{name} {out[name]}")
        if on_chip:
            _require(dev.platform == "tpu", dev.platform)
        return reply(**out)

    # ---- layers that lend and borrow state ---------------------------
    @register_function("smoke", "shared_state")
    def shared_state(ctx):
        from benchmarks import program_phi4flash, weights_phi4flash
        from benchmarks.reference import phi4flash as reference
        from faabric_tpu.models.generate import (
            call_sizes,
            forward_with_cache,
            init_kv_cache,
        )

        dev = ctx.device
        with open(SHARED_CONFIG if on_chip else SHARED_CONFIG_TINY) as f:
            # the shallowest stack with every kind: Mamba-1 at 0, 2 and 4
            # (the memory's source), windows at 1 and 3, the full
            # attention at 5, a gated memory unit and a cross attention
            config = dict(json.load(f), num_hidden_layers=8)
        sizes = weights_phi4flash.sizes_of(config)
        kinds = program_phi4flash.model_config(config)
        window = kinds.sliding_window
        # the second chunk wraps the rings; the steps evict from them
        chunks, steps = (window - window // 4, window // 2), 2
        rows, compared = (64, 4) if on_chip else (8, 4)
        s_p = sum(chunks)
        ids = weights_phi4flash.token_rows(7, 1, 0, rows, s_p + steps,
                                           sizes["vocab"])
        with jax.default_device(dev):
            params = weights_phi4flash.make_weights(
                7, sizes, kinds.param_dtype, dev)
            cache = init_kv_cache(kinds, rows, 128 * -(-ids.shape[1] // 128))
            at = 0
            for length in chunks:
                logits, cache = jax.jit(
                    lambda p, t, c, at=at: forward_with_cache(
                        p, t, c, at, kinds, last_only=True))(
                    params, jnp.asarray(ids[:, at:at + length]), cache)
                at += length
            got = [np.asarray(logits, np.float32)]
            step = jax.jit(lambda p, t, c, pos: forward_with_cache(
                p, t, c, pos, kinds))
            for pos in range(s_p, ids.shape[1]):
                logits, cache = step(params, jnp.asarray(
                    ids[:, pos:pos + 1]), cache, jnp.int32(pos))
                got.append(np.asarray(logits, np.float32))
            got = np.concatenate(got, axis=1)[:compared]
            want = np.asarray(reference.logits_of_rows(
                params, jnp.asarray(ids[:compared]), sizes,
                at=slice(s_p - 1, None)))
        counted = call_sizes(kinds, rows, s_p, steps, chunks[0])
        out = dict(
            device=_device_report(dev), n_params=sum(
                int(x.size) for x in jax.tree.leaves(params)),
            rows=rows, prompt=s_p,
            prefill_rel_err=_rel_err(got[:, :1], want[:, :1]),
            cached_steps_rel_err=_rel_err(got[:, 1:], want[:, 1:]),
            **{name: counted[name] for name in (
                "window_slots", "window_cache_bytes", "shared_cache_bytes",
                "state_bytes", "attention_streamed_layers",
                "scan_streamed_layers", "prefill_skipped_layers")})
        _require(np.isfinite(got).all(), "a logit is not finite")
        _require([None if c is None else sorted(c) for c in cache]
                 == [["conv", "state"], ["k", "v"]] * 3 + [None, None],
                 "the layers' state is "
                 f"{[None if c is None else sorted(c) for c in cache]}")
        _require(counted["attention_streamed_layers"] == 4,
                 f"{counted['attention_streamed_layers']} attentions stream")
        _require(counted["scan_streamed_layers"] == 3,
                 f"{counted['scan_streamed_layers']} scans keep S on chip")
        for name in ("prefill_rel_err", "cached_steps_rel_err"):
            _require(out[name] < TOL_SHARED_STATE_LOGITS,
                     f"{name} {out[name]}")
        if on_chip:
            _require(dev.platform == "tpu", dev.platform)
        return reply(**out)

    # ---- a dense layer, then an expert layer, at a long reach ---------
    @register_function("smoke", "long_latent")
    def long_latent(ctx):
        from benchmarks import program_axk1, weights_axk1
        from benchmarks.reference import axk1 as reference
        from faabric_tpu.models.generate import (
            call_sizes,
            forward_with_cache,
            init_kv_cache,
        )

        dev = ctx.device
        with open(LONG_CONFIG if on_chip else LONG_CONFIG_TINY) as f:
            # the leading dense layer and the first expert layer
            config = dict(json.load(f), num_hidden_layers=2)
        sizes = weights_axk1.sizes_of(config)
        kinds = program_axk1.model_config(config)
        s_p, chunk = (8192, 1024) if on_chip else (48, 16)
        rows, compared, steps = 8, 2, 2
        ids = weights_axk1.token_rows(7, 1, 0, rows, s_p + steps,
                                      sizes["vocab"])
        with jax.default_device(dev):
            params = weights_axk1.make_weights(7, sizes, kinds.param_dtype,
                                               dev)
            cache = init_kv_cache(kinds, rows, 128 * -(-ids.shape[1] // 128))
            for at in range(0, s_p, chunk):
                logits, cache = jax.jit(
                    lambda p, t, c, at=at: forward_with_cache(
                        p, t, c, at, kinds, last_only=True))(
                    params, jnp.asarray(ids[:, at:at + chunk]), cache)
            got = [np.asarray(logits, np.float32)]
            step = jax.jit(lambda p, t, c, pos: forward_with_cache(
                p, t, c, pos, kinds))
            for pos in range(s_p, ids.shape[1]):
                logits, cache = step(params, jnp.asarray(
                    ids[:, pos:pos + 1]), cache, jnp.int32(pos))
                got.append(np.asarray(logits, np.float32))
            got = np.concatenate(got, axis=1)[:compared]
            want = np.asarray(reference.logits_of_rows(
                params, jnp.asarray(ids[:compared]), sizes,
                at=slice(s_p - 1, None)))
            counted = np.asarray(cache[1]["counters"]).tolist()
        sized = call_sizes(kinds, rows, s_p, steps, chunk)
        out = dict(
            device=_device_report(dev), n_params=sum(
                int(x.size) for x in jax.tree.leaves(params)),
            rows=rows, prompt=s_p,
            prefill_rel_err=_rel_err(got[:, :1], want[:, :1]),
            cached_steps_rel_err=_rel_err(got[:, 1:], want[:, 1:]),
            picks_held_zero_absent_experts_hit_tiles=counted,
            **{name: sized[name] for name in (
                "prefill_chunks", "score_blocks", "expanded_bytes",
                "latent_streamed_layers", "latent_streamed_chunks",
                "ffn_streamed_layers", "dense_layers", "expert_layers")})
        _require(np.isfinite(got).all(), "a logit is not finite")
        _require([sorted(c) for c in cache]
                 == [["latent"], ["counters", "latent"]],
                 f"the layers' state is {[sorted(c) for c in cache]}")
        _require(sum(counted[:3]) == rows * (s_p + steps) * sizes["top_k"]
                 and counted[1] == 0, f"the picks do not add up: {counted}")
        _require(sized["ffn_streamed_layers"] == 2,
                 f"{sized['ffn_streamed_layers']} feed-forwards stream")
        # at the cell's widths every chunk keeps its scores on the chip
        # (ops/latent_attention.py); the toy's lanes are no whole tiles
        streamed = (2, s_p // chunk, 0) if on_chip else (0, 0, s_p // chunk)
        _require((sized["latent_streamed_layers"],
                  sized["latent_streamed_chunks"],
                  sized["score_blocks"]) == streamed,
                 f"prefill's attention streams {sized}")
        for name in ("prefill_rel_err", "cached_steps_rel_err"):
            _require(out[name] < TOL_LONG_LATENT_LOGITS,
                     f"{name} {out[name]}")
        if on_chip:
            _require(dev.platform == "tpu", dev.platform)
        return reply(**out)

    # ---- gang --------------------------------------------------------
    @register_function("smoke", "gang")
    def gang(ctx):
        from faabric_tpu.device_plane.copies import device_copy_totals
        from faabric_tpu.device_plane.pallas_ring import ring_backend
        from faabric_tpu.mpi import MpiOp
        from faabric_tpu.telemetry import get_metrics

        world = ctx.mpi_world()
        rank, n = ctx.message.mpi_rank, world.size
        dev = ctx.device
        activated = world.activate_device_plane(rank)
        _require(activated, f"rank {rank}: device plane did not activate")
        plane = world.device_plane()
        fallbacks = get_metrics().counter(
            "faabric_device_plane_fallbacks_total")
        world.barrier(rank)
        copies0, fallbacks0 = device_copy_totals(), fallbacks.value

        m = 1 << 16
        mine = np.arange(m, dtype=np.float32) + 1000.0 * rank
        everyone = np.stack([np.arange(m, dtype=np.float32) + 1000.0 * r
                             for r in range(n)])
        x = jax.device_put(mine, dev)

        def on_my_chip(arr, what):
            _require(hasattr(arr, "devices") and arr.devices() == {dev},
                     f"rank {rank}: {what} lives on "
                     f"{getattr(arr, 'devices', lambda: type(arr))()}")
            return np.asarray(arr)

        got = on_my_chip(world.allreduce(rank, x, MpiOp.SUM), "allreduce")
        np.testing.assert_allclose(got, everyone.sum(axis=0), rtol=1e-6)
        got = on_my_chip(world.allgather(rank, x), "allgather")
        np.testing.assert_array_equal(got, everyone.reshape(-1))
        got = on_my_chip(world.reduce_scatter(rank, x, MpiOp.SUM),
                         "reduce_scatter")
        np.testing.assert_allclose(
            got, everyone.sum(axis=0).reshape(n, -1)[rank], rtol=1e-6)
        backend = ring_backend(plane.mesh)
        for shift in (1, n - 1):
            got = on_my_chip(plane.ring_permute(rank, x, shift),
                             "ring_permute")
            np.testing.assert_array_equal(got, everyone[(rank - shift) % n])

        world.barrier(rank)
        copies1 = device_copy_totals()
        out = dict(
            rank=rank, device=_device_report(dev), activated=activated,
            ring_backend=backend, disabled_reason=plane.disabled_reason,
            fallbacks=fallbacks.value - fallbacks0,
            host_device_copies=copies1["count"] - copies0["count"],
            plane_executables=plane.summary()["executable_cache"])
        _require(plane.disabled_reason is None, str(out))
        _require(out["fallbacks"] == 0, str(out))
        _require(out["host_device_copies"] == 0, str(out))
        if on_chip:
            _require(dev.platform == "tpu", dev.platform)
            _require(backend == "pallas", f"ring backend {backend}")
        return reply(**out)


def worker_main(rehearse: bool) -> int:
    sys.path.insert(0, REPO)
    from faabric_tpu.util.device_env import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    devices = jax.local_devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        print(f"NO_TPU platform={platform}", flush=True)
        return EXIT_NO_TPU
    compiles = _CompileCounter()
    model, run = (TINY, TINY_RUN) if rehearse else (LARGE, LARGE_RUN)
    _register_guests(model, run, on_chip=not rehearse, compiles=compiles)

    from faabric_tpu.executor import JaxExecutorFactory
    from faabric_tpu.runner import WorkerRuntime
    from faabric_tpu.util import native

    n = len(devices)
    runtime = WorkerRuntime(host=WORKER_HOST, slots=n, n_devices=n,
                            factory=JaxExecutorFactory(),
                            planner_host=PLANNER_HOST)
    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    runtime.start()
    try:
        print("READY " + json.dumps({
            "platform": platform, "kind": devices[0].device_kind, "count": n,
            "versions": {pkg: importlib.metadata.version(pkg)
                         for pkg in ("jax", "jaxlib", "libtpu")},
            "compile_cache_dir": cache_dir,
            "compile_cache_entries_at_start": _count_files(cache_dir),
        }), flush=True)
        # Runs until the parent says stop, or is gone
        parent = os.getppid()
        while not stop and os.getppid() == parent:
            time.sleep(0.2)
        print("BYE " + json.dumps({
            "compiles": compiles.snapshot(),
            "compile_cache_entries_at_end": _count_files(cache_dir),
            "native_helpers": native.loaded_helpers(),
        }), flush=True)
    finally:
        runtime.shutdown()
    return 0


def _count_files(path: str) -> int:
    return sum(len(files) for _r, _d, files in os.walk(path))


# ---------------------------------------------------------------------------
# Parent side: no JAX
# ---------------------------------------------------------------------------

def _die_with_parent() -> None:
    """preexec_fn: a child must not outlive this script, however it ends."""
    import ctypes

    PR_SET_PDEATHSIG = 1
    ctypes.CDLL("libc.so.6", use_errno=True).prctl(PR_SET_PDEATHSIG,
                                                   signal.SIGKILL)


def _free_port_offset() -> int:
    """A port offset at which the planner's (offset) and the worker's
    (offset + 1000) listener ranges are both free."""
    for offset in range(2000, 20000, 500):
        ports = [offset + extra + p for extra in (0, 1000)
                 for p in range(8003, 8015)]
        socks = []
        try:
            for port in ports:
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", port))
            return offset
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free port range for the planner and the worker")


class SmokeFailed(Exception):
    pass


class NoTpu(SmokeFailed):
    pass


class Cluster:
    """The planner and the worker as child processes, and the REST client."""

    def __init__(self, rehearse: bool) -> None:
        from faabric_tpu.endpoint.http_server import HttpMessageType
        from faabric_tpu.util.network import get_free_port

        self.http = HttpMessageType
        os.makedirs(OUT_DIR, exist_ok=True)
        offset = _free_port_offset()
        self.http_port = get_free_port()
        self.env = dict(
            os.environ, FAABRIC_METRICS="1", PYTHONPATH=REPO,
            FAABRIC_HOST_ALIASES=(
                f"{PLANNER_HOST}=127.0.0.1+{offset},"
                f"{WORKER_HOST}=127.0.0.1+{offset + 1000}"))
        self.procs: list[subprocess.Popen] = []
        self.logs = []
        self.planner = self._spawn(
            "planner", [sys.executable, "-m", "faabric_tpu.runner", "planner",
                        "--port-offset", str(offset),
                        "--http-port", str(self.http_port)])
        argv = [sys.executable, os.path.abspath(__file__), "--worker"]
        self.worker = self._spawn("worker",
                                  argv + (["--rehearse"] if rehearse else []))
        # The worker's stdout is its line protocol (READY / BYE / NO_TPU)
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self.worker.stdout:
            self._lines.put(line.strip())
        self._lines.put(None)

    def _spawn(self, name: str, argv: list) -> subprocess.Popen:
        log = open(os.path.join(OUT_DIR, f"{name}.log"), "w")
        self.logs.append(log)
        p = subprocess.Popen(argv, cwd=REPO, env=self.env, text=True,
                             stdout=subprocess.PIPE, stderr=log,
                             preexec_fn=_die_with_parent)
        self.procs.append(p)
        return p

    def worker_line(self, tag: str, deadline: float) -> dict:
        """The JSON of the worker's next ``<tag> {json}`` stdout line."""
        while True:
            try:
                line = self._lines.get(
                    timeout=max(0.0, deadline - time.monotonic()))
            except queue.Empty:
                raise SmokeFailed(f"worker never printed {tag}") from None
            if line is None:
                raise SmokeFailed(
                    f"worker exited ({self.worker.wait()}) before {tag}; "
                    f"see {OUT_DIR}/worker.log")
            if line.startswith("NO_TPU"):
                raise NoTpu(line)
            if line.startswith(tag + " "):
                return json.loads(line[len(tag) + 1:])

    def post(self, http_type, payload: str = "") -> dict:
        body = json.dumps({"http_type": int(http_type),
                           "payload": payload}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.http_port}/", data=body, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            raise SmokeFailed(f"planner answered {e.code}: {e.read()!r}")

    def wait_planner(self, deadline: float) -> None:
        while time.monotonic() < deadline:
            if self.planner.poll() is not None:
                raise SmokeFailed(f"planner exited {self.planner.returncode}")
            try:
                self.post(self.http.GET_CONFIG)
                return
            except (urllib.error.URLError, ConnectionError):
                time.sleep(0.2)
        raise SmokeFailed("planner REST endpoint never answered")

    def invoke(self, function: str, n_messages: int, deadline: float,
               mpi_world_size: int = 0) -> list[dict]:
        """One invocation through the planner; every message's report."""
        from faabric_tpu.proto import batch_exec_factory

        req = batch_exec_factory("smoke", function, n_messages)
        if mpi_world_size:
            req.messages[0].mpi_rank = 0
            req.messages[0].mpi_world_size = mpi_world_size
        expected = mpi_world_size or n_messages
        self.post(self.http.EXECUTE_BATCH, json.dumps(req.to_dict()))
        while True:
            status = self.post(self.http.EXECUTE_BATCH_STATUS,
                               json.dumps({"app_id": req.app_id}))
            # A failed rank fails the phase at once: its gang may be
            # parked in a collective it will never leave
            reports = []
            for m in status["messageResults"]:
                output = bytes.fromhex(m["output_data"]).decode(
                    errors="replace")
                if m["return_value"] != 0:
                    raise SmokeFailed(f"smoke/{function} failed: {output}")
                reports.append(json.loads(output))
            if status["finished"] and len(reports) >= expected:
                return reports
            if self.worker.poll() is not None:
                raise SmokeFailed(
                    f"worker exited {self.worker.returncode} in {function}")
            if time.monotonic() > deadline:
                raise SmokeFailed(f"smoke/{function} did not finish in time")
            time.sleep(0.5)

    def stop(self) -> dict:
        """Stop the worker (keeping its last words), then the planner."""
        last = {}
        if self.worker.poll() is None:
            self.worker.terminate()
            try:
                last = self.worker_line("BYE", time.monotonic() + 30)
            except SmokeFailed:
                pass
        for p in (self.worker, self.planner):
            if p.poll() is None:
                p.terminate()
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        for log in self.logs:
            log.close()
        return last


def _run_phases(cluster: Cluster, summary: dict, deadline: float) -> None:
    cluster.wait_planner(deadline)
    ready = summary["worker"] = cluster.worker_line("READY", deadline)
    n = ready["count"]
    hosts = cluster.post(cluster.http.GET_AVAILABLE_HOSTS)["hosts"]
    if [(h["slots"], h["nDevices"]) for h in hosts] != [(n, n)]:
        raise SmokeFailed(f"planner sees hosts {hosts}, worker has {n}")
    phases = summary["phases"] = {}
    phases["kernels"] = cluster.invoke("kernels", 1, deadline)[0]
    train = cluster.invoke("train", n, deadline)
    phases["train"] = next(r for r in train if r["rank"] == 0)
    pinned = {r["device_id"] for r in train if r["rank"] != 0}
    if len(pinned) != n - 1:
        raise SmokeFailed(f"train gang pinned chips {sorted(pinned)}")
    phases["decode"] = cluster.invoke("decode", 1, deadline)[0]
    phases["latent_experts"] = cluster.invoke("latent_experts", 1,
                                              deadline)[0]
    phases["state_space"] = cluster.invoke("state_space", 1, deadline)[0]
    phases["shared_state"] = cluster.invoke("shared_state", 1, deadline)[0]
    phases["long_latent"] = cluster.invoke("long_latent", 1, deadline)[0]
    if n >= 2:
        phases["gang"] = sorted(
            cluster.invoke("gang", 1, deadline, mpi_world_size=n),
            key=lambda r: r["rank"])
        chips = {r["device"]["id"] for r in phases["gang"]}
        if len(chips) != n:
            raise SmokeFailed(f"{n} ranks ran on chips {sorted(chips)}")


def main(rehearse: bool) -> int:
    sys.path.insert(0, REPO)
    deadline = time.monotonic() + DEADLINE_S
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(EXIT_FAILED))
    cluster = Cluster(rehearse)
    summary: dict = {"rehearsal": rehearse,
                     "model": TINY if rehearse else LARGE}
    failure = None
    try:
        _run_phases(cluster, summary, deadline)
    except NoTpu as e:
        failure = (EXIT_NO_TPU, f"JAX found no TPU ({e})")
    except (SmokeFailed, KeyError, ValueError) as e:
        failure = (EXIT_FAILED, f"FAILED — {e}")
    finally:
        summary["worker_exit"] = cluster.stop()
    exits = summary["child_exit_codes"] = [p.returncode
                                           for p in cluster.procs]
    summary["parent_touched_jax"] = "jax" in sys.modules
    if not failure and (any(exits) or summary["parent_touched_jax"]):
        failure = (EXIT_FAILED, f"FAILED — child exits {exits}, parent "
                   f"touched jax: {summary['parent_touched_jax']}")
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if failure:
        print(f"chip_smoke: {failure[1]}", file=sys.stderr)
        return failure[0]
    print(json.dumps(summary))
    device = {k: summary["worker"][k] for k in ("platform", "kind", "count")}
    if rehearse:
        # never the chip's result line, whatever the backend was
        print(json.dumps({"rehearsal": "passed", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    if "--worker" in sys.argv:
        sys.exit(worker_main("--rehearse" in sys.argv))
    sys.exit(main("--rehearse" in sys.argv))
