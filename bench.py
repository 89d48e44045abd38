"""Benchmark harness — prints ONE JSON line to stdout.

Reproduces the reference's benchmark shapes
(/root/reference/tests/dist/mpi/benchmarks/mpi_bench.cpp:18-85): MPI
allreduce effective rate using the same workload formula
4·(np−1)·payload_bytes/s with the ResNet-50-scale payload, plus
point-to-point dispatch latency — the BASELINE.md north-star metric
(<1 ms p50) — measured over real loopback sockets between two aliased
hosts.

The device phase runs in a watchdog subprocess (this parent never
touches JAX: a chip belongs to one process) and only on a TPU: a device
stage that finds no chip, or whose section raises, makes bench.py exit
non-zero — a CPU timing is never written under a device metric's name.
Every measured loop runs ON the device (lax.scan/fori_loop inside one
jit, iterations data-dependent) and fences completion with a scalar
readback; per-iteration time is the two-point slope
(t_N − t_1)/(N − 1), cancelling per-call dispatch. It times:
- the flagship compiled train step with the Pallas kernels (auto =
  flash attention + fused norm on TPU) AND with the reference jnp impls,
  reporting both and the MFU (6·N·tokens/s over platform peak FLOPs);
- a DeviceCollectives.allreduce bandwidth curve 1 MiB → 1 GiB with bus
  bandwidth (NCCL convention, 2·(n−1)/n · S/t) and % of ICI ring
  bandwidth when n ≥ 2 — the BASELINE.json north star;
- HBM read+write bandwidth (single-chip proxy for the memory system).

Output contract: stdout carries EXACTLY ONE compact
(<2 KB) JSON line — metric/value/unit/vs_baseline plus a small "summary"
of the device numbers (MFU, step_ms, flash speedup, allreduce GiB/s) —
printed LAST so a tail-truncating driver still parses it. Everything
else (full curves, calibration, errors) is written incrementally to the
BENCH_EXTRAS.json sidecar; progress logs go to stderr.

Device phase staging: the TPU stage orders its sections cheapest-first
(device probe → Mosaic compile-check → tiny-step MFU → small allreduce →
...) and the parent watchdog meters EACH section via the child's
progress file, so one wedged compile can never starve the numbers
already produced.

Headline metric: ptp_dispatch_p50_ms (vs_baseline = 1 ms target / actual,
>1 is better than target).
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time

# Peak dense bf16 FLOP/s and ICI per-link one-direction bandwidth (B/s)
# per TPU generation; public numbers (jax-ml.github.io/scaling-book).
# A bidirectional ring over one torus axis can use 2·link_bw, which is
# the denominator for pct_of_ici_ring.
_TPU_SPECS = {
    "v2": {"peak_flops": 45e12, "ici_link_bw": 0.0},
    "v3": {"peak_flops": 123e12, "ici_link_bw": 0.0},
    "v4": {"peak_flops": 275e12, "ici_link_bw": 4.5e10},
    "v5e": {"peak_flops": 197e12, "ici_link_bw": 4.5e10},
    "v5p": {"peak_flops": 459e12, "ici_link_bw": 9e10},
    "v6e": {"peak_flops": 918e12, "ici_link_bw": 9e10},
}


# libtpu device_kind strings use "lite" names for the e-series
# (e.g. "TPU v5 lite" = v5e, "TPU v6 lite" = v6e)
_TPU_KIND_ALIASES = {"v5lite": "v5e", "v6lite": "v6e"}


def _tpu_spec(device_kind: str) -> dict:
    """The peaks of a TPU ``device_kind`` as libtpu reports it (a v5e
    says "TPU v5 lite"). A kind that is not in the table is an error:
    dropping MFU and the ICI share silently reads as a benchmark that
    never had them."""
    kind = device_kind.lower().replace(" ", "")
    for alias, name in _TPU_KIND_ALIASES.items():
        if alias in kind:
            return _TPU_SPECS[name]
    # longest-match so "v5e"/"v5p" win over "v5"
    for name in sorted(_TPU_SPECS, key=len, reverse=True):
        if name in kind:
            return _TPU_SPECS[name]
    raise KeyError(f"no peak numbers for TPU device_kind {device_kind!r}; "
                   "add it to _TPU_SPECS with its source")


def bench_ptp_dispatch(iters: int = 400) -> dict:
    """One-way PTP dispatch latency between two aliased hosts over real
    loopback TCP (send → remote broker delivery → recv), measured as
    ping-pong RTT/2."""
    from faabric_tpu.batch_scheduler.decision import SchedulingDecision
    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )
    from faabric_tpu.transport.point_to_point import PointToPointBroker
    from faabric_tpu.transport.ptp_remote import PointToPointServer

    # Stay clear of the ephemeral port range (>=32768)
    base = random.randint(10, 200) * 100
    register_host_alias("benchA", "127.0.0.1", base)
    register_host_alias("benchB", "127.0.0.1", base + 1000)
    brokers = {h: PointToPointBroker(h) for h in ("benchA", "benchB")}
    servers = [PointToPointServer(b) for b in brokers.values()]
    for s in servers:
        s.start()
    try:
        d = SchedulingDecision(app_id=1, group_id=1)
        d.add_message("benchA", 1, 0, 0)
        d.add_message("benchB", 2, 1, 1)
        for b in brokers.values():
            b.set_up_local_mappings_from_decision(d)

        payload = b"x" * 64
        errs = []

        def echo():
            try:
                for _ in range(iters):
                    brokers["benchB"].recv_message(1, 0, 1, timeout=30.0)
                    brokers["benchB"].send_message(1, 1, 0, payload)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        warmup = 20
        t = threading.Thread(target=echo)
        t.start()
        lat = []
        a = brokers["benchA"]
        for i in range(iters):
            t0 = time.perf_counter()
            a.send_message(1, 0, 1, payload)
            a.recv_message(1, 1, 0, timeout=30.0)
            if i >= warmup:  # exclude connection establishment / cold path
                lat.append((time.perf_counter() - t0) / 2)
        t.join(timeout=10.0)
        if errs:
            raise errs[0]
        lat.sort()
        return {
            "p50_ms": 1000 * lat[len(lat) // 2],
            "p99_ms": 1000 * lat[min(len(lat) - 1, int(len(lat) * 0.99))],
            "min_ms": 1000 * lat[0],
        }
    finally:
        for s in servers:
            s.stop()
        for b in brokers.values():
            b.clear()
        clear_host_aliases()


def bench_host_allreduce(n_ranks: int = 4, elems: int = 25_500_000,
                         rounds: int = 3) -> dict:
    """Host-path allreduce, reference workload formula: effective bytes =
    4·(np−1)·payload per round (mpi_bench.cpp:60-85), ResNet-50-scale
    payload (~97 MiB of int32)."""
    import numpy as np

    from faabric_tpu.batch_scheduler.decision import SchedulingDecision
    from faabric_tpu.mpi import MpiOp, MpiWorld
    from faabric_tpu.transport.point_to_point import PointToPointBroker

    broker = PointToPointBroker("bench-host")
    d = SchedulingDecision(app_id=2, group_id=2)
    for r in range(n_ranks):
        d.add_message("bench-host", 10 + r, r, r)
    broker.set_up_local_mappings_from_decision(d)
    world = MpiWorld(broker, 2, n_ranks, 2)

    datas = [np.full(elems, r, dtype=np.int32) for r in range(n_ranks)]
    expected_head = sum(range(n_ranks))

    def rank_fn(rank, out):
        res = None
        for _ in range(rounds):
            res = world.allreduce(rank, datas[rank], MpiOp.SUM)
        out[rank] = res

    out: dict = {}
    t0 = time.perf_counter()
    threads = [threading.Thread(target=rank_fn, args=(r, out))
               for r in range(n_ranks)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    assert out[0][0] == expected_head

    payload_bytes = elems * 4
    effective = 4 * (n_ranks - 1) * payload_bytes * rounds
    gibs = effective / elapsed / (1 << 30)

    # Ring-backed cousins on the same world: reduce_scatter (fold phase
    # + rotation) and allgather (reference circulation), reported with
    # the same effective-bytes convention (bytes the wire would carry:
    # (np-1)/np · N per rank each way)
    extras = {}
    for name, fn, elems_total in (
            ("reduce_scatter",
             lambda r: world.reduce_scatter(r, datas[r], MpiOp.SUM),
             elems),
            ("allgather",
             lambda r: world.allgather(r, datas[r][:elems // n_ranks]),
             elems)):
        def loop(rank, fn=fn):
            for _ in range(rounds):
                fn(rank)
        t0 = time.perf_counter()
        ts = [threading.Thread(target=loop, args=(r,))
              for r in range(n_ranks)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        el = time.perf_counter() - t0
        moved = 2 * (n_ranks - 1) * (elems_total // n_ranks) * 4 \
            * n_ranks * rounds
        extras[f"{name}_gibs"] = round(moved / el / (1 << 30), 2)
    broker.clear()

    # Same-box floor: the allreduce's own data movement (root copy +
    # (np-1) in-place adds + (np-1) broadcast copies per round) executed
    # sequentially on one thread with the full memory bandwidth. The
    # threaded collective cannot beat this; the ratio is the honest
    # efficiency number (residual = queue wakeups + bandwidth sharing).
    acc = datas[0].copy()
    sink = [np.empty_like(acc) for _ in range(n_ranks - 1)]
    t0 = time.perf_counter()
    for _ in range(rounds):
        np.copyto(acc, datas[0])
        for r in range(1, n_ranks):
            np.add(acc, datas[r], out=acc)
        for o in sink:
            np.copyto(o, acc)
    floor_s = time.perf_counter() - t0
    floor_gibs = effective / floor_s / (1 << 30)
    return {"effective_gibs": gibs, "np": n_ranks,
            "payload_mib": payload_bytes / (1 << 20), "rounds": rounds,
            "seq_floor_gibs": floor_gibs,
            "pct_of_floor": round(100 * gibs / floor_gibs, 1),
            **extras}


def _mpi_sum():
    from faabric_tpu.mpi import MpiOp

    return MpiOp.SUM


def _comm_cells_delta(before: dict, after: dict) -> list[dict]:
    """Per-(src, dst, plane, codec) growth between two CommMatrix
    snapshots (cells split per wire codec since ISSUE 11 — keying on
    the 3-tuple would collide a link's raw and delta rows and compute
    deltas against the wrong baseline)."""
    idx = {(c["src"], c["dst"], c["plane"], c.get("codec", "raw")): c
           for c in (before or {}).get("cells", [])}
    out = []
    for c in (after or {}).get("cells", []):
        prev = idx.get((c["src"], c["dst"], c["plane"],
                        c.get("codec", "raw")))
        d_bytes = c["bytes"] - (prev["bytes"] if prev else 0)
        d_msgs = c["messages"] - (prev["messages"] if prev else 0)
        if not d_msgs:
            continue
        d_lat = c["lat_sum"] - (prev["lat_sum"] if prev else 0.0)
        d_n = c["lat_count"] - (prev["lat_count"] if prev else 0)
        out.append({
            "src": c["src"], "dst": c["dst"], "plane": c["plane"],
            "codec": c.get("codec", "raw"),
            "messages": d_msgs, "bytes": d_bytes,
            "mean_send_ms": round(d_lat / d_n * 1000, 3) if d_n else None,
            "gibs": (round(d_bytes / d_lat / (1 << 30), 2)
                     if d_lat > 0 else None),
        })
    out.sort(key=lambda r: -r["bytes"])
    return out


def _bandwidth_attribution(prof0: dict, prof1: dict,
                           cm0: dict, cm1: dict,
                           wall_s: float, n_local_ranks: int) -> dict:
    """Decompose a collective's wall time into per-hop phases (this
    process's ranks only — each bench process attributes its own side):

    - ``serialize``    — building the wire payload (mpi.wire/serialize)
    - ``enqueue_wait`` — consumer blocked before the message was
      deliverable (ptp/recv span time, minus nothing: overlap with the
      peer's compute IS the wait)
    - ``wire``         — socket/ring occupancy (transport.bulk tcp_send
      + shm_push spans)
    - ``deserialize``  — wire bytes → array (mpi.wire/deserialize)

    plus the per-link comm-matrix delta and a ranked suspect list, so a
    0.62-vs-6.01 GiB/s gap reads as "enqueue_wait is 71% of rank-time on
    link 1→2(shm)" instead of one number."""
    def tot(prof, key):
        return (prof.get(key) or {}).get("total_s", 0.0)

    def delta(key):
        return tot(prof1, key) - tot(prof0, key)

    phases = {
        "serialize_s": delta("mpi.wire/serialize"),
        "enqueue_wait_s": delta("ptp/recv"),
        "wire_s": (delta("transport.bulk/tcp_send")
                   + delta("transport.bulk/shm_push")),
        "deserialize_s": delta("mpi.wire/deserialize"),
    }
    rank_time = wall_s * max(1, n_local_ranks)
    accounted = sum(v for v in phases.values() if v > 0)
    suspects = sorted(((k, v) for k, v in phases.items() if v > 0),
                      key=lambda kv: -kv[1])
    links = _comm_cells_delta(cm0, cm1)
    return {
        "phases": {k: round(v, 4) for k, v in phases.items()},
        "wall_s": round(wall_s, 4),
        "rank_seconds": round(rank_time, 4),
        "accounted_share": (round(accounted / rank_time, 4)
                            if rank_time > 0 else None),
        "suspects": [{"phase": k, "seconds": round(v, 4),
                      "share_of_rank_time": (round(v / rank_time, 4)
                                             if rank_time > 0 else None)}
                     for k, v in suspects],
        "links": links,
        "commmatrix_bytes": sum(r["bytes"] for r in links),
    }


def _bench_world(my_host: str, app_id: int = 3):
    """Both bench processes build the same 4-rank/2-host world: ranks 0-1
    on xbenchA, 2-3 on xbenchB (mappings installed directly — the planner
    path is exercised elsewhere; this isolates the data plane)."""
    from faabric_tpu.batch_scheduler.decision import SchedulingDecision
    from faabric_tpu.mpi import MpiWorld
    from faabric_tpu.transport.point_to_point import PointToPointBroker
    from faabric_tpu.transport.ptp_remote import PointToPointServer

    d = SchedulingDecision(app_id=app_id, group_id=app_id)
    d.add_message("xbenchA", 30, 0, 0)
    d.add_message("xbenchA", 31, 1, 1)
    d.add_message("xbenchB", 32, 2, 2)
    d.add_message("xbenchB", 33, 3, 3)
    broker = PointToPointBroker(my_host)
    server = PointToPointServer(broker)
    server.start()
    broker.set_up_local_mappings_from_decision(d)
    world = MpiWorld(broker, app_id, 4, app_id)
    world.refresh_rank_hosts()
    return broker, server, world


def _allreduce_procs_passes(world, my_ranks, elems: int, rounds: int):
    """Run the fp32 allreduce workload once per wire-codec mode —
    ``raw`` (codec plane off), ``governed`` (``auto,quant``: the
    adaptive governor with lossy fold-leg quant ALLOWED — on this
    container's loopback stand-in links it correctly picks raw, so
    this pass measures the governor's overhead, which must be ~zero),
    then ``forced`` (``delta,quant``: every codec engaged, recording
    the wire-byte wins) — barrier-fenced so every process flips the
    process-wide governor at a quiesced point. Each round mutates a
    rotating ~1% slice of the payload: the iterative-solver shape the
    delta streams exist for.

    Returns (per-mode elapsed seconds, ok, err, quant deviation of the
    forced result vs the exact raw sum at element 0)."""
    import numpy as np

    from faabric_tpu.transport.codec import set_wire_codec

    slice_len = max(1, elems // 100)
    span_hi = max(1, elems // 2 - slice_len)
    elapsed, out0 = {}, {}
    errors: list = []
    orig_hier = world.hier_enabled
    # Exact expected sum at element 0 (mutations stay in the upper half)
    expected0 = float(sum(r + 1 for r in range(world.size)))
    for mode, spec in (("raw", "raw"), ("governed", "auto,quant"),
                       ("forced", "delta,quant")):
        set_wire_codec(spec)
        world.hier_enabled = "force"
        results: dict = {}

        def rank_fn(rank, _mode=mode):
            try:
                data = np.full(elems, float(rank + 1), dtype=np.float32)
                world.barrier(rank)
                t0 = time.perf_counter()
                out = None
                for k in range(rounds):
                    if k:
                        off = elems // 2 + (k * slice_len) % span_hi
                        data[off:off + slice_len] += float(k)
                    out = world.allreduce(rank, data, _mpi_sum())
                world.barrier(rank)
                results[rank] = (time.perf_counter() - t0, float(out[0]))
            except Exception as e:  # noqa: BLE001 — reported upward
                errors.append(f"{_mode} rank {rank}: {e!r}")

        threads = [threading.Thread(target=rank_fn, args=(r,))
                   for r in my_ranks]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            break
        elapsed[mode] = max(v[0] for v in results.values())
        out0[mode] = results[my_ranks[0]][1]
    set_wire_codec(os.environ.get("FAABRIC_WIRE_CODEC", "auto"))
    world.hier_enabled = orig_hier
    if errors:
        return elapsed, False, "; ".join(errors)[:160], None
    quant_dev = abs(out0.get("forced", expected0) - expected0)
    ok = (out0.get("raw") == expected0  # non-quant paths bitwise-exact
          and out0.get("governed") == expected0  # auto picked lossless
          and quant_dev < 1.0)
    err = "" if ok else (f"out0 raw={out0.get('raw')} "
                         f"gov={out0.get('governed')} "
                         f"forced={out0.get('forced')}")
    return elapsed, ok, err, quant_dev


def _allreduce_worker_main(elems: int, rounds: int) -> None:
    """Child process body: ranks 2-3 on xbenchB (aliases via
    FAABRIC_HOST_ALIASES in the env)."""
    broker, server, world = _bench_world("xbenchB")
    print("READY", flush=True)
    try:
        _, ok, err, _dev = _allreduce_procs_passes(world, (2, 3), elems,
                                                   rounds)
        print("DONE" if ok else f"FAILED {err}"[:160], flush=True)
    except Exception as e:  # noqa: BLE001 — reported to parent
        print(f"FAILED {e!r}"[:160], flush=True)
    finally:
        server.stop()
        broker.clear()


def bench_host_allreduce_procs(elems: int = 25_500_000,
                               rounds: int = 3) -> dict:
    """Cross-PROCESS allreduce over the PTP + bulk data planes: 2 OS
    processes × 2 ranks, 97 MiB fp32 per rank, reference effective-rate
    formula 4·(np−1)·payload·rounds/elapsed (mpi_bench.cpp:60-85). The
    cross-process leg rides transport/bulk.py's tuned sockets with
    chunk-pipelined leader trees.

    ISSUE 11 acceptance shape: THREE barrier-fenced passes over the
    same iterative workload (~1% of the payload mutates per round) —
    fp32 raw, governor in ``auto,quant``, and forced ``delta,quant``.
    The headline ``effective_gibs`` is the GOVERNED rate: on this
    container the loopback links outrun memcpy, so the correct
    governor verdict is raw and the pass proves the adaptive plane
    costs ~nothing when it should stay out of the way (it also
    exercises the per-link NaN-scale raw passthrough on the tagged
    fold leg). The forced pass records ``coded_wire_speedup`` — the
    raw-vs-wire byte ratio a bandwidth-bound cross-host link would
    actually gain (the ≥1.5× effective-rate criterion is only
    demonstrable on such links; see container_note). Shm rings are
    disabled for all passes (the loopback TCP links are the cross-host
    stand-in).

    Ceiling analysis (compare against extras.host_calibration): one round
    is serially 2 wire legs (reduce up + broadcast down) + ~4 unavoidable
    97 MiB copies (root/leader accumulators, broadcast fan-out copies) +
    3 in-place adds. With memcpy at M GiB/s and loopback at W GiB/s the
    round floor is ≈ 0.095·(2/W + 4/M + 3/(3·M)) s; the effective rate is
    1.14 GiB/round over that. On a box with M≈2, W≈2.5 (this dev VM) the
    ceiling is ≈ 3.4 GiB/s effective; on hardware with M≈10 the same
    code clears 8+."""
    import subprocess

    import numpy as np

    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )

    # Listener ports must stay clear of the kernel ephemeral range
    # (>=32768): max here is 15000 + 8014 (bulk) = 23014
    base_a = random.randint(10, 120) * 100
    base_b = base_a + 3000
    clear_host_aliases()
    register_host_alias("xbenchA", "127.0.0.1", base_a)
    register_host_alias("xbenchB", "127.0.0.1", base_b)

    # The cross-process legs are the CROSS-HOST stand-in: shm rings off
    # (a ring memcpy would bypass the wire entirely — and the governor
    # would rightly refuse to code it), generous delta-cache budget for
    # the 97 MiB working set. Applies to parent AND child.
    codec_env = {"SHM_RING_BYTES": "0", "FAABRIC_DELTA_CACHE_MB": "384"}
    saved_env = {k: os.environ.get(k) for k in codec_env}
    os.environ.update(codec_env)
    env = {**os.environ,
           "FAABRIC_HOST_ALIASES":
           f"xbenchA=127.0.0.1+{base_a},xbenchB=127.0.0.1+{base_b}"}
    # Parent servers must exist BEFORE the child runs: the child's rank
    # threads immediately dial the parent-hosted group barrier
    broker, server, world = _bench_world("xbenchA")
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--allreduce-worker",
         str(elems), str(rounds)],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = child.stdout.readline().strip()
        assert line == "READY", f"worker said {line!r}"

        try:
            from faabric_tpu.telemetry import get_comm_matrix, summary_data

            def data_plane_cells():
                cells = (get_comm_matrix().snapshot() or {}).get(
                    "cells", [])
                return [c for c in cells
                        if c["plane"] in ("shm", "bulk-tcp")]

            cm0, prof0 = get_comm_matrix().snapshot(), summary_data()
            wire0 = {(c["src"], c["dst"], c["plane"], c["codec"]):
                     (c["bytes"], c["bytes_raw"])
                     for c in data_plane_cells()}
            elapsed, ok, err, quant_dev = _allreduce_procs_passes(
                world, (0, 1), elems, rounds)
            status = child.stdout.readline().strip()
            assert status == "DONE", f"worker reported: {status!r}"
            assert ok, f"parent pass check failed: {err}"

            payload_bytes = elems * 4
            effective = 4 * 3 * payload_bytes * rounds  # np=4
            rates = {m: effective / s / (1 << 30)
                     for m, s in elapsed.items()}
            # Per-codec wire accounting over both passes (parent side):
            # the governed pass must show delta/quant rows whose wire
            # bytes undercut their raw bytes
            codec_rows = {}
            for c in data_plane_cells():
                b0 = wire0.get((c["src"], c["dst"], c["plane"],
                                c["codec"]), (0, 0))
                row = codec_rows.setdefault(
                    c["codec"], {"bytes_wire": 0, "bytes_raw": 0})
                row["bytes_wire"] += c["bytes"] - b0[0]
                row["bytes_raw"] += c["bytes_raw"] - b0[1]
            # Bandwidth attribution (this process's ranks 0-1): ranked
            # per-hop decomposition of where the wall time went, plus
            # the per-link comm-matrix delta — the 0.62-vs-6.01 GiB/s
            # investigation reads from here
            attribution = _bandwidth_attribution(
                prof0, summary_data(), cm0, get_comm_matrix().snapshot(),
                sum(elapsed.values()), n_local_ranks=2)
            coded_wire = sum(v["bytes_wire"] for c, v in
                             codec_rows.items() if c != "raw")
            coded_raw = sum(v["bytes_raw"] for c, v in
                            codec_rows.items() if c != "raw")
            return {"effective_gibs": rates.get("governed"),
                    "raw_gibs": rates.get("raw"),
                    "coded_gibs": rates.get("forced"),
                    "governed_speedup": (
                        rates["governed"] / rates["raw"]
                        if rates.get("raw") else None),
                    # How much longer the raw bytes would have occupied
                    # the wire vs what the forced-codec pass shipped —
                    # the quantity the codec plane actually controls
                    "coded_wire_speedup": (coded_raw / coded_wire
                                           if coded_wire else None),
                    "quant_dev_elem0": quant_dev,
                    "codec_rows": codec_rows,
                    "container_note": (
                        "loopback on this container moves bytes faster "
                        "than memcpy (~3.4 GiB/s), so wall-clock cannot "
                        "reward wire compression; the governed (auto) "
                        "pass demonstrates the governor correctly "
                        "staying raw at ~zero overhead, and the coded "
                        "pass's wire ratio shows what a "
                        "bandwidth-bound link would gain"),
                    "np": 4, "n_processes": 2,
                    "payload_mib": payload_bytes / (1 << 20),
                    "rounds": rounds,
                    "attribution": attribution}
        finally:
            server.stop()
            broker.clear()
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            child.wait(timeout=10)
        except Exception:  # noqa: BLE001
            child.kill()
        clear_host_aliases()


DELTA_STREAM_SHARD_ELEMS = 3 << 20  # 12 MiB fp32 shards


def _delta_stream_passes(world, my_ranks, elems: int, rounds: int):
    """Iterative sharded parameter broadcast for the delta-stream
    bench: every round, rank 0 pushes the same 97 MiB fp32 parameter
    image to the remote rank as a stream of 8 MiB shards, with a
    rotating ~1% CONTIGUOUS mutation between rounds (the parameter-
    server partial-update shape — scattered elementwise noise would
    dirty every 4 KiB page and no page-granular codec could help). The
    receiver consumes via ``recv_shared`` — the zero-copy receive the
    repeated-payload path exists for (unchanged shards deliver as the
    SAME immutable cached buffer; mutated shards as the freshly
    patched one) — and acks each round, the solver ping-pong cadence.

    Pass 1 raw, pass 2 delta; returns (per-mode elapsed, ok). The
    receiver keeps the final round's shards and verifies them BITWISE
    against the sender's deterministic mutation schedule after the
    clock stops — the lossless contract is asserted, not assumed."""
    import numpy as np

    from faabric_tpu.transport.codec import set_wire_codec

    slice_len = max(1, elems // 100)
    span_hi = max(1, elems - slice_len)
    shard = min(DELTA_STREAM_SHARD_ELEMS, elems)
    bounds = [(lo, min(lo + shard, elems))
              for lo in range(0, elems, shard)]
    rng = np.random.default_rng(42)
    base = rng.standard_normal(elems).astype(np.float32)

    def mutate(data, k):
        off = (k * 7919 * slice_len) % span_hi
        data[off:off + slice_len] += np.float32(k)

    elapsed, oks = {}, []
    sender = my_ranks[0] == 0
    # Best-of-2 per mode (the ingress bench's pattern): loopback TCP
    # on this container occasionally stalls an entire raw pass, and
    # the second delta rep measures the WARM steady state (bases
    # already cached) the iterative workload actually lives in
    for mode, spec in (("raw", "raw"), ("delta", "delta"),
                       ("raw", "raw"), ("delta", "delta")):
        set_wire_codec(spec)
        data = base.copy()
        world.barrier(my_ranks[0])
        t0 = time.perf_counter()
        last: list = []
        for k in range(rounds):
            if sender:
                if k:
                    mutate(data, k)
                for lo, hi in bounds:
                    world.send(0, 1, data[lo:hi])
                ack, _ = world.recv(1, 0)
            else:
                last = [world.recv_shared(0, 1)[0] for _ in bounds]
                # Consumer touch: read one element per shard (serving
                # weights reads them; it does not rewrite them)
                touch = float(sum(float(a.reshape(-1)[0]) for a in last))
                world.send(1, 0, np.array([touch], dtype=np.float32))
        world.barrier(my_ranks[0])
        rep = time.perf_counter() - t0
        elapsed[mode] = min(elapsed.get(mode, rep), rep)
        if sender:
            oks.append(True)
        else:
            expected = base.copy()
            for k in range(1, rounds):
                mutate(expected, k)
            got = np.concatenate([np.asarray(a).reshape(-1).view(
                np.float32) for a in last])
            oks.append(np.array_equal(got, expected))
    set_wire_codec(os.environ.get("FAABRIC_WIRE_CODEC", "auto"))
    return elapsed, all(oks)


def _stream_bench_world(my_host: str, app_id: int = 6):
    """One rank per process (rank 0 on xbenchA, rank 1 on xbenchB): the
    delta-stream bench must be WIRE-bound — a wider world's in-process
    fan-out copies swamp the link on a 2-core box and no wire codec
    could show through."""
    from faabric_tpu.batch_scheduler.decision import SchedulingDecision
    from faabric_tpu.mpi import MpiWorld
    from faabric_tpu.transport.point_to_point import PointToPointBroker
    from faabric_tpu.transport.ptp_remote import PointToPointServer

    d = SchedulingDecision(app_id=app_id, group_id=app_id)
    d.add_message("xbenchA", 40, 0, 0)
    d.add_message("xbenchB", 41, 1, 1)
    broker = PointToPointBroker(my_host)
    server = PointToPointServer(broker)
    server.start()
    broker.set_up_local_mappings_from_decision(d)
    world = MpiWorld(broker, app_id, 2, app_id)
    world.refresh_rank_hosts()
    return broker, server, world


def _delta_stream_worker_main(elems: int, rounds: int) -> None:
    """Child body for bench_delta_stream: rank 1 on xbenchB."""
    broker, server, world = _stream_bench_world("xbenchB")
    print("READY", flush=True)
    try:
        _, ok = _delta_stream_passes(world, (1,), elems, rounds)
        print("DONE" if ok else "FAILED broadcast-not-bitwise", flush=True)
    except Exception as e:  # noqa: BLE001 — reported to parent
        print(f"FAILED {e!r}"[:160], flush=True)
    finally:
        server.stop()
        broker.clear()


def bench_delta_stream(elems: int = 25_500_000,
                      rounds: int = 10) -> dict:
    """ISSUE 11 acceptance bench: effective GiB/s of an ITERATIVE
    97 MiB sharded parameter broadcast (sender on process A, consumer
    on process B) with ~1% of the payload mutating per round. The raw
    pass pays the full payload on the wire every round; the delta pass
    ships the XOR delta stream (full frames round 1, ~1% thereafter)
    and the consumer reads unchanged shards zero-copy from the receive
    cache (``recv_shared``). ``delta_stream_gibs`` = payload·rounds /
    delta-pass wall — REQUIRED in bench_gate. The ≥2× wall-clock
    criterion against the raw baseline is only demonstrable on
    bandwidth-bound links; this container's loopback outruns memcpy,
    so ``wire_speedup`` (raw/wire bytes, typically 40×+) carries the
    codec's controlled quantity here (see container_note)."""
    import subprocess

    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )

    base_a = random.randint(10, 120) * 100
    base_b = base_a + 3000
    clear_host_aliases()
    register_host_alias("xbenchA", "127.0.0.1", base_a)
    register_host_alias("xbenchB", "127.0.0.1", base_b)
    codec_env = {"SHM_RING_BYTES": "0", "FAABRIC_DELTA_CACHE_MB": "768"}
    saved_env = {k: os.environ.get(k) for k in codec_env}
    os.environ.update(codec_env)
    env = {**os.environ,
           "FAABRIC_HOST_ALIASES":
           f"xbenchA=127.0.0.1+{base_a},xbenchB=127.0.0.1+{base_b}"}
    broker, server, world = _stream_bench_world("xbenchA")
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--delta-stream-worker", str(elems), str(rounds)],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = child.stdout.readline().strip()
        assert line == "READY", f"worker said {line!r}"
        try:
            from faabric_tpu.telemetry import get_comm_matrix

            cm0 = {(c["src"], c["dst"], c["codec"]):
                   (c["bytes"], c["bytes_raw"])
                   for c in (get_comm_matrix().snapshot() or {}).get(
                       "cells", []) if c["plane"] == "bulk-tcp"}
            elapsed, ok = _delta_stream_passes(world, (0,), elems,
                                               rounds)
            status = child.stdout.readline().strip()
            assert status == "DONE", f"worker reported: {status!r}"
            assert ok, "root-side broadcast results not bitwise-exact"
            coded_wire = coded_raw = 0
            for c in (get_comm_matrix().snapshot() or {}).get(
                    "cells", []):
                if c["plane"] != "bulk-tcp" or c["codec"] == "raw":
                    continue
                b0 = cm0.get((c["src"], c["dst"], c["codec"]), (0, 0))
                coded_wire += c["bytes"] - b0[0]
                coded_raw += c["bytes_raw"] - b0[1]
            payload_bytes = elems * 4
            rates = {m: payload_bytes * rounds / s / (1 << 30)
                     for m, s in elapsed.items()}
            return {"delta_gibs": rates.get("delta"),
                    "raw_gibs": rates.get("raw"),
                    "speedup": (rates["delta"] / rates["raw"]
                                if rates.get("raw") else None),
                    # The codec-controlled quantity: how much longer
                    # the logical bytes would have occupied the wire
                    "wire_speedup": (coded_raw / coded_wire
                                     if coded_wire else None),
                    "payload_mib": payload_bytes / (1 << 20),
                    "rounds": rounds, "n_processes": 2,
                    "mutation_share": 0.01,
                    "container_note": (
                        "loopback here outruns memcpy, so the "
                        "wall-clock ratio saturates near 1; on a "
                        "bandwidth-bound link the wire_speedup is the "
                        "operative factor")}
        finally:
            server.stop()
            broker.clear()
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            child.wait(timeout=10)
        except Exception:  # noqa: BLE001
            child.kill()
        clear_host_aliases()


def _hier_bench_world(my_host_idx: int, n_hosts: int,
                      ranks_per_host: int, app_id: int = 9):
    """Every bench process builds the same INTERLEAVED world: rank r on
    simulated host (r % n_hosts) — the topology-BLIND placement where
    every flat-ring link crosses hosts. This is the worst case the
    gang-scheduling hook prevents and the hierarchical composition
    repairs; grouped placement would hide most of the wire savings."""
    from faabric_tpu.batch_scheduler.decision import SchedulingDecision
    from faabric_tpu.mpi import MpiWorld
    from faabric_tpu.transport.point_to_point import PointToPointBroker
    from faabric_tpu.transport.ptp_remote import PointToPointServer

    hosts = [f"xhier{i}" for i in range(n_hosts)]
    n = n_hosts * ranks_per_host
    d = SchedulingDecision(app_id=app_id, group_id=app_id)
    for r in range(n):
        d.add_message(hosts[r % n_hosts], 60 + r, r, r)
    broker = PointToPointBroker(hosts[my_host_idx])
    server = PointToPointServer(broker)
    server.start()
    broker.set_up_local_mappings_from_decision(d)
    world = MpiWorld(broker, app_id, n, app_id)
    world.refresh_rank_hosts()
    my_ranks = [r for r in range(n) if r % n_hosts == my_host_idx]
    return broker, server, world, my_ranks


def _quant_bench_data(rank: int, elems: int):
    """Deterministic varied fp32 payload for the quant mode — every
    process derives the same per-rank arrays (constant vectors would
    quantize exactly and report a misleading 0 error)."""
    import numpy as np

    rng = np.random.default_rng(1000 + rank)
    return rng.uniform(-1000.0, 1000.0, elems).astype(np.float32)


def _hier_allreduce_modes(world, my_ranks, elems, rounds):
    """Run the allreduce workload once per mode — flat ring,
    hierarchical, and hierarchical + int8 leader-ring quantization
    (FAABRIC_ALLREDUCE_QUANT satellite, fp32 payload) — barrier-fenced
    so every process flips the world knobs at a quiesced point. Returns
    (per-mode elapsed seconds, per-mode outbound comm-matrix byte
    deltas for THIS process, ok, max-abs quantization error over this
    process's ranks)."""
    import numpy as np

    from faabric_tpu.telemetry import get_comm_matrix

    def cm_bytes():
        # Data planes only (as the dist test): the ptp control plane
        # (barriers, mappings) would bias the hier/flat ratio toward 1
        return sum(c["bytes"] for c in
                   (get_comm_matrix().snapshot() or {}).get("cells", [])
                   if c["plane"] in ("shm", "bulk-tcp"))

    elapsed, cross, oks = {}, {}, []
    quant_err = 0.0
    # "force": the simulated hosts all resolve to loopback, and plain
    # "on" composes only across real machines (_hier_wins)
    for mode, hier in (("flat", False), ("hier", "force"),
                       ("quant", "force")):
        world.hier_enabled = hier
        world.allreduce_quant = "int8" if mode == "quant" else ""
        results = {}

        def rank_fn(rank, _mode=mode):
            if _mode == "quant":
                data = _quant_bench_data(rank, elems)
            else:
                data = np.full(elems, rank + 1, dtype=np.int32)
            world.barrier(rank)
            t0 = time.perf_counter()
            out = None
            for _ in range(rounds):
                out = world.allreduce(rank, data, _mpi_sum())
            world.barrier(rank)
            results[rank] = (time.perf_counter() - t0, out)

        b0 = cm_bytes()
        threads = [threading.Thread(target=rank_fn, args=(r,))
                   for r in my_ranks]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        cross[mode] = cm_bytes() - b0
        elapsed[mode] = max(v[0] for v in results.values())
        if mode == "quant":
            exact = sum(_quant_bench_data(r, elems)
                        for r in range(world.size))
            quant_err = max(
                float(np.max(np.abs(v[1] - exact)))
                for v in results.values())
            # Loose sanity bound: per-fold error ≤ scale/2 with interim
            # magnitudes ≤ n·1000 → scale ≤ n·1000/127; (H−1) fold hops
            oks.append(quant_err < world.size * 1000.0 / 16)
        else:
            expected = world.size * (world.size + 1) // 2
            oks.append(all(int(v[1][0]) == expected
                           for v in results.values()))
    world.allreduce_quant = ""
    return elapsed, cross, all(oks), quant_err


def _hier_worker_main(host_idx: int, n_hosts: int, ranks_per_host: int,
                      elems: int, rounds: int) -> None:
    """Child body: one simulated host's ranks (aliases via env)."""
    broker, server, world, my_ranks = _hier_bench_world(
        host_idx, n_hosts, ranks_per_host)
    print("READY", flush=True)
    try:
        _, cross, ok, _err = _hier_allreduce_modes(world, my_ranks, elems,
                                                   rounds)
        print(f"BYTES {cross['flat']} {cross['hier']} {cross['quant']}",
              flush=True)
        print("DONE" if ok else "FAILED bad-allreduce-value", flush=True)
    except Exception as e:  # noqa: BLE001 — reported to parent
        print(f"FAILED {e!r}"[:160], flush=True)
    finally:
        server.stop()
        broker.clear()


def bench_host_allreduce_hier(n_hosts: int = 4, ranks_per_host: int = 2,
                              elems: int = 6_000_000,
                              rounds: int = 2) -> dict:
    """ISSUE 9 acceptance bench: hierarchical allreduce over
    ``n_hosts`` SIMULATED hosts (one OS process each) × N ranks with a
    topology-blind interleaved placement. Runs the same payload through
    the flat ring and the hierarchical composition and reports both
    rates plus ``cross_host_bytes`` — the comm-matrix byte totals the
    two algorithms put on the wire (sum over every process's outbound
    cells; in-process same-host traffic is invisible to the matrix by
    design). Model: flat moves 2·(N−1)·payload across processes, the
    leader ring 2·(H−1)·payload → ratio ≈ (H−1)/(N−1) ≈
    1/ranks-per-host."""
    import subprocess

    from faabric_tpu.mpi import MpiWorld
    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )

    # Below the ring/hier eligibility floor BOTH modes silently run the
    # leader tree and the "ratio" measures nothing — fail loudly instead
    assert elems * 4 >= 2 * MpiWorld.CHUNK_BYTES, (
        f"payload {elems * 4} B below the 2×CHUNK_BYTES "
        f"({2 * MpiWorld.CHUNK_BYTES} B) ring/hier floor")

    base = random.randint(10, 50) * 100
    clear_host_aliases()
    aliases = []
    for i in range(n_hosts):
        register_host_alias(f"xhier{i}", "127.0.0.1", base + i * 5000)
        aliases.append(f"xhier{i}=127.0.0.1+{base + i * 5000}")
    env = {**os.environ, "FAABRIC_HOST_ALIASES": ",".join(aliases)}

    broker, server, world, my_ranks = _hier_bench_world(
        0, n_hosts, ranks_per_host)
    children = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--hier-worker",
         str(i), str(n_hosts), str(ranks_per_host), str(elems),
         str(rounds)],
        stdout=subprocess.PIPE, text=True, env=env)
        for i in range(1, n_hosts)]
    try:
        for c in children:
            line = c.stdout.readline().strip()
            assert line == "READY", f"hier worker said {line!r}"
        elapsed, cross, ok, quant_err = _hier_allreduce_modes(
            world, my_ranks, elems, rounds)
        assert ok, "parent ranks saw a bad allreduce value"
        flat_bytes, hier_bytes = cross["flat"], cross["hier"]
        quant_bytes = cross["quant"]
        for c in children:
            bline = c.stdout.readline().split()
            assert bline and bline[0] == "BYTES", bline
            flat_bytes += int(bline[1])
            hier_bytes += int(bline[2])
            quant_bytes += int(bline[3])
            status = c.stdout.readline().strip()
            assert status == "DONE", f"hier worker reported {status!r}"

        n = n_hosts * ranks_per_host
        payload_bytes = elems * 4
        effective = 4 * (n - 1) * payload_bytes * rounds
        return {
            "effective_gibs": effective / elapsed["hier"] / (1 << 30),
            "flat_effective_gibs": effective / elapsed["flat"] / (1 << 30),
            "np": n, "n_hosts": n_hosts,
            "ranks_per_host": ranks_per_host,
            "payload_mib": payload_bytes / (1 << 20), "rounds": rounds,
            "placement": "interleaved",
            "cross_host_bytes": {
                "flat": flat_bytes, "hier": hier_bytes,
                "ratio": round(hier_bytes / flat_bytes, 4)
                if flat_bytes else None,
                "model_ratio": round((n_hosts - 1) / (n - 1), 4),
            },
            # FAABRIC_ALLREDUCE_QUANT satellite: same fp32 payload
            # through the hierarchical path with the leader ring's fold
            # leg quantized to int8 + per-chunk scales. Model: the fold
            # leg drops to ~1/4 of its fp32 bytes, the (unquantized)
            # allgather leg is unchanged → ~5/8 of the hier bytes.
            "quant": {
                "mode": "int8",
                "effective_gibs": effective / elapsed["quant"] / (1 << 30),
                "max_abs_err": quant_err,
                "cross_host_bytes": quant_bytes,
                "vs_hier_bytes_ratio": round(quant_bytes / hier_bytes, 4)
                if hier_bytes else None,
            },
        }
    finally:
        server.stop()
        broker.clear()
        for c in children:
            try:
                c.wait(timeout=10)
            except Exception:  # noqa: BLE001
                c.kill()
        clear_host_aliases()


def _alltoall_modes(world, my_ranks, block_elems, rounds):
    """Run the alltoall workload once per mode — naive all-pairs vs the
    compiled ``alltoall.hier`` schedule (ISSUE 13) — barrier-fenced so
    every process flips ``sched_enabled`` at a quiesced point. Returns
    (per-mode elapsed, per-mode comm-matrix (bytes, messages) deltas
    for THIS process, ok)."""
    import numpy as np

    from faabric_tpu.telemetry import get_comm_matrix

    n = world.size

    def cm_wire():
        cells = (get_comm_matrix().snapshot() or {}).get("cells", [])
        b = sum(c["bytes"] for c in cells
                if c["plane"] in ("shm", "bulk-tcp"))
        m = sum(c["messages"] for c in cells
                if c["plane"] in ("shm", "bulk-tcp"))
        return b, m

    datas = {r: (np.arange(n * block_elems, dtype=np.int64)
                 + (r + 1) * 10_000_000) for r in my_ranks}
    elapsed, cross, oks = {}, {}, []
    # "force": the simulated hosts all resolve to loopback, and plain
    # "on" selects the flat schedule for fast/local links
    for mode, sched in (("naive", False), ("sched", "force")):
        world.sched_enabled = sched
        results = {}

        def rank_fn(rank):
            world.barrier(rank)
            t0 = time.perf_counter()
            out = None
            for _ in range(rounds):
                out = world.alltoall(rank, datas[rank])
            world.barrier(rank)
            results[rank] = (time.perf_counter() - t0, out)

        b0, m0 = cm_wire()
        threads = [threading.Thread(target=rank_fn, args=(r,))
                   for r in my_ranks]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b1, m1 = cm_wire()
        cross[mode] = (b1 - b0, m1 - m0)
        elapsed[mode] = max(v[0] for v in results.values())
        # Spot-check: rank r's output block from src s starts at s's
        # base + r·block offset — out[0] comes from rank 0 (cross-host
        # for most ranks), out[r·block] from r itself
        oks.append(all(
            int(v[1][0]) == 10_000_000 + rank * block_elems
            and int(v[1][rank * block_elems])
            == (rank + 1) * 10_000_000 + rank * block_elems
            for rank, v in results.items()))
    return elapsed, cross, all(oks)


def _alltoall_worker_main(host_idx: int, n_hosts: int,
                          ranks_per_host: int, block_elems: int,
                          rounds: int) -> None:
    """Child body: one simulated host's ranks (aliases via env)."""
    broker, server, world, my_ranks = _hier_bench_world(
        host_idx, n_hosts, ranks_per_host, app_id=13)
    print("READY", flush=True)
    try:
        _, cross, ok = _alltoall_modes(world, my_ranks, block_elems,
                                       rounds)
        print(f"WIRE {cross['naive'][0]} {cross['naive'][1]} "
              f"{cross['sched'][0]} {cross['sched'][1]}", flush=True)
        print("DONE" if ok else "FAILED bad-alltoall-value", flush=True)
    except Exception as e:  # noqa: BLE001 — reported to parent
        print(f"FAILED {e!r}"[:160], flush=True)
    finally:
        server.stop()
        broker.clear()


def bench_host_alltoall(n_hosts: int = 4, ranks_per_host: int = 3,
                        block_elems: int = 150_000,
                        rounds: int = 2) -> dict:
    """ISSUE 13 acceptance bench: schedule-compiled alltoall over
    ``n_hosts`` simulated hosts (one OS process each) with the
    topology-blind interleaved placement. Reports the compiled and
    naive rates plus the comm-matrix cross-host accounting. Model:
    alltoall is a permutation, so cross-host BYTES are invariant
    (ratio ≈ 1.0 — the parity is the accounting correctness signal);
    the composition cuts cross-host MESSAGES to H·(H−1) vs naive's
    N·(N−m) ≈ 1/ranks-per-host², the per-message cost the schedule
    selector's slow-link verdict targets."""
    import subprocess

    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )

    base = random.randint(10, 50) * 100 + 61
    clear_host_aliases()
    aliases = []
    for i in range(n_hosts):
        register_host_alias(f"xhier{i}", "127.0.0.1", base + i * 5000)
        aliases.append(f"xhier{i}=127.0.0.1+{base + i * 5000}")
    env = {**os.environ, "FAABRIC_HOST_ALIASES": ",".join(aliases)}

    broker, server, world, my_ranks = _hier_bench_world(
        0, n_hosts, ranks_per_host, app_id=13)
    children = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--alltoall-worker",
         str(i), str(n_hosts), str(ranks_per_host), str(block_elems),
         str(rounds)],
        stdout=subprocess.PIPE, text=True, env=env)
        for i in range(1, n_hosts)]
    try:
        for c in children:
            line = c.stdout.readline().strip()
            assert line == "READY", f"alltoall worker said {line!r}"
        elapsed, cross, ok = _alltoall_modes(world, my_ranks,
                                             block_elems, rounds)
        assert ok, "parent ranks saw a bad alltoall value"
        naive_bytes, naive_msgs = cross["naive"]
        sched_bytes, sched_msgs = cross["sched"]
        for c in children:
            wline = c.stdout.readline().split()
            assert wline and wline[0] == "WIRE", wline
            naive_bytes += int(wline[1])
            naive_msgs += int(wline[2])
            sched_bytes += int(wline[3])
            sched_msgs += int(wline[4])
            status = c.stdout.readline().strip()
            assert status == "DONE", f"alltoall worker said {status!r}"

        n = n_hosts * ranks_per_host
        payload_bytes = n * block_elems * 8  # per-rank payload
        moved = n * payload_bytes * rounds
        return {
            "effective_gibs": moved / elapsed["sched"] / (1 << 30),
            "naive_effective_gibs": moved / elapsed["naive"] / (1 << 30),
            "np": n, "n_hosts": n_hosts,
            "ranks_per_host": ranks_per_host,
            "payload_mib": payload_bytes / (1 << 20), "rounds": rounds,
            "placement": "interleaved",
            "cross_host": {
                "naive_bytes": naive_bytes, "sched_bytes": sched_bytes,
                "bytes_ratio": round(sched_bytes / naive_bytes, 4)
                if naive_bytes else None,
                "naive_msgs": naive_msgs, "sched_msgs": sched_msgs,
                "msgs_ratio": round(sched_msgs / naive_msgs, 4)
                if naive_msgs else None,
                "model_msgs_ratio": round(1 / ranks_per_host ** 2, 4),
            },
        }
    finally:
        server.stop()
        broker.clear()
        for c in children:
            try:
                c.wait(timeout=10)
            except Exception:  # noqa: BLE001
                c.kill()
        clear_host_aliases()


def _device_plane_worker_main(elems: int, rounds: int) -> None:
    """Child body (ISSUE 10 bench): ONE process, 4 rank threads × 4
    virtual CPU devices. The same payload runs through the host flat
    ring first (plane not yet activated), then through the activated
    device plane; prints one JSON line with both rates, bitwise
    identity, and the comm-matrix accounting proof (device rows carry
    the traffic, host data planes carry none of it)."""
    import json as _json

    import jax
    import numpy as np

    from faabric_tpu.batch_scheduler.decision import SchedulingDecision
    from faabric_tpu.mpi import MpiWorld
    from faabric_tpu.telemetry import get_comm_matrix
    from faabric_tpu.transport.point_to_point import PointToPointBroker

    n = 4
    broker = PointToPointBroker("xdev")
    d = SchedulingDecision(app_id=12, group_id=12)
    for r in range(n):
        d.add_message("xdev", 70 + r, r, r, device_id=r)
    broker.set_up_local_mappings_from_decision(d)
    world = MpiWorld(broker, 12, n, 12)
    world.refresh_rank_hosts()

    datas = {r: np.full(elems, r + 1, dtype=np.int32) for r in range(n)}
    expected0 = n * (n + 1) // 2

    def run_rounds(tag, n_rounds=None):
        n_rounds = rounds if n_rounds is None else n_rounds
        results = {}

        def rank_fn(rank):
            world.barrier(rank)
            t0 = time.perf_counter()
            out = None
            for _ in range(n_rounds):
                out = world.allreduce(rank, datas[rank], _mpi_sum())
            world.barrier(rank)
            results[rank] = (time.perf_counter() - t0, out)

        threads = [threading.Thread(target=rank_fn, args=(r,))
                   for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(int(v[1][0]) == expected0 for v in results.values()), (
            tag, {r: int(v[1][0]) for r, v in results.items()})
        return (max(v[0] for v in results.values()),
                {r: v[1] for r, v in results.items()})

    def plane_bytes():
        cells = (get_comm_matrix().snapshot() or {}).get("cells", [])
        out: dict = {}
        for c in cells:
            out[c["plane"]] = out.get(c["plane"], 0) + c["bytes"]
        return out

    host_elapsed, host_out = run_rounds("host")

    acts = {}

    def act(rank):
        acts[rank] = world.activate_device_plane(rank)

    threads = [threading.Thread(target=act, args=(r,)) for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(acts.values()), f"activation failed: {acts}"
    run_rounds("warm", n_rounds=1)  # the compile happens off the clock
    b0 = plane_bytes()
    dev_elapsed, dev_out = run_rounds("device")
    b1 = plane_bytes()
    delta = {p: b1.get(p, 0) - b0.get(p, 0) for p in set(b0) | set(b1)}

    # -- ISSUE 15: the device-RESIDENT phase — the same payloads already
    # living on the chips as committed jax arrays. The timed rounds must
    # move ZERO bytes across the host<->device boundary (the new
    # faabric_device_copy_* accounting) on top of the ISSUE 10 zero
    # host-plane-bytes invariant.
    from faabric_tpu.device_plane import device_copy_totals

    resident_datas = {r: jax.device_put(datas[r], jax.local_devices()[r])
                      for r in range(n)}

    def run_resident_rounds(n_rounds):
        results = {}

        def rank_fn(rank):
            world.barrier(rank)
            t0 = time.perf_counter()
            out = None
            for _ in range(n_rounds):
                out = world.allreduce(rank, resident_datas[rank],
                                      _mpi_sum())
            # Device results are async; block before stopping the clock
            if hasattr(out, "block_until_ready"):
                out.block_until_ready()
            world.barrier(rank)
            results[rank] = (time.perf_counter() - t0, out)

        threads = [threading.Thread(target=rank_fn, args=(r,))
                   for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return (max(v[0] for v in results.values()),
                {r: v[1] for r, v in results.items()})

    run_resident_rounds(1)  # resident-key compile off the clock
    c0 = device_copy_totals()
    rb0 = plane_bytes()
    res_elapsed, res_out = run_resident_rounds(rounds)
    c1 = device_copy_totals()
    rb1 = plane_bytes()
    rdelta = {p: rb1.get(p, 0) - rb0.get(p, 0) for p in set(rb0) | set(rb1)}
    resident_identical = all(
        np.array_equal(np.asarray(res_out[r]), host_out[r])
        and hasattr(res_out[r], "sharding")
        for r in range(n))

    payload = elems * 4
    effective = 4 * (n - 1) * payload * rounds
    identical = all(np.array_equal(dev_out[r], host_out[r])
                    for r in range(n))
    plane = world.device_plane()
    print(_json.dumps({
        "effective_gibs": effective / dev_elapsed / (1 << 30),
        "host_effective_gibs": effective / host_elapsed / (1 << 30),
        "resident_gibs": effective / res_elapsed / (1 << 30),
        "np": n, "n_devices": len(jax.devices()),
        "platform": jax.devices()[0].platform,
        "payload_mib": payload / (1 << 20), "rounds": rounds,
        "identical": identical,
        "resident_identical": resident_identical,
        # Accounting proof: the timed device rounds put n·payload·rounds
        # on plane=device rows and ZERO on the host data planes
        "device_bytes": delta.get("device", 0),
        "device_bytes_expected": n * payload * rounds,
        "host_plane_bytes": sum(v for p, v in delta.items()
                                if p in ("shm", "bulk-tcp")),
        # ...and the resident rounds additionally moved ZERO bytes
        # across the host<->device boundary
        "resident_copy_bytes": c1["bytes"] - c0["bytes"],
        "resident_copy_count": c1["count"] - c0["count"],
        "resident_device_bytes": rdelta.get("device", 0),
        "resident_host_plane_bytes": sum(
            v for p, v in rdelta.items() if p in ("shm", "bulk-tcp")),
        "cached_executables": len(
            (plane.summary() or {}).get("cached_executables", []))
        if plane else 0,
    }), flush=True)


def bench_host_allreduce_device(elems: int = 6_000_000,
                                rounds: int = 2) -> dict:
    """ISSUE 10 acceptance bench: the device collective plane vs the
    host flat ring on the SAME payload, same process shape (4 rank
    threads), CPU backend with 4 virtual devices — the configuration
    this container can actually run; on TPU the identical code path
    rides ICI. Subprocess-isolated because the forced device count and
    backend pin must be set before JAX initialises."""
    import json as _json
    import subprocess

    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=4")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": " ".join(flags)}
    p = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--device-plane-worker", str(elems), str(rounds)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, (p.stdout[-500:], p.stderr[-500:])
    line = [ln for ln in p.stdout.splitlines()
            if ln.startswith("{")][-1]
    out = _json.loads(line)
    assert out["identical"], "device plane result != host ring result"
    assert out["host_plane_bytes"] == 0, out
    assert out["device_bytes"] == out["device_bytes_expected"], out
    # ISSUE 15 acceptance: the device-RESIDENT rounds are bitwise
    # identical to the host ring AND moved zero bytes across both the
    # host data planes and the host<->device boundary
    assert out["resident_identical"], \
        "device-resident result != host ring result"
    assert out["resident_copy_bytes"] == 0, out
    assert out["resident_copy_count"] == 0, out
    assert out["resident_host_plane_bytes"] == 0, out
    return out


def _bench_journal_micro(quick: bool = False) -> dict:
    """ISSUE 4 micro-costs: raw journal append latency, the cost of the
    disabled-path gate, and the end-to-end overhead the journal adds to
    the planner's hot set_message_result path (acceptance: < 5%)."""
    import shutil
    import tempfile
    import timeit

    from faabric_tpu.planner.journal import NULL_JOURNAL, PlannerJournal
    from faabric_tpu.proto import message_factory
    from faabric_tpu.util.config import get_system_config

    n = 5_000 if quick else 20_000
    # Disabled path: one enabled-check (what every call site pays when
    # FAABRIC_PLANNER_JOURNAL_DIR is unset — no allocation, no call)
    noop_gate_ns = timeit.timeit(
        lambda: None if NULL_JOURNAL.enabled else None,
        number=n * 10) / (n * 10) * 1e9

    # Raw append, two views of a representative result record:
    # enqueue latency (what set_message_result pays inline — the
    # write-behind push) and sustained cost (encode + os.write once the
    # drain keeps up at max rate)
    d = tempfile.mkdtemp(prefix="bench_journal_")
    j = PlannerJournal(d, fsync_interval=0.05, compact_records=10**9)
    msg = message_factory("bench", "fn")
    msg.output_data = b"x" * 64
    fields = {"msg": msg.to_dict()}
    j.DRAIN_BACKPRESSURE = 10**9  # pure enqueue: no early drains
    enqueue_ns = timeit.timeit(
        lambda: j.append("result", fields), number=n) / n * 1e9
    j.flush()
    j.DRAIN_BACKPRESSURE = PlannerJournal.DRAIN_BACKPRESSURE
    append_ns = timeit.timeit(
        lambda: j.append("result", fields), number=n) / n * 1e9
    j.close()
    shutil.rmtree(d, ignore_errors=True)

    # End-to-end set_message_result over real loopback RPC, journal off
    # vs on: a PlannerServer + PlannerClient per run (the acceptance
    # denominator is the real hot path — wire encode, sockets, handler
    # decode, planner apply — not a mock-mode in-process call)
    def _results_seconds(journal_dir: str | None, base: int) -> float:
        import faabric_tpu.planner.planner as planner_mod
        from faabric_tpu.planner import PlannerClient, PlannerServer
        from faabric_tpu.proto import message_factory
        from faabric_tpu.transport.common import register_host_alias

        saved = os.environ.get("FAABRIC_PLANNER_JOURNAL_DIR")
        if journal_dir is None:
            os.environ.pop("FAABRIC_PLANNER_JOURNAL_DIR", None)
        else:
            os.environ["FAABRIC_PLANNER_JOURNAL_DIR"] = journal_dir
        get_system_config().reset()
        planner_mod._planner = None  # rebuild with this journal config
        register_host_alias("bjpl", "127.0.0.1", base)
        server = PlannerServer(port_offset=base)
        client = PlannerClient("bjcli", planner_host="bjpl")
        try:
            server.start()
            m = 500 if quick else 2_000
            msgs = []
            for i in range(m):
                x = message_factory("bench", "fn")
                x.output_data = b"x" * 64
                msgs.append(x)
            planner = planner_mod.get_planner()
            t0 = time.perf_counter()
            for x in msgs:
                client.set_message_result(x)
            # The async plane is FIFO per connection: the last result
            # being applied means the server processed them all
            deadline = time.time() + 60
            while time.time() < deadline:
                if planner.get_message_result(
                        msgs[-1].app_id, msgs[-1].id) is not None:
                    break
                time.sleep(0.001)
            return time.perf_counter() - t0
        finally:
            client.close()
            server.stop()  # closes the planner journal too
            planner_mod._planner = None
            if saved is None:
                os.environ.pop("FAABRIC_PLANNER_JOURNAL_DIR", None)
            else:
                os.environ["FAABRIC_PLANNER_JOURNAL_DIR"] = saved
            get_system_config().reset()

    # Interleaved repeats, min per leg: a single loopback run varies
    # ±20% with machine state, an order of magnitude more than the
    # ~1 µs enqueue actually under test — min-of-N is the standard
    # noise-robust latency estimator
    b = random.randint(10, 120) * 100
    offs, ons = [], []
    for i in range(2 if quick else 3):
        offs.append(_results_seconds(None, b + 5000 * i))
        jd = tempfile.mkdtemp(prefix="bench_journal_planner_")
        ons.append(_results_seconds(jd, b + 5000 * i + 2500))
        shutil.rmtree(jd, ignore_errors=True)
    off_s, on_s = min(offs), min(ons)
    m = 500 if quick else 2_000
    # Two views: throughput overhead at saturation (includes the drain
    # thread's amortized encode+fsync competing for the GIL) and the
    # latency the append itself adds to one result's hot path (the
    # write-behind enqueue over the measured end-to-end per-op time —
    # the < 5% acceptance number)
    throughput_pct = (on_s - off_s) / off_s * 100.0 if off_s > 0 else 0.0
    per_op_ns = off_s / m * 1e9
    latency_pct = enqueue_ns / per_op_ns * 100.0 if per_op_ns > 0 else 0.0
    return {
        "append_ns": round(append_ns, 1),
        "append_enqueue_ns": round(enqueue_ns, 1),
        "noop_gate_ns": round(noop_gate_ns, 2),
        "set_result_off_s": round(off_s, 4),
        "set_result_on_s": round(on_s, 4),
        "result_throughput_overhead_pct": round(throughput_pct, 2),
        "result_latency_overhead_pct": round(latency_pct, 2),
    }


def _bench_planner_restart(quick: bool = False) -> dict:
    """ISSUE 4 macro-cost: SIGKILL the planner mid-batch, restart it on
    the same journal dir, and measure kill → batch-complete — the
    control-plane outage blip the journal bounds (replay + worker
    rejoin + buffered-result flush)."""
    import signal
    import subprocess
    import tempfile

    from faabric_tpu.transport.common import clear_host_aliases
    from faabric_tpu.util.config import get_system_config

    b = random.randint(10, 120) * 100
    aliases = (f"pjpl=127.0.0.1+{b},pjw0=127.0.0.1+{b + 2500},"
               f"pjcli=127.0.0.1+{b + 5000}")
    journal_dir = tempfile.mkdtemp(prefix="bench_pjournal_")
    knobs = {"PLANNER_HOST_TIMEOUT": "3",
             "FAABRIC_PLANNER_JOURNAL_DIR": journal_dir,
             "FAABRIC_PLANNER_RECONCILE_GRACE": "5"}
    env = {**os.environ, "FAABRIC_HOST_ALIASES": aliases,
           "JAX_PLATFORMS": "cpu", **knobs}
    saved = {k: os.environ.get(k)
             for k in ["FAABRIC_HOST_ALIASES", "PLANNER_HOST_TIMEOUT"]}
    os.environ.update({"FAABRIC_HOST_ALIASES": aliases,
                       "PLANNER_HOST_TIMEOUT": "3"})
    clear_host_aliases()
    get_system_config().reset()

    children = []

    def spawn(*args):
        return _spawn_ready_child(children, env, *args)

    me = None
    try:
        planner = spawn("planner", str(b))
        spawn("worker", "pjw0", "pjpl", "8")

        from faabric_tpu.executor import ExecutorFactory
        from faabric_tpu.proto import ReturnValue, batch_exec_factory
        from faabric_tpu.runner import WorkerRuntime

        class NullFactory(ExecutorFactory):
            def create_executor(self, msg):
                raise RuntimeError("client runs nothing")

        me = WorkerRuntime(host="pjcli", slots=0, factory=NullFactory(),
                           planner_host="pjpl")
        me.start()

        task_s = 1.0 if quick else 2.5
        req = batch_exec_factory("dist", "sleep", 8)
        for i, m in enumerate(req.messages):
            m.input_data = (b"0.3" if i < 4 else str(task_s).encode())
        me.planner_client.call_functions(req)

        # Pre-crash results must be on disk before the kill
        deadline = time.time() + 20
        while time.time() < deadline:
            status = me.planner_client.get_batch_results(req.app_id)
            if len(status.message_results) >= 2:
                break
            time.sleep(0.1)

        planner.send_signal(signal.SIGKILL)
        planner.wait(timeout=5)
        t_kill = time.perf_counter()
        spawn("planner", str(b))  # restart on the same journal dir

        deadline = time.time() + 90
        status = None
        while time.time() < deadline:
            try:
                status = me.planner_client.get_batch_results(req.app_id)
                if status.finished:
                    break
            except Exception:  # noqa: BLE001 — planner down mid-poll
                pass
            time.sleep(0.1)
        recover_s = time.perf_counter() - t_kill
        ok = (status is not None and status.finished
              and all(m.return_value == int(ReturnValue.SUCCESS)
                      for m in status.message_results))
        return {
            "planner_kill_to_recover_s": round(recover_s, 3),
            "n_messages": 8, "task_s": task_s,
            "all_success": ok,
        }
    finally:
        if me is not None:
            me.shutdown()
        for p in children:
            p.terminate()
        for p in children:
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001
                p.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        clear_host_aliases()
        get_system_config().reset()
        import shutil

        shutil.rmtree(journal_dir, ignore_errors=True)


def _spawn_ready_child(children: list, env: dict, *args) -> object:
    """Spawn a tests/dist/procs.py child and block until it prints
    READY (log lines may precede it). Shared by every bench section
    that stands up a real planner/worker cluster."""
    import subprocess

    procs_py = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tests", "dist", "procs.py")
    p = subprocess.Popen([sys.executable, procs_py, *args],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, text=True, env=env)
    children.append(p)
    while True:
        line = p.stdout.readline()
        assert line, f"bench child {args} died before READY"
        if line.strip() == "READY":
            return p


def bench_invocations(quick: bool = False) -> dict:
    """ISSUE 8 high-QPS invocation path: planner + 2 REAL worker
    processes, ≥10k concurrent no-op invocations driven through the
    ingress (admission → batched scheduling ticks → group-commit
    journal → pipelined per-host dispatch), with the journal ON so the
    measured path includes group commit.

    Reports:
    - ``invocations_per_s`` — the headline: completed invocations per
      second with concurrent submitters (required bench_gate key);
    - ``invocations_per_s_serial`` — the single-invocation-RPC baseline
      measured in the SAME round (one sync CALL_BATCH + result wait at
      a time; the ≥5× acceptance ratio reads off these two);
    - ``invocation_p50_ms`` — serial submit→result p50, the
      immediate-path cutover criterion (must not regress vs the
      pre-ingress direct path).
    """
    import statistics
    import subprocess
    import tempfile
    import urllib.request

    from faabric_tpu.transport.common import clear_host_aliases
    from faabric_tpu.util.config import get_system_config

    b = random.randint(10, 120) * 100
    aliases = (f"iqpl=127.0.0.1+{b},iqw0=127.0.0.1+{b + 2500},"
               f"iqw1=127.0.0.1+{b + 5000},iqcli=127.0.0.1+{b + 7500}")
    http_port = b + 3100
    journal_dir = tempfile.mkdtemp(prefix="bench_ingress_journal_")
    knobs = {"FAABRIC_PLANNER_JOURNAL_DIR": journal_dir,
             "DIST_HTTP_PORT": str(http_port)}
    env = {**os.environ, "FAABRIC_HOST_ALIASES": aliases,
           "JAX_PLATFORMS": "cpu", **knobs}
    saved = {k: os.environ.get(k) for k in ["FAABRIC_HOST_ALIASES"]}
    os.environ["FAABRIC_HOST_ALIASES"] = aliases
    clear_host_aliases()
    get_system_config().reset()

    children = []

    def spawn(*args):
        return _spawn_ready_child(children, env, *args)

    def healthz() -> dict:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/healthz", timeout=5) as r:
            return json.loads(r.read())

    me = None
    try:
        spawn("planner", str(b))
        # Generous slots: no-op tasks turn over in ~ms, so slot count
        # bounds in-flight concurrency, not steady-state throughput
        spawn("worker", "iqw0", "iqpl", "256")
        spawn("worker", "iqw1", "iqpl", "256")

        from faabric_tpu.executor import ExecutorFactory
        from faabric_tpu.proto import ReturnValue, batch_exec_factory
        from faabric_tpu.runner import WorkerRuntime

        class NullFactory(ExecutorFactory):
            def create_executor(self, msg):
                raise RuntimeError("client runs nothing")

        me = WorkerRuntime(host="iqcli", slots=0, factory=NullFactory(),
                           planner_host="iqpl")
        me.start()

        # -- serial single-invocation-RPC baseline (and p50) ----------
        # Measured BEFORE and AFTER the concurrent phase and averaged:
        # this container's effective CPU budget drifts across a heavy
        # run (cgroup quota), and a one-sided baseline would randomly
        # flatter or sandbag the speedup ratio.
        n_serial = 20 if quick else 50

        def serial_phase() -> tuple[float, list[float]]:
            lat_ms = []
            t_serial = time.perf_counter()
            for _ in range(n_serial):
                req = batch_exec_factory("dist", "noop", 1)
                t0 = time.perf_counter()
                me.planner_client.call_functions(req)
                msg = me.planner_client.get_message_result(
                    req.app_id, req.messages[0].id, timeout=15.0)
                lat_ms.append((time.perf_counter() - t0) * 1000.0)
                assert msg.return_value == int(ReturnValue.SUCCESS)
            return n_serial / (time.perf_counter() - t_serial), lat_ms

        serial_qps_pre, lat_pre = serial_phase()

        # -- concurrent phase: the firehose ---------------------------
        # Bulk submissions (many independent 1-message apps per RPC):
        # at target QPS one sync round-trip per invocation would make
        # the CLIENT the bottleneck — same batching story as the
        # server-side ticks
        total = 2000 if quick else 10000
        n_threads = 4
        bulk = 100
        per_thread = total // n_threads
        total = per_thread * n_threads
        from faabric_tpu.planner.client import PlannerClient

        clients = [PlannerClient("iqcli", "iqpl")
                   for _ in range(n_threads)]
        base_results = healthz().get("resultsTotal", 0)
        shed_retries = [0] * n_threads
        submit_errs = []
        app_ids: list[list[int]] = [[] for _ in range(n_threads)]

        def submitter(ti: int) -> None:
            client = clients[ti]
            try:
                left = per_thread
                while left > 0:
                    n = min(bulk, left)
                    reqs = [batch_exec_factory("dist", "noop", 1)
                            for _ in range(n)]
                    while True:
                        accepted, retry_after = \
                            client.submit_functions_many(reqs)
                        if accepted:
                            break
                        shed_retries[ti] += 1
                        time.sleep(retry_after)
                    app_ids[ti].extend(r.app_id for r in reqs)
                    left -= n
            except Exception as e:  # noqa: BLE001 — report to the round
                submit_errs.append(f"{ti}: {e}")

        # Best-of-2 rounds: the container's effective CPU budget swings
        # run to run (same convention as the journal micro-bench's
        # interleaved min-of-3) — each round is a full ``total``-sized
        # run, so the acceptance-sized workload is measured both times
        rates = []
        for _ in range(2):
            for ids in app_ids:
                ids.clear()
            h0 = healthz()
            base_results = h0.get("resultsTotal", 0)
            base_failed = h0.get("resultsFailed", 0)
            t_start = time.perf_counter()
            threads = [threading.Thread(target=submitter, args=(i,),
                                        name=f"ingress-submit-{i}")
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not submit_errs, submit_errs

            deadline = time.time() + (120 if quick else 300)
            done = 0
            while time.time() < deadline:
                done = healthz().get("resultsTotal", 0) - base_results
                if done >= total:
                    break
                time.sleep(0.2)
            elapsed = time.perf_counter() - t_start
            assert done >= total, f"only {done}/{total} completed"
            # Quality gate on the gated figure: deadline-shed FAILED
            # results count toward resultsTotal too — a throttled round
            # must fail loudly, not report shed work as throughput
            failed = healthz().get("resultsFailed", 0) - base_failed
            assert failed == 0, f"{failed} FAILED results in QPS run"
            rates.append(total / elapsed)
        qps = max(rates)

        # Spot-check correctness on a sample of RECENT apps (full
        # per-app polling would measure the poller, not the path; the
        # oldest apps age out of the planner's bounded result
        # retention, so only the newest are still queryable)
        sample = [ids[-1] for ids in app_ids if ids][:8]
        verified = 0
        for app_id in sample:
            status = me.planner_client.get_batch_results(app_id)
            if not status.expected_num_messages \
                    and not status.message_results:
                # Evicted from the planner's bounded retention
                # (MAX_KEPT_APP_RESULTS < apps per round): this thread
                # finished submitting ahead of the pack, so its last
                # app completed >1000 completions ago. A genuinely
                # unfinished app keeps expected>0 (and stays in-flight)
                # and still fails below.
                continue
            assert status.finished, f"app {app_id} not finished"
            assert all(m.return_value == int(ReturnValue.SUCCESS)
                       for m in status.message_results), app_id
            verified += 1
        assert verified, "every sampled app aged out of result retention"

        serial_qps_post, lat_post = serial_phase()
        serial_qps = (serial_qps_pre + serial_qps_post) / 2.0
        p50_ms = statistics.median(lat_pre + lat_post)

        health = healthz()
        ingress = health.get("ingress", {})
        # ISSUE 14: the planner-folded admit→record e2e digest of the
        # concurrent run (log-bucket quantiles; REPORTED_ONLY key)
        lifecycle = health.get("lifecycle") or {}
        e2e = lifecycle.get("e2e") or {}
        return {
            "invocations_per_s": round(qps, 1),
            "invocation_p99_ms": e2e.get("p99_ms"),
            "lifecycle_dominant_phase": next(
                (d.get("phase")
                 for d in lifecycle.get("dominant_p99") or []), None),
            "invocations_per_s_rounds": [round(r, 1) for r in rates],
            "invocations_per_s_serial": round(serial_qps, 1),
            "invocations_per_s_serial_pre": round(serial_qps_pre, 1),
            "invocations_per_s_serial_post": round(serial_qps_post, 1),
            "concurrent_vs_serial_speedup": round(qps / serial_qps, 2),
            "invocation_p50_ms": round(p50_ms, 3),
            "n_invocations": total,
            "n_submit_threads": n_threads,
            "shed_retries": sum(shed_retries),
            "ingress": {k: ingress.get(k) for k in (
                "immediateTotal", "batchedTotal", "ticks",
                "avgTickOccupancy", "shedTotal", "queueDepth")},
            "decision_cache": health.get("decisionCache"),
        }
    finally:
        if me is not None:
            me.shutdown()
        try:
            for c in clients:
                c.close()
        except NameError:
            pass
        for p in children:
            p.terminate()
        for p in children:
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001
                p.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        clear_host_aliases()
        get_system_config().reset()
        import shutil

        shutil.rmtree(journal_dir, ignore_errors=True)


def bench_concurrency(quick: bool = False) -> dict:
    """ISSUE 7 concurrency-conformance section: the detector's cost
    envelope and the static gate's runtime.

    - ``lock_plain_ns``: baseline acquire/release of an uninstrumented
      ``threading.Lock`` (the production path — lockcheck off changes
      NOTHING, verified by identity below).
    - ``lockcheck_checked_ns``: acquire/release through the
      CheckedLockFactory wrapper (what FAABRIC_LOCKCHECK=1 test runs
      pay per lock op).
    - ``lockcheck_noop_gate_ns``: the disabled-path decision cost —
      one ``enabled_by_env()`` check, paid once per process at conftest
      import, reported so the "off" path stays ~ns-scale and visible
      round-over-round.
    - ``concheck_static_pass_s``: full guarded-by + protodrift run over
      the package (what tools/check.sh pays per invocation).
    """
    import threading as _threading
    import timeit

    from faabric_tpu.analysis import lockcheck

    out: dict = {}
    n = 50_000 if quick else 200_000

    assert not lockcheck.installed()
    # Production locks are untouched while the detector is off — the
    # no-op path is the original C factory, by identity
    out["lock_factory_untouched"] = _threading.Lock is lockcheck._orig_lock

    plain = _threading.Lock()

    def plain_cycle():
        with plain:
            pass

    out["lock_plain_ns"] = round(
        timeit.timeit(plain_cycle, number=n) / n * 1e9, 1)

    # force_site: bench.py sits at the repo root, outside the factory's
    # caller-scope filter — without it this would measure a plain lock
    checked = lockcheck.CheckedLockFactory(
        False, force_site="bench.py:concurrency")()
    assert type(checked).__name__ == "_CheckedLock"

    def checked_cycle():
        with checked:
            pass

    out["lockcheck_checked_ns"] = round(
        timeit.timeit(checked_cycle, number=n) / n * 1e9, 1)
    lockcheck.reset()

    out["lockcheck_noop_gate_ns"] = round(
        timeit.timeit(lockcheck.enabled_by_env, number=n) / n * 1e9, 1)

    t0 = time.perf_counter()
    try:
        from faabric_tpu.analysis.guards import analyze_paths
        from faabric_tpu.analysis.protodrift import analyze_package

        repo = os.path.dirname(os.path.abspath(__file__))
        n_findings = len(analyze_paths(repo)) + len(analyze_package(repo))
        out["concheck_findings"] = n_findings
        out["concheck_static_pass_s"] = round(time.perf_counter() - t0, 3)
    except Exception as e:  # noqa: BLE001
        out["concheck_error"] = str(e)[:200]
    return out


def bench_perf_introspection(quick: bool = False) -> dict:
    """ISSUE 12: (a) per-sample overhead of the rolling profile store's
    ``observe()`` — every bulk frame send pays this — measured with the
    plane enabled AND as the ``FAABRIC_METRICS=0`` no-op object (the
    contract: disabled must be one no-op method call, nothing more);
    (b) the cluster doctor end-to-end over the built-in synthetic
    cluster (ingest → every analyzer → ranked findings)."""
    from faabric_tpu.runner.doctor import diagnose, selftest_sources
    from faabric_tpu.telemetry.perfprofile import (
        NULL_PERF_STORE,
        PerfProfileStore,
    )

    n = 20_000 if quick else 200_000
    store = PerfProfileStore(label="bench-feed", max_links=64)
    t0 = time.perf_counter()
    for _ in range(n):
        store.observe("peer", "bulk-tcp", 1 << 20, 0.001)
    feed_ns = (time.perf_counter() - t0) / n * 1e9
    t0 = time.perf_counter()
    for _ in range(n):
        NULL_PERF_STORE.observe("peer", "bulk-tcp", 1 << 20, 0.001)
    noop_ns = (time.perf_counter() - t0) / n * 1e9
    sources = selftest_sources()
    t0 = time.perf_counter()
    findings = diagnose(sources)
    doctor_ms = (time.perf_counter() - t0) * 1e3
    return {
        "feed_ns": round(feed_ns, 1),
        "feed_noop_ns": round(noop_ns, 1),
        "doctor_selftest_ms": round(doctor_ms, 2),
        "doctor_findings": len(findings),
    }


def bench_lifecycle(quick: bool = False) -> dict:
    """ISSUE 14: the per-stamp cost of the invocation phase ledger —
    every message pays ~10 of these across its life (admit → record) —
    measured enabled AND as the ``FAABRIC_METRICS=0`` no-op singleton
    (the contract: disabled stamping is one no-op method call,
    identity-checked). Also the fold cost (ledger → per-phase digests)
    the planner pays once per recorded result."""
    from faabric_tpu.proto import message_factory
    from faabric_tpu.telemetry.lifecycle import (
        NULL_LIFECYCLE,
        PHASE_ADMIT,
        PHASE_DISPATCH,
        PHASE_EXEC_QUEUE_EXIT,
        PHASE_QUEUE_EXIT,
        PHASE_RECORDED,
        PHASE_RESULT_PUSH,
        PHASE_RUN_END,
        PHASE_RUN_START,
        PHASE_SCHED,
        Lifecycle,
        LifecycleStats,
        lifecycle_enabled,
    )

    n = 50_000 if quick else 400_000
    lc = Lifecycle()
    msg = message_factory("bench", "noop")
    t0 = time.perf_counter()
    for _ in range(n):
        lc.stamp(msg, PHASE_ADMIT)
    stamp_ns = (time.perf_counter() - t0) / n * 1e9
    t0 = time.perf_counter()
    for _ in range(n):
        NULL_LIFECYCLE.stamp(msg, PHASE_ADMIT)
    noop_ns = (time.perf_counter() - t0) / n * 1e9

    # Fold cost: a full 9-stamp ledger through the planner-side digest
    phases = (PHASE_ADMIT, PHASE_QUEUE_EXIT, PHASE_SCHED, PHASE_DISPATCH,
              PHASE_EXEC_QUEUE_EXIT, PHASE_RUN_START, PHASE_RUN_END,
              PHASE_RESULT_PUSH, PHASE_RECORDED)
    msgs = []
    for i in range(2_000 if quick else 10_000):
        m = message_factory("bench", "noop")
        base = 1_000_000_000 + i * 100_000
        m.lc = {p: base + j * 2_000 for j, p in enumerate(phases)}
        msgs.append(m)
    stats = LifecycleStats()
    t0 = time.perf_counter()
    stats.fold(msgs)
    fold_ns = (time.perf_counter() - t0) / len(msgs) * 1e9
    return {
        "stamp_ns": round(stamp_ns, 1),
        "stamp_noop_ns": round(noop_ns, 1),
        "fold_ns_per_result": round(fold_ns, 1),
        # The identity contract behind the no-op figure
        "enabled_plane_is_real": lifecycle_enabled(),
    }


def bench_continuous_profile(quick: bool = False) -> dict:
    """ISSUE 18: the always-on stack sampler's three contract figures.
    (a) one sampler pass — ``sys._current_frames`` walk +
    ``/proc/self/task`` CPU scan + trie fold — the cost every
    ``FAABRIC_PROFILE_INTERVAL_MS`` tick pays; (b) the sampler's
    measured drag while a CPU-bound workload runs at the default 25 ms
    cadence (acceptance: ≤ 2%); (c) the GIL-pressure drift gauge on an
    idle process (contract: ~0 — a hot reading here means the
    estimator, not the workload, is noisy)."""
    from faabric_tpu.telemetry.profiler import Profiler

    p = Profiler(interval_s=0.025)
    n = 200 if quick else 1_000
    t0 = time.perf_counter()
    for _ in range(n):
        p.sample_now(0.0)
    sample_ns = (time.perf_counter() - t0) / n * 1e9

    # Measured drag = min-of-trials wall time for a FIXED CPU-bound
    # work unit, sampler off vs on. min-of is the low-noise estimator
    # (scheduler preemption only ever ADDS time) and still includes the
    # sampler's cost, which recurs every 25 ms tick regardless. The
    # sampler's self-measured cost share rides as a companion figure —
    # it OVERSTATES under GIL contention (its GIL wait counts toward
    # the sample cost while the workload keeps running).
    def _burn_units(units: int) -> float:
        x = 1
        t0 = time.perf_counter()
        for _ in range(units * 10_000):
            x = (x * 48271) % 2147483647
        return time.perf_counter() - t0

    per_unit = _burn_units(5) / 5
    work = max(1, int((0.3 if quick else 0.8) / per_unit))
    trials = 3 if quick else 5
    # Interleaved off/on pairs so slow container drift (cold caches,
    # background settling) hits both sides equally; median of the
    # per-pair deltas so one descheduled trial on this 1-core container
    # cannot fake (or mask) a regression
    prof = Profiler(interval_s=0.025)
    deltas = []
    for _ in range(trials):
        off_t = _burn_units(work)
        prof.start()
        try:
            on_t = _burn_units(work)
        finally:
            prof.stop()
        if off_t > 0:
            deltas.append((on_t - off_t) / off_t * 100.0)
    busy = prof.snapshot()
    deltas.sort()
    overhead_pct = max(0.0, deltas[len(deltas) // 2]) if deltas else 0.0

    idle = Profiler(interval_s=0.025)
    idle.start()
    try:
        time.sleep(0.5 if quick else 1.0)
    finally:
        idle.stop()
    idle_snap = idle.snapshot()
    return {
        "sample_ns": round(sample_ns, 1),
        "overhead_pct": round(overhead_pct, 3),
        "sampler_cost_pct": busy["overhead_pct"],
        "samples": busy["samples"],
        "gil_pressure_busy": busy["gil"]["pressure"],
        "gil_pressure_idle": idle_snap["gil"]["pressure"],
        "idle_samples": idle_snap["samples"],
    }


def bench_state(quick: bool = False) -> dict:
    """ISSUE 16 state plane: master-image hot reads, replica pull and
    dirty-chunk partial push over a real loopback StateServer, and the
    per-key access ledger's record cost enabled vs the shared
    ``FAABRIC_METRICS=0`` no-op singleton (contract: a disabled state op
    pays one no-op method call — tens of ns, not a locked dict walk)."""
    from faabric_tpu.state import STATE_CHUNK_SIZE, State, StateKeyValue
    from faabric_tpu.state.remote import StateClient, StateServer
    from faabric_tpu.telemetry.statestats import (
        NULL_STATE_STATS,
        StateStatsStore,
    )
    from faabric_tpu.transport.client_pool import ClientPool
    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )

    # Ledger feed cost: one private store so the figures are not skewed
    # by whatever the process-wide ledger already holds
    n = 20_000 if quick else 200_000
    store = StateStatsStore(max_keys=64)
    t0 = time.perf_counter()
    for _ in range(n):
        store.record("bench/blob", "get", nbytes=4096)
    record_ns = (time.perf_counter() - t0) / n * 1e9
    t0 = time.perf_counter()
    for _ in range(n):
        NULL_STATE_STATS.record("bench/blob", "get", nbytes=4096)
    record_noop_ns = (time.perf_counter() - t0) / n * 1e9

    # Hot read: one-chunk get_chunk against the local master image — the
    # per-step cost a training loop pays re-reading unchanged state
    size = (1 << 20) if quick else (4 << 20)
    master_state = State("benchstateA")
    kv = master_state.get_kv("bench", "blob", size)
    kv.set(b"\x5a" * size)
    reads = 5_000 if quick else 50_000
    t0 = time.perf_counter()
    for _ in range(reads):
        kv.get_chunk(0, STATE_CHUNK_SIZE)
    hot_read_ns = (time.perf_counter() - t0) / reads * 1e9

    # Replica ↔ master chunk protocol over real loopback TCP. Stay clear
    # of the ephemeral port range (>=32768)
    base = random.randint(10, 200) * 100
    register_host_alias("benchstateA", "127.0.0.1", base)
    register_host_alias("benchstateB", "127.0.0.1", base + 1000)
    server = StateServer(master_state, "benchstateA")
    server.start()
    pool = ClientPool(StateClient)
    backup_server = None
    try:
        rkv = StateKeyValue("bench", "blob", size, False, "benchstateA",
                            client_factory=pool.get,
                            local_host="benchstateB")
        pulls = 2 if quick else 6
        rkv.pull()  # warm the connection / cold path
        t0 = time.perf_counter()
        for _ in range(pulls):
            rkv.pull()
        pull_gibs = pulls * size / (time.perf_counter() - t0) / 2**30

        # Partial push: every other chunk dirty, so only half the value
        # travels — the dirty-mask path, not a full-value copy
        pushes = 2 if quick else 6
        chunk = b"\xa5" * STATE_CHUNK_SIZE
        push_s, push_bytes = 0.0, 0
        for _ in range(pushes):
            for off in range(0, size, 2 * STATE_CHUNK_SIZE):
                rkv.set_chunk(off, chunk)
            dirty = rkv.n_dirty_chunks()
            t0 = time.perf_counter()
            rkv.push_partial()
            push_s += time.perf_counter() - t0
            push_bytes += dirty * STATE_CHUNK_SIZE
        push_gibs = push_bytes / push_s / 2**30

        # Replicated write path (ISSUE 19): the same dirty-chunk client
        # push, but the master synchronously forwards every acked chunk
        # to a backup host BEFORE responding — the honest cost of
        # FAABRIC_STATE_REPLICAS=1 vs push_partial_gibs above (the
        # FAABRIC_STATE_REPLICAS=0 figure)
        register_host_alias("benchstateC", "127.0.0.1", base + 2000)
        backup_state = State("benchstateC")
        backup_server = StateServer(backup_state, "benchstateC")
        backup_server.start()
        mkv = master_state.get_kv("bench", "rblob", size)
        mkv.set(b"\x5a" * size)
        mkv.adopt_placement("benchstateC", 1)
        rkv2 = StateKeyValue("bench", "rblob", size, False, "benchstateA",
                             client_factory=pool.get,
                             local_host="benchstateB", epoch=1)
        rkv2.pull()
        rep_s, rep_bytes = 0.0, 0
        for _ in range(pushes):
            for off in range(0, size, 2 * STATE_CHUNK_SIZE):
                rkv2.set_chunk(off, chunk)
            dirty = rkv2.n_dirty_chunks()
            t0 = time.perf_counter()
            rkv2.push_partial()
            rep_s += time.perf_counter() - t0
            rep_bytes += dirty * STATE_CHUNK_SIZE
        replicated_gibs = rep_bytes / rep_s / 2**30

        # Epoch-fenced failover end to end over real loopback: planner
        # drops the master -> backup promoted (PROMOTE RPC, with
        # self-promotion as the fallback) -> the stale master's next
        # forward is fenced -> the client re-resolves and its write
        # acks on the new master. Measured remove_host -> first ack.
        from faabric_tpu.planner.planner import Planner

        planner = Planner()
        planner.register_host("benchstateA", 2, 0)
        planner.register_host("benchstateC", 2, 0)
        fm, fb, fe = planner.claim_state_master("bench", "fo",
                                                "benchstateA")
        fsize = 1 << 20
        fkv = master_state.get_kv("bench", "fo", fsize)
        fkv.set(b"\x11" * fsize)
        fkv.adopt_placement(fb, fe)
        ckv = StateKeyValue(
            "bench", "fo", fsize, False, "benchstateA",
            client_factory=pool.get, local_host="benchstateB",
            epoch=fe,
            resolver=lambda: planner.claim_state_master(
                "bench", "fo", "benchstateB"))
        ckv.set_chunk(0, chunk)
        ckv.push_partial()  # acked baseline: the backup holds a replica
        failover_s = None
        t0 = time.perf_counter()
        planner.remove_host("benchstateA")
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                ckv.set_chunk(0, chunk)
                ckv.push_partial()
                failover_s = time.perf_counter() - t0
                break
            except Exception:  # noqa: BLE001 — fenced mid-failover
                time.sleep(0.005)
    finally:
        pool.close_all()
        server.stop()
        if backup_server is not None:
            backup_server.stop()
        clear_host_aliases()

    return {
        "hot_read_ns": round(hot_read_ns, 1),
        "pull_gibs": round(pull_gibs, 4),
        "push_partial_gibs": round(push_gibs, 4),
        "replicated_push_gibs": round(replicated_gibs, 4),
        "master_failover_s": (round(failover_s, 4)
                              if failover_s is not None else None),
        "record_ns": round(record_ns, 1),
        "record_noop_ns": round(record_noop_ns, 1),
        "value_mib": size >> 20,
    }


def bench_robustness(quick: bool = False) -> dict:
    """ISSUE 2 robustness section: recovery latency under worker loss.

    Stands up a real planner + 2 worker PROCESSES (tests/dist/procs.py),
    spreads a sleep batch over both, SIGKILLs one worker mid-batch and
    measures kill → batch-complete: keep-alive expiry detection + the
    planner's requeue-with-backoff onto the survivor + re-execution.
    Also measures the disabled fault-point hot-path cost (the shared
    no-op handle) so regressions in the "faults off" overhead are
    caught by the round-over-round JSON."""
    import signal
    import subprocess
    import tempfile
    import timeit

    from faabric_tpu.faults import NULL_FAULT
    from faabric_tpu.transport.common import clear_host_aliases
    from faabric_tpu.util.config import get_system_config

    # Disabled-path overhead: one fire() on the shared no-op handle
    n = 200_000
    noop_ns = timeit.timeit(NULL_FAULT.fire, number=n) / n * 1e9

    b = random.randint(10, 120) * 100
    aliases = (f"rbpl=127.0.0.1+{b},rbw0=127.0.0.1+{b + 2500},"
               f"rbw1=127.0.0.1+{b + 5000},rbcli=127.0.0.1+{b + 7500}")
    # Every process (planner + workers) records into the flight ring and
    # dumps on its trigger; the section reports the merged black box
    flight_dir = tempfile.mkdtemp(prefix="bench_flight_")
    knobs = {"PLANNER_HOST_TIMEOUT": "3", "PLANNER_REQUEUE_BACKOFF": "0.3",
             "PLANNER_MAX_REQUEUES": "5",
             "FAABRIC_FLIGHT_DIR": flight_dir}
    env = {**os.environ, "FAABRIC_HOST_ALIASES": aliases,
           "JAX_PLATFORMS": "cpu", **knobs}
    saved = {k: os.environ.get(k)
             for k in ["FAABRIC_HOST_ALIASES", *knobs]}
    os.environ.update({"FAABRIC_HOST_ALIASES": aliases, **knobs})
    clear_host_aliases()
    get_system_config().reset()

    children = []

    def spawn(*args):
        return _spawn_ready_child(children, env, *args)

    me = None
    try:
        spawn("planner", str(b))
        spawn("worker", "rbw0", "rbpl", "8")
        victim = spawn("worker", "rbw1", "rbpl", "4")

        from faabric_tpu.executor import ExecutorFactory
        from faabric_tpu.proto import ReturnValue, batch_exec_factory
        from faabric_tpu.runner import WorkerRuntime

        class NullFactory(ExecutorFactory):
            def create_executor(self, msg):
                raise RuntimeError("client runs nothing")

        me = WorkerRuntime(host="rbcli", slots=0, factory=NullFactory(),
                           planner_host="rbpl")
        me.start()

        task_s = 1.0 if quick else 2.5
        req = batch_exec_factory("dist", "sleep", 12)
        for m in req.messages:
            m.input_data = str(task_s).encode()
        decision = me.planner_client.call_functions(req)
        n_on_victim = sum(1 for h in decision.hosts if h == "rbw1")
        assert n_on_victim, decision.hosts

        time.sleep(0.5)
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=5)
        t_kill = time.perf_counter()

        deadline = time.time() + 90
        status = me.planner_client.get_batch_results(req.app_id)
        while not status.finished and time.time() < deadline:
            time.sleep(0.1)
            status = me.planner_client.get_batch_results(req.app_id)
        kill_to_complete = time.perf_counter() - t_kill
        ok = status.finished and all(
            m.return_value == int(ReturnValue.SUCCESS)
            for m in status.message_results)

        # Black-box check: the SIGKILL scenario must leave flight dumps
        # (the planner dumps on the recovery requeue; survivors on any
        # abort) — the merged ring is the section's post-mortem evidence
        from faabric_tpu.runner import flightdump

        merged = flightdump.merge(flight_dir)
        flight = {
            "dumps": len(flightdump.load_dumps(flight_dir)),
            "events": len(merged),
            "kinds": sorted({e.get("kind", "?") for e in merged}),
        }
        out = {
            "kill_to_complete_s": round(kill_to_complete, 3),
            "recovered_messages": n_on_victim,
            "n_messages": 12, "task_s": task_s,
            "host_timeout_s": 3.0, "requeue_backoff_s": 0.3,
            "all_success": ok,
            "noop_fault_point_ns": round(noop_ns, 1),
            "flight": flight,
        }
    finally:
        if me is not None:
            me.shutdown()
        for p in children:
            p.terminate()
        for p in children:
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001
                p.kill()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        clear_host_aliases()
        get_system_config().reset()
        import shutil

        shutil.rmtree(flight_dir, ignore_errors=True)

    # ISSUE 4: journal micro-costs + the planner-crash recovery blip
    # (each phase manages its own processes/env; a failure records the
    # error rather than voiding the section)
    try:
        out["journal"] = _bench_journal_micro(quick)
    except Exception as e:  # noqa: BLE001
        out["journal_error"] = str(e)[:200]
    try:
        out.update(_bench_planner_restart(quick))
    except Exception as e:  # noqa: BLE001
        out["planner_restart_error"] = str(e)[:200]
    # ISSUE 6: planned-disruption latencies (live migration pause,
    # freeze→thaw resume, host-pair partition heal)
    out.update(_bench_lifecycle(quick))
    return out


def _bench_lifecycle(quick: bool = False) -> dict:
    """ISSUE 6 planned-disruption metrics, one scenario per key:

    - ``migration_pause_ms``: worst staying-rank pause while a 3-rank
      MPI world under all-to-all traffic live-migrates (consolidation)
      — prepare_migration to first completed post-migration round.
    - ``thaw_to_first_result_s``: spot-frozen THREADS app (snapshot
      parked on the planner) thawed onto a different host — thaw
      request to first restored result.
    - ``partition_heal_s``: worst per-rank MpiWorldAborted latency when
      the fault registry partitions a worker pair one-directionally
      (the far side heals through the planner's abort relay).

    Each scenario stands up its own ChaosCluster (tests/dist) and
    records an error key instead of voiding the section on failure.
    The scenario choreography mirrors tests/dist/test_lifecycle.py —
    the TESTS carry the correctness assertions (placement, restored
    state, no result loss); these copies are deliberately
    assert-light so a degraded scenario reports an error key rather
    than aborting the whole bench round. Change the scenarios THERE
    first and mirror here."""
    root = os.path.dirname(os.path.abspath(__file__))
    if root not in sys.path:
        sys.path.insert(0, root)
    from faabric_tpu.proto import (
        BatchExecuteType,
        ReturnValue,
        batch_exec_factory,
    )
    from tests.dist.test_chaos import ChaosCluster, wait_finished

    out: dict = {}

    # -- live migration under traffic ---------------------------------
    try:
        cluster = ChaosCluster("bmM", n_workers=2, slots=(4, 4)).start()
        try:
            me = cluster.me
            for count in (2, 3):
                blk = batch_exec_factory("dist", "sleep", count)
                for m in blk.messages:
                    m.input_data = b"3.0" if quick else b"4.0"
                me.planner_client.call_functions(blk)
            req = batch_exec_factory("dist", "mpi_migrate_traffic", 1)
            req.messages[0].mpi_rank = 0
            me.planner_client.call_functions(req)
            status = wait_finished(me, req.app_id, timeout=90)
            pauses = []
            for m in status.message_results:
                if m.return_value != int(ReturnValue.SUCCESS):
                    raise RuntimeError(f"migration rank failed: "
                                       f"{m.output_data!r}")
                pause = float(m.output_data.decode().rsplit(":", 1)[1])
                if pause >= 0:
                    pauses.append(pause)
            if not pauses:
                raise RuntimeError("no staying rank measured a pause")
            out["migration_pause_ms"] = round(max(pauses), 1)
        finally:
            cluster.stop()
    except Exception as e:  # noqa: BLE001
        out["migration_error"] = str(e)[:200]

    # -- spot freeze → thaw on a different host -----------------------
    try:
        import urllib.request

        import numpy as np

        from faabric_tpu.endpoint import HttpMessageType
        from faabric_tpu.snapshot import SnapshotData

        cluster = ChaosCluster(
            "bmS", n_workers=2, slots=(4, 4),
            extra_env={"BATCH_SCHEDULER_MODE": "spot"})
        http_port = cluster.base + 3100
        cluster.env["DIST_HTTP_PORT"] = str(http_port)
        cluster.start()
        try:
            me = cluster.me
            req = batch_exec_factory("dist", "spot", 2)
            req.type = int(BatchExecuteType.THREADS)
            for i, m in enumerate(req.messages):
                m.group_idx = i
            req.snapshot_key = f"dist/spot_{req.app_id}"
            me.snapshot_registry.register_snapshot(
                req.snapshot_key,
                SnapshotData(np.zeros(16384, np.uint8).tobytes()))
            d = me.planner_client.call_functions(req)
            victim = d.hosts[0]
            time.sleep(1.0)
            blockers = batch_exec_factory("dist", "sleep", 4)
            for m in blockers.messages:
                m.input_data = b"4"
            me.planner_client.call_functions(blockers)
            body = json.dumps({
                "http_type": int(HttpMessageType.SET_NEXT_EVICTED_VM),
                "payload": victim}).encode()
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{http_port}/", data=body,
                method="POST"), timeout=10).read()
            me.planner_client.check_migration(req.app_id)
            deadline = time.time() + 20
            while time.time() < deadline:
                if me.planner_client.get_scheduling_decision(
                        req.app_id) is None:
                    break
                time.sleep(0.2)
            time.sleep(1.0)
            wait_finished(me, blockers.app_id, timeout=30)
            thaw = batch_exec_factory("dist", "spot", 1)
            thaw.app_id = req.app_id
            t_thaw = time.perf_counter()
            d2 = me.planner_client.call_functions(thaw)
            first = me.planner_client.get_message_result(
                req.app_id, d2.message_ids[0], timeout=30.0)
            thaw_s = time.perf_counter() - t_thaw
            if first.return_value != int(ReturnValue.SUCCESS) \
                    or not first.output_data.startswith(b"thawed:"):
                raise RuntimeError(f"thaw failed: {first.output_data!r}")
            out["thaw_to_first_result_s"] = round(thaw_s, 3)
        finally:
            cluster.stop()
    except Exception as e:  # noqa: BLE001
        out["thaw_error"] = str(e)[:200]

    # -- host-pair partition heal -------------------------------------
    try:
        w0, w1 = "bmNw0", "bmNw1"
        partition = ";".join([
            f"transport.send=kill_conn@src={w1}@host={w0}@times=400",
            f"transport.bulk=kill_conn@src={w1}@dest={w0}"
            "@after=200@times=400",
        ])
        cluster = ChaosCluster(
            "bmN", n_workers=2, slots=(4, 4),
            extra_env={"MPI_ABORT_CHECK_SECONDS": "1",
                       "PLANNER_HOST_TIMEOUT": "30"},
            worker_env={"FAABRIC_FAULTS": partition}).start()
        try:
            me = cluster.me
            req = batch_exec_factory("dist", "mpi_partition", 1)
            req.messages[0].mpi_rank = 0
            me.planner_client.call_functions(req)
            status = wait_finished(me, req.app_id, timeout=90)
            aborted = []
            for m in status.message_results:
                if m.return_value != int(ReturnValue.SUCCESS):
                    raise RuntimeError(f"partition rank failed: "
                                       f"{m.output_data!r}")
                aborted.append(float(m.output_data.split(b":")[1]))
            out["partition_heal_s"] = round(max(aborted), 3)
        finally:
            cluster.stop()
    except Exception as e:  # noqa: BLE001
        out["partition_error"] = str(e)[:200]

    return out


def _sendrecv_sizes() -> list[int]:
    """Reference mpi_send_recv.cpp workload shape (mpi_bench.cpp:18-57):
    a 'small' burst of 1000×8-int messages plus a ResNet-50-scale mix of
    variably-sized gradient buckets. The mix below reproduces the
    magnitude profile (a few multi-MiB conv buckets, a long tail of
    sub-KiB bn/bias buckets, ~25.5M ints total) without copying the
    verbatim per-layer table."""
    import numpy as np

    sizes = [8] * 1000
    rng = np.random.RandomState(50)
    big = [2359296, 2097152, 1048576, 1048576, 1048576, 1048576,
           589824, 589824, 524288, 262144, 262144, 262144, 147456,
           131072, 65536, 36864, 16384, 9408]
    sizes += big * 3
    small_tail = rng.choice([64, 128, 256, 512, 1024, 2048], 400).tolist()
    sizes += [int(s) for s in small_tail]
    total = sum(sizes)
    target = 25_500_000
    if total < target:
        sizes.append(target - total)
    return sizes


def _sendrecv_warmup_sizes() -> list[int]:
    """Element counts that establish every data-plane path before the
    clock starts: one over-threshold frame per data stripe (each dials
    its connection and creates/announces its shm ring) plus one small
    frame for the control stripe. Connection + 32 MiB-ring setup is a
    one-time ~100 ms cost that would otherwise be billed to a ~100 ms
    steady-state measurement."""
    from faabric_tpu.transport.bulk import BULK_STRIPES, BULK_THRESHOLD

    return [BULK_THRESHOLD // 4 + 1] * max(1, BULK_STRIPES) + [8]


def _sendrecv_worker_main() -> None:
    """Child process body for the cross-process send/recv bench: rank 2
    on xbenchB receives the warmup frames then the full size
    distribution from rank 0, and acks with one byte so the parent's
    clock includes wire drain."""
    import numpy as np

    broker, server, world = _bench_world("xbenchB", app_id=4)
    print("READY", flush=True)
    try:
        sizes = _sendrecv_sizes()
        # Handshake instead of a barrier: only ranks 0 and 2 are driven
        world.send(2, 0, np.array([7], np.int32))
        for n in _sendrecv_warmup_sizes():
            world.recv(0, 2)
        world.send(2, 0, np.array([7], np.int32))  # warm-up drained
        ok = True
        for n in sizes:
            got, _ = world.recv(0, 2)
            ok = ok and got.size == n
        world.send(2, 0, np.array([1 if ok else 0], np.int32))
        print("DONE" if ok else "FAILED size mismatch", flush=True)
    finally:
        server.stop()
        broker.clear()


def bench_host_sendrecv_procs() -> dict:
    """MPI point-to-point rate across OS processes (the reference's
    second headline harness, mpi_send_recv.cpp:13-48): rank 0 streams
    the size distribution to rank 2 over the bulk plane; rate =
    total workload bytes / wall time, as mpi_bench.cpp:60-85 reports."""
    import subprocess

    import numpy as np

    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )

    base_a = random.randint(10, 120) * 100
    base_b = base_a + 3000
    clear_host_aliases()
    register_host_alias("xbenchA", "127.0.0.1", base_a)
    register_host_alias("xbenchB", "127.0.0.1", base_b)
    env = {**os.environ,
           "FAABRIC_HOST_ALIASES":
           f"xbenchA=127.0.0.1+{base_a},xbenchB=127.0.0.1+{base_b}"}
    broker, server, world = _bench_world("xbenchA", app_id=4)
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--sendrecv-worker"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        line = child.stdout.readline().strip()
        assert line == "READY", f"worker said {line!r}"
        sizes = _sendrecv_sizes()
        bufs = [np.zeros(n, np.int32) for n in sizes]
        hello, _ = world.recv(2, 0)  # receiver up (no barrier: 2 ranks)
        assert int(hello[0]) == 7
        # Establish every stripe + ring outside the clock (steady-state
        # data-plane rate, not connection setup)
        for n in _sendrecv_warmup_sizes():
            world.send(0, 2, np.zeros(n, np.int32))
        warm, _ = world.recv(2, 0)
        assert int(warm[0]) == 7
        t0 = time.perf_counter()
        for buf in bufs:
            world.send(0, 2, buf)
        ack, _ = world.recv(2, 0)
        elapsed = time.perf_counter() - t0
        assert int(ack[0]) == 1, "receiver saw wrong sizes"
        status = child.stdout.readline().strip()
        assert status == "DONE", f"worker reported: {status!r}"
        workload = sum(sizes) * 4
        return {"rate_gibs": workload / elapsed / (1 << 30),
                "workload_mib": workload / (1 << 20),
                "n_messages": len(sizes), "n_processes": 2}
    finally:
        server.stop()
        broker.clear()
        try:
            child.wait(timeout=10)
        except Exception:  # noqa: BLE001
            child.kill()
        clear_host_aliases()


def _count_params(params) -> int:
    import jax

    return sum(int(x.size) for x in jax.tree.leaves(params))


def _fenced_loop_time(run, fence, n_hi: int, n_lo: int = 1):
    """Wall-times ``fence(run(n))`` at two loop lengths and returns
    (per_iter_s, overhead_s): the slope cancels the constant per-call
    dispatch + fence cost, and overhead is that constant (t_lo minus
    n_lo iterations' worth). ``run(n)`` must execute its n iterations ON
    the device (a lax loop inside one jit, each iteration data-dependent
    on the last) and ``fence`` must pull a scalar to the host, so the
    timing ends when the device does and not when the call returns.

    A non-positive slope means timing jitter swamped the measurement:
    per_iter_s comes back None (callers must mark the number invalid,
    never fabricate throughput from a clamp)."""
    fence(run(n_lo))  # compile both trip counts
    fence(run(n_hi))
    t0 = time.perf_counter()
    fence(run(n_lo))
    t_lo = time.perf_counter() - t0
    t0 = time.perf_counter()
    fence(run(n_hi))
    t_hi = time.perf_counter() - t0
    per = (t_hi - t_lo) / (n_hi - n_lo)
    if per <= 0:
        return None, t_lo
    return per, max(0.0, t_lo - n_lo * per)


def bench_device_probe() -> dict:
    """Cheapest possible proof the device answers: one tiny compiled op,
    timed end to end (backend init + compile + execute + readback). This
    is the first section of the device stage so the watchdog learns
    within one budget whether a chip answers at all."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    devices = jax.devices()
    t_init = time.perf_counter() - t0
    x = jnp.arange(8, dtype=jnp.float32)
    t0 = time.perf_counter()
    y = jax.jit(lambda v: v * 2 + 1)(x)
    val = float(y[3])
    t_op = time.perf_counter() - t0
    assert val == 7.0
    return {"platform": devices[0].platform,
            "device_kind": getattr(devices[0], "device_kind", ""),
            "n_devices": len(devices),
            "init_s": round(t_init, 3), "first_op_s": round(t_op, 3)}


# The flagship attention shape and a long-context one (B, S, H, D)
_ATTENTION_SHAPES = [(8, 512, 8, 64), (1, 4096, 8, 64)]


def bench_pallas_compile() -> dict:
    """Lower + compile the Pallas kernels on the real backend (Mosaic on
    TPU) WITHOUT running them — cheap, and catches Mosaic rejections that
    interpreter-mode CPU testing cannot: a small shape and the shapes
    bench_device_attention times. Records per-kernel compile wall time."""
    import jax
    import jax.numpy as jnp

    from faabric_tpu.ops import flash_attention, rms_norm

    if jax.default_backend() != "tpu":
        raise RuntimeError("Mosaic lowering needs a TPU backend, found "
                           + jax.default_backend())

    xs = jnp.zeros((4, 256, 512), jnp.bfloat16)
    sc = jnp.ones((512,), jnp.float32)
    out: dict = {}

    def timed(name, build):
        t0 = time.perf_counter()
        build()
        out[name + "_compile_s"] = round(time.perf_counter() - t0, 3)

    grad_fn = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c).astype(jnp.float32)), argnums=(0, 1, 2))
    for shape in [(2, 256, 4, 64), *_ATTENTION_SHAPES]:
        q = jnp.zeros(shape, jnp.bfloat16)
        tag = "s%d" % shape[1]
        timed(f"flash_fwd_{tag}", lambda: jax.jit(flash_attention)
              .lower(q, q, q).compile())
        timed(f"flash_bwd_{tag}", lambda: jax.jit(grad_fn)
              .lower(q, q, q).compile())
    timed("rms_norm", lambda: jax.jit(rms_norm).lower(xs, sc).compile())
    out["mosaic_ok"] = True
    return out


# Step shapes: "tiny" proves the train-step path fast (first TPU number
# inside the watchdog's first budget); "full" is the flagship config the
# rest of the repo uses; "large" is sized so the MXU sees real work
# (d_model=1024 matmuls, ~110M params) and the MFU number means something.
_STEP_SIZES = {
    "tiny": dict(vocab_size=1024, d_model=128, n_layers=2, n_heads=4,
                 d_ff=512, max_seq=128, seq=128, batch_per_dev=2),
    "full": dict(vocab_size=8192, d_model=512, n_layers=4, n_heads=8,
                 d_ff=2048, max_seq=512, seq=512, batch_per_dev=8),
    "large": dict(vocab_size=16384, d_model=1024, n_layers=8, n_heads=16,
                  d_ff=4096, max_seq=1024, seq=1024, batch_per_dev=8),
}


def bench_device_step(size: str = "full", attention_impl: str = "auto",
                      norm_impl: str = "auto") -> dict:
    """Flagship model compiled train step on the available device."""
    import jax
    import numpy as np

    from faabric_tpu.models import (
        ModelConfig,
        data_sharding,
        init_train_state,
    )
    from faabric_tpu.models.transformer import resolve_impls
    from faabric_tpu.parallel import MeshConfig, build_mesh

    devices = jax.devices()
    n = len(devices)
    sz = dict(_STEP_SIZES[size])
    seq, batch = sz.pop("seq"), sz.pop("batch_per_dev") * n
    cfg = ModelConfig(attention_impl=attention_impl, norm_impl=norm_impl,
                      **sz)
    mesh = build_mesh(devices, MeshConfig())
    params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg, mesh)

    rng = np.random.RandomState(0)
    tokens = jax.device_put(
        rng.randint(0, cfg.vocab_size, (batch, seq), dtype=np.int32),
        data_sharding(mesh))
    targets = jax.device_put(
        rng.randint(0, cfg.vocab_size, (batch, seq), dtype=np.int32),
        data_sharding(mesh))

    # The n-steps-per-dispatch form: timing threads the (donated) state
    # through each call, fencing on a loss readback; the (t8 − t1)/7
    # slope cancels the per-call dispatch cost
    from faabric_tpu.models import make_multi_step

    run = make_multi_step(cfg, mesh)
    n_params = _count_params(params)
    n_lo, n_hi = 1, 8
    # Two warm passes per trip count: the first compiles, the second
    # absorbs the relayout-recompile that donated carries can trigger
    # when one variant's output layout feeds the other variant
    for k in (n_lo, n_hi, n_lo, n_hi):
        params, opt_state, loss = run(params, opt_state, tokens, targets, k)
        float(loss)
    t0 = time.perf_counter()
    params, opt_state, loss = run(params, opt_state, tokens, targets, n_lo)
    float(loss)
    t_lo = time.perf_counter() - t0
    t0 = time.perf_counter()
    params, opt_state, loss = run(params, opt_state, tokens, targets, n_hi)
    float(loss)
    t_hi = time.perf_counter() - t0
    per_step = (t_hi - t_lo) / (n_hi - n_lo)
    invalid = per_step <= 0

    resolved = resolve_impls(cfg, mesh)
    out = {
        "platform": devices[0].platform,
        "device_kind": getattr(devices[0], "device_kind", ""),
        "n_devices": n,
        "size": size,
        "attention_impl": resolved.attention_impl,
        "norm_impl": resolved.norm_impl,
        "step_ms": None if invalid else 1000 * per_step,
        "dispatch_ms": (1000 * t_lo if invalid
                        else 1000 * max(0.0, t_lo - n_lo * per_step)),
        "tokens_per_s": None if invalid else batch * seq / per_step,
        "loss": float(loss),
        "n_params": n_params,
    }
    if invalid:
        out["error"] = "timing jitter swamped the step slope"
    tokens_per_s = out["tokens_per_s"]
    # MFU: train step ≈ 6·N FLOPs/token (2 fwd + 4 bwd), vs platform peak
    if tokens_per_s:
        model_flops = 6.0 * out["n_params"] * tokens_per_s
        out["mfu"] = model_flops / (
            _tpu_spec(out["device_kind"])["peak_flops"] * n)
    return out


def bench_device_allreduce(mibs: list | None = None) -> dict:
    """DeviceCollectives.allreduce bandwidth curve (north star #1,
    BASELINE.json; workload analog mpi_bench.cpp:60-85).

    Bus bandwidth uses the NCCL convention 2·(n−1)/n·S/t with S = bytes
    per rank. pct_of_ici_ring compares against 2·ICI-link bandwidth (a
    bidirectional ring over one torus axis) and needs n ≥ 2 TPU chips;
    on a single chip the collective is a compiled no-op, so the curve is
    recorded but the ICI percentage is marked unavailable.
    """
    import jax
    import numpy as np

    from faabric_tpu.mpi.types import MpiOp
    from faabric_tpu.parallel.collectives import DeviceCollectives

    devices = jax.devices()
    n = len(devices)
    col = DeviceCollectives(devices)

    if mibs is None:
        mibs = [1, 16, 128, 1024]
    curve = []
    for mib in mibs:
        elems = mib * (1 << 20) // 4  # float32, per rank
        try:
            x = col.shard_stacked(
                [np.full(elems, r, np.float32) for r in range(n)])
            # n chained collectives per dispatch (allreduce_loop), fenced
            # by a scalar readback; the two-point slope cancels dispatch.
            # n_lo=2 (not 1): allreduce_loop's post-loop SUM rescale only
            # exists for n >= 2, so with n_lo=1 the slope would charge
            # that constant full-buffer pass to per-hop time (ADVICE r3).
            # Bound total work at the GiB end: n_hi=4 keeps the slope
            # while the stage watchdog budget stays safe
            dt, over_s = _fenced_loop_time(
                lambda k: col.allreduce_loop(x, k, MpiOp.SUM),
                lambda y: float(y.reshape(-1)[0]),
                4 if mib >= 1024 else 8, n_lo=2)
            s_bytes = elems * 4
            if dt is None:
                entry = {"payload_mib": mib,
                         "error": "timing jitter swamped the slope"}
            else:
                bus_bw = (2 * (n - 1) / n * s_bytes / dt if n > 1
                          else s_bytes / dt)
                entry = {"payload_mib": mib, "time_ms": dt * 1000,
                         "dispatch_ms": over_s * 1000,
                         "bus_gibs": bus_bw / (1 << 30)}
            del x
            curve.append(entry)
        except Exception as e:  # noqa: BLE001 — OOM at the big end is data
            curve.append({"payload_mib": mib, "error": str(e)[:120]})
            break

    result = {"platform": devices[0].platform, "n_devices": n,
              "curve": curve}
    spec = _tpu_spec(devices[0].device_kind)
    if spec["ici_link_bw"] and n > 1:
        ring_bw = 2 * spec["ici_link_bw"]
        best = max((c.get("bus_gibs", 0) for c in curve), default=0)
        result["ici_ring_gibs"] = ring_bw / (1 << 30)
        result["pct_of_ici_ring"] = 100.0 * best * (1 << 30) / ring_bw
    elif n == 1:
        result["ici_note"] = ("single chip: allreduce is a compiled no-op; "
                              "ICI % needs >= 2 chips (driver dryrun "
                              "validates the multi-chip path)")
    return result


def bench_device_attention(shapes: list | None = None) -> dict:
    """Flash vs reference attention, fwd and fwd+bwd, at the flagship
    shape AND a long-context shape (where the O(S²) reference starts
    paying for its score matrix) — the kernel-level evidence for the
    Pallas path. Iterations chain on device (scan feeding each output
    back as the next input) so the timing sees the kernels, not the
    per-call dispatch."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from faabric_tpu.ops import flash_attention
    from faabric_tpu.ops.flash_attention import _reference_attention

    if jax.default_backend() != "tpu":
        # Interpret-mode Pallas (CPU) is an emulator — timing it says
        # nothing; the flash-vs-reference comparison is TPU-only
        raise RuntimeError("flash kernel micro-bench needs a TPU backend, "
                           "found " + jax.default_backend())

    if shapes is None:
        shapes = _ATTENTION_SHAPES
    impls = [("flash", flash_attention),
             ("reference", lambda q, k, v: _reference_attention(q, k, v))]
    out: dict = {"shapes": [list(s) for s in shapes]}
    for b, s, h, d in shapes:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        sec: dict = {}
        for name, fn in impls:
            # fwd chain: output shape == q shape, and attention outputs
            # are convex combinations of v, so values stay bounded
            @functools.partial(jax.jit, static_argnames="n")
            def run_f(q, k, v, n, fn=fn):
                def body(carry, _):
                    return fn(carry, k, v).astype(carry.dtype), None
                y, _ = jax.lax.scan(body, q, None, length=n)
                return y

            grad_fn = jax.grad(
                lambda q, k, v, fn=fn: jnp.sum(
                    fn(q, k, v).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))

            # fwd+bwd chain: feed normalized grads back as next inputs
            # (normalization keeps values finite; its cost is O(S·D),
            # noise next to the O(S²·D) attention)
            @functools.partial(jax.jit, static_argnames="n")
            def run_fb(q, k, v, n, grad_fn=grad_fn):
                def norm(g):
                    g32 = g.astype(jnp.float32)
                    return (g32 / (1.0 + jnp.max(jnp.abs(g32))))

                def body(carry, _):
                    dq, dk, dv = grad_fn(*carry)
                    return (norm(dq).astype(carry[0].dtype),
                            norm(dk).astype(carry[1].dtype),
                            norm(dv).astype(carry[2].dtype)), None
                (q2, _, _), _ = jax.lax.scan(body, (q, k, v), None, length=n)
                return q2

            fence = lambda y: float(y.reshape(-1)[0])  # noqa: E731
            # Per-impl isolation: an OOM at the long-context shape (the
            # O(S²) reference's score matrices) must not discard the
            # numbers already measured for the other impl/shape
            try:
                per_f, _ = _fenced_loop_time(
                    lambda n: run_f(q, k, v, n), fence, 8)
                sec[name + "_fwd_ms"] = (None if per_f is None
                                         else per_f * 1000)
            except Exception as e:  # noqa: BLE001
                sec[name + "_fwd_error"] = str(e)[:120]
            try:
                per_fb, _ = _fenced_loop_time(
                    lambda n: run_fb(q, k, v, n), fence, 8)
                sec[name + "_fwdbwd_ms"] = (None if per_fb is None
                                            else per_fb * 1000)
            except Exception as e:  # noqa: BLE001
                sec[name + "_fwdbwd_error"] = str(e)[:120]
        for tag in ("fwd", "fwdbwd"):
            fl = sec.get(f"flash_{tag}_ms")
            ref = sec.get(f"reference_{tag}_ms")
            if fl and ref:
                sec[f"flash_speedup_{tag}"] = ref / fl
        out[f"s{s}"] = sec
    return out


def bench_device_snapshot(mib: int = 256) -> dict:
    """DeviceSnapshot dirty-page scan + diff extraction on the device
    (snapshot/device_snapshot.py — the no-mprotect-on-HBM design): how
    fast a sparse change in a big HBM value is detected and pulled."""
    import jax.numpy as jnp

    from faabric_tpu.snapshot import DeviceSnapshot

    n = mib * (1 << 20) // 4
    arr = jnp.arange(n, dtype=jnp.float32)
    snap = DeviceSnapshot(arr)
    new = arr.at[n // 2].set(0.0).at[7].set(-1.0).at[n - 1].set(3.0)

    snap.dirty_pages(new)  # compile + warm the flags kernel
    snap.diff(new)         # ...and the gather kernel
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        flags = snap.dirty_pages(new)
    scan_ms = 1000 * (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        diffs = snap.diff(new)
    diff_ms = 1000 * (time.perf_counter() - t0) / iters
    return {"image_mib": mib, "dirty_pages": int(flags.sum()),
            "scan_ms": scan_ms, "diff_ms": diff_ms,
            "scan_gibs": mib / 1024 / (scan_ms / 1000),
            "diff_bytes": sum(len(d.data) for d in diffs)}


def bench_hbm_bandwidth(mib: int = 256) -> dict:
    """HBM read+write bandwidth via an on-device scale chain (each
    fori_loop iteration reads + writes the buffer, each data-dependent
    on the last so the loop cannot be collapsed)."""
    import functools

    import jax
    import jax.numpy as jnp

    n_bytes = mib * (1 << 20)
    x = jnp.arange(n_bytes // 4, dtype=jnp.float32)

    @functools.partial(jax.jit, static_argnames="n")
    def run(x, n):
        return jax.lax.fori_loop(
            0, n, lambda i, y: y * jnp.float32(1.0000001), x)

    per, over_s = _fenced_loop_time(lambda k: run(x, k),
                                    lambda y: float(y[123_457]), 16)
    if per is None:
        return {"payload_mib": n_bytes >> 20,
                "error": "timing jitter swamped the slope"}
    return {"traffic_gibs": 2 * n_bytes / per / (1 << 30),
            "payload_mib": n_bytes >> 20, "dispatch_ms": over_s * 1000}


# Device bench sections, each independently runnable and individually
# watchdogged by the parent (a stage-level timeout alone let one slow
# compile starve every number). Ordered cheapest-first in the stage list
# below so the first TPU number lands within the first section budget.
_DEVICE_SECTIONS = {
    "probe": bench_device_probe,
    "pallas_compile": bench_pallas_compile,
    "step_tiny": lambda: bench_device_step("tiny"),
    "allreduce_small": lambda: bench_device_allreduce([1, 16]),
    "attention_tiny": lambda: bench_device_attention([(2, 256, 4, 64)]),
    "attention_full": lambda: bench_device_attention(),
    "step": lambda: bench_device_step("full"),
    "step_reference": lambda: bench_device_step(
        "full", attention_impl="reference", norm_impl="reference"),
    "step_large": lambda: bench_device_step("large"),
    "allreduce_big": lambda: bench_device_allreduce([128, 1024]),
    "hbm": bench_hbm_bandwidth,
    "device_snapshot": bench_device_snapshot,
}

# TPU stage: prove the chip, prove Mosaic, land MFU + a collective
# point early; everything after that is bonus depth.
_TPU_SECTIONS = ["probe", "pallas_compile", "step_tiny", "allreduce_small",
                 "attention_tiny", "step", "step_reference",
                 "attention_full", "step_large", "allreduce_big", "hbm",
                 "device_snapshot"]

# Per-section watchdog budgets (seconds). The probe budget absorbs
# backend init; step budgets absorb first-time XLA compiles (the on-disk
# compilation cache makes reruns cheap). The parent also enforces the
# overall stage budget.
_SECTION_BUDGETS = {
    "probe": 45, "pallas_compile": 150, "step_tiny": 180,
    "allreduce_small": 120, "attention_tiny": 150, "attention_full": 240,
    "step": 300, "step_reference": 240, "step_large": 300,
    "allreduce_big": 240, "hbm": 120, "device_snapshot": 120,
}


def _atomic_json_dump(path: str, obj, indent: int | None = None) -> None:
    """Write-temp-then-replace: a kill mid-write must never leave a
    truncated file that discards what was already recorded."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=indent)
    os.replace(tmp, path)


def bench_device_phase(sections: list[str],
                       out_path: str | None = None) -> dict:
    """Run the named device bench sections, writing the results file
    after EVERY section (and a ``_running`` marker before each) so the
    parent watchdog can meter per-section progress and a kill still
    leaves everything that finished.

    A backend that is not a TPU aborts the phase: these sections' names
    are device metrics, and a CPU timing is never written under them. A
    section that raises is recorded as ``<name>_error``; either way the
    caller exits non-zero (``failed``).
    """
    from faabric_tpu.util.device_env import configure_compile_cache

    configure_compile_cache()
    import jax

    results: dict = {}

    def flush():
        if out_path:
            _atomic_json_dump(out_path, results)

    results["_running"] = "probe"
    flush()
    results["platform"] = jax.default_backend()
    results["n_devices"] = len(jax.devices())
    if results["platform"] != "tpu":
        results["aborted"] = (f"backend is {results['platform']}, not tpu: "
                              "no device section ran")
        sections = []
    for name in sections:
        results["_running"] = name
        flush()
        try:
            results[name] = _DEVICE_SECTIONS[name]()
        except Exception as e:  # noqa: BLE001 — recorded, and fails the run
            results[name + "_error"] = str(e)[:200]
        flush()
    del results["_running"]
    results["failed"] = sorted(k for k in results
                               if k == "aborted" or k.endswith("_error"))
    flush()
    return results


def bench_host_calibration() -> dict:
    """Hardware context for the host-path numbers: what THIS machine's
    memory system and loopback TCP can do at all. The allreduce effective
    rate is bounded by ~ (wire legs + tree copies/adds) against these."""
    import numpy as np

    n = 25_500_000
    a = np.zeros(n, np.int32)
    b = np.ones(n, np.int32)
    a.copy()
    t0 = time.perf_counter()
    for _ in range(5):
        a.copy()
    memcpy_gibs = 5 * a.nbytes / (time.perf_counter() - t0) / (1 << 30)
    np.add(a, b, out=a)
    t0 = time.perf_counter()
    for _ in range(5):
        np.add(a, b, out=a)
    add_gibs = 5 * a.nbytes / (time.perf_counter() - t0) / (1 << 30)

    import socket as sk

    srv = sk.socket()
    srv.setsockopt(sk.SOL_SOCKET, sk.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    got = {}

    def sink():
        c, _ = srv.accept()
        buf = bytearray(1 << 20)
        total = 0
        while True:
            k = c.recv_into(buf)
            if not k:
                break
            total += k
        got["n"] = total
        c.close()

    th = threading.Thread(target=sink)
    th.start()
    c = sk.create_connection(("127.0.0.1", port))
    payload = bytes(64 << 20)
    t0 = time.perf_counter()
    for _ in range(4):
        c.sendall(payload)
    c.close()
    th.join(timeout=10)
    loopback_gibs = (4 * len(payload)) / (time.perf_counter() - t0) / (1 << 30)
    srv.close()
    out = {"memcpy_gibs": round(memcpy_gibs, 2),
           "int32_add_gibs": round(add_gibs, 2),
           "loopback_tcp_gibs": round(loopback_gibs, 2)}

    # Raw shm-ring plane (native/shm_ring.cpp) at the bulk chunk size —
    # the same-machine alternative to that loopback number
    try:
        from faabric_tpu.transport.shm import ShmRing, shm_available

        if shm_available():
            ring = ShmRing.create("calib", 32 << 20)
            cons = ShmRing.attach(ring.name)
            frame = np.zeros(4 << 20, np.uint8)
            n_frames = 64  # 256 MiB

            def drain():
                k = 0
                while k < n_frames:
                    if cons.try_pop() is None:
                        cons.wait_data(20_000)
                    else:
                        k += 1

            td = threading.Thread(target=drain)
            t0 = time.perf_counter()
            td.start()
            for _ in range(n_frames):
                ring.push([frame], timeout=30)
            td.join(timeout=30)
            out["shm_ring_gibs"] = round(
                n_frames * frame.nbytes
                / (time.perf_counter() - t0) / (1 << 30), 2)
            cons.close()
            ring.close()
    except Exception as e:  # noqa: BLE001
        out["shm_ring_error"] = str(e)[:120]
    return out


def bench_dirty_tracker(quick: bool = False) -> dict:
    """Tracker bracketing cost vs image size (every tracked task pays
    O(image); region hints cut it to O(write set))."""
    import numpy as np

    from faabric_tpu.util.dirty import make_dirty_tracker

    sizes_mib = [16] if quick else [16, 128]
    out: dict = {}
    for size_mib in sizes_mib:
        mem = np.zeros(size_mib << 20, np.uint8)
        per_mode: dict = {}
        stamp = 0
        for mode in ("compare", "native", "hash", "segv", "softpte",
                     "uffd"):
            stamp += 1  # each bracket must see a REAL change
            t = make_dirty_tracker(mode)
            if t.mode != mode:
                per_mode[mode] = {"skipped": f"fell back to {t.mode}"}
                continue
            t0 = time.perf_counter()
            t.start_tracking(mem)
            mem[4096 * 3] = stamp
            flags = t.get_dirty_pages(mem)
            bracket_ms = 1000 * (time.perf_counter() - t0)
            t.stop_tracking(mem)
            per_mode[mode] = {"bracket_ms": bracket_ms}
            assert bool(flags[3])
        # Hinted: a 64 KiB declared write extent in the same image
        t = make_dirty_tracker("hash")
        hints = [(4096 * 2, 65536)]
        t0 = time.perf_counter()
        t.start_tracking(mem, region_hints=hints)
        mem[4096 * 3] = stamp + 1
        flags = t.get_dirty_pages(mem)
        per_mode["hash_hinted_64k"] = {
            "bracket_ms": 1000 * (time.perf_counter() - t0)}
        assert bool(flags[3])
        out[f"{size_mib}mib"] = per_mode
    return out


def bench_delta_codec(quick: bool = False) -> dict:
    """Snapshot delta encode/apply over a sparse change (the freeze/thaw
    and snapshot-transfer hot path): one native page scan + coalesced
    runs, reference delta.cpp analog."""
    import numpy as np

    from faabric_tpu.util.delta import (
        DeltaSettings,
        apply_delta,
        serialize_delta,
    )

    size = (32 if quick else 256) << 20
    old = np.zeros(size, np.uint8)
    new = old.copy()
    new[np.random.RandomState(3).randint(0, size, 64)] = 9
    s = DeltaSettings(page_size=4096, use_xor=True, zlib_level=1)
    serialize_delta(s, old[:8], old[:8])  # warm the native lib

    t0 = time.perf_counter()
    d = serialize_delta(s, old, new)
    enc_ms = 1000 * (time.perf_counter() - t0)
    # Fresh-allocation apply (cold path: new image materialized)
    t0 = time.perf_counter()
    out = apply_delta(d, old)
    app_ms = 1000 * (time.perf_counter() - t0)
    assert bytes(out) == new.tobytes()
    # Reused destination buffer (the freeze/thaw hot path: one steady-
    # state memcpy + O(delta) patching)
    reuse = np.empty(size, np.uint8)
    apply_delta(d, old, out=reuse)  # warm the pages
    t0 = time.perf_counter()
    apply_delta(d, old, out=reuse)
    app_reuse_ms = 1000 * (time.perf_counter() - t0)
    # In-place patch of the resident image: O(delta), no base copy
    inplace = old.copy()
    t0 = time.perf_counter()
    apply_delta(d, inplace, out=inplace)
    app_inplace_ms = 1000 * (time.perf_counter() - t0)
    assert bytes(inplace[:64]) == bytes(new[:64])
    # Same-box ceiling for the reuse path: one warm 256 MiB memcpy
    t0 = time.perf_counter()
    np.copyto(reuse, old)
    memcpy_ms = 1000 * (time.perf_counter() - t0)
    return {"image_mib": size >> 20, "dirty_pages": 64,
            "encode_ms": enc_ms, "apply_ms": app_ms,
            "apply_reuse_ms": app_reuse_ms,
            "apply_inplace_ms": app_inplace_ms,
            "memcpy_ms": memcpy_ms,
            "delta_bytes": len(d)}


def _log(msg: str) -> None:
    """Progress goes to stderr: stdout must carry NOTHING but the final
    compact JSON line (a driver that keeps only the tail of stdout
    would otherwise truncate the headline clean off)."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _run_device_child(sections: list, budget: float) -> tuple:
    """One child run under the per-section watchdog. Returns
    (partial, error, killed_section): ``killed_section`` names the
    section whose budget overran (the parent may respawn with the
    sections after it), or None if the child exited on its own or hit
    the overall budget."""
    import subprocess
    import tempfile

    fd, out_file = tempfile.mkstemp(suffix=".json", prefix="bench_dev_")
    os.close(fd)
    err_f = tempfile.TemporaryFile(mode="w+")
    argv = [sys.executable, os.path.abspath(__file__), "--device-only",
            "--out", out_file, "--sections", ",".join(sections)]
    # The child places its own compile cache (util/device_env.py): the
    # environment's JAX_COMPILATION_CACHE_DIR is passed on untouched
    proc = subprocess.Popen(argv, stdout=subprocess.DEVNULL, stderr=err_f)

    def read_partial() -> dict:
        try:
            with open(out_file) as f:
                return json.load(f)
        except Exception:  # noqa: BLE001 — not written yet
            return {}

    start = time.perf_counter()
    sec_start = start
    current = "probe"  # the child's first marker; covers jax init too
    err = ""
    killed_section = None
    while True:
        try:
            proc.wait(timeout=2)
            break
        except subprocess.TimeoutExpired:
            pass
        now = time.perf_counter()
        partial = read_partial()
        running = partial.get("_running")
        if running is not None and running != current:
            _log(f"device: finished through {current!r}, now {running!r} "
                 f"({now - start:.0f}s into child)")
            current, sec_start = running, now
        budget_s = _SECTION_BUDGETS.get(current, 120)
        if now - start > budget:
            err = f"child budget {budget:.0f}s exceeded in {current!r}"
        elif now - sec_start > budget_s:
            err = f"section {current!r} exceeded its {budget_s}s budget"
            killed_section = current
        if err:
            proc.kill()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                # A child that cannot be reaped still owns the chip: no
                # next child may be started, so the stage ends here
                # (the progress file still has the finished sections)
                err += " (child could not be reaped; stage ended)"
                killed_section = None
            break
    partial = read_partial()
    if not err and proc.returncode not in (0, None):
        # The child exits 1 when a section failed and names it in the
        # progress file; anything else died without saying why
        err_f.seek(0)
        err = (f"rc={proc.returncode}: "
               f"{partial.get('failed') or err_f.read()[-300:]}")
    err_f.close()
    partial.pop("_running", None)
    for leftover in (out_file, out_file + ".tmp"):
        try:
            os.unlink(leftover)
        except OSError:
            pass
    return partial, err, killed_section


def run_device_stage(sections: list, total_budget: int) -> tuple:
    """Run a device stage with per-section watchdogs, RESPAWNING the
    child past a wedged section so one stuck compile forfeits only that
    section, not everything ordered after it (the XLA disk cache makes
    respawn compiles cheap). No respawn when backend init itself is the
    wedge (probe killed / nothing completed). Returns (merged, error)."""
    merged: dict = {}
    errors: list = []
    remaining = list(sections)
    start = time.perf_counter()
    spawns = 0
    while remaining and spawns < 4:
        left = total_budget - (time.perf_counter() - start)
        if left < 30:
            errors.append(f"stage budget {total_budget}s exhausted with "
                          f"{remaining} unrun")
            break
        spawns += 1
        partial, err, killed = _run_device_child(remaining, left)
        progressed = any(k in partial or k + "_error" in partial
                         for k in remaining)
        merged.update(partial)
        if err:
            errors.append(err)
        if killed is None or killed not in remaining:
            break  # child exited, total-budget kill, or unreaped child
        if killed == "probe" or not progressed:
            break  # backend init is the wedge; a respawn would wedge too
        merged[killed + "_error"] = "killed: " + err
        remaining = remaining[remaining.index(killed) + 1:]
        if remaining:
            _log(f"respawning device child for {remaining}")
    return merged, "; ".join(errors)


def _device_summary(dev: dict) -> dict:
    """The handful of numbers the compact stdout line carries."""
    s: dict = {}
    for k in ("platform", "n_devices"):
        if k in dev:
            s[k] = dev[k]
    probe = dev.get("probe") or {}
    if probe.get("device_kind"):
        s["device_kind"] = probe["device_kind"]
    step = dev.get("step") or dev.get("step_large") or dev.get("step_tiny")
    if step:
        for k in ("size", "step_ms", "tokens_per_s", "mfu",
                  "attention_impl"):
            if step.get(k) is not None:
                s[k] = (round(step[k], 4) if isinstance(step[k], float)
                        else step[k])
    ref = dev.get("step_reference")
    if (ref and ref.get("step_ms") and step and step.get("step_ms")
            and ref.get("size") == step.get("size")):
        s["vs_reference_impls"] = round(ref["step_ms"] / step["step_ms"], 3)
    att = dev.get("attention_full") or dev.get("attention_tiny") or {}
    speedups = [v for sec in att.values() if isinstance(sec, dict)
                for k, v in sec.items() if k.startswith("flash_speedup")]
    if speedups:
        s["flash_speedup_max"] = round(max(speedups), 2)
    curves = [(dev.get("allreduce_big") or {}).get("curve", []),
              (dev.get("allreduce_small") or {}).get("curve", [])]
    best = max((c.get("bus_gibs", 0) for cur in curves for c in cur),
               default=0)
    if best:
        s["allreduce_bus_gibs"] = round(best, 2)
    if (dev.get("pallas_compile") or {}).get("mosaic_ok"):
        s["mosaic_ok"] = True
    return s


def main() -> None:
    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, repo)
    quick = os.environ.get("BENCH_QUICK") == "1"
    sidecar = os.environ.get("BENCH_EXTRAS_FILE",
                             os.path.join(repo, "BENCH_EXTRAS.json"))
    extras: dict = {}

    def save_extras():
        # Full results ride a sidecar FILE; stdout gets only the compact
        # headline line. Written after every section so even a
        # driver-level kill leaves the evidence on disk.
        try:
            _atomic_json_dump(sidecar, extras, indent=1)
        except OSError as e:
            _log(f"sidecar write failed: {e}")

    # Telemetry rides along: a /metrics-equivalent snapshot brackets
    # every host section, so each BENCH_*.json carries bytes-moved /
    # frame-count deltas and phase-time shares per section — per-phase
    # perf trajectory across rounds for free (ISSUE 1)
    from faabric_tpu.telemetry import (
        get_metrics,
        set_tracing,
        snapshot_delta,
        summary_data,
    )

    # FAABRIC_TRACING=0 captures untraced timings (span recording does
    # perturb hot multi-threaded sections a little); the phase_shares
    # block is then simply absent
    if os.environ.get("FAABRIC_TRACING", "1") != "0":
        set_tracing(True)

    def _phase_shares(before: dict, after: dict) -> dict:
        deltas = {k: after[k]["total_s"] - before.get(k, {}).get("total_s", 0)
                  for k in after}
        total = sum(v for v in deltas.values() if v > 0)
        if total <= 0:
            return {}
        return {k: round(v / total, 4)
                for k, v in sorted(deltas.items(), key=lambda kv: -kv[1])
                if v / total >= 0.005}

    def host_section(name, fn):
        t0 = time.perf_counter()
        m0, p0 = get_metrics().snapshot(), summary_data()
        try:
            extras[name] = fn()
        except Exception as e:  # noqa: BLE001
            extras[name + "_error"] = str(e)[:200]
        tel = {k: v for k, v in (
            ("metrics_delta", snapshot_delta(m0, get_metrics().snapshot())),
            ("phase_shares", _phase_shares(p0, summary_data())),
        ) if v}
        if tel:
            extras.setdefault("telemetry", {})[name] = tel
        _log(f"{name}: {time.perf_counter() - t0:.1f}s")
        save_extras()

    host_section("host_calibration", bench_host_calibration)
    host_section("dirty_tracker", lambda: bench_dirty_tracker(quick))
    host_section("delta_codec", lambda: bench_delta_codec(quick))
    host_section("ptp", lambda: bench_ptp_dispatch(
        iters=100 if quick else 400))
    host_section("host_allreduce", lambda: bench_host_allreduce(
        n_ranks=4, elems=1_000_000 if quick else 25_500_000,
        rounds=1 if quick else 3))
    host_section("host_sendrecv_procs", bench_host_sendrecv_procs)
    host_section("host_allreduce_procs", lambda: bench_host_allreduce_procs(
        elems=1_000_000 if quick else 25_500_000,
        rounds=1 if quick else 3))
    host_section("delta_stream", lambda: bench_delta_stream(
        elems=2_500_000 if quick else 25_500_000,
        rounds=3 if quick else 10))
    host_section("host_allreduce_hier",
                 lambda: bench_host_allreduce_hier(
                     # quick must stay ABOVE the 2×CHUNK_BYTES (8 MiB)
                     # ring/hier eligibility floor or BOTH modes
                     # silently run the leader tree and the byte ratio
                     # reads a meaningless ~1.0
                     elems=2_500_000 if quick else 6_000_000,
                     rounds=1 if quick else 2))
    host_section("host_alltoall", lambda: bench_host_alltoall(
        block_elems=60_000 if quick else 150_000,
        rounds=1 if quick else 2))
    host_section("host_allreduce_device",
                 lambda: bench_host_allreduce_device(
                     elems=1_500_000 if quick else 6_000_000,
                     rounds=1 if quick else 2))
    host_section("concurrency", lambda: bench_concurrency(quick))
    host_section("invocations", lambda: bench_invocations(quick))
    host_section("robustness", lambda: bench_robustness(quick))
    host_section("perf_introspection",
                 lambda: bench_perf_introspection(quick))
    host_section("lifecycle", lambda: bench_lifecycle(quick))
    host_section("state", lambda: bench_state(quick))
    host_section("continuous_profile",
                 lambda: bench_continuous_profile(quick))

    device_failed = False
    if not quick or os.environ.get("BENCH_DEVICE") == "1":
        # Device phase, TPU only, with per-section watchdogs. The child
        # streams completed sections to a progress file, so a watchdog
        # kill keeps everything that finished; the on-disk XLA
        # compilation cache makes retried compiles cheap. No chip, a
        # section that raised or a child that died: the numbers that
        # did land are kept, and this run exits non-zero.
        t_tpu = int(os.environ.get("BENCH_DEVICE_TIMEOUT", "600"))
        _log("device stage: tpu")
        dev, err = run_device_stage(_TPU_SECTIONS, t_tpu)
        extras["device"] = dev
        device_failed = bool(err or dev.get("failed")
                             or dev.get("platform") != "tpu")
        if device_failed:
            extras["device_errors"] = {
                "tpu": err or str(dev.get("failed") or dev.get("aborted")
                                  or "no result from the device child")}
            _log(f"device stage FAILED: {extras['device_errors']['tpu']}")
        save_extras()

    ptp = extras.get("ptp") or {}
    p50 = ptp.get("p50_ms")
    summary: dict = {}
    if "device" in extras:
        summary = _device_summary(extras["device"])
        summary["device_stage"] = "failed" if device_failed else "tpu"
    ar = extras.get("host_allreduce") or {}
    if ar.get("effective_gibs"):
        summary["host_allreduce_gibs"] = round(ar["effective_gibs"], 2)
    arp = extras.get("host_allreduce_procs") or {}
    if arp.get("effective_gibs"):
        summary["host_allreduce_procs_gibs"] = round(
            arp["effective_gibs"], 2)
    # ISSUE 11 adaptive wire-codec keys: the governed-vs-raw speedup
    # (criterion ≥1.5×) plus the raw fp32 reference it is judged
    # against, and the REQUIRED iterative-broadcast delta-stream rate
    # (criterion ≥2× its raw baseline)
    if arp.get("raw_gibs"):
        summary["host_allreduce_procs_raw_gibs"] = round(
            arp["raw_gibs"], 2)
    if arp.get("coded_gibs"):
        summary["host_allreduce_procs_coded_gibs"] = round(
            arp["coded_gibs"], 2)
    if arp.get("governed_speedup"):
        summary["allreduce_governed_speedup"] = round(
            arp["governed_speedup"], 2)
    if arp.get("coded_wire_speedup"):
        summary["allreduce_coded_wire_speedup"] = round(
            arp["coded_wire_speedup"], 1)
    ds = extras.get("delta_stream") or {}
    if ds.get("delta_gibs"):
        summary["delta_stream_gibs"] = round(ds["delta_gibs"], 2)
    if ds.get("raw_gibs"):
        summary["delta_stream_raw_gibs"] = round(ds["raw_gibs"], 2)
    if ds.get("speedup"):
        summary["delta_stream_speedup"] = round(ds["speedup"], 2)
    if ds.get("wire_speedup"):
        summary["delta_stream_wire_speedup"] = round(
            ds["wire_speedup"], 1)
    # ISSUE 9 hierarchical keys (REPORTED_ONLY in bench_gate this first
    # round): the 4-simulated-host hierarchical rate, and the measured
    # wire-byte ratio hier/flat (model: (H-1)/(N-1) ≈ 1/ranks-per-host)
    hr = extras.get("host_allreduce_hier") or {}
    if hr.get("effective_gibs"):
        summary["host_allreduce_hier_gibs"] = round(
            hr["effective_gibs"], 2)
    if (hr.get("cross_host_bytes") or {}).get("ratio") is not None:
        summary["cross_host_bytes_ratio"] = hr["cross_host_bytes"]["ratio"]
    if (hr.get("quant") or {}).get("max_abs_err") is not None:
        summary["allreduce_quant_max_abs_err"] = round(
            hr["quant"]["max_abs_err"], 4)
    # ISSUE 13 schedule-compiler keys (REPORTED_ONLY this first round,
    # per the PR 9/10 promotion precedent): the compiled alltoall rate
    # over 4 simulated hosts, the cross-host BYTE parity ratio (model
    # ≈ 1.0 — alltoall is a permutation; parity proves the accounting)
    # and the cross-host MESSAGE collapse (model ≈ 1/ranks-per-host²)
    a2a = extras.get("host_alltoall") or {}
    if a2a.get("effective_gibs"):
        summary["host_alltoall_gibs"] = round(a2a["effective_gibs"], 2)
    if (a2a.get("cross_host") or {}).get("bytes_ratio") is not None:
        summary["alltoall_cross_host_bytes_ratio"] = \
            a2a["cross_host"]["bytes_ratio"]
    if (a2a.get("cross_host") or {}).get("msgs_ratio") is not None:
        summary["alltoall_cross_host_msgs_ratio"] = \
            a2a["cross_host"]["msgs_ratio"]
    # ISSUE 10 device collective plane (REPORTED_ONLY first round): the
    # compiled-mesh allreduce rate on the CPU backend, vs the host flat
    # ring on the identical payload/process shape
    dv = extras.get("host_allreduce_device") or {}
    if dv.get("effective_gibs"):
        summary["host_allreduce_device_gibs"] = round(
            dv["effective_gibs"], 2)
    # ISSUE 15 device-resident plane (REPORTED_ONLY first round, both
    # directions pinned in tests/unit/test_bench_gate.py): the
    # zero-host-copy allreduce rate on jax arrays already living on the
    # chips, and the host<->device bytes the timed resident rounds
    # moved — the tentpole's asserted-zero accounting figure
    if dv.get("resident_gibs"):
        summary["device_resident_allreduce_gibs"] = round(
            dv["resident_gibs"], 2)
    if dv.get("resident_copy_bytes") is not None:
        summary["device_host_copy_bytes"] = int(
            dv["resident_copy_bytes"])
    sr = extras.get("host_sendrecv_procs") or {}
    if sr.get("rate_gibs"):
        summary["host_sendrecv_gibs"] = round(sr["rate_gibs"], 2)
    dc = extras.get("delta_codec") or {}
    if dc.get("apply_reuse_ms") is not None:
        summary["delta_apply_reuse_ms"] = round(dc["apply_reuse_ms"], 1)
    inv = extras.get("invocations") or {}
    # ISSUE 8 headline keys: the QPS figure is a REQUIRED bench_gate
    # key; serial baseline + p50 ride along so the ≥5× speedup and the
    # immediate-path p50 criterion are checkable per round
    for key in ("invocations_per_s", "invocations_per_s_serial",
                "invocation_p50_ms", "invocation_p99_ms"):
        if inv.get(key) is not None:
            summary[key] = inv[key]
    rb = extras.get("robustness") or {}
    if rb.get("planner_kill_to_recover_s") is not None:
        summary["planner_kill_to_recover_s"] = rb[
            "planner_kill_to_recover_s"]
    if (rb.get("journal") or {}).get("append_ns") is not None:
        summary["journal_append_ns"] = rb["journal"]["append_ns"]
    # ISSUE 6 planned-disruption latencies (reported; bench_gate tracks
    # them as informational keys, not yet hard-gated)
    for key in ("migration_pause_ms", "thaw_to_first_result_s",
                "partition_heal_s"):
        if rb.get(key) is not None:
            summary[key] = rb[key]
    # ISSUE 12 perf-introspection keys (REPORTED_ONLY this round): the
    # per-frame profile feed cost, its FAABRIC_METRICS=0 no-op floor,
    # and the doctor's end-to-end synthetic-cluster runtime
    pi = extras.get("perf_introspection") or {}
    if pi.get("feed_ns") is not None:
        summary["perf_feed_ns"] = pi["feed_ns"]
    if pi.get("feed_noop_ns") is not None:
        summary["perf_feed_noop_ns"] = pi["feed_noop_ns"]
    if pi.get("doctor_selftest_ms") is not None:
        summary["doctor_selftest_ms"] = pi["doctor_selftest_ms"]
    # ISSUE 14 lifecycle keys (REPORTED_ONLY this round): the enabled
    # per-stamp ledger cost (~100 ns target); invocation_p99_ms rides
    # up from the invocations section's healthz lifecycle digest
    lf = extras.get("lifecycle") or {}
    if lf.get("stamp_ns") is not None:
        summary["lifecycle_stamp_ns"] = lf["stamp_ns"]
    # ISSUE 16 state-plane keys (REPORTED_ONLY this round): master-image
    # hot read, replica pull / partial-push throughput over loopback,
    # and the access-ledger record cost enabled vs the no-op singleton
    # ISSUE 19 adds the replicated-write rate (same dirty-chunk push
    # with a synchronous backup forward before the ack — compare
    # against state_push_partial_gibs for the replication overhead)
    # and the measured loopback failover: planner remove_host → first
    # acked write through the promoted backup
    st = extras.get("state") or {}
    for src, dst in (("hot_read_ns", "state_hot_read_ns"),
                     ("pull_gibs", "state_pull_gibs"),
                     ("push_partial_gibs", "state_push_partial_gibs"),
                     ("replicated_push_gibs", "state_replicated_push_gibs"),
                     ("master_failover_s", "master_failover_s"),
                     ("record_ns", "statestats_record_ns"),
                     ("record_noop_ns", "statestats_record_noop_ns")):
        if st.get(src) is not None:
            summary[dst] = st[src]
    # ISSUE 18 continuous-profiling keys (REPORTED_ONLY this round, all
    # three lower-is-better — directions pinned in the unit test): one
    # stack-sampler pass, the measured busy-workload drag at the
    # default 25 ms cadence (acceptance ≤ 2%), and the idle-process
    # GIL drift gauge (contract ~0)
    cp = extras.get("continuous_profile") or {}
    for src, dst in (("sample_ns", "profile_sample_ns"),
                     ("overhead_pct", "profile_overhead_pct"),
                     ("gil_pressure_idle", "gil_pressure_idle")):
        if cp.get(src) is not None:
            summary[dst] = cp[src]
    result = {
        "metric": "ptp_dispatch_p50_ms",
        "value": round(p50, 4) if p50 else None,
        "unit": "ms",
        # North star: <1 ms p50 (BASELINE.md); >1 here beats the target
        "vs_baseline": round(1.0 / p50, 3) if p50 else None,
        "summary": summary,
        "extras_file": os.path.basename(sidecar),
    }
    line = json.dumps(result)
    if len(line) > 2000:  # hard ceiling: the driver tails stdout
        del result["summary"]
        line = json.dumps(result)
    print(line)
    if device_failed:
        sys.exit(1)


if __name__ == "__main__":
    if "--sendrecv-worker" in sys.argv:
        _sendrecv_worker_main()
        sys.exit(0)
    if "--allreduce-worker" in sys.argv:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        i = sys.argv.index("--allreduce-worker")
        _allreduce_worker_main(int(sys.argv[i + 1]), int(sys.argv[i + 2]))
    elif "--delta-stream-worker" in sys.argv:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        i = sys.argv.index("--delta-stream-worker")
        _delta_stream_worker_main(int(sys.argv[i + 1]),
                                  int(sys.argv[i + 2]))
    elif "--hier-worker" in sys.argv:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        i = sys.argv.index("--hier-worker")
        _hier_worker_main(*(int(a) for a in sys.argv[i + 1:i + 6]))
    elif "--alltoall-worker" in sys.argv:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        i = sys.argv.index("--alltoall-worker")
        _alltoall_worker_main(*(int(a) for a in sys.argv[i + 1:i + 6]))
    elif "--device-plane-worker" in sys.argv:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        i = sys.argv.index("--device-plane-worker")
        _device_plane_worker_main(int(sys.argv[i + 1]),
                                  int(sys.argv[i + 2]))
    elif "--device-only" in sys.argv:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        out_path = None
        if "--out" in sys.argv:
            out_path = sys.argv[sys.argv.index("--out") + 1]
        if "--sections" in sys.argv:
            secs = sys.argv[sys.argv.index("--sections") + 1].split(",")
        else:
            secs = list(_TPU_SECTIONS)
        res = bench_device_phase(secs, out_path=out_path)
        print(json.dumps(res), file=sys.stderr)
        sys.exit(1 if res["failed"] else 0)
    else:
        main()
