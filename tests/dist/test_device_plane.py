"""ISSUE 10 acceptance: the device collective plane across OS processes.

Two child processes × 2 virtual CPU devices each join one
``jax.distributed`` plane (gloo cross-process collectives), build a brokered 4-rank MpiWorld (ranks 0-1 on w0, 2-3 on
w1), run the activation handshake, and prove:

(a) a device-eligible allreduce/allgather/reduce_scatter executes
    through faabric_tpu/device_plane/ with BITWISE-identical results to
    the host flat ring (exact int32/int64 payloads; fp32 would only
    differ by fold order, which is pinned at unit level);
(b) the collective payload puts ZERO bytes on the host shm/tcp planes —
    the comm-matrix ``plane=device`` rows carry the traffic instead;
(c) ISSUE 15: a device-RESIDENT allreduce (jax arrays already committed
    on the chips) additionally moves ZERO bytes across the host↔device
    boundary — the ``faabric_device_copy_*`` accounting — with results
    bitwise identical and still on device;
(d) an ineligible shape (non-commuting UserOp) falls back to the host
    ladder and still agrees with numpy.

The parent only orchestrates — ``jax.distributed.initialize`` is
once-per-process and must not poison the pytest process. Children
report one JSON line each (bench-style child body via __main__).
"""

import json
import os
import subprocess
import sys

import numpy as np

N_PROCS = 2
RANKS_PER_PROC = 2
N = N_PROCS * RANKS_PER_PROC
GROUP = 9910
HOSTS = ["wdp0", "wdp1"]
DATA_PLANES = ("shm", "bulk-tcp")
ELEMS = 200_000


def _child_main(my_idx: int, coord_port: int) -> None:
    from faabric_tpu.parallel.distributed import (
        DevicePlaneSpec,
        force_cpu_virtual_devices,
        join_device_plane,
    )

    force_cpu_virtual_devices(RANKS_PER_PROC)
    join_device_plane(DevicePlaneSpec(
        coordinator_host="127.0.0.1", coordinator_port=coord_port,
        num_processes=N_PROCS, process_id=my_idx))

    import threading

    from faabric_tpu.batch_scheduler.decision import SchedulingDecision
    from faabric_tpu.mpi import MpiOp, MpiWorld
    from faabric_tpu.mpi.types import UserOp
    from faabric_tpu.telemetry import get_comm_matrix
    from faabric_tpu.transport.point_to_point import PointToPointBroker
    from faabric_tpu.transport.ptp_remote import PointToPointServer

    decision = SchedulingDecision(app_id=GROUP, group_id=GROUP)
    for r in range(N):
        # device_id is the per-host chip index (0..1 on each worker)
        decision.add_message(HOSTS[r // RANKS_PER_PROC], 5200 + r, r, r,
                             device_id=r % RANKS_PER_PROC)
    broker = PointToPointBroker(HOSTS[my_idx])
    server = PointToPointServer(broker)
    server.start()
    broker.set_up_local_mappings_from_decision(decision)
    world = MpiWorld(broker, GROUP, N, GROUP)
    world.refresh_rank_hosts()
    my_ranks = [r for r in range(N) if r // RANKS_PER_PROC == my_idx]
    print("READY", flush=True)

    report = {"ok": True, "err": "", "activated": False}

    def run_ranks(fn):
        out, errs = {}, []

        def go(rank):
            try:
                out[rank] = fn(rank)
            except Exception as e:  # noqa: BLE001 — reported upward
                errs.append(f"rank {rank}: {e!r}"[:200])

        threads = [threading.Thread(target=go, args=(r,))
                   for r in my_ranks]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        if errs or any(t.is_alive() for t in threads):
            raise RuntimeError(errs or "rank threads hung")
        return out

    def plane_bytes():
        cells = (get_comm_matrix().snapshot() or {}).get("cells", [])
        out: dict = {}
        for c in cells:
            out[c["plane"]] = out.get(c["plane"], 0) + c["bytes"]
        return out

    try:
        rng = np.random.default_rng(17)
        ar_datas = {r: rng.integers(-9999, 9999, ELEMS).astype(np.int32)
                    for r in range(N)}
        rs_datas = {r: rng.integers(-9999, 9999, N * 500).astype(np.int32)
                    for r in range(N)}

        # Host-ladder reference FIRST (plane not yet activated)
        flat_ar = run_ranks(lambda r: world.allreduce(
            r, ar_datas[r].copy(), MpiOp.SUM))

        acts = run_ranks(lambda r: world.activate_device_plane(r))
        report["activated"] = all(acts.values())
        if not report["activated"]:
            raise RuntimeError(f"activation failed: {acts}")

        b0 = plane_bytes()
        dev_ar = run_ranks(lambda r: world.allreduce(
            r, ar_datas[r].copy(), MpiOp.SUM))
        dev_ag = run_ranks(lambda r: world.allgather(
            r, np.full(64, r + 1, np.int32)))
        dev_rs = run_ranks(lambda r: world.reduce_scatter(
            r, rs_datas[r].copy(), MpiOp.SUM))
        b1 = plane_bytes()

        # (a) bitwise identity, device plane vs host ring vs numpy
        ar_expected = sum(ar_datas.values())
        ag_expected = np.concatenate(
            [np.full(64, r + 1, np.int32) for r in range(N)])
        rs_expected = sum(rs_datas.values())
        for r in my_ranks:
            # dtype equality too: np.array_equal is dtype-blind, and a
            # silent 64-bit downcast must never hide behind small values
            assert dev_ar[r].dtype == flat_ar[r].dtype == np.int32, r
            assert np.array_equal(dev_ar[r], flat_ar[r]), r
            assert np.array_equal(dev_ar[r], ar_expected), r
            assert dev_ag[r].dtype == np.int32, r
            assert np.array_equal(dev_ag[r], ag_expected), r
            assert dev_rs[r].dtype == np.int32, r
            assert np.array_equal(dev_rs[r],
                                  rs_expected[r * 500:(r + 1) * 500]), r

        # 64-bit payloads fall back to the exact host ladder (x64 off:
        # the device rung would downcast); sums past 2^31 stay right
        big = {r: np.full(256, 2 ** 40 + r, np.int64) for r in range(N)}
        big_out = run_ranks(lambda r: world.allreduce(
            r, big[r].copy(), MpiOp.SUM))
        big_expected = sum(big.values())
        assert int(big_expected[0]) > 2 ** 31
        for r in my_ranks:
            assert big_out[r].dtype == np.int64, r
            assert np.array_equal(big_out[r], big_expected), r

        # (b) accounting: device rows carry the traffic, host data
        # planes carry none of the collective payload
        delta = {p: b1.get(p, 0) - b0.get(p, 0) for p in set(b0) | set(b1)}
        report["device_bytes"] = delta.get("device", 0)
        report["device_bytes_expected"] = sum(
            ar_datas[r].nbytes + 64 * 4 + rs_datas[r].nbytes
            for r in my_ranks)
        report["host_plane_bytes"] = sum(
            v for p, v in delta.items() if p in DATA_PLANES)

        # (c) ISSUE 15 acceptance: device-RESIDENT allreduce — inputs
        # already committed on the chips — records ZERO bytes on the
        # host data planes AND ZERO host<->device staging copies (the
        # new faabric_device_copy_* accounting), with results bitwise
        # identical to the host flat ring AND still device-resident
        import jax

        from faabric_tpu.device_plane import device_copy_totals

        plane = world.device_plane()
        dev_datas = {r: jax.device_put(ar_datas[r], plane.devices[r])
                     for r in my_ranks}
        # resident-key compile off the accounting clock (compiles move
        # no payload, but keep the measured window clean)
        run_ranks(lambda r: world.allreduce(r, dev_datas[r], MpiOp.SUM))
        c0 = device_copy_totals()
        rb0 = plane_bytes()
        res = run_ranks(lambda r: world.allreduce(r, dev_datas[r],
                                                  MpiOp.SUM))
        c1 = device_copy_totals()
        rb1 = plane_bytes()
        rdelta = {p: rb1.get(p, 0) - rb0.get(p, 0)
                  for p in set(rb0) | set(rb1)}
        report["resident_copy_count"] = c1["count"] - c0["count"]
        report["resident_copy_bytes"] = c1["bytes"] - c0["bytes"]
        report["resident_host_plane_bytes"] = sum(
            v for p, v in rdelta.items() if p in DATA_PLANES)
        report["resident_device_bytes"] = rdelta.get("device", 0)
        report["resident_device_bytes_expected"] = sum(
            ar_datas[r].nbytes for r in my_ranks)
        for r in my_ranks:
            assert hasattr(res[r], "sharding"), type(res[r])
            out = np.asarray(res[r])
            assert out.dtype == np.int32, r
            assert np.array_equal(out, flat_ar[r]), r

        # (d) ineligible op falls back and still agrees
        op = UserOp(lambda a, b: np.maximum(a, b), commute=True)
        fb = run_ranks(lambda r: world.allreduce(
            r, ar_datas[r].copy(), op))
        fb_expected = np.max(np.stack([ar_datas[r] for r in range(N)]),
                             axis=0)
        for r in my_ranks:
            assert np.array_equal(fb[r], fb_expected), r
        plane = world.device_plane()
        report["disabled"] = plane.disabled_reason if plane else "GONE"
        report["cached"] = len(plane.summary()["cached_executables"]) \
            if plane else 0
    except Exception as e:  # noqa: BLE001 — reported to the parent
        report = {"ok": False, "err": repr(e)[:300]}
    finally:
        server.stop()
        broker.clear()
    print("REPORT " + json.dumps(report), flush=True)


def _next_line(child, prefix: str) -> str:
    """The child's next stdout line that starts with ``prefix``."""
    while True:
        line = child.stdout.readline()
        assert line, f"child exited before printing {prefix!r}"
        if line.startswith(prefix):
            return line.strip()


def test_dist_device_plane_cross_process_bitwise_and_accounting():
    from faabric_tpu.transport.common import clear_host_aliases
    from tests.conftest import next_port_base

    base = next_port_base()
    aliases = []
    for i, h in enumerate(HOSTS):
        aliases.append(f"{h}=127.0.0.1+{base + i * 1200}")
    coord_port = base + 2900
    env = {**os.environ, "FAABRIC_HOST_ALIASES": ",".join(aliases),
           "JAX_PLATFORMS": "cpu"}

    children = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--dp-child",
         str(i), str(coord_port)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env) for i in range(N_PROCS)]
    reports = []
    try:
        # jaxlib's Gloo layer prints its own banner lines on stdout
        # ("[Gloo] Rank 0 is connected to ..."): read past anything that
        # is not ours
        for c in children:
            _next_line(c, "READY")
        for c in children:
            line = _next_line(c, "REPORT ")
            reports.append(json.loads(line[len("REPORT "):]))
    finally:
        for c in children:
            try:
                c.wait(timeout=30)
            except subprocess.TimeoutExpired:
                c.kill()
        clear_host_aliases()

    for i, rep in enumerate(reports):
        assert rep["ok"], f"proc {i}: {rep.get('err')}"
        assert rep["activated"]
        # the collective payload rode the device plane, not the host
        # data planes (the handshake/barrier control traffic is ptp)
        assert rep["device_bytes"] == rep["device_bytes_expected"], rep
        assert rep["host_plane_bytes"] == 0, rep
        # ISSUE 15: the resident rounds moved zero host<->device bytes
        # and zero host-plane bytes; the device rows carried them
        assert rep["resident_copy_count"] == 0, rep
        assert rep["resident_copy_bytes"] == 0, rep
        assert rep["resident_host_plane_bytes"] == 0, rep
        assert rep["resident_device_bytes"] == \
            rep["resident_device_bytes_expected"], rep
        # the ineligible-op fallback did NOT disable the plane — it
        # never entered the rung
        assert rep["disabled"] is None, rep
        # 3 host-round executables + the residency-keyed allreduce
        assert rep["cached"] == 4, rep


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
    if "--dp-child" in sys.argv:
        i = sys.argv.index("--dp-child")
        _child_main(int(sys.argv[i + 1]), int(sys.argv[i + 2]))
