"""Worker/planner process bodies for the distributed tests.

The reference runs dist tests as two containers + planner
(tests/dist, dist-test/run.sh); here each logical host is a real OS
process on aliased loopback ports, launched by the harness in
test_multiprocess.py. Invoke as:

    python procs.py planner
    python procs.py worker <host> <behaviour>
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

from faabric_tpu.executor import Executor, ExecutorFactory  # noqa: E402
from faabric_tpu.proto import ReturnValue  # noqa: E402


class DistExecutor(Executor):
    """Behaviour registry keyed by function name — the reference's
    DistTestExecutor callback pattern (tests/dist/DistTestExecutor.cpp)."""

    MEM = 16384

    def __init__(self, msg):
        super().__init__(msg)
        self.memory = np.zeros(self.MEM, dtype=np.uint8)

    def get_memory_view(self):
        return self.memory

    def set_memory_size(self, size):
        if size > self.memory.size:
            self.memory = np.concatenate(
                [self.memory, np.zeros(size - self.memory.size, np.uint8)])

    def execute_task(self, pool_idx, msg_idx, req):
        msg = req.messages[msg_idx]
        fn = getattr(self, f"fn_{msg.function}", None)
        if fn is None:
            msg.output_data = f"unknown function {msg.function}".encode()
            return int(ReturnValue.FAILED)
        return fn(msg, req)

    # ------------------------------------------------------------------
    def fn_noop(self, msg, req):
        """ISSUE 8 high-QPS workload: the cheapest possible invocation,
        so the bench/chaos QPS numbers measure the invocation PATH
        (admission, tick, journal, dispatch, result), not the task."""
        msg.output_data = b"ok"
        return int(ReturnValue.SUCCESS)

    def fn_square(self, msg, req):
        n = int(msg.input_data.decode())
        msg.output_data = str(n * n).encode()
        return int(ReturnValue.SUCCESS)

    def fn_mpi(self, msg, req):
        from faabric_tpu.mpi import MpiOp, get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7100
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        out = world.allreduce(rank, np.full(65536, float(rank),
                                            dtype=np.float32), MpiOp.SUM)
        world.barrier(rank)
        msg.output_data = f"r{rank}:{int(out[0])}".encode()
        return int(ReturnValue.SUCCESS)

    def fn_mpi_big(self, msg, req):
        """12 MiB-per-rank allreduce: exercises the chunk-pipelined
        leader trees + bulk data plane inside a planner-scheduled world
        across real worker processes."""
        return self._allreduce_workload(msg, 7500, 12 << 20)

    def fn_mpi_telemetry(self, msg, req):
        """12 MiB-per-rank allreduce on its OWN world id, driven by the
        telemetry acceptance test — worlds persist per worker process,
        so reusing mpi_big's id would collide with its test."""
        return self._allreduce_workload(msg, 7510, 12 << 20)

    def _allreduce_workload(self, msg, world_id: int, nbytes: int,
                            rounds: int = 1):
        """Shared body for the one-shot allreduce workloads: create/join
        a world on ``world_id``, run ``rounds`` allreduces of
        ``nbytes`` int32 per rank, verify every element equals
        sum(1..size)."""
        from faabric_tpu.mpi import MpiOp, get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = world_id
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        n = nbytes // 4
        out = None
        for _ in range(rounds):
            out = world.allreduce(rank, np.full(n, rank + 1, np.int32),
                                  MpiOp.SUM)
        world.barrier(rank)
        expected = world.size * (world.size + 1) // 2
        ok = bool((out == expected).all())
        msg.output_data = f"r{rank}:{'ok' if ok else int(out[0])}".encode()
        return int(ReturnValue.SUCCESS if ok else ReturnValue.FAILED)

    def fn_mpi_flow(self, msg, req):
        """Cross-host trace-propagation workload (PR 3): a few 1 MiB
        allreduces on a dedicated world id so the /trace scrape finds
        fresh remote send/recv flow pairs across the worker processes."""
        return self._allreduce_workload(msg, 7520, 1 << 20, rounds=3)

    def fn_mpi_perf(self, msg, req):
        """Performance-introspection workload (ISSUE 12): several
        bulk-sized allreduce rounds on a dedicated world, with ONE
        planted straggler — the rank named by MPI_PERF_SLOW_RANK sleeps
        before entering each collective, so every other rank waits on it
        while only ITS entry stamp reads late. Combined with a planted
        transport.bulk delay fault on one worker (the slow link), this
        is the doctor's dist acceptance scenario."""
        import time as _time

        from faabric_tpu.mpi import MpiOp, get_mpi_context

        slow_rank = int(os.environ.get("MPI_PERF_SLOW_RANK", "-1"))
        slow_s = float(os.environ.get("MPI_PERF_SLOW_S", "0.08"))
        rounds = int(os.environ.get("MPI_PERF_ROUNDS", "8"))
        nbytes = int(os.environ.get("MPI_PERF_NBYTES", str(16 << 20)))
        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7600
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        n = nbytes // 4
        out = None
        for _ in range(rounds):
            if rank == slow_rank:
                _time.sleep(slow_s)
            out = world.allreduce(rank, np.full(n, rank + 1, np.int32),
                                  MpiOp.SUM)
        world.barrier(rank)
        expected = world.size * (world.size + 1) // 2
        ok = bool((out == expected).all())
        msg.output_data = f"r{rank}:{'ok' if ok else int(out[0])}".encode()
        return int(ReturnValue.SUCCESS if ok else ReturnValue.FAILED)

    def fn_mpi_matrix(self, msg, req):
        """Comm-matrix acceptance workload: a 12 MiB-per-rank allreduce
        on its own world id so /commmatrix sees fresh bulk-plane bytes
        regardless of which other dist tests ran first."""
        return self._allreduce_workload(msg, 7530, 12 << 20)

    def fn_mpi_ring_chunked(self, msg, req):
        """ISSUE 5 acceptance: a ring allreduce whose per-rank segments
        EXCEED one bulk frame (RING_CHUNK_BYTES), so the ring paths must
        chunk-pipeline instead of bailing to the tree (the deleted
        RING_MSG_CAP fallback). Bitwise-exact integer results prove the
        chunked fold/forward ownership protocol across processes."""
        from faabric_tpu.mpi import MpiOp, get_mpi_context
        from faabric_tpu.mpi.world import RING_CHUNK_BYTES

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7540
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        # This workload pins the FLAT chunked ring (algo=ring).
        # Defensive: the simulated hosts resolve to loopback, so plain
        # "on" already stays flat (_hier_wins), but the pin keeps this
        # true even if that rule changes — identically on every process
        # of the world, or algorithm choice desyncs. The composed path
        # has its own dist coverage (test_hier_collectives.py).
        world.hier_enabled = False
        n = 10 << 20  # 40 MiB int32 per rank → ~5 MiB ring segments
        seg_bytes = (n * 4) // world.size
        base = np.arange(n, dtype=np.int32) % 1000
        out = world.allreduce(rank, base + rank, MpiOp.SUM)
        world.barrier(rank)
        expected = base * world.size \
            + world.size * (world.size - 1) // 2
        ok = bool((out == expected).all())
        chunked = seg_bytes > RING_CHUNK_BYTES
        verdict = "ok" if ok and chunked else (
            "unchunked" if ok else "wrong")
        msg.output_data = f"r{rank}:{verdict}".encode()
        return int(ReturnValue.SUCCESS if ok and chunked
                   else ReturnValue.FAILED)

    def fn_mpi_reduce_many(self, msg, req):
        """Port of the reference example mpi_reduce_many
        (tests/dist/mpi/examples/mpi_reduce_many.cpp): 100 back-to-back
        reduces of a 3-vector — collective state must not bleed between
        repetitions."""
        from faabric_tpu.mpi import MpiOp, get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7700
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        size = world.size

        expected = np.array([sum(range(size)), 10 * sum(range(size)),
                             100 * sum(range(size))], np.int64)
        mine = np.array([rank, 10 * rank, 100 * rank], np.int64)
        for _ in range(100):
            res = world.reduce(rank, 0, mine, MpiOp.SUM)
            if rank == 0 and not np.array_equal(res, expected):
                msg.output_data = f"bad:{res.tolist()}".encode()
                return int(ReturnValue.FAILED)
        world.barrier(rank)
        msg.output_data = b"reduce-many-ok"
        return int(ReturnValue.SUCCESS)

    def fn_mpi_sync_async(self, msg, req):
        """Port of the reference example mpi_send_sync_async: rank 0
        interleaves an isend and a blocking send to every rank; receivers
        irecv twice and wait OUT OF ORDER."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7800
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()

        if rank == 0:
            for r in range(1, world.size):
                rid = world.isend(0, r, np.array([r], np.int32))
                world.send(0, r, np.array([r], np.int32))
                world.await_async(0, rid)
            msg.output_data = b"sent"
        else:
            r1 = world.irecv(0, rank)
            r2 = world.irecv(0, rank)
            v2 = world.await_async(rank, r2)  # out of order
            v1 = world.await_async(rank, r1)
            ok = int(v1[0][0]) == rank and int(v2[0][0]) == rank
            msg.output_data = (b"sync-async-ok" if ok
                               else f"got:{v1[0][0]},{v2[0][0]}".encode())
            if not ok:
                return int(ReturnValue.FAILED)
        world.barrier(rank)
        return int(ReturnValue.SUCCESS)

    def fn_mpi_collectives(self, msg, req):
        """Ports of the remaining small reference collective examples in
        one cross-process world: mpi_allgather.cpp, mpi_bcast.cpp (root
        2), mpi_gather.cpp (root 2), mpi_scatter.cpp, mpi_scan.cpp,
        mpi_reduce.cpp and mpi_helloworld.cpp's world sanity."""
        from faabric_tpu.mpi import MpiOp, get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 8300
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        size = world.size
        if rank < 0 or size <= 1:  # helloworld's sanity
            return int(ReturnValue.FAILED)

        def fail(tag, got):
            msg.output_data = f"{tag}:{got}".encode()
            return int(ReturnValue.FAILED)

        # allgather: rank contributes [4r, 4r+4) -> everyone sees 0..4n
        n_per = 4
        got = world.allgather(rank, np.arange(
            rank * n_per, (rank + 1) * n_per, dtype=np.int32))
        if not np.array_equal(got, np.arange(size * n_per, dtype=np.int32)):
            return fail("allgather", got[:8].tolist())

        # bcast from a non-zero root (reference uses root 2)
        expected = np.array([0, 1, 2, 3], np.int32)
        out = world.broadcast(2, rank,
                              expected if rank == 2 else np.empty(0))
        if not np.array_equal(out, expected):
            return fail("bcast", out.tolist())

        # gather to root 2
        got = world.gather(rank, 2, np.arange(
            rank * n_per, (rank + 1) * n_per, dtype=np.int32))
        if rank == 2 and not np.array_equal(
                got, np.arange(size * n_per, dtype=np.int32)):
            return fail("gather", got[:8].tolist())

        # scatter from rank 0
        all_data = np.arange(size * n_per, dtype=np.int32) \
            if rank == 0 else np.empty(0, np.int32)
        mine = world.scatter(0, rank, all_data, n_per)
        if not np.array_equal(mine, np.arange(
                rank * n_per, (rank + 1) * n_per, dtype=np.int32)):
            return fail("scatter", mine.tolist())

        # scan: inclusive prefix sum of [10r, 10r+1, 10r+2]
        got = world.scan(rank, np.array(
            [rank * 10 + i for i in range(3)], np.int64), MpiOp.SUM)
        expected = np.array(
            [sum(r * 10 + i for r in range(rank + 1)) for i in range(3)],
            np.int64)
        if not np.array_equal(got, expected):
            return fail("scan", got.tolist())

        # reduce to a non-zero root
        got = world.reduce(rank, 3, np.full(5, rank, np.int64), MpiOp.SUM)
        if rank == 3 and not np.array_equal(
                got, np.full(5, sum(range(size)), np.int64)):
            return fail("reduce", got.tolist())

        world.barrier(rank)
        msg.output_data = b"collectives-ok"
        return int(ReturnValue.SUCCESS)

    def fn_mpi_p2p_suite(self, msg, req):
        """Ports of mpi_send.cpp, mpi_sendrecv.cpp, mpi_barrier.cpp
        (barrier + alltoall rounds) and mpi_cart_create.cpp (two distinct
        cartesian comms over one world) across real worker processes."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 8400
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        size = world.size

        def fail(tag, got):
            msg.output_data = f"{tag}:{got}".encode()
            return int(ReturnValue.FAILED)

        # mpi_send: 0 -> 1 one int
        if rank == 0:
            world.send(0, 1, np.array([42], np.int32))
        elif rank == 1:
            got, _ = world.recv(0, 1)
            if int(got[0]) != 42:
                return fail("send", int(got[0]))

        # mpi_sendrecv: ring exchange — send right, receive from left
        right, left = (rank + 1) % size, (rank - 1) % size
        got, _ = world.sendrecv(np.array([rank], np.int32), rank,
                                right, left, rank)
        if int(got[0]) != left:
            return fail("sendrecv", int(got[0]))

        # mpi_barrier: barrier + alltoall rounds (reference does 100;
        # 10 keeps the dist suite quick while still interleaving)
        for i in range(10):
            world.barrier(rank)
            contrib = np.full(size, rank * 100 + i, np.int32)
            mixed = world.alltoall(rank, contrib)
            expected = np.array([r * 100 + i for r in range(size)],
                                np.int32)
            if not np.array_equal(mixed, expected):
                return fail("alltoall", mixed.tolist())

        # mpi_cart_create: creating the cartesian topology twice must be
        # stable (the reference asserts two distinct comm handles; here
        # the world owns the topology, so re-create must agree and the
        # coords round-trip must survive it)
        d1 = world.cart_create(world.cart_dims())
        d2 = world.cart_create(world.cart_dims())
        if d1 != d2 or world.cart_rank(world.cart_coords(rank)) != rank:
            return fail("cart_create", (d1, d2))

        world.barrier(rank)
        msg.output_data = b"p2p-suite-ok"
        return int(ReturnValue.SUCCESS)

    def fn_mpi_send_many(self, msg, req):
        """Port of the reference example mpi_send_many
        (tests/dist/mpi/examples/mpi_send_many.cpp): 100 rounds of rank 0
        fanning one int to every rank and collecting one response each —
        sustained small-message ping-pong across the process boundary."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 8100
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        n_msg = 100

        if rank == 0:
            for _ in range(n_msg):
                for dest in range(1, world.size):
                    world.send(0, dest, np.array([100 + dest], np.int32))
                for r in range(1, world.size):
                    got, _ = world.recv(r, 0)
                    if int(got[0]) != 100 - r:
                        msg.output_data = f"bad:{r}:{got[0]}".encode()
                        return int(ReturnValue.FAILED)
            msg.output_data = b"send-many-ok"
        else:
            for _ in range(n_msg):
                got, _ = world.recv(0, rank)
                if int(got[0]) != 100 + rank:
                    msg.output_data = f"bad:{got[0]}".encode()
                    return int(ReturnValue.FAILED)
                world.send(rank, 0, np.array([100 - rank], np.int32))
            msg.output_data = b"send-many-ok"
        world.barrier(rank)
        return int(ReturnValue.SUCCESS)

    def fn_mpi_checks(self, msg, req):
        """Port of the reference example mpi_checks
        (tests/dist/mpi/examples/mpi_checks.cpp): world sanity (rank >= 0,
        size > 1), one fan-out of -100-rank, responses counted at 0."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 8200
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        if rank < 0 or world.size <= 1:
            return int(ReturnValue.FAILED)

        if rank == 0:
            for dest in range(1, world.size):
                world.send(0, dest, np.array([-100 - dest], np.int32))
            responses = 0
            for r in range(1, world.size):
                got, _ = world.recv(r, 0)
                if int(got[0]) == r:
                    responses += 1
            ok = responses == world.size - 1
            msg.output_data = f"checks:{responses}".encode()
            if not ok:
                return int(ReturnValue.FAILED)
        else:
            got, _ = world.recv(0, rank)
            if int(got[0]) != -100 - rank:
                msg.output_data = f"bad:{got[0]}".encode()
                return int(ReturnValue.FAILED)
            world.send(rank, 0, np.array([rank], np.int32))
            msg.output_data = b"checks-ok"
        world.barrier(rank)
        return int(ReturnValue.SUCCESS)

    def fn_mpi_typesize(self, msg, req):
        """Port of the reference example mpi_typesize
        (tests/dist/mpi/examples/mpi_typesize.cpp): MPI_Type_size over
        the datatype enum must match the C sizes."""
        from faabric_tpu.mpi.api import mpi_type_size
        from faabric_tpu.mpi.types import MpiDataType

        expected = {
            MpiDataType.INT: 4, MpiDataType.LONG: 8,
            MpiDataType.LONG_LONG: 8, MpiDataType.LONG_LONG_INT: 8,
            MpiDataType.DOUBLE: 8, MpiDataType.DOUBLE_INT: 12,
            MpiDataType.FLOAT: 4, MpiDataType.CHAR: 1,
        }
        for dt, size in expected.items():
            if mpi_type_size(dt) != size:
                msg.output_data = f"bad:{dt.name}".encode()
                return int(ReturnValue.FAILED)
        msg.output_data = b"typesize-ok"
        return int(ReturnValue.SUCCESS)

    def fn_mpi_cartesian(self, msg, req):
        """Port of the reference example mpi_cartesian
        (tests/dist/mpi/examples/mpi_cartesian.cpp): cart_create with a
        square side, coords round-trip through cart_rank, and a shift."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7900
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()

        world.cart_create(world.cart_dims())  # default near-square grid
        coords = world.cart_coords(rank)
        if world.cart_rank(coords) != rank:
            msg.output_data = f"roundtrip:{coords}".encode()
            return int(ReturnValue.FAILED)
        src, dst = world.cart_shift(rank, 0, 1)
        # The actual neighbours along dim 0 (periodic)
        if dst != world.cart_rank((coords[0] + 1, coords[1])) or \
                src != world.cart_rank((coords[0] - 1, coords[1])):
            msg.output_data = f"shift:{src},{dst}".encode()
            return int(ReturnValue.FAILED)
        world.barrier(rank)
        msg.output_data = f"cart-ok:{coords[0]}x{coords[1]}".encode()
        return int(ReturnValue.SUCCESS)

    def fn_mpi_order(self, msg, req):
        """Port of the reference example mpi_order
        (tests/dist/mpi/examples/mpi_order.cpp): rank 0 sends to 1/2/3
        and receives the echoes OUT OF ORDER (3, 1, 2) — per-pair
        channels must not bleed into each other."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7600
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()

        if rank == 0:
            out = {1: 111, 2: 222, 3: 333}
            for dst, v in out.items():
                world.send(0, dst, np.array([v], np.int32))
            got = {}
            for src in (3, 1, 2):  # deliberately out of order
                arr, _ = world.recv(src, 0)
                got[src] = int(arr[0])
            if got != out:
                msg.output_data = f"mismatch:{got}".encode()
                return int(ReturnValue.FAILED)
            msg.output_data = b"order-ok"
        elif rank <= 3:
            arr, _ = world.recv(0, rank)
            world.send(rank, 0, arr)
            msg.output_data = f"echoed:{int(arr[0])}".encode()
        else:
            msg.output_data = b"idle"
        world.barrier(rank)
        return int(ReturnValue.SUCCESS)

    def fn_mpi_status(self, msg, req):
        """Port of the reference example mpi_status
        (tests/dist/mpi/examples/mpi_status.cpp): rank 0 sends 40 ints;
        rank 1 probes, receives, and checks MPI_Get_count reports the
        ACTUAL count, not the buffer capacity it asked for."""
        from faabric_tpu.mpi import get_mpi_context
        from faabric_tpu.mpi.api import mpi_get_count

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7300
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()

        actual_count = 40
        if rank == 0:
            world.send(0, 1, np.arange(actual_count, dtype=np.int32))
            msg.output_data = f"sent:{actual_count}".encode()
        elif rank == 1:
            st = world.probe(0, 1, timeout=20.0)
            if mpi_get_count(st) != actual_count:
                msg.output_data = f"probe:{st.count}".encode()
                return int(ReturnValue.FAILED)
            arr, st2 = world.recv(0, 1)
            if mpi_get_count(st2) != actual_count or arr.size != actual_count:
                msg.output_data = f"recv:{st2.count}".encode()
                return int(ReturnValue.FAILED)
            msg.output_data = f"got:{st2.count}".encode()
        else:
            msg.output_data = b"idle"
        world.barrier(rank)
        return int(ReturnValue.SUCCESS)

    def fn_mpi_isendrecv(self, msg, req):
        """Port of the reference example mpi_isendrecv
        (tests/dist/mpi/examples/mpi_isendrecv.cpp): every rank
        asynchronously receives from its left neighbour and sends its
        rank to the right, then waits on both requests."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7400
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()

        right = (rank + 1) % world.size
        left = (rank - 1) % world.size
        recv_req = world.irecv(left, rank)
        send_req = world.isend(rank, right, np.array([rank], np.int32))
        results = world.waitall(rank, [recv_req, send_req])
        got = int(results[0][0][0])
        world.barrier(rank)
        if got != left:
            msg.output_data = f"r{rank}:got{got}wanted{left}".encode()
            return int(ReturnValue.FAILED)
        msg.output_data = f"r{rank}:async-ok".encode()
        return int(ReturnValue.SUCCESS)

    def fn_sleep(self, msg, req):
        """Slot blocker: hold a scheduler slot for input_data seconds."""
        time.sleep(float(msg.input_data.decode() or "1"))
        msg.output_data = b"slept"
        return int(ReturnValue.SUCCESS)

    def fn_mpi_abort(self, msg, req):
        """Chaos behaviour: loop small allreduces with think-time. When
        a peer worker is SIGKILLed mid-loop, the surviving ranks'
        collective must raise MpiWorldAborted within the configured
        bound (MPI_ABORT_CHECK_SECONDS + probe) instead of hanging to
        the 60s socket timeout. Reports 'aborted:<secs-to-abort>' with
        the time from entering the failing collective to the raise."""
        from faabric_tpu.mpi import MpiOp, MpiWorldAborted, get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 9100
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        data = np.ones(1024, np.float32)
        t0 = time.monotonic()
        for _ in range(600):  # ≤30s of rounds; the test kills a peer early
            t_round = time.monotonic()
            try:
                world.allreduce(rank, data, MpiOp.SUM)
            except MpiWorldAborted:
                elapsed = time.monotonic() - t_round
                msg.output_data = f"aborted:{elapsed:.2f}".encode()
                return int(ReturnValue.SUCCESS)
            time.sleep(0.05)
        msg.output_data = f"done:{time.monotonic() - t0:.1f}".encode()
        return int(ReturnValue.SUCCESS)

    @staticmethod
    def _all_to_all_round(world, rank, i) -> bool:
        """The reference's doAllToAll (tests/dist/mpi/mpi_native.cpp):
        every rank exchanges a distinct row with every rank and verifies
        the full matrix."""
        size = world.size
        rows = np.array([rank * 1000 + r * 10 + i for r in range(size)],
                        np.int64)
        out = world.alltoall(rank, rows).reshape(size)
        want = np.array([r * 1000 + rank * 10 + i for r in range(size)],
                        np.int64)
        return bool((out == want).all())

    def fn_mpi_migrate(self, msg, req):
        """Port of the reference example mpi_migration
        (tests/dist/mpi/examples/mpi_migration.cpp) to REAL worker
        processes: an MPI world spread over both workers loops
        barrier + all-to-all; at the check iteration every rank hits a
        migration point — the planner consolidates the freed cluster,
        moved ranks prepare the world and vacate with
        FunctionMigratedException, re-enter on the target host, and the
        world finishes the remaining loops across the migration."""
        from faabric_tpu.executor.executor import FunctionMigratedException
        from faabric_tpu.mpi import get_mpi_context
        from faabric_tpu.proto import BatchExecuteType

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7950
            msg.mpi_world_size = 3
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        my_host = self.scheduler.host
        pc = self.scheduler.planner_client

        loops, check = 8, 3
        migrated_entry = req.type == BatchExecuteType.MIGRATION
        start = check + 1 if migrated_entry else 0
        if migrated_entry:
            # Complete the group's post-migration barrier: the stayed
            # ranks are parked in their post_migration_hook waiting for
            # every member — including this re-entered one — to re-sync
            # on the new group id before anyone resumes the loop
            self.scheduler.ptp_broker.post_migration_hook(
                msg.group_id, msg.group_idx)
            world.refresh_rank_hosts()
        for i in range(start, loops):
            world.barrier(rank)
            if not self._all_to_all_round(world, rank, i):
                msg.output_data = f"r{rank}:bad-alltoall@{i}".encode()
                return int(ReturnValue.FAILED)

            if i == check and not migrated_entry:
                # Migration point (reference mpiMigrationPoint). Rank 0
                # asks the planner; everyone learns the outcome through
                # the world itself, then reads the new decision.
                world.barrier(rank)
                old_gid = world.group_id
                if rank == 0:
                    deadline = time.time() + 20
                    dec = None
                    while dec is None and time.time() < deadline:
                        dec = pc.check_migration(msg.app_id)
                        if dec is None:
                            time.sleep(0.25)
                    flag = np.array([1 if dec is not None else 0], np.int64)
                    world.broadcast(0, 0, flag)
                else:
                    flag = world.broadcast(0, rank, np.zeros(1, np.int64))
                if int(flag[0]) == 0:
                    msg.output_data = f"r{rank}:no-migration".encode()
                    return int(ReturnValue.FAILED)
                # Fetch the post-migration decision (group id changed)
                dec = pc.get_scheduling_decision(msg.app_id)
                deadline = time.time() + 10
                while (dec is None or dec.group_id == old_gid) \
                        and time.time() < deadline:
                    time.sleep(0.1)
                    dec = pc.get_scheduling_decision(msg.app_id)
                idx = dec.app_idxs.index(msg.app_idx)
                target = dec.hosts[idx]
                world.prepare_migration(rank, dec.group_id)
                if target != my_host:
                    raise FunctionMigratedException()
                self.scheduler.ptp_broker.post_migration_hook(
                    dec.group_id, dec.group_idxs[idx])
                world.refresh_rank_hosts()

        world.barrier(rank)
        msg.output_data = f"r{rank}:migrate-ok:{my_host}".encode()
        return int(ReturnValue.SUCCESS)

    def fn_mpi_migrate_traffic(self, msg, req):
        """ISSUE 6 lifecycle chaos: live migration of an MPI world UNDER
        TRAFFIC. Same migration protocol as fn_mpi_migrate, but the world
        streams barrier+all-to-all rounds continuously and every STAYING
        rank measures the migration pause — from entering the migration
        point to completing its first post-migration round. Reports
        ``r<rank>:migrate-traffic-ok:<host>:<pause_ms>`` (pause_ms = -1
        for the moved rank, whose wall time spans two executions)."""
        from faabric_tpu.executor.executor import FunctionMigratedException
        from faabric_tpu.mpi import get_mpi_context
        from faabric_tpu.proto import BatchExecuteType

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7970
            msg.mpi_world_size = 3
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        my_host = self.scheduler.host
        pc = self.scheduler.planner_client

        loops, check = 24, 6
        migrated_entry = req.type == BatchExecuteType.MIGRATION
        start = check + 1 if migrated_entry else 0
        pause_ms = -1.0
        if migrated_entry:
            self.scheduler.ptp_broker.post_migration_hook(
                msg.group_id, msg.group_idx)
            world.refresh_rank_hosts()
            # Join the stayers' pause-measurement round (they run it
            # right after their own post_migration_hook)
            world.barrier(rank)
            if not self._all_to_all_round(world, rank, 1000 + check):
                msg.output_data = f"r{rank}:bad-postmig".encode()
                return int(ReturnValue.FAILED)
        for i in range(start, loops):
            world.barrier(rank)
            if not self._all_to_all_round(world, rank, i):
                msg.output_data = f"r{rank}:bad-alltoall@{i}".encode()
                return int(ReturnValue.FAILED)

            if i == check and not migrated_entry:
                t_pause = time.monotonic()
                world.barrier(rank)
                old_gid = world.group_id
                if rank == 0:
                    deadline = time.time() + 20
                    dec = None
                    while dec is None and time.time() < deadline:
                        dec = pc.check_migration(msg.app_id)
                        if dec is None:
                            time.sleep(0.25)
                    flag = np.array([1 if dec is not None else 0], np.int64)
                    world.broadcast(0, 0, flag)
                else:
                    flag = world.broadcast(0, rank, np.zeros(1, np.int64))
                if int(flag[0]) == 0:
                    msg.output_data = f"r{rank}:no-migration".encode()
                    return int(ReturnValue.FAILED)
                dec = pc.get_scheduling_decision(msg.app_id)
                deadline = time.time() + 10
                while (dec is None or dec.group_id == old_gid) \
                        and time.time() < deadline:
                    time.sleep(0.1)
                    dec = pc.get_scheduling_decision(msg.app_id)
                idx = dec.app_idxs.index(msg.app_idx)
                target = dec.hosts[idx]
                world.prepare_migration(rank, dec.group_id)
                if target != my_host:
                    raise FunctionMigratedException()
                self.scheduler.ptp_broker.post_migration_hook(
                    dec.group_id, dec.group_idxs[idx])
                world.refresh_rank_hosts()
                # Pause ends when the rewired world completes a round
                world.barrier(rank)
                if not self._all_to_all_round(world, rank, 1000 + i):
                    msg.output_data = f"r{rank}:bad-postmig".encode()
                    return int(ReturnValue.FAILED)
                pause_ms = (time.monotonic() - t_pause) * 1000.0

        world.barrier(rank)
        msg.output_data = (f"r{rank}:migrate-traffic-ok:{my_host}:"
                           f"{pause_ms:.0f}").encode()
        return int(ReturnValue.SUCCESS)

    def fn_spot(self, msg, req):
        """ISSUE 6 lifecycle chaos: spot freeze → thaw with snapshot
        restore on a different host. First entry stamps a marker into the
        executor memory and waits to be frozen (the test evicts this
        host via the spot policy); on the freeze it parks the live
        memory image on the PLANNER's snapshot registry and vacates with
        FunctionFrozenException. The thawed re-entry — wherever the
        planner placed it — sees the restored marker and reports its
        host."""
        from faabric_tpu.executor.executor import FunctionFrozenException
        from faabric_tpu.snapshot import SnapshotData
        from faabric_tpu.snapshot.remote import SnapshotClient

        pc = self.scheduler.planner_client
        # Per-task marker slot: every task of the batch shares this
        # executor's memory, so a single shared marker would make the
        # second task mistake the first task's stamp for a thaw restore
        off = 64 * (1 + msg.group_idx)
        marker = self.memory[off:off + 8].view(np.int64)
        if marker[0] == 4242:
            msg.output_data = f"thawed:{self.scheduler.host}".encode()
            return int(ReturnValue.SUCCESS)
        marker[0] = 4242

        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                dec = pc.get_scheduling_decision(msg.app_id)
            except Exception:  # noqa: BLE001 — planner blip: keep waiting
                dec = object()
            if dec is None:
                # Frozen (the app left the in-flight set): park the live
                # image under the batch's snapshot key so the thaw
                # dispatch can restore it on ANY host, then vacate
                snap = SnapshotData(self.memory.tobytes())
                with self._batch_lock:
                    try:
                        SnapshotClient(pc.host).push_snapshot(
                            req.snapshot_key, snap)
                    except Exception:  # noqa: BLE001 — report, don't wedge
                        msg.output_data = b"snapshot-park-failed"
                        return int(ReturnValue.FAILED)
                raise FunctionFrozenException()
            time.sleep(0.1)
        msg.output_data = b"never-frozen"
        return int(ReturnValue.FAILED)

    def fn_mpi_partition(self, msg, req):
        """ISSUE 6 lifecycle chaos: network partition between a host
        pair. Loops small allreduces; when the fault registry partitions
        this world's hosts (transport.send/bulk kill_conn with src/dest
        ctx matchers), the abort machinery must surface MpiWorldAborted
        in bounded time — reported as ``aborted:<secs>`` like
        fn_mpi_abort, on a dedicated world id."""
        from faabric_tpu.mpi import MpiOp, MpiWorldAborted, get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 9200
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        data = np.ones(1024, np.float32)
        for _ in range(600):
            t_round = time.monotonic()
            try:
                world.allreduce(rank, data, MpiOp.SUM)
            except MpiWorldAborted:
                elapsed = time.monotonic() - t_round
                msg.output_data = f"aborted:{elapsed:.2f}".encode()
                return int(ReturnValue.SUCCESS)
            time.sleep(0.05)
        msg.output_data = b"never-partitioned"
        return int(ReturnValue.FAILED)

    def fn_mpi_alltoall_sleep(self, msg, req):
        """Port of the reference example mpi_alltoall_sleep
        (tests/dist/mpi/examples/mpi_alltoall_sleep.cpp): many
        barrier + all-to-all rounds, one rank goes to sleep mid-stream
        (the straggler), then the rounds resume — overlap/buffering in
        the data plane must absorb the stall without reordering."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7960
            msg.mpi_world_size = 8
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()

        rounds = 50
        for i in range(rounds):
            world.barrier(rank)
            if not self._all_to_all_round(world, rank, i):
                msg.output_data = f"r{rank}:bad@{i}".encode()
                return int(ReturnValue.FAILED)
        if rank == 3:
            time.sleep(2.0)  # the straggler
        for i in range(rounds):
            world.barrier(rank)
            if not self._all_to_all_round(world, rank, rounds + i):
                msg.output_data = f"r{rank}:bad@{rounds + i}".encode()
                return int(ReturnValue.FAILED)
        world.barrier(rank)
        msg.output_data = f"r{rank}:alltoall-sleep-ok".encode()
        return int(ReturnValue.SUCCESS)

    def fn_threads(self, msg, req):
        counter = self.memory[:8].view(np.int64)
        # One executor runs all local threads; serialise the shared add
        with self._batch_lock:
            counter[0] += msg.group_idx + 1
        self.memory[512 * (1 + msg.group_idx)] = 200 + msg.group_idx
        return int(ReturnValue.SUCCESS)

    def fn_train(self, msg, req):
        """Distributed data-parallel training: each rank computes grads on
        its own data shard and allreduces them through the framework's MPI
        before applying the update — every rank's params stay bit-identical
        without any parameter server."""
        import jax
        import jax.numpy as jnp

        from faabric_tpu.mpi import MpiOp, get_mpi_context
        from faabric_tpu.models import ModelConfig, init_params, loss_fn

        ctx = get_mpi_context()
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            msg.mpi_world_id = 7200
            msg.mpi_world_size = 6
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        rank = msg.mpi_rank
        world.refresh_rank_hosts()
        size = world.size

        cfg = ModelConfig(vocab_size=64, d_model=16, n_layers=1, n_heads=2,
                          d_ff=32, max_seq=16, compute_dtype=jnp.float32,
                          remat=False)
        # Same seed everywhere → identical initial params
        params = init_params(jax.random.PRNGKey(0), cfg)
        leaves, treedef = jax.tree.flatten(params)
        shapes = [l.shape for l in leaves]
        sizes = [int(np.prod(s)) for s in shapes]

        grad_fn = jax.jit(jax.grad(loss_fn), static_argnums=(3,))
        data_rng = np.random.RandomState(100 + rank)  # rank-local shard
        lr = 0.5
        for step in range(3):
            tokens = jnp.asarray(data_rng.randint(0, 64, (2, 8)),
                                 dtype=jnp.int32)
            targets = jnp.asarray(data_rng.randint(0, 64, (2, 8)),
                                  dtype=jnp.int32)
            grads = grad_fn(params, tokens, targets, cfg)
            flat = np.concatenate([np.asarray(g).ravel()
                                   for g in jax.tree.leaves(grads)])
            summed = world.allreduce(rank, flat.astype(np.float32),
                                     MpiOp.SUM) / size
            # Unflatten and SGD-update
            out, off = [], 0
            for shp, n in zip(shapes, sizes):
                out.append(summed[off:off + n].reshape(shp))
                off += n
            params = jax.tree.unflatten(
                treedef, [l - lr * jnp.asarray(g)
                          for l, g in zip(jax.tree.leaves(params), out)])
        # Param checksum must agree across ranks (synchronous training)
        checksum = float(sum(np.abs(np.asarray(l)).sum()
                             for l in jax.tree.leaves(params)))
        world.barrier(rank)
        msg.output_data = f"r{rank}:{checksum:.6f}".encode()
        return int(ReturnValue.SUCCESS)

    def fn_state(self, msg, req):
        """Non-master host pulls a shared value, doubles one chunk and
        pushes it back."""
        state = self.scheduler.state
        kv = state.get_kv("dist", "shared")
        data = np.frombuffer(kv.get_chunk(0, 1024), dtype=np.uint8)
        kv.set_chunk(0, (data * 2).astype(np.uint8).tobytes())
        kv.push_partial()
        msg.output_data = b"state-ok"
        return int(ReturnValue.SUCCESS)

    def fn_state_hot(self, msg, req):
        """ISSUE 16 statemap acceptance: hammer the planted hot key
        from this (non-master) host — repeated full re-pulls (pull
        amplification) plus a two-chunk dirty push — and report the
        wire bytes moved, so the test can check the per-key ledger
        against the plane=state comm-matrix rows independently."""
        from faabric_tpu.state import STATE_CHUNK_SIZE

        state = self.scheduler.state
        kv = state.get_kv("dist", "hot")
        wire = 0
        for _ in range(3):
            kv.pull()
            wire += kv.size
        kv.set_chunk(0, b"\x09" * STATE_CHUNK_SIZE)
        kv.set_chunk(2 * STATE_CHUNK_SIZE, b"\x09" * STATE_CHUNK_SIZE)
        wire += kv.n_dirty_chunks() * STATE_CHUNK_SIZE
        kv.push_partial()
        msg.output_data = f"wire={wire}".encode()
        return int(ReturnValue.SUCCESS)

    def fn_state_claim(self, msg, req):
        """ISSUE 19 chaos helper: claim mastership of the key named in
        input_data on THIS host (first writer = master) and seed a
        recognizable image, so the failover test controls exactly which
        worker process masters which key before the SIGKILL."""
        from faabric_tpu.state import STATE_CHUNK_SIZE

        key = msg.input_data.decode()
        state = self.scheduler.state
        kv = state.get_kv("chaos", key, 4 * STATE_CHUNK_SIZE)
        kv.set_chunk(0, bytes([7]) * STATE_CHUNK_SIZE)
        kv.push_partial()
        msg.output_data = f"{key}@{state.host}".encode()
        return int(ReturnValue.SUCCESS)

    def fn_state_stale_probe(self, msg, req):
        """ISSUE 19 fencing probe: attempt an acked write through a
        master KV this (revived) host still holds from BEFORE a
        failover promoted its backup. The epoch fence must reject the
        ack — the output reports what actually happened so the chaos
        test can assert split-brain is structurally impossible."""
        from faabric_tpu.state import STATE_CHUNK_SIZE, StaleStateEpoch

        key = msg.input_data.decode()
        state = self.scheduler.state
        kv = state.try_get_kv("chaos", key)
        if kv is None or not kv.is_master:
            msg.output_data = b"no-master-kv"
            return int(ReturnValue.SUCCESS)
        kv.set_chunk(0, b"\xee" * STATE_CHUNK_SIZE)
        try:
            kv.push_partial()
        except StaleStateEpoch:
            msg.output_data = b"fenced:StaleStateEpoch"
        except Exception as e:  # noqa: BLE001 — report, never ack
            msg.output_data = f"error:{type(e).__name__}".encode()
        else:
            msg.output_data = b"ACKED"
        return int(ReturnValue.SUCCESS)

    def fn_profile_spin(self, msg, req):
        """ISSUE 18 profiling acceptance: burn this executor-pool
        thread inside a distinctively named frame for input_data
        seconds, with two light lock-convoy helper threads contending a
        shared lock alongside it — the planted cpu_hotspot +
        gil_saturation scenario the merged /profile and the doctor must
        attribute to THIS host and thread class while it runs."""
        import threading

        dur = float(msg.input_data.decode() or "4")
        stop = threading.Event()
        lock = threading.Lock()

        def convoy():
            # Short bursts under the lock, mostly parked: enough GIL
            # handoff churn to keep the drift estimator honest without
            # out-burning the planted frame below
            x = 0
            while not stop.is_set():
                with lock:
                    for _ in range(2_000):
                        x = (x * 48271) % 2147483647
                stop.wait(0.002)

        helpers = [threading.Thread(target=convoy,
                                    name=f"test/convoy@{i}", daemon=True)
                   for i in range(2)]
        for t in helpers:
            t.start()
        try:
            _planted_profile_burn(dur)
        finally:
            stop.set()
            for t in helpers:
                t.join(timeout=5)
        msg.output_data = b"spun"
        return int(ReturnValue.SUCCESS)


def _planted_profile_burn(dur: float) -> None:
    """Distinctive frame the ISSUE 18 dist test hunts for in the merged
    /profile ranking — keep the name unique across the tree."""
    end = time.monotonic() + dur
    x = 0
    while time.monotonic() < end:
        for _ in range(5_000):
            x = (x * 1103515245 + 12345) & 0x7FFFFFFF


class DistFactory(ExecutorFactory):
    def create_executor(self, msg):
        return DistExecutor(msg)


def run_planner(port_offset: int = 0) -> None:
    from faabric_tpu.planner import PlannerServer

    server = PlannerServer(port_offset=port_offset)
    server.start()
    endpoint = None
    http_port = int(os.environ.get("DIST_HTTP_PORT", "0"))
    if http_port:
        # REST surface for the telemetry tests: GET /metrics + /trace
        from faabric_tpu.endpoint import PlannerHttpEndpoint

        endpoint = PlannerHttpEndpoint(port=http_port)
        endpoint.start()
    print("READY", flush=True)
    time.sleep(int(os.environ.get("DIST_PROC_TTL", "120")))
    if endpoint is not None:
        endpoint.stop()
    server.stop()


def run_worker(host: str, planner_host: str = "127.0.0.1",
               slots: int = 4) -> None:
    from faabric_tpu.runner import WorkerRuntime

    w = WorkerRuntime(host=host, slots=slots, n_devices=4,
                      factory=DistFactory(), planner_host=planner_host)
    w.start()
    print("READY", flush=True)
    time.sleep(int(os.environ.get("DIST_PROC_TTL", "120")))
    w.shutdown()


def run_plane_worker(host: str, n_procs: int) -> None:
    """Multi-process device plane worker (parallel/distributed.py): joins
    the planner-coordinated plane at boot with 4 virtual CPU devices,
    then proves a cross-process device collective — the shards of one
    global array live in BOTH worker processes and each process verifies
    its own shards of the result. Reference analog: the cross-host MPI
    data plane (src/mpi/MpiWorld.cpp:1789-1934), replaced here by XLA
    collectives over one jax.distributed plane."""
    from faabric_tpu.parallel.distributed import force_cpu_virtual_devices

    force_cpu_virtual_devices(4)

    from faabric_tpu.runner import WorkerRuntime

    # register=False: plane workers take no scheduled work (and must not
    # linger in the planner's host table after this short-lived proc)
    w = WorkerRuntime(host=host, slots=1, n_devices=4,
                      factory=DistFactory(), planner_host="127.0.0.1",
                      device_plane_size=n_procs)
    w.start(register=False)
    try:
        import jax

        from faabric_tpu.mpi import MpiOp
        from faabric_tpu.parallel import DeviceCollectives, plane_summary

        s = plane_summary()
        col = DeviceCollectives(jax.devices())
        local_ranks = [r for r, d in enumerate(col.devices)
                       if d.process_index == jax.process_index()]
        local = {r: np.full(4096, float(r + 1), np.float32)
                 for r in local_ranks}
        x = col.shard_stacked_addressable(local, (4096,), np.float32)
        out = col.allreduce(x, MpiOp.SUM)
        expected = col.n * (col.n + 1) / 2
        ok = all(bool((col.addressable_shard(out, r) == expected).all())
                 for r in local_ranks)

        # Second collective shape: allgather a per-rank scalar row and
        # check every process reconstructs the full plane-wide vector
        g = col.allgather(col.shard_stacked_addressable(
            {r: np.full(8, float(r), np.float32) for r in local_ranks},
            (8,), np.float32))
        got = np.asarray(g.addressable_shards[0].data).reshape(col.n, 8)
        ok = ok and all((got[r] == r).all() for r in range(col.n))

        # The big one: a FULL jitted train step over a (dp=4, tp=2) mesh
        # whose devices span both worker processes — gradients allreduce
        # across the process boundary inside one XLA program
        import jax.numpy as jnp

        from faabric_tpu.models import (
            ModelConfig,
            data_sharding,
            init_train_state,
            make_train_step,
        )
        from faabric_tpu.parallel import MeshConfig, build_mesh

        cfg = ModelConfig(vocab_size=128, d_model=32, n_layers=2,
                          n_heads=4, d_ff=64, max_seq=16,
                          compute_dtype=jnp.float32, remat=False)
        mesh = build_mesh(jax.devices(), MeshConfig(tp=2))
        params, opt_state = init_train_state(jax.random.PRNGKey(0), cfg,
                                             mesh)
        step = make_train_step(cfg, mesh)
        rng = np.random.RandomState(0)  # same data in both controllers
        tokens = jax.device_put(
            rng.randint(0, 128, (8, 16)).astype(np.int32),
            data_sharding(mesh))
        targets = jax.device_put(
            rng.randint(0, 128, (8, 16)).astype(np.int32),
            data_sharding(mesh))
        loss = None
        for _ in range(2):
            params, opt_state, loss = step(params, opt_state, tokens,
                                           targets)
        loss = float(loss)
        ok = ok and np.isfinite(loss)

        # Cross-process PIPELINE: a {dp:2, tp:2, pp:2} mesh whose pp=2
        # stages live in DIFFERENT worker processes. Default process-
        # major device order would put both pp stages of every dp slice
        # in ONE process (the mesh reshapes (dp, sp, pp, ep, tp), so pp
        # stride is ep*tp=2 — pairs {0,2},{1,3},...). Interleave the two
        # processes' devices so every pp partner pair spans the process
        # boundary and the compiled 1F1B step's inter-stage ppermute
        # truly crosses processes.
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from faabric_tpu.parallel.pipeline import (
            init_pp_train_state,
            make_pp_train_step,
        )

        ds = sorted(jax.devices(), key=lambda d: d.id)
        pp_order = [ds[i] for i in (0, 1, 4, 5, 2, 3, 6, 7)]
        pp_mesh = build_mesh(pp_order, MeshConfig(tp=2, pp=2))
        # Every pp hop must cross the process boundary, or this test
        # proves nothing beyond the dp allreduce above
        pidx = np.vectorize(lambda d: d.process_index)(pp_mesh.devices)
        pp_axis = pp_mesh.axis_names.index("pp")
        stage0, stage1 = (pidx.take(0, axis=pp_axis).ravel(),
                          pidx.take(1, axis=pp_axis).ravel())
        ok = ok and bool((stage0 != stage1).all())
        pp_params, pp_opt = init_pp_train_state(
            jax.random.PRNGKey(0), cfg, pp_mesh)
        pp_step = make_pp_train_step(cfg, pp_mesh, n_microbatches=2,
                                     schedule_name="1f1b")
        batch_sharding = NamedSharding(pp_mesh, P("dp", None))
        pp_tokens = jax.device_put(
            rng.randint(0, 128, (8, 16)).astype(np.int32), batch_sharding)
        pp_targets = jax.device_put(
            rng.randint(0, 128, (8, 16)).astype(np.int32), batch_sharding)
        _, _, pp_loss = pp_step(pp_params, pp_opt, pp_tokens, pp_targets)
        pp_loss = float(pp_loss)
        ok = ok and np.isfinite(pp_loss)

        print(f"PLANE-{'OK' if ok else 'FAIL'} proc={s['process_index']}/"
              f"{s['process_count']} gdev={s['global_devices']} "
              f"ldev={s['local_devices']} ranks={local_ranks} "
              f"pp_loss={pp_loss:.6f} loss={loss:.6f}", flush=True)
    except Exception as e:  # noqa: BLE001 — report to the harness
        print(f"PLANE-FAIL {type(e).__name__}: {e}"[:200], flush=True)
    time.sleep(int(os.environ.get("DIST_PROC_TTL", "120")))
    w.shutdown()


if __name__ == "__main__":
    # Debugging aid: SIGUSR1 dumps every thread's stack to stderr
    import faulthandler
    import signal

    faulthandler.register(signal.SIGUSR1)
    # Black box on teardown: when FAABRIC_FLIGHT_DIR is set, SIGTERM
    # leaves a flight dump before the process exits
    from faabric_tpu.telemetry.flight import install_signal_dump

    install_signal_dump()
    role = sys.argv[1]
    if role == "planner":
        run_planner(int(sys.argv[2]) if len(sys.argv) > 2 else 0)
    elif role == "planeworker":
        run_plane_worker(sys.argv[2], int(sys.argv[3]))
    else:
        run_worker(sys.argv[2],
                   sys.argv[3] if len(sys.argv) > 3 else "127.0.0.1",
                   int(sys.argv[4]) if len(sys.argv) > 4 else 4)
