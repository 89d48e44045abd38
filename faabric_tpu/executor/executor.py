"""Executor: the per-host execution engine.

Reference analog: src/executor/Executor.cpp:111-215 (executeTasks),
:307-581 (threadPoolThread), include/faabric/executor/Executor.h:21-118.

An executor is bound to one function (user/function) and runs one batch at a
time (claim/release). It owns a pool of worker threads with per-thread task
queues; ``execute_task`` is the virtual the embedding runtime implements —
on TPU typically a jitted JAX callable running on the chip the planner
pinned this rank to (``ExecutorContext.get().device_id``).

Snapshot restore / dirty tracking hooks (``restore``, ``get_memory_view``,
``set_memory_size``) mirror the reference's THREADS path; the snapshot layer
wires into them.
"""

from __future__ import annotations

import threading
import time
from typing import TYPE_CHECKING, Optional

from faabric_tpu.executor.context import ExecutorContext
from faabric_tpu.faults import fault_point, faults_enabled
from faabric_tpu.proto import (
    BatchExecuteRequest,
    BatchExecuteType,
    Message,
    ReturnValue,
    get_main_thread_snapshot_key,
)
from faabric_tpu.telemetry import get_lifecycle, get_metrics
from faabric_tpu.telemetry.lifecycle import (
    PHASE_EXEC_QUEUE_EXIT,
    PHASE_RUN_CPU,
    PHASE_RUN_END,
    PHASE_RUN_START,
)
from faabric_tpu.util.config import get_system_config
from faabric_tpu.util.logging import get_logger
from faabric_tpu.util.queues import Queue

if TYPE_CHECKING:  # pragma: no cover
    from faabric_tpu.scheduler.scheduler import Scheduler

logger = get_logger(__name__)

POOL_SHUTDOWN = -1

_FAULTS = faults_enabled()
_FP_RUN = fault_point("executor.run")

_LC = get_lifecycle()

_metrics = get_metrics()
_QUEUE_WAIT_SECONDS = _metrics.histogram(
    "faabric_executor_queue_wait_seconds",
    "Task time spent queued before a pool thread picked it up")
_RUN_SECONDS = _metrics.histogram(
    "faabric_executor_run_seconds",
    "Guest execute_task run time")
_TASKS_TOTAL = _metrics.counter(
    "faabric_executor_tasks_total", "Tasks executed")


class FunctionMigratedException(Exception):
    """Thrown by guest code when it detects it must migrate
    (reference include/faabric/executor/Executor.h)."""


class FunctionFrozenException(Exception):
    """Thrown by guest code when its app is spot-frozen."""


class ExecutorTask:
    def __init__(self, msg_idx: int, req: BatchExecuteRequest) -> None:
        self.msg_idx = msg_idx
        self.req = req
        self.enqueue_ns = time.monotonic_ns()


def _merge_dirty_flags(acc, new):
    """OR page-flag arrays that may differ in length (memory grown
    mid-batch: unseen pages count as dirty for the thread that grew)."""
    import numpy as np

    if acc is None:
        return new
    if acc.size == new.size:
        return acc | new
    n, m = max(acc.size, new.size), min(acc.size, new.size)
    out = np.ones(n, dtype=bool)  # grown pages are dirty by definition
    out[:m] = acc[:m] | new[:m]
    return out


class Executor:
    """Base executor; subclasses implement ``execute_task`` and the memory
    hooks."""

    def __init__(self, msg: Message) -> None:
        conf = get_system_config()
        self.bound_msg = msg
        self.id = f"{msg.user}/{msg.function}-{msg.id}"

        self.pool_size = conf.get_usable_cores()
        self._task_queues: dict[int, Queue[ExecutorTask]] = {}
        self._pool_threads: dict[int, threading.Thread] = {}

        self._claimed = False
        self._claim_lock = threading.Lock()

        self.last_exec: float = time.monotonic()

        # Batch bookkeeping: tasks outstanding in the current batch
        self._batch_lock = threading.Lock()
        self._tasks_outstanding = 0

        self._chained_lock = threading.Lock()
        self._chained_messages: dict[int, Message] = {}

        self._shutdown = False

        # Set by the scheduler right after the factory creates the executor;
        # carries host identity and the planner client used to report
        # results.
        self.scheduler: Optional["Scheduler"] = None

        # THREADS batch snapshot state (set per batch in execute_tasks)
        self._batch_snapshot_key = ""
        self._batch_tracker = None
        self._batch_dirty = None  # accumulated dirty page flags (OR)
        self._batch_hints = None  # (offset, length) write extents or None

    def _region_hints_for(self, snapshot_key: str):
        """Merge regions as write-extent hints, when DIRTY_REGION_HINTS
        promises guest writes stay inside declared regions."""
        from faabric_tpu.util.config import get_system_config

        if not get_system_config().dirty_region_hints:
            return None
        registry = getattr(self.scheduler, "snapshot_registry", None)
        if registry is None:
            return None
        snap = registry.try_get_snapshot(snapshot_key)
        if snap is None:
            return None
        regions = snap.get_merge_regions()
        if not regions:
            return None
        # Hints only help when the declared write set is a small part of
        # the image: after a previous batch's fill_gaps_with_bytewise_
        # regions() the regions span everything, and whole-image "hints"
        # bracket SLOWER than plain tracking (fancy-index page copies)
        covered = sum(r.length for r in regions)
        if covered * 2 >= snap.size:
            return None
        return [(r.offset, r.length) for r in regions]

    # ------------------------------------------------------------------
    # Virtual hooks (reference Executor.h:60-104)
    # ------------------------------------------------------------------
    def execute_task(self, thread_pool_idx: int, msg_idx: int,
                     req: BatchExecuteRequest) -> int:
        raise NotImplementedError

    def reset(self, msg: Message) -> None:
        """Return the executor to a clean state between batches."""

    def restore(self, snapshot_key: str) -> None:
        """Map a snapshot onto this executor's memory (THREADS batches).
        Default: fetch from the host's registry, size memory, copy in
        (reference Executor.cpp:640-654)."""
        registry = getattr(self.scheduler, "snapshot_registry", None)
        if registry is None:
            return
        snap = registry.get_snapshot(snapshot_key)
        self.set_memory_size(snap.size)
        mem = self.get_memory_view()
        if mem is not None:
            snap.map_to_memory(mem)

    def get_memory_view(self) -> Optional[memoryview]:
        return None

    def set_memory_size(self, size: int) -> None:
        pass

    def get_max_memory_size(self) -> int:
        return 0

    # ------------------------------------------------------------------
    # Claiming (reference Executor::tryClaim/releaseClaim)
    # ------------------------------------------------------------------
    def try_claim(self) -> bool:
        with self._claim_lock:
            if self._claimed:
                return False
            self._claimed = True
            return True

    def release_claim(self) -> None:
        with self._claim_lock:
            self._claimed = False

    def is_claimed(self) -> bool:
        with self._claim_lock:
            return self._claimed

    # ------------------------------------------------------------------
    # Batch execution
    # ------------------------------------------------------------------
    def execute_tasks(self, msg_idxs: list[int], req: BatchExecuteRequest) -> None:
        logger.debug("%s executing %d/%d tasks of app %d", self.id,
                     len(msg_idxs), req.n_messages(), req.app_id)
        self.last_exec = time.monotonic()

        is_threads = req.type == int(BatchExecuteType.THREADS)

        # Multi-host THREADS batches restore from the main thread's snapshot
        # before any task runs and start dirty tracking so each thread's
        # writes can merge back as diffs (reference Executor.cpp:137-160).
        self._batch_snapshot_key = ""
        self._batch_tracker = None
        self._batch_dirty = None
        if is_threads and not req.single_host and req.snapshot_key:
            self.restore(req.snapshot_key)
            mem = self.get_memory_view()
            if mem is not None:
                from faabric_tpu.util.dirty import make_dirty_tracker

                self._batch_snapshot_key = req.snapshot_key
                self._batch_tracker = make_dirty_tracker()
                # Opt-in region hints: when the batch's snapshot declares
                # merge regions AND the config promises writes stay inside
                # them, bracketing cost scales with the declared write
                # set, not the image
                self._batch_hints = self._region_hints_for(req.snapshot_key)
                self._batch_tracker.start_tracking(
                    mem, region_hints=self._batch_hints)

        with self._batch_lock:
            self._tasks_outstanding += len(msg_idxs)

        for msg_idx in msg_idxs:
            # Tasks spread over the pool by message index; THREADS batches
            # of up to pool_size threads therefore get one thread each.
            self._enqueue(msg_idx % self.pool_size, ExecutorTask(msg_idx, req))

    def _enqueue(self, pool_idx: int, task: ExecutorTask) -> None:
        if pool_idx not in self._task_queues:
            self._task_queues[pool_idx] = Queue()
            t = threading.Thread(
                target=self._pool_thread_loop, args=(pool_idx,),
                name=f"executor/pool@{self.id}-{pool_idx}", daemon=True,
            )
            self._pool_threads[pool_idx] = t
            t.start()
        self._task_queues[pool_idx].enqueue(task)

    def _pool_thread_loop(self, pool_idx: int) -> None:
        q = self._task_queues[pool_idx]
        while not self._shutdown:
            task = q.dequeue()
            if task is POOL_SHUTDOWN:
                return
            try:
                self._run_task(pool_idx, task)
            except Exception:  # noqa: BLE001 — a reporting failure must not
                # kill the pool thread; the task's own errors are already
                # folded into its result inside _run_task
                logger.exception("%s result handling failed for task %d",
                                 self.id, task.msg_idx)

    def _run_task(self, pool_idx: int, task: ExecutorTask) -> None:
        req = task.req
        msg = req.messages[task.msg_idx]
        is_threads = req.type == int(BatchExecuteType.THREADS)
        msg.executed_host = self.scheduler.host if self.scheduler else ""
        # One helper marks each boundary of the task (eqx, rns, rne): it
        # stamps the lifecycle ledger, opens the profiler's span, and its
        # two clock reads are what the histograms and the exec graph's
        # queue_us / exec_us below are computed from
        with _LC.phase_span(msg, PHASE_EXEC_QUEUE_EXIT, None,
                            "run_prep") as prep:
            # Thread-local dirty tracking brackets the task so each thread
            # reports only its own writes (reference Executor.cpp:464-476)
            tracker = self._batch_tracker
            mem = self.get_memory_view() if tracker is not None else None
            if tracker is not None and mem is not None:
                tracker.start_thread_local_tracking(
                    mem, region_hints=self._batch_hints)
            ExecutorContext.set(self, req, task.msg_idx)

        run = _LC.phase_span(msg, PHASE_RUN_START, PHASE_RUN_END, "run",
                             cpu_phase=PHASE_RUN_CPU)
        try:
            with run:
                if _FAULTS:
                    # delay rules make stragglers; raise rules fail the
                    # task (the generic handler below folds it into the
                    # result)
                    _FP_RUN.fire(function=f"{msg.user}/{msg.function}",
                                 msg_id=msg.id)
                ret = self.execute_task(pool_idx, task.msg_idx, req)
        except FunctionMigratedException:
            logger.debug("%s task %d migrated", self.id, msg.id)
            ret = int(ReturnValue.MIGRATED)
        except FunctionFrozenException:
            logger.debug("%s task %d frozen", self.id, msg.id)
            ret = int(ReturnValue.FROZEN)
        except Exception as e:  # noqa: BLE001 — guest errors become results
            logger.exception("%s task %d failed", self.id, msg.id)
            ret = int(ReturnValue.FAILED)
            msg.output_data = str(e).encode()
            # Post-mortem: the unhandled guest exception is a flight-dump
            # trigger — the ring's recent sends/faults around it are the
            # context a stack trace alone cannot give. Guarded: recording
            # must never replace the handled guest error (the FAILED
            # result still has to reach the planner).
            try:
                from faabric_tpu.telemetry import (
                    flight_dump,
                    flight_record,
                )

                flight_record("executor_exception", msg_id=msg.id,
                              function=f"{msg.user}/{msg.function}",
                              error=str(e)[:200])
                flight_dump("executor_exception")
            except Exception:  # noqa: BLE001
                logger.exception("Flight dump on task failure failed")
        finally:
            ExecutorContext.unset()

        # The worker's half of the result push: from the guest's return
        # to the push RPC written (rsp is stamped inside, by the planner
        # client, as the last thing before the wire)
        with _LC.phase_span(msg, None, None, "result_push"):
            queue_ns = prep.start_ns - task.enqueue_ns
            run_ns = run.end_ns - run.start_ns
            _QUEUE_WAIT_SECONDS.observe(queue_ns / 1e9)
            _RUN_SECONDS.observe(run_ns / 1e9)
            _TASKS_TOTAL.inc()
            msg.return_value = ret
            msg.finish_timestamp = time.time()
            # Per-message timing rides the result into the planner, so
            # ExecGraph.to_json() can report wall/queue/exec durations per
            # node (util/exec_graph.py)
            msg.int_exec_graph_details["queue_us"] = queue_ns // 1000
            msg.int_exec_graph_details["exec_us"] = run_ns // 1000
            self.last_exec = time.monotonic()
            last_in_batch = self._report_result(msg, ret, is_threads,
                                                tracker, mem)

        # Last task of the batch returns the executor to the pool
        # (reference Executor.cpp:520-570).
        if last_in_batch:
            if is_threads and self._batch_tracker is not None:
                # Unprotect/retire the batch-level bracket now rather than
                # at the next batch's reassignment: segv mode would
                # otherwise leave untouched pages PROT_READ and charge
                # later non-THREADS work a fault per page
                if mem is None:
                    mem = self.get_memory_view()
                if mem is not None:
                    self._batch_tracker.stop_tracking(mem)
                self._batch_tracker = None
            if not is_threads:
                self.reset(self.bound_msg)
            self.release_claim()
            if self.scheduler is not None:
                self.scheduler.notify_executor_idle(self)

    def _report_result(self, msg: Message, ret: int, is_threads: bool,
                       tracker, mem) -> bool:
        """Fold this task's dirty pages into the batch, count it done and
        report its result. True for the batch's last task."""
        # Each thread contributes its dirty pages BEFORE the outstanding
        # count drops: the decrement elects the last thread, and that
        # thread must see every earlier thread's pages when it computes the
        # batch diff (reference Executor.cpp:684-737 mergeDirtyRegions).
        if is_threads and tracker is not None and mem is not None:
            tracker.stop_thread_local_tracking(mem)
            dirty = tracker.get_thread_local_dirty_pages(mem)
            with self._batch_lock:
                self._batch_dirty = _merge_dirty_flags(self._batch_dirty,
                                                       dirty)

        with self._batch_lock:
            self._tasks_outstanding -= 1
            last_in_batch = self._tasks_outstanding == 0

        # Report the result. THREADS results carry the batch's snapshot
        # diffs back to the main host (computed once, by the last task);
        # everything else reports straight to the planner.
        if self.scheduler is not None:
            if is_threads:
                diffs = None
                if last_in_batch and mem is not None:
                    registry = getattr(self.scheduler,
                                       "snapshot_registry", None)
                    if registry is not None and self._batch_snapshot_key:
                        snap = registry.try_get_snapshot(
                            self._batch_snapshot_key)
                        if snap is not None:
                            with self._batch_lock:
                                batch_dirty = self._batch_dirty
                            if batch_dirty is not None:
                                # Writes outside declared merge regions must
                                # not vanish (reference Executor.cpp:713)
                                snap.fill_gaps_with_bytewise_regions()
                                diffs = snap.diff_with_dirty_regions(
                                    mem, batch_dirty)
                self.scheduler.report_thread_result(
                    msg, ret, self._batch_snapshot_key, diffs)
            else:
                self.scheduler.report_message_result(msg)

        return last_in_batch

    # ------------------------------------------------------------------
    # Chained messages (reference Executor::getChainedMessage)
    # ------------------------------------------------------------------
    def add_chained_message(self, msg: Message) -> None:
        with self._chained_lock:
            self._chained_messages[msg.id] = msg

    def get_chained_message(self, msg_id: int) -> Message:
        with self._chained_lock:
            return self._chained_messages[msg_id]

    def get_chained_message_ids(self) -> list[int]:
        with self._chained_lock:
            return list(self._chained_messages)

    # ------------------------------------------------------------------
    def shutdown(self) -> None:
        self._shutdown = True
        for idx, q in self._task_queues.items():
            q.enqueue(POOL_SHUTDOWN)
        for t in self._pool_threads.values():
            t.join(timeout=2.0)
        self._pool_threads.clear()
        self._task_queues.clear()

    def uptime_idle(self) -> float:
        return time.monotonic() - self.last_exec
