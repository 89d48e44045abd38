"""The system under test as child processes, and its REST client.

Copied from ``chip_smoke.py`` (``Cluster``, ``_free_port_offset``,
``_die_with_parent``) so that a later change to the smoke cannot move the
benchmark. The parent that uses this never imports JAX: a chip belongs to
one process, the worker. The planner is the program's own
``python -m faabric_tpu.runner planner``; the worker is
``benchmarks/worker.py``, which embeds the program's ``WorkerRuntime`` over
every local chip and registers the cell's guest.
"""

from __future__ import annotations

import fcntl
import http.client
import json
import os
import queue
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

PLANNER_HOST = "bench-planner"
WORKER_HOST = "bench-worker"
USER = "bench"
# REST message types (faabric_tpu/endpoint/http_server.py HttpMessageType)
GET_AVAILABLE_HOSTS, GET_CONFIG = 5, 6
EXECUTE_BATCH, EXECUTE_BATCH_STATUS = 10, 11


class BenchFailed(Exception):
    pass


class NoAccelerator(BenchFailed):
    pass


def _die_with_parent() -> None:
    """preexec_fn: a child must not outlive the benchmark, however it
    ends."""
    import ctypes

    ctypes.CDLL("libc.so.6", use_errno=True).prctl(1, signal.SIGKILL)


_held_slots: list = []  # lock files of the port ranges this process holds


def _lock_slot(offset: int):
    """An exclusive lock on one port range for the life of this process,
    so that two benchmarks that probe at the same moment (the test suite
    runs several at once) do not both find it free."""
    path = os.path.join(tempfile.gettempdir(),
                        f"faabric-bench-ports-{offset}.lock")
    f = open(path, "w")
    try:
        fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except OSError:
        f.close()
        return None
    return f


def free_port_offset() -> int:
    """A port offset at which the planner's (offset) and the worker's
    (offset + 1000) listener ranges are both free, and no other benchmark
    process holds either."""
    start = 2000 + 500 * (os.getpid() % 30)
    for offset in list(range(start, 20000, 500)) + list(
            range(2000, start, 500)):
        locks = [_lock_slot(offset), _lock_slot(offset + 1000)]
        ports = [offset + extra + p for extra in (0, 1000)
                 for p in range(8003, 8015)]
        socks = []
        try:
            if not all(locks):
                raise OSError("held by another benchmark")
            for port in ports:
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", port))
            _held_slots.extend(locks)
            return offset
        except OSError:
            for f in locks:
                if f:
                    f.close()
            continue
        finally:
            for s in socks:
                s.close()
    raise BenchFailed("no free port range for the planner and the worker")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Cluster:
    """Planner and worker as child processes, logs under ``out_dir``."""

    def __init__(self, root: str, out_dir: str, worker_argv: list) -> None:
        self.root, self.out_dir = root, out_dir
        os.makedirs(out_dir, exist_ok=True)
        offset = free_port_offset()
        self.http_port = _free_port()
        path = os.pathsep.join(p for p in (
            root, os.environ.get("PYTHONPATH", "")) if p)
        self.env = dict(
            os.environ, PYTHONPATH=path,
            FAABRIC_HOST_ALIASES=(
                f"{PLANNER_HOST}=127.0.0.1+{offset},"
                f"{WORKER_HOST}=127.0.0.1+{offset + 1000}"))
        self.procs: list = []
        self.logs: list = []
        self.planner = self._spawn("planner", [
            sys.executable, "-m", "faabric_tpu.runner", "planner",
            "--port-offset", str(offset), "--http-port",
            str(self.http_port)])
        self.worker = self._spawn("worker", [
            sys.executable, os.path.join(root, "benchmarks", "worker.py"),
            *worker_argv])
        # The worker's stdout is its line protocol (READY / BYE / NO_CHIP)
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    @classmethod
    def for_cell(cls, root: str, out_dir: str, manifest_path: str,
                 workload: str, rehearse: bool) -> "Cluster":
        """The cluster whose worker registers this cell's guest."""
        return cls(root, out_dir, [
            "--manifest", os.path.abspath(manifest_path), "--workload",
            workload, "--out-dir", out_dir,
            *(["--rehearse"] if rehearse else [])])

    def _pump(self) -> None:
        for line in self.worker.stdout:
            self._lines.put(line.strip())
        self._lines.put(None)

    def _spawn(self, name: str, argv: list) -> subprocess.Popen:
        log = open(os.path.join(self.out_dir, f"{name}.log"), "w")
        self.logs.append(log)
        p = subprocess.Popen(argv, cwd=self.root, env=self.env, text=True,
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
                raise BenchFailed(f"worker never printed {tag}") from None
            if line is None:
                raise BenchFailed(
                    f"worker exited ({self.worker.wait()}) before {tag}; "
                    f"see {self.out_dir}/worker.log")
            if line.startswith("NO_CHIP"):
                raise NoAccelerator(line)
            if line.startswith(tag + " "):
                return json.loads(line[len(tag) + 1:])

    def post(self, http_type: int, payload: str = "") -> dict:
        body = json.dumps({"http_type": int(http_type), "payload": payload})
        conn = http.client.HTTPConnection("127.0.0.1", self.http_port,
                                          timeout=60)
        try:
            conn.request("POST", "/", body=body)
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise BenchFailed(f"planner answered {resp.status}: {data!r}")
        return json.loads(data)

    def wait_planner(self, deadline: float) -> None:
        while time.monotonic() < deadline:
            if self.planner.poll() is not None:
                raise BenchFailed(f"planner exited {self.planner.returncode}")
            try:
                self.post(GET_CONFIG)
                return
            except (OSError, http.client.HTTPException):
                time.sleep(0.1)
        raise BenchFailed("planner REST endpoint never answered")

    def hosts(self) -> list:
        return self.post(GET_AVAILABLE_HOSTS)["hosts"]

    def invoke(self, function: str, payloads: list, deadline: float,
               poll_s: float) -> dict:
        """One invocation of ``len(payloads)`` messages through the planner
        (a gang where there are several). Returns the client's two stamps
        (``posted``, ``seen``, by ``time.time()``) and every message's
        reply, in message order. A message that fails raises."""
        from faabric_tpu.proto import batch_exec_factory

        req = batch_exec_factory(USER, function, len(payloads))
        for msg, payload in zip(req.messages, payloads):
            msg.input_data = json.dumps(payload).encode()
        body = json.dumps(req.to_dict())
        status_body = json.dumps({"app_id": req.app_id})
        posted = time.time()
        self.post(EXECUTE_BATCH, body)
        while True:
            status = self.post(EXECUTE_BATCH_STATUS, status_body)
            results = status["messageResults"]
            for m in results:
                if m["return_value"] != 0:
                    raise BenchFailed(
                        f"{USER}/{function} failed: "
                        + bytes.fromhex(m["output_data"]).decode(
                            errors="replace"))
            if status["finished"] and len(results) >= len(payloads):
                seen = time.time()
                results.sort(key=lambda m: m["app_idx"])
                return {"posted": posted, "seen": seen, "replies": [
                    json.loads(bytes.fromhex(m["output_data"]))
                    for m in results]}
            if self.worker.poll() is not None:
                raise BenchFailed(
                    f"worker exited {self.worker.returncode} in {function}")
            if time.monotonic() > deadline:
                raise BenchFailed(f"{USER}/{function} did not finish in time")
            time.sleep(poll_s)

    def stop(self) -> dict:
        """Stop the worker (keeping its last words), then the planner, and
        wait for both."""
        last = {}
        if self.worker.poll() is None:
            self.worker.terminate()
            try:
                last = self.worker_line("BYE", time.monotonic() + 30)
            except BenchFailed:
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
