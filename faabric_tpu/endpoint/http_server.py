"""Planner HTTP REST API.

Reference analog: src/planner/PlannerEndpointHandler.cpp:15-422 and the
HttpMessage schema (src/planner/planner.proto:33-66). POST a JSON body
``{"http_type": <int>, "payload": <json string>}``; responses are JSON.

The reference serves this from Boost.Beast inside the planner binary; the
idiomatic Python analog is a stdlib ThreadingHTTPServer on a background
thread — the REST plane is a control surface, not a data plane.
"""

from __future__ import annotations

import enum
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from faabric_tpu.batch_scheduler import reset_batch_scheduler
from faabric_tpu.batch_scheduler.scheduler import get_batch_scheduler_mode
from faabric_tpu.batch_scheduler.decision import (
    MUST_FREEZE,
    NOT_ENOUGH_SLOTS,
    SchedulingDecision,
)
from faabric_tpu.planner.planner import Planner, get_planner
from faabric_tpu.proto import (
    BatchExecuteRequest,
    is_batch_exec_request_valid,
)
from faabric_tpu.telemetry import get_lifecycle
from faabric_tpu.telemetry.lifecycle import PHASE_HTTP_IN
from faabric_tpu.util.config import get_system_config
from faabric_tpu.util.exec_graph import build_exec_graph
from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

_LC = get_lifecycle()


class HttpMessageType(enum.IntEnum):
    # mirror of planner.proto HttpMessage.Type
    NO_TYPE = 0
    RESET = 1
    FLUSH_AVAILABLE_HOSTS = 2
    FLUSH_EXECUTORS = 3
    FLUSH_SCHEDULING_STATE = 4
    GET_AVAILABLE_HOSTS = 5
    GET_CONFIG = 6
    GET_EXEC_GRAPH = 7
    GET_IN_FLIGHT_APPS = 8
    EXECUTE_BATCH = 10
    EXECUTE_BATCH_STATUS = 11
    PRELOAD_SCHEDULING_DECISION = 12
    SET_POLICY = 13
    GET_POLICY = 14
    SET_NEXT_EVICTED_VM = 15


class PlannerHttpEndpoint:
    def __init__(self, port: int | None = None,
                 planner: Optional[Planner] = None,
                 host: str | None = None) -> None:
        conf = get_system_config()
        self.port = port if port is not None else conf.endpoint_port
        # The REST API exposes destructive unauthenticated ops (RESET,
        # FLUSH, SET_POLICY...): bind loopback unless ENDPOINT_INTERFACE
        # explicitly widens the exposure (e.g. "0.0.0.0" for a cluster)
        self.host = (host if host is not None
                     else conf.endpoint_interface or "127.0.0.1")
        self.planner = planner or get_planner()
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    def start(self) -> None:
        if self._server is not None:
            return
        endpoint = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self) -> None:  # noqa: N802 — stdlib API
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length)
                status, payload, extra_headers = endpoint.handle(body)
                data = payload.encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for key, val in (extra_headers or {}).items():
                    self.send_header(key, val)
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self) -> None:  # noqa: N802
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                try:
                    if path == "/metrics":
                        body = endpoint.metrics_text().encode()
                        ctype = "text/plain; version=0.0.4"
                    elif path == "/trace":
                        body = endpoint.trace_json().encode()
                        ctype = "application/json"
                    elif path == "/commmatrix":
                        body = endpoint.commmatrix_json().encode()
                        ctype = "application/json"
                    elif path == "/perf":
                        body = endpoint.perf_json().encode()
                        ctype = "application/json"
                    elif path == "/healthz":
                        body = endpoint.healthz_json().encode()
                        ctype = "application/json"
                    elif path == "/timeseries":
                        body = endpoint.timeseries_json().encode()
                        ctype = "application/json"
                    elif path == "/flight":
                        body = endpoint.flight_json().encode()
                        ctype = "application/json"
                    elif path == "/topology":
                        body = endpoint.topology_json().encode()
                        ctype = "application/json"
                    elif path == "/statemap":
                        body = endpoint.statemap_json().encode()
                        ctype = "application/json"
                    elif path == "/profile":
                        body = endpoint.profile_json().encode()
                        ctype = "application/json"
                    else:
                        body = b'{"status": "running"}'
                        ctype = "application/json"
                except Exception as e:  # noqa: BLE001 — scrape errors
                    logger.exception("HTTP GET %s failed", path)
                    body = json.dumps({"error": str(e)}).encode()
                    self.send_response(500)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, fmt, *args):  # quiet
                logger.debug("http: " + fmt, *args)

        self._server = ThreadingHTTPServer((self.host, self.port), Handler)
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="endpoint/planner-http", daemon=True)
        self._thread.start()
        logger.debug("Planner HTTP endpoint on :%d", self.port)

    def stop(self) -> None:
        if self._server is None:
            return
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        self._server = None
        self._thread = None

    # ------------------------------------------------------------------
    # Telemetry export (GET /metrics, GET /trace)
    # ------------------------------------------------------------------
    def metrics_text(self) -> str:
        """Prometheus text exposition merging every registered host's
        local registry (plus the planner's own) under a ``host`` label.
        Each host's communication matrix rides along as
        ``faabric_comm_*`` families with ``src``/``dst``/``plane``
        labels (cardinality-capped at the source — commmatrix.py)."""
        from faabric_tpu.telemetry import (
            families_from_cells,
            render_snapshots,
        )

        tel = self.planner.collect_telemetry(
            blocks=("metrics", "commmatrix"))
        merged = {}
        for host, t in tel.items():
            snap = dict(t.get("metrics", {}))
            cells = (t.get("commmatrix") or {}).get("cells", [])
            snap.update(families_from_cells(cells))
            merged[host] = snap
        return render_snapshots(merged)

    def commmatrix_json(self) -> str:
        """Per-link communication matrix: every host's (src rank, dst
        rank, plane) send counters, plus a cross-host merged totals view
        (hosts only report their own outbound sends, so the merge is a
        plain sum)."""
        from faabric_tpu.telemetry import merge_cell_rows

        tel = self.planner.collect_telemetry(blocks=("commmatrix",))
        per_host = {host: (t.get("commmatrix") or {}).get("cells", [])
                    for host, t in tel.items()}
        return json.dumps({
            "hosts": per_host,
            "total": merge_cell_rows(per_host),
        })

    def perf_json(self) -> str:
        """Cluster-wide performance profile (ISSUE 12): every host's
        rolling link estimators tagged with their source host, merged
        collective phase series with cross-host critical-path and
        straggler analysis. Each aggregation is checkpointed to
        ``FAABRIC_PERF_PROFILE_DIR`` (best-effort) so the doctor — and
        the next planner — can read the last known cluster profile
        without a live scrape."""
        from faabric_tpu.telemetry import aggregate_perf, persist_cluster

        doc = aggregate_perf(
            self.planner.collect_telemetry(blocks=("perf",)))
        self.planner.note_perf_aggregation(doc)
        persist_cluster(doc)
        return json.dumps(doc)

    def healthz_json(self) -> str:
        return json.dumps(self.planner.health_summary())

    def statemap_json(self) -> str:
        """Cluster state map (ISSUE 16): every host's per-key access
        ledger merged into per-key master/size/origin rows with hot-key
        ranking, per-host mastership totals, and the cluster locality
        ratio. ISSUE 19 overlays the planner's authoritative placement
        journal (master/backup/epoch) — host ledgers lag right after a
        failover, the journal never does."""
        from faabric_tpu.telemetry import aggregate_statemap, merge_placement

        doc = aggregate_statemap(
            self.planner.collect_telemetry(blocks=("statestats",)))
        merge_placement(doc, self.planner.state_placement())
        return json.dumps(doc)

    def profile_json(self) -> str:
        """Cluster CPU profile (ISSUE 18): every host's stack-sampler
        trie merged into ranked per-host × thread-class × collapsed-
        stack rows with CPU weighting and per-process GIL pressure —
        the evidence surface for the planner-shard / native-transport
        ROADMAP items."""
        from faabric_tpu.telemetry import aggregate_profile

        doc = aggregate_profile(
            self.planner.collect_telemetry(blocks=("profile",)))
        return json.dumps(doc)

    def timeseries_json(self) -> str:
        """Cluster-merged time-series rings (ISSUE 14): every host's
        sampled gauge history keyed by host — the trend surface behind
        the doctor's queue-growth and capacity-exhaustion analyzers."""
        import time as _time

        # Blocks-narrowed scrape: a trend poll repeats continuously and
        # must not pay for every host's full metrics/comm-matrix/perf
        # payload just to discard it
        tel = self.planner.collect_telemetry(blocks=("timeseries",))
        hosts = {host: (t.get("timeseries") or {})
                 for host, t in tel.items()}
        return json.dumps({"generated_at": _time.time(), "hosts": hosts})

    def flight_json(self) -> str:
        """The planner process's LIVE flight-recorder ring (ISSUE 14
        satellite): read the black box without waiting for a crash
        dump. Workers serve the same path on their own HTTP endpoints;
        ``flightdump --url`` merges them."""
        from faabric_tpu.telemetry.flight import live_ring_doc

        return json.dumps(live_ring_doc())

    def topology_json(self) -> str:
        """Cluster topology snapshot (ISSUE 9): per-host capacity plus
        the rank→host Topology of every in-flight gang-scheduled MPI
        world — the scrape surface for dashboards and placement
        debugging (`Planner.get_cluster_topology`). ISSUE 15: each
        host's live device-plane summaries ride along under
        ``device_planes`` — executable-cache stats (entries / hits /
        compiles / compile ms) and host↔device copy accounting, so the
        doctor can attribute a first-call latency spike to a device
        compile instead of guessing."""
        doc = self.planner.get_cluster_topology()
        tel = self.planner.collect_telemetry(blocks=("device_planes",))
        doc["device_planes"] = {
            host: t.get("device_planes") or []
            for host, t in tel.items()
            if t.get("device_planes")}
        return json.dumps(doc)

    def trace_json(self) -> str:
        """Chrome trace_event JSON merging every host's span buffer onto
        one wall-clock timeline (load in chrome://tracing / Perfetto).
        Raw pids are remapped per (host, pid): containerized workers are
        routinely all pid 1, and colliding pids would collapse different
        hosts onto one Perfetto process row."""
        tel = self.planner.collect_telemetry(include_trace=True,
                                             blocks=())
        events: list = []
        pid_map: dict[tuple[str, int], int] = {}
        for host in sorted(tel):
            for e in tel[host].get("trace") or []:
                key = (host, e.get("pid", 0))
                pid = pid_map.setdefault(key, len(pid_map) + 1)
                # Copy: the planner's own events are live tracer state
                events.append({**e, "pid": pid})
        return json.dumps({"traceEvents": events, "displayTimeUnit": "ms"})

    # ------------------------------------------------------------------
    def handle(self, body: bytes) -> tuple[int, str, dict]:
        """(status_code, response_json, extra_headers) for one
        HttpMessage. Handlers may return 2- or 3-tuples; the headers
        slot carries e.g. ``Retry-After`` on a 429 shed."""
        # The lifecycle ledger's first stamp (hin): the body has been
        # read and nothing of it parsed yet
        received_ns = time.monotonic_ns()
        try:
            msg = json.loads(body or b"{}")
        except json.JSONDecodeError:
            return 400, json.dumps({"error": "Bad JSON in request"}), {}
        if not isinstance(msg, dict):
            return (400,
                    json.dumps({"error": "Request body must be an object"}),
                    {})
        http_type = msg.get("http_type", int(HttpMessageType.NO_TYPE))
        payload = msg.get("payload", "")
        try:
            out = self._dispatch(http_type, payload, received_ns)
        except Exception as e:  # noqa: BLE001 — REST errors cross the wire
            logger.exception("HTTP handler error (type %s)", http_type)
            return 500, json.dumps({"error": str(e)}), {}
        if len(out) == 2:
            return out[0], out[1], {}
        return out

    def _dispatch(self, http_type: int, payload: str,
                  received_ns: int) -> tuple[int, str]:
        planner = self.planner
        t = HttpMessageType(http_type)

        if t == HttpMessageType.RESET:
            planner.reset()
            return 200, json.dumps({"status": "reset"})

        if t == HttpMessageType.FLUSH_AVAILABLE_HOSTS:
            planner.flush_hosts()
            return 200, json.dumps({"status": "flushed hosts"})

        if t == HttpMessageType.FLUSH_EXECUTORS:
            hosts = planner.flush_all_executors()
            return 200, json.dumps({"status": "flushed executors",
                                    "hosts": hosts})

        if t == HttpMessageType.FLUSH_SCHEDULING_STATE:
            planner.flush_scheduling_state()
            return 200, json.dumps({"status": "flushed scheduling state"})

        if t == HttpMessageType.GET_AVAILABLE_HOSTS:
            hosts = [{"ip": h.ip, "slots": h.slots,
                      "usedSlots": h.used_slots, "nDevices": h.n_devices}
                     for h in planner.get_available_hosts()]
            return 200, json.dumps({"hosts": hosts})

        if t == HttpMessageType.GET_CONFIG:
            conf = get_system_config()
            return 200, json.dumps({
                "ip": conf.planner_host,
                "hostTimeout": conf.planner_host_timeout,
                "policy": get_batch_scheduler_mode(),
            })

        if t == HttpMessageType.GET_EXEC_GRAPH:
            req = json.loads(payload) if payload else {}
            app_id = req.get("app_id", 0) or req.get("appId", 0)
            msg_id = req.get("id", 0)

            def get_result(aid, mid):
                result = planner.get_message_result(aid, mid)
                if result is None:
                    raise KeyError(f"No result for msg {mid} (app {aid})")
                return result

            graph = build_exec_graph(get_result, msg_id, app_id)
            return 200, graph.to_json()

        if t == HttpMessageType.GET_IN_FLIGHT_APPS:
            return 200, json.dumps(planner.in_flight_summary())

        if t == HttpMessageType.EXECUTE_BATCH:
            req = BatchExecuteRequest.from_dict(json.loads(payload))
            if not is_batch_exec_request_valid(req):
                return 400, json.dumps({"error": "Bad BatchExecRequest"})
            _LC.backdate(req.messages, PHASE_HTTP_IN, received_ns)
            # Through the invocation ingress (ISSUE 8): admission
            # control + batched scheduling ticks. Sources are tenants
            # (the request's user) — one runaway tenant sheds before it
            # can starve the others. A lone request takes the immediate
            # cutover path, so interactive latency is unchanged.
            from faabric_tpu.ingress import IngressShedError

            try:
                # Queue wait bounded to ~1s: each waiting REST request
                # parks a live ThreadingHTTPServer thread, and a full
                # cluster must answer "No available hosts" promptly
                # (pre-ingress semantics) instead of accumulating up to
                # a queue-bound's worth of parked HTTP threads
                decision = planner.ingress.submit(
                    req, source=req.user or "rest", timeout=1.0)
            except IngressShedError as e:
                # Load shedding, not failure: bounded queue + explicit
                # backpressure instead of collapse. Retry-After is the
                # backlog-scaled hint admission computed.
                return (429, json.dumps({
                    "error": "Overloaded: invocation shed",
                    "reason": e.reason,
                    "retryAfterSeconds": round(e.retry_after, 3),
                }), {"Retry-After": str(max(1, int(e.retry_after + 0.5)))})
            if decision.app_id == NOT_ENOUGH_SLOTS:
                return 500, json.dumps({"error": "No available hosts"})
            if decision.app_id == MUST_FREEZE:
                return 200, json.dumps({"appId": req.app_id,
                                        "frozen": True})
            return 200, json.dumps({"appId": req.app_id,
                                    "groupId": decision.group_id,
                                    "hosts": decision.hosts,
                                    "messageIds": decision.message_ids})

        if t == HttpMessageType.EXECUTE_BATCH_STATUS:
            req = json.loads(payload) if payload else {}
            app_id = req.get("app_id", 0) or req.get("appId", 0)
            status = planner.get_batch_results(app_id)
            return 200, json.dumps({
                "appId": status.app_id,
                "finished": status.finished,
                "expectedNumMessages": status.expected_num_messages,
                "messageResults": [m.to_dict()
                                   for m in status.message_results],
            })

        if t == HttpMessageType.PRELOAD_SCHEDULING_DECISION:
            decision = SchedulingDecision.from_dict(json.loads(payload))
            planner.preload_scheduling_decision(decision)
            return 200, json.dumps({"status": "preloaded",
                                    "appId": decision.app_id})

        if t == HttpMessageType.SET_POLICY:
            policy = payload.strip().strip('"')
            if policy not in ("bin-pack", "compact", "spot"):
                return 400, json.dumps({"error": f"Unknown policy {policy}"})
            reset_batch_scheduler(policy)
            return 200, json.dumps({"policy": policy})

        if t == HttpMessageType.GET_POLICY:
            return 200, json.dumps({"policy": get_batch_scheduler_mode()})

        if t == HttpMessageType.SET_NEXT_EVICTED_VM:
            ip = payload.strip().strip('"')
            planner.set_next_evicted_host_ips([ip] if ip else [])
            return 200, json.dumps({"nextEvictedVmIps": [ip] if ip else []})

        return 400, json.dumps({"error": f"Unsupported request type {t}"})
