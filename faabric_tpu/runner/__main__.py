"""Deployment entry points — the planner_server / worker binaries analog
(reference src/planner/planner_server.cpp:9-43, src/runner/FaabricMain.cpp).

    python -m faabric_tpu.runner planner [--port-offset N] [--http-port P]
    python -m faabric_tpu.runner worker --host IP [--slots N] [--devices N]
    python -m faabric_tpu.runner redis [--port P]

The planner role serves RPC + its snapshot server + the REST endpoint; the
worker boots a full WorkerRuntime (function/PTP/snapshot/state servers,
keep-alive registration); the redis role runs the in-repo RESP server
(the docker-compose `redis` service analog for STATE_MODE=redis
deployments without an external Redis). All run until SIGTERM/SIGINT.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading

from faabric_tpu.util.crash import install_crash_handler
from faabric_tpu.util.logging import get_logger

logger = get_logger("faabric_tpu.runner")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="faabric_tpu.runner")
    sub = parser.add_subparsers(dest="role", required=True)

    p_planner = sub.add_parser("planner")
    p_planner.add_argument("--port-offset", type=int, default=0)
    p_planner.add_argument("--http-port", type=int, default=0,
                           help="REST endpoint port (0 = config default)")

    p_redis = sub.add_parser("redis")
    p_redis.add_argument("--port", type=int, default=6379)
    p_redis.add_argument("--bind", default="127.0.0.1")

    p_worker = sub.add_parser("worker")
    p_worker.add_argument("--host", default="",
                          help="this worker's identity (default: primary IP)")
    p_worker.add_argument("--slots", type=int, default=None,
                          help="execution slots (default: one per usable core; 0 = observer host)")
    p_worker.add_argument("--devices", type=int, default=None,
                          help="chips to register (default: every local "
                               "jax device)")
    p_worker.add_argument("--planner-host", default=None)

    args = parser.parse_args(argv)
    install_crash_handler()

    stop = threading.Event()

    def _on_signal(signum, _frame):
        # Dump the flight ring while the process state is still intact —
        # but the shutdown signal must survive ANY flight failure. The
        # recorded kind names the ACTUAL signal (a post-mortem must not
        # claim a SIGTERM for an operator's Ctrl-C).
        try:
            from faabric_tpu.telemetry import flight_dump, flight_record

            name = signal.Signals(signum).name.lower()
            flight_record(name, role=args.role)
            flight_dump(name)
        except Exception:  # noqa: BLE001 — never lose the shutdown
            pass
        finally:
            stop.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)

    if args.role == "planner":
        from faabric_tpu.endpoint import PlannerHttpEndpoint
        from faabric_tpu.planner import PlannerServer

        server = PlannerServer(port_offset=args.port_offset)
        server.start()
        endpoint = PlannerHttpEndpoint(
            port=args.http_port or None)
        endpoint.start()
        logger.info("Planner up (rpc offset %d, http :%d)", args.port_offset,
                    endpoint.port)
        stop.wait()
        endpoint.stop()
        server.stop()
    elif args.role == "redis":
        from faabric_tpu.redis import MiniRedisServer

        srv = MiniRedisServer(host=args.bind, port=args.port)
        srv.start()
        logger.info("Mini redis up on %s:%d", args.bind, srv.port)
        stop.wait()
        srv.stop()
    else:
        from faabric_tpu.runner import WorkerRuntime
        from faabric_tpu.util.device_env import configure_compile_cache

        # The worker is the one process that owns this host's chips:
        # guests compile through the placed cache, and the planner pins
        # ranks over exactly the chips jax sees here
        configure_compile_cache()
        n_devices = args.devices
        if n_devices is None:
            import jax

            n_devices = len(jax.local_devices())
        runtime = WorkerRuntime(host=args.host, slots=args.slots,
                                n_devices=n_devices,
                                planner_host=args.planner_host)
        runtime.start()
        logger.info("Worker %s up", runtime.host)
        stop.wait()
        runtime.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
