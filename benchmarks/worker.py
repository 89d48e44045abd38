"""The one process that touches JAX: the program's ``WorkerRuntime`` over
every local chip, with the cell's guest registered.

    python benchmarks/worker.py --manifest M --workload W --out-dir D [--rehearse]

Line protocol on stdout: ``NO_CHIP ...`` (and exit 3) where JAX finds no
TPU, ``READY {json}`` once the worker is registered with the planner,
``BYE {json}`` on SIGTERM. The compile cache is placed here, before any
backend starts: ``JAX_COMPILATION_CACHE_DIR`` wins, else the fixed
``<checkout>/.jax_cache``; every program is written to it, small ones
too, so that a second run of a cell compiles nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_CHIP = 3


class CompileCounter:
    """Compile requests and persistent-cache hits, through jax.monitoring
    (copied from ``chip_smoke.py``'s ``_CompileCounter``)."""

    def __init__(self) -> None:
        import jax

        self.requests = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def place_compile_cache() -> str:
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--manifest", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)

    from benchmarks import cells
    from benchmarks.cluster import PLANNER_HOST, USER, WORKER_HOST

    cache_dir = place_compile_cache()
    import jax

    devices = jax.local_devices()
    platform = devices[0].platform
    manifest = cells.load_manifest(args.manifest)
    cell = cells.load_cell(manifest, args.workload)
    if not args.rehearse and (platform != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"NO_CHIP platform={platform} count={len(devices)} "
              f"needed={cell['chips']}", flush=True)
        return EXIT_NO_CHIP
    compiles = CompileCounter()

    from faabric_tpu.executor import JaxExecutorFactory, register_function
    from faabric_tpu.runner import WorkerRuntime

    guest = cells.load_module(manifest, "guests", cell["guest"])
    handler = guest.make_guest(dict(
        cell, rehearse=args.rehearse, out_dir=args.out_dir,
        compiles=compiles))
    register_function(USER, cell["guest"], handler)

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
            "compile_cache_dir": cache_dir}), flush=True)
        parent = os.getppid()
        while not stop and os.getppid() == parent:
            time.sleep(0.1)
        print("BYE " + json.dumps({"compiles": compiles.snapshot()}),
              flush=True)
    finally:
        runtime.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
