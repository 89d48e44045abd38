"""Test harness configuration.

All tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the chip itself is reached only through
``chiprun``: ``benchmarks/run.py`` and ``chip_smoke.py``).
"""

import os

# Must be set before any backend initialises.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Runtime concurrency detector (ISSUE 7): FAABRIC_LOCKCHECK=1 wraps the
# threading.Lock/RLock factories BEFORE jax (or any faabric module)
# loads, so every lock created from faabric_tpu/ or tests/ joins the
# held-before graph. The session gate below fails the run on any
# potential-deadlock cycle (FAABRIC_LOCKCHECK_GATE=0 demotes to report).
from faabric_tpu.analysis import lockcheck as _lockcheck  # noqa: E402

if _lockcheck.enabled_by_env():
    _lockcheck.install()

import itertools  # noqa: E402
import random  # noqa: E402

import pytest  # noqa: E402

# Port-range allocator for fixtures that stand up aliased hosts. Two
# constraints learned the hard way: (a) bases must be session-unique so
# concurrent fixture ranges never overlap (random bases collided ~1/150
# runs); (b) outgoing connections must not squat listener ports — this
# container's ephemeral range starts at 16000, INSIDE the listener plan,
# so the framework pins client SOURCE ports above 30500
# (util/network.py safe_create_connection); a stray plain connect() in a
# test can still intermittently EADDRINUSE a later fixture's bind.
# Bases cycle through 7 slots; sequential fixtures reuse a slot only
# after its predecessor tore down (SO_REUSEADDR covers TIME_WAIT).
_BASES = [1000, 4000, 7000, 10000, 13000, 16000, 19000]
_port_iter = itertools.count(random.randrange(len(_BASES)))


def _slot_looks_free(base: int) -> bool:
    """Probe every canonical service port a standard (planner, hostA,
    hostB) fixture will bind. Two ways a slot goes bad: a leaked
    listener from a fixture that errored mid-setup, and — observed in
    this container — an unrelated long-lived process whose OUTGOING
    connection's ephemeral source port (range starts at 16000, inside
    the listener plan) lands on a fixture port and holds it for hours.
    Either way the slot would EADDRINUSE every fixture that cycles onto
    it — one squatted port cascading into a dozen errors — so skip it."""
    import socket

    from faabric_tpu.transport import common as tc

    from faabric_tpu.transport.bulk import BULK_PORT

    # The bulk data-plane listener (8014) sits past the contiguous RPC
    # range — a squatter there sailed past this probe and EADDRINUSE'd
    # a fixture's BulkServer (observed once in a tier-1 run)
    service_ports = [*range(tc.STATE_ASYNC_PORT, tc.PLANNER_SYNC_PORT + 1),
                     BULK_PORT]
    for off in (0, 1000, 2000):
        for port in service_ports:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                # Bind exactly as the servers do (0.0.0.0): the observed
                # squatter was an HTTPS connection bound to the eth0
                # address — a 127.0.0.1 probe sails past it while the
                # wildcard server bind still collides.
                s.bind(("0.0.0.0", base + off + port))
            except OSError:
                return False
            finally:
                s.close()
    return True


def next_port_base() -> int:
    for _ in range(len(_BASES)):
        base = _BASES[next(_port_iter) % len(_BASES)]
        if _slot_looks_free(base):
            return base
    return base  # every slot busy: let the fixture surface the bind error


@pytest.fixture(autouse=True)
def _reset_globals():
    """Reset global singletons between tests (the reference's fixture-reset
    discipline, tests/utils/fixtures.h:55-250)."""
    from faabric_tpu.util.config import get_system_config
    from faabric_tpu.util.testing import set_mock_mode, set_test_mode
    from faabric_tpu.transport.common import clear_host_aliases

    set_test_mode(True)
    yield
    set_mock_mode(False)
    set_test_mode(False)
    clear_host_aliases()
    get_system_config().reset()

    # Drain every mock-recording queue (the reference's fixture reset
    # discipline — stale recordings otherwise leak across tests)
    from faabric_tpu.planner.client import clear_mock_planner_calls
    from faabric_tpu.scheduler.function_call import clear_mock_requests
    from faabric_tpu.snapshot.remote import clear_mock_snapshot_requests
    from faabric_tpu.state.remote import clear_mock_state_requests
    from faabric_tpu.transport.ptp_remote import clear_sent_ptp

    clear_mock_planner_calls()
    clear_mock_requests()
    clear_mock_snapshot_requests()
    clear_mock_state_requests()
    clear_sent_ptp()


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_session_gate():
    """With FAABRIC_LOCKCHECK=1, the whole run doubles as a deadlock
    hunt: any held-before cycle observed across every test fails the
    session (teardown assertion), and the full report prints in the
    terminal summary either way."""
    yield
    from faabric_tpu.analysis import lockcheck

    if not lockcheck.installed():
        return
    if os.environ.get("FAABRIC_LOCKCHECK_GATE", "1") in ("0", "false"):
        return
    rep = lockcheck.report()
    assert not rep["cycles"], (
        "lockcheck: potential deadlock cycle(s) observed:\n"
        + lockcheck.format_report(rep))


def pytest_terminal_summary(terminalreporter):
    from faabric_tpu.analysis import lockcheck

    if lockcheck.installed():
        terminalreporter.write_line("")
        terminalreporter.write_line(lockcheck.format_report())


def run_threads(fns, timeout=60.0):
    """Run zero-arg callables on threads; join with timeout, re-raise the
    first captured exception (a swallowed rank error otherwise presents
    as a hang)."""
    import threading

    errors = []

    def wrap(fn):
        def run():
            try:
                fn()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
        return run

    ts = [threading.Thread(target=wrap(fn)) for fn in fns]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in ts), "worker thread hung"
    assert not errors, errors
