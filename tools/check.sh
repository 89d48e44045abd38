#!/usr/bin/env bash
# One-shot repo conformance gate (ISSUE 7 satellite): ruff (when
# installed) + the concurrency conformance suite + the tier-1 failure
# gate, each against its committed baseline.
#
#   tools/check.sh [--with-tests] [--with-chaos]
#
# Without --with-tests the failure gate re-reads the last tier-1 log at
# /tmp/_t1.log (written by the canonical tier-1 command in ROADMAP.md);
# with it, the tier-1 suite runs first. --with-chaos additionally runs
# the chaos-marked state-failover proof (real worker processes
# SIGKILLed/SIGSTOPped mid-write, ISSUE 19) — slow, opt-in. Exit
# nonzero on the first failing gate.
set -u -o pipefail

repo="$(cd "$(dirname "$0")/.." && pwd)"
cd "$repo"
rc=0

echo "== ruff =="
if command -v ruff >/dev/null 2>&1; then
    if ! ruff check .; then rc=1; fi
elif python -m ruff --version >/dev/null 2>&1; then
    if ! python -m ruff check .; then rc=1; fi
else
    echo "ruff not installed; skipping (pyproject.toml pins the config" \
         "for environments that have it — do not pip install here)"
fi

echo "== concheck (guarded-by lint + protocol drift) =="
if ! python tools/concheck.py; then rc=1; fi

echo "== doctor selftest (perf introspection smoke) =="
if ! JAX_PLATFORMS=cpu python -m faabric_tpu.runner.doctor --selftest; then
    rc=1
fi

echo "== schedule verifier selftest (collective schedule compiler) =="
if ! JAX_PLATFORMS=cpu python -m faabric_tpu.mpi.schedule_compile \
        --selftest; then
    rc=1
fi

echo "== profile selftest (stack sampler attribution) =="
if ! JAX_PLATFORMS=cpu python -m faabric_tpu.runner.profile --selftest; then
    rc=1
fi

# The Pallas ring selftest (python -m faabric_tpu.device_plane.pallas_ring
# --selftest) and chip_smoke.py are chip checks: both exit non-zero
# without a TPU, so they are not gates of this CPU-side script. The ring
# kernel's body runs here in TPU interpret mode inside the tier-1 suite
# (tests/unit/test_device_resident.py).

for arg in "$@"; do
    if [ "$arg" = "--with-chaos" ]; then
        echo "== chaos: replicated state failover (ISSUE 19) =="
        # Zero lost acked writes across a SIGKILLed master + the
        # revived-stale-master fencing proof, against real processes
        if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
                python -m pytest tests/dist/test_state_failover.py \
                -q -m chaos -p no:cacheprovider -p no:xdist \
                -p no:randomly; then
            rc=1
        fi
    fi
done

if [ "${1:-}" = "--with-tests" ]; then
    echo "== tier-1 suite =="
    rm -f /tmp/_t1.log
    timeout -k 10 870 env JAX_PLATFORMS=cpu \
        python -m pytest tests/ -q -m 'not slow' \
        --continue-on-collection-errors -p no:cacheprovider \
        -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
    t1=${PIPESTATUS[0]}
    if [ "$t1" -ne 0 ]; then
        echo "tier-1 exited $t1 (failure gate decides pass/fail below)"
    fi
fi

echo "== failure gate (tier-1 vs baseline) =="
if [ -f /tmp/_t1.log ]; then
    if ! python tools/failure_gate.py --log /tmp/_t1.log; then rc=1; fi
else
    echo "no tier-1 log at /tmp/_t1.log; run tools/check.sh --with-tests"
    rc=1
fi

exit $rc
