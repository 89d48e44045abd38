"""tools/bench_trend.py (ISSUE 12 satellite): per-key trajectory math
over synthetic bench rounds plus direction/status pins."""

import json
import os
import sys


def _tools():
    import importlib

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), "tools"))
    return importlib.import_module("bench_trend")


def _write_round(tmp_path, name, value, summary):
    # the driver's round record: bench.py's stdout line under "parsed"
    (tmp_path / name).write_text(json.dumps({"parsed": {
        "metric": "ptp_dispatch_p50_ms", "value": value, "unit": "ms",
        "summary": summary}}))


def test_collect_reads_round_history(tmp_path):
    bt = _tools()
    _write_round(tmp_path, "BENCH_r01.json", 0.05,
                 {"host_sendrecv_gibs": 0.5, "step_ms": 40.0})
    _write_round(tmp_path, "BENCH_r02.json", 0.04,
                 {"host_sendrecv_gibs": 0.6, "step_ms": 30.0})
    series = bt.collect(str(tmp_path))
    # The headline latency rides as `value` in every round
    assert series["value"] == [("r01", 0.05), ("r02", 0.04)]
    rounds = [r for r, _v in series["host_sendrecv_gibs"]]
    assert rounds == sorted(rounds), "rounds must be oldest → newest"
    rows = bt.trend_rows(series)
    by_key = {r["key"]: r for r in rows}
    # The container-drift-exempt keys never report as regressions
    assert by_key["value"]["status"] == "exempt"
    # Rendering never raises and marks gated keys
    out = bt.render(rows)
    assert "status" in out and "*" in out


def test_trend_rows_directions_and_statuses():
    bt = _tools()
    rows = bt.trend_rows({
        # higher-better key that collapsed >20%: REGRESSED (gated)
        "host_sendrecv_gibs": [("r01", 1.0), ("r02", 0.5)],
        # higher-better ungated key, mild drift
        "allreduce_bus_gibs": [("r01", 10.0), ("r02", 9.0)],
        # lower-better key that IMPROVED: still OK (best == latest)
        "step_ms": [("r01", 40.0), ("r02", 30.0)],
        # lower-better key that got worse by 50%
        "journal_append_ns": [("r01", 100.0), ("r02", 150.0)],
        # single round: new
        "perf_feed_ns": [("r02", 900.0)],
    })
    by_key = {r["key"]: r for r in rows}
    assert by_key["host_sendrecv_gibs"]["status"] == "REGRESSED"
    assert by_key["host_sendrecv_gibs"]["gated"] is True
    assert by_key["host_sendrecv_gibs"]["off_best_pct"] == 50.0
    assert by_key["allreduce_bus_gibs"]["status"] == "drift"
    assert by_key["step_ms"]["status"] == "OK"
    assert by_key["step_ms"]["best"] == 30.0
    assert by_key["step_ms"]["direction"] == "down"
    assert by_key["journal_append_ns"]["status"] == "regressed"
    assert by_key["perf_feed_ns"]["status"] == "new"
    # Gated keys sort first so the gate-relevant drift leads the table
    assert rows[0]["gated"] is True
