"""Adaptive wire-codec plane (ISSUE 11): delta streams, governor
policy, and the self-healing full-frame escape.

The escape-protocol tests drive a REAL BulkServer/BulkClient pair over
loopback TCP with shm rings disabled (the coded path never rides a
ring) and assert the one property the protocol exists for: a torn,
missing, corrupt or epoch-mismatched base can never decode garbage —
every such frame heals to a bitwise-exact full frame with the same
sequence number, without stalling the stream.
"""

import threading
import time

import numpy as np
import pytest

from faabric_tpu.transport.codec import (
    CODEC_DELTA,
    CODEC_FULL,
    CODEC_ZLIB,
    ReceiverDeltaCache,
    SenderDeltaCache,
    WireCodecGovernor,
    payload_entropy,
    set_wire_codec,
)

GROUP = 7700


# ---------------------------------------------------------------------------
# Pure codec units: probe, segmented serializer, caches
# ---------------------------------------------------------------------------

def test_sampled_overlap_and_parts_probe():
    from faabric_tpu.util.delta import sampled_overlap, sampled_overlap_parts

    rng = np.random.default_rng(0)
    a = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
    b = a.copy()
    assert sampled_overlap(a, b) == 1.0
    b[:300_000] ^= 1  # ~30% of pages differ
    frac = sampled_overlap(a, b)
    assert 0.4 < frac < 1.0
    # Size mismatch is a different stream generation, never a match
    assert sampled_overlap(a, b[:-1]) == 0.0
    # Segmented probe agrees with the flat one on a [header|body] split
    assert sampled_overlap_parts(a, [b[:64], b[64:]]) == pytest.approx(
        frac, abs=0.3)


def test_serialize_delta_parts_matches_flat_and_applies():
    from faabric_tpu.util.delta import (
        DeltaSettings,
        apply_delta,
        serialize_delta,
        serialize_delta_parts,
    )

    rng = np.random.default_rng(1)
    old = rng.integers(0, 255, 300_000, dtype=np.uint8)
    new = old.copy()
    new[5000:6000] ^= 3
    new[200_000:200_100] ^= 7
    s = DeltaSettings(page_size=4096, use_xor=True, zlib_level=1)
    # Segmented encoding (arbitrary split) decodes to the same image
    for split in (0, 33, 150_000, 299_999):
        d = serialize_delta_parts(s, old, [new[:split], new[split:]])
        assert bytes(apply_delta(d, old)) == new.tobytes()
    # and the single-part form equals the classic serializer
    assert serialize_delta_parts(s, old, [new]) == serialize_delta(
        s, old, new)
    # Growth past the base's end emits overwrites
    grown = np.concatenate([new, np.arange(100, dtype=np.uint8)])
    d = serialize_delta_parts(s, old, [grown[:100], grown[100:]])
    assert bytes(apply_delta(d, old)) == grown.tobytes()


def test_sender_cache_identity_reuses_epoch_and_mutation_inserts():
    c = SenderDeltaCache(budget_bytes=1 << 30)
    rng = np.random.default_rng(2)
    p = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
    f0 = c.encode(("s",), [p], 0)
    assert f0.codec == CODEC_FULL and f0.self_epoch == 1
    # Identical payload: delta against the base, SAME epoch, no copy
    f1 = c.encode(("s",), [p.copy()], 1)
    assert f1.codec == CODEC_DELTA
    assert f1.base_epoch == 1 and f1.self_epoch == 1
    assert f1.wire.nbytes < 64
    before = c.cached_bytes
    # Mutation: new epoch, one new cache entry
    q = p.copy()
    q[1000:2000] ^= 1
    f2 = c.encode(("s",), [q], 2)
    assert f2.codec == CODEC_DELTA and f2.self_epoch == 2
    assert f2.wire.nbytes < q.nbytes // 10
    assert c.cached_bytes == before + q.nbytes
    # NACK resend window holds the payloads
    got = c.take_for_resend(("s",), 2)
    assert got is not None and bytes(got[0]) == q.tobytes()
    # and an unknown seq reports unhealable
    assert c.take_for_resend(("s",), 99) is None


def test_sender_cache_budget_eviction():
    c = SenderDeltaCache(budget_bytes=3 << 20)
    rng = np.random.default_rng(3)
    for i in range(6):
        p = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
        c.encode((f"s{i}",), [p], 0)
    assert c.cached_bytes <= 3 << 20


def test_zlib_full_frame_roundtrip():
    tx = SenderDeltaCache(budget_bytes=1 << 30)
    rx = ReceiverDeltaCache(budget_bytes=1 << 30)
    p = np.zeros(1 << 20, dtype=np.uint8)  # entropy 0 → zlib full frame
    f = tx.encode(("z",), [p], 0)
    assert f.codec == CODEC_ZLIB and f.wire.nbytes < p.nbytes // 4
    out = rx.decode(("z",), f.codec, f.flags, f.base_epoch, f.self_epoch,
                    f.crc, f.wire, f.raw_nbytes)
    assert out is not None and bytes(out) == p.tobytes()
    # The zlib frame established a base: a delta can now follow
    q = p.copy()
    q[10:20] = 7
    f2 = tx.encode(("z",), [q], 1)
    assert f2.codec == CODEC_DELTA and f2.base_epoch == f.self_epoch
    out2 = rx.decode(("z",), f2.codec, f2.flags, f2.base_epoch,
                     f2.self_epoch, f2.crc, f2.wire, f2.raw_nbytes)
    assert bytes(out2) == q.tobytes()


def test_receiver_rejects_crc_and_missing_base():
    tx = SenderDeltaCache(budget_bytes=1 << 30)
    rx = ReceiverDeltaCache(budget_bytes=1 << 30)
    rng = np.random.default_rng(4)
    p = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
    f0 = tx.encode(("k",), [p], 0)
    assert rx.decode(("k",), f0.codec, f0.flags, 0, f0.self_epoch,
                     f0.crc, f0.wire, f0.raw_nbytes) is not None
    q = p.copy()
    q[5000:5100] ^= 9
    f1 = tx.encode(("k",), [q], 1)
    assert f1.codec == CODEC_DELTA
    # Corrupt wire bytes → crc verdict None (never garbage)
    bad = f1.wire.copy()
    bad[:4] ^= 0x5A
    assert rx.decode(("k",), f1.codec, f1.flags, f1.base_epoch,
                     f1.self_epoch, f1.crc, bad, f1.raw_nbytes) is None
    # Dropped base → None
    rx.drop_bases()
    assert rx.decode(("k",), f1.codec, f1.flags, f1.base_epoch,
                     f1.self_epoch, f1.crc, f1.wire,
                     f1.raw_nbytes) is None


def test_payload_entropy_bounds():
    assert payload_entropy(np.zeros(4096, np.uint8)) == 0.0
    rng = np.random.default_rng(5)
    noisy = rng.integers(0, 255, 1 << 16, dtype=np.uint8)
    assert payload_entropy(noisy) > 7.0


# ---------------------------------------------------------------------------
# Governor policy
# ---------------------------------------------------------------------------

def test_governor_modes_and_locality():
    gov = WireCodecGovernor(mode="auto")
    # Same-machine / shm-capable links stay raw in auto mode
    assert gov.bulk_codec("peer", True, 0, 1, 1 << 20) == "raw"
    # Unmeasured non-local link: assumed slow → delta
    assert gov.bulk_codec("far-host", False, 0, 1, 1 << 20) == "delta"
    assert WireCodecGovernor(mode="raw").bulk_codec(
        "far", False, 0, 1, 1 << 20) == "raw"
    assert WireCodecGovernor(mode="delta").bulk_codec(
        "peer", True, 0, 1, 1 << 20) == "delta"
    assert WireCodecGovernor(mode="zlib").bulk_codec(
        "peer", True, 0, 1, 1 << 20) == "zlib"
    # Unknown tokens degrade to auto instead of raising
    assert "auto" in WireCodecGovernor(mode="bogus,").mode


def test_governor_quant_policy():
    gov = WireCodecGovernor(mode="auto")
    # Legacy knob forces every hop (the PR 10 contract)
    assert gov.quant_mode("int8") == "int8"
    assert gov.quant_for_link("int8", "h", True) is True
    # No knob, no token: off
    assert gov.quant_mode("") == ""
    assert gov.quant_for_link("", "h", False) is False
    # Governor token: allowed, but auto skips same-machine hops
    gov = WireCodecGovernor(mode="auto,quant")
    assert gov.quant_mode("") == "int8"
    assert gov.quant_for_link("", "h", True) is False
    assert gov.quant_for_link("", "h", False) is True
    # Forced mode quantizes everywhere, like the knob
    gov = WireCodecGovernor(mode="delta,quant")
    assert gov.quant_for_link("", "h", True) is True


class _StubPerfStore:
    """Minimal PerfProfileStore stand-in for threshold tests."""

    def __init__(self, raw_gibs=None, delta_gibs=None):
        self.raw_gibs = raw_gibs
        self.delta_gibs = delta_gibs

    def link_gibs(self, dst, plane=None, min_bytes=0, codec=None):
        return self.delta_gibs if codec == "delta" else self.raw_gibs


def _inject_matrix(gov, cells):
    import time as _time

    gov._matrix_cells = cells
    gov._matrix_expires = _time.monotonic() + 999.0


def test_governor_tuned_threshold_from_perf_store(monkeypatch):
    """ISSUE 15 satellite (the ROADMAP item-1 leftover): with the env
    knob unset, the auto-mode break-even threshold is TUNED from the
    perf store's measured delta-path rate × the observed raw/wire
    compression ratio — compression pays exactly while the raw link is
    slower than what delta would effectively deliver."""
    import faabric_tpu.transport.codec as codec_mod

    monkeypatch.delenv("FAABRIC_WIRE_CODEC_MIN_GIBS", raising=False)
    # delta moves wire bytes at 0.05 GiB/s, and historically compressed
    # 100:1 on this link → effective 5 GiB/s of payload; the raw link
    # measures 1.0 GiB/s < 5 → delta wins despite being "fast" by the
    # old fixed 4.0 default... and with a poor 2:1 ratio the tuned
    # threshold collapses to the 0.25 clamp and raw wins.
    store = _StubPerfStore(raw_gibs=1.0, delta_gibs=0.05)
    monkeypatch.setattr(codec_mod, "get_perf_store", lambda: store)
    gov = WireCodecGovernor(mode="auto")
    assert not gov.min_gibs_env_set
    _inject_matrix(gov, [{"plane": "bulk-tcp", "codec": "delta",
                          "src": "0", "dst": "1",
                          "bytes": 1_000, "bytes_raw": 100_000}])
    threshold, src = gov._threshold_gibs("far-a", 0, 1)
    assert src == "tuned" and threshold == pytest.approx(5.0)
    assert gov.bulk_codec("far-a", False, 0, 1, 1 << 20) == "delta"

    gov2 = WireCodecGovernor(mode="auto")
    _inject_matrix(gov2, [{"plane": "bulk-tcp", "codec": "delta",
                           "src": "0", "dst": "1",
                           "bytes": 100_000, "bytes_raw": 200_000}])
    threshold, src = gov2._threshold_gibs("far-b", 0, 1)
    assert src == "tuned"
    assert threshold == pytest.approx(gov2.TUNED_MIN_GIBS)  # clamped
    assert gov2.bulk_codec("far-b", False, 0, 1, 1 << 20) == "raw"

    # A fresh (src, dst) pair with no delta history borrows the
    # matrix-wide aggregate ratio instead of giving up
    threshold, src = gov2._threshold_gibs("far-c", 7, 8)
    assert src == "tuned"


def test_governor_threshold_env_knob_overrides(monkeypatch):
    """An explicitly set FAABRIC_WIRE_CODEC_MIN_GIBS remains the
    operator override: tuned evidence is ignored."""
    import faabric_tpu.transport.codec as codec_mod

    monkeypatch.setenv("FAABRIC_WIRE_CODEC_MIN_GIBS", "9.5")
    store = _StubPerfStore(raw_gibs=6.0, delta_gibs=0.05)
    monkeypatch.setattr(codec_mod, "get_perf_store", lambda: store)
    gov = WireCodecGovernor(mode="auto")
    assert gov.min_gibs_env_set
    _inject_matrix(gov, [{"plane": "bulk-tcp", "codec": "delta",
                          "src": "0", "dst": "1",
                          "bytes": 100_000, "bytes_raw": 200_000}])
    threshold, src = gov._threshold_gibs("far-d", 0, 1)
    assert (threshold, src) == (9.5, "env")
    # measured 6.0 < 9.5 → delta (the override, not the 0.25 tuned)
    assert gov.bulk_codec("far-d", False, 0, 1, 1 << 20) == "delta"


def test_governor_threshold_defaults_without_delta_evidence(monkeypatch):
    """No delta history anywhere: the 4 GiB/s default holds, exactly
    as before this PR."""
    import faabric_tpu.transport.codec as codec_mod

    monkeypatch.delenv("FAABRIC_WIRE_CODEC_MIN_GIBS", raising=False)
    store = _StubPerfStore(raw_gibs=5.0, delta_gibs=None)
    monkeypatch.setattr(codec_mod, "get_perf_store", lambda: store)
    gov = WireCodecGovernor(mode="auto")
    _inject_matrix(gov, [])
    threshold, src = gov._threshold_gibs("far-e", 0, 1)
    assert (threshold, src) == (4.0, "default")
    # 5.0 ≥ 4.0 → raw, the pre-PR behaviour
    assert gov.bulk_codec("far-e", False, 0, 1, 1 << 20) == "raw"


def test_quant_codec_per_link_raw_passthrough():
    """encode(quantize=False) ships the NaN-scale raw form — the
    receiver decodes BITWISE-identical fp32, carried in-band."""
    from faabric_tpu.mpi.quant import Int8ChunkCodec

    codec = Int8ChunkCodec()
    chunk = np.linspace(-5.0, 5.0, 1000, dtype=np.float32)
    raw_wire = codec.encode(chunk, quantize=False)
    assert np.array_equal(codec.decode(raw_wire), chunk)
    # while the quantized form is lossy but close
    q = codec.decode(codec.encode(chunk, quantize=True))
    assert np.max(np.abs(q - chunk)) <= 5.0 / 127 + 1e-6
    assert not np.array_equal(q, chunk)


# ---------------------------------------------------------------------------
# End-to-end escape protocol over a real loopback bulk pair
# ---------------------------------------------------------------------------

class _SinkBroker:
    def __init__(self):
        self.host = "codec-sink"
        self.got = []

    def deliver(self, gid, s, r, data, seq, chan):
        self.got.append((seq, data))

    def deliver_many(self, gid, s, r, items, chan):
        for seq, d in items:
            self.deliver(gid, s, r, d, seq, chan)


@pytest.fixture
def bulk_codec_pair(monkeypatch):
    """Real BulkServer + BulkClient over loopback, rings disabled,
    governor forced to delta."""
    from faabric_tpu.transport.bulk import BulkClient, BulkServer
    from faabric_tpu.transport.common import (
        clear_host_aliases,
        register_host_alias,
    )

    monkeypatch.setenv("SHM_RING_BYTES", "0")
    clear_host_aliases()
    register_host_alias("codec-peer", "127.0.0.1", 23500)
    broker = _SinkBroker()
    server = BulkServer(broker, port_offset=23500)
    server.start()
    set_wire_codec("delta")
    client = BulkClient("codec-peer")
    try:
        yield broker, server, client
    finally:
        set_wire_codec("auto")
        client.close()
        server.stop()
        clear_host_aliases()


def _await(broker, n, timeout=10.0):
    deadline = time.monotonic() + timeout
    while len(broker.got) < n and time.monotonic() < deadline:
        time.sleep(0.02)
    return len(broker.got) >= n


def test_delta_stream_delivers_bitwise_and_saves_wire(bulk_codec_pair):
    broker, server, client = bulk_codec_pair
    rng = np.random.default_rng(7)
    payload = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
    sent = []
    for rnd in range(5):
        p = payload.copy()
        p[rnd * 500:rnd * 500 + 2048] ^= 0x1
        client.send(GROUP, 0, 1, [p], rnd, 0)
        payload = p
        sent.append(p)
    assert _await(broker, 5)
    for (seq, got), want in zip(sorted(broker.got), sent):
        assert np.array_equal(np.asarray(got), want)
    assert client.coded_frames == 5
    assert client.escape_frames == 0


def test_dropped_base_nacks_and_heals_without_another_send(
        bulk_codec_pair):
    """Epoch mismatch (migration remap / receiver cache loss): the
    NACK reader re-ships the seq FULL even if the sender never touches
    the stripe again."""
    broker, server, client = bulk_codec_pair
    rng = np.random.default_rng(8)
    p = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
    client.send(GROUP, 0, 1, [p], 0, 0)
    assert _await(broker, 1)
    server.drop_codec_bases()  # the migration-remap shape
    q = p.copy()
    q[100:200] ^= 0x3
    client.send(GROUP, 0, 1, [q], 1, 0)
    assert _await(broker, 2), "NACK escape did not heal the stream"
    assert np.array_equal(np.asarray(broker.got[-1][1]), q)
    assert client.escape_frames >= 1
    # The stream recovers to deltas afterwards
    r = q.copy()
    r[5000:5050] ^= 0x9
    client.send(GROUP, 0, 1, [r], 2, 0)
    assert _await(broker, 3)
    assert np.array_equal(np.asarray(broker.got[-1][1]), r)


def test_receiver_restart_mid_stream_recovers(bulk_codec_pair):
    from faabric_tpu.transport.bulk import BulkServer

    broker, server, client = bulk_codec_pair
    rng = np.random.default_rng(9)
    p = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
    client.send(GROUP, 0, 1, [p], 0, 0)
    assert _await(broker, 1)
    server.stop()
    server2 = BulkServer(broker, port_offset=23500)
    server2.start()
    try:
        time.sleep(0.4)  # the back-channel reader resets the stripe
        q = p.copy()
        q[300:400] ^= 0x5
        client.send(GROUP, 0, 1, [q], 1, 0)
        assert _await(broker, 2), "restart did not recover"
        assert np.array_equal(np.asarray(broker.got[-1][1]), q)
        # and the NEXT frame rides a delta on the fresh base pair
        r = q.copy()
        r[9000:9050] ^= 0x2
        client.send(GROUP, 0, 1, [r], 2, 0)
        assert _await(broker, 3)
        assert np.array_equal(np.asarray(broker.got[-1][1]), r)
    finally:
        server2.stop()


def test_corrupt_delta_frame_heals_via_fault_point(bulk_codec_pair):
    """FAABRIC_FAULTS-style corruption through the transport.bulk fault
    point: a DROP rule matching codec=delta scrambles the coded wire
    bytes; the receiver's crc check NACKs and the escape re-ships the
    same seq bitwise-exactly."""
    import faabric_tpu.transport.bulk as bulkmod
    from faabric_tpu.faults.registry import (
        get_fault_registry,
        parse_fault_spec,
        set_faults_enabled,
    )

    broker, server, client = bulk_codec_pair
    rng = np.random.default_rng(10)
    p = rng.integers(0, 255, 1 << 20, dtype=np.uint8)
    client.send(GROUP, 0, 1, [p], 0, 0)
    assert _await(broker, 1)
    set_faults_enabled(True)
    pt = get_fault_registry().point("transport.bulk")
    pt.set_rules(parse_fault_spec(
        "transport.bulk=drop@codec=delta@times=1"))
    old_faults, old_fp = bulkmod._FAULTS, bulkmod._FP_BULK
    bulkmod._FAULTS, bulkmod._FP_BULK = True, pt
    try:
        q = p.copy()
        q[100:150] ^= 0x2
        client.send(GROUP, 0, 1, [q], 1, 0)
        assert _await(broker, 2), "corrupt frame did not heal"
        assert np.array_equal(np.asarray(broker.got[-1][1]), q)
        assert client.escape_frames >= 1
    finally:
        bulkmod._FAULTS, bulkmod._FP_BULK = old_faults, old_fp
        pt.set_rules([])
        set_faults_enabled(False)


def test_coded_streams_pin_to_one_stripe(bulk_codec_pair):
    """Base/delta frames of one stream must share a FIFO connection:
    every coded frame of a stream lands on the same stripe."""
    broker, server, client = bulk_codec_pair
    rng = np.random.default_rng(11)
    p = rng.integers(0, 255, 1 << 19, dtype=np.uint8)
    for rnd in range(4):
        client.send(GROUP, 0, 1, [p], rnd, 0)
    assert _await(broker, 4)
    coded_stripes = [s for s in client.stripes() if s.coded_frames > 0]
    assert len(coded_stripes) == 1
    assert coded_stripes[0].coded_frames == 4
