"""ReplaySpool + DurableSender units: the durable rank-side send path
(docs/developer_guide/fault-tolerance.md).  The spool's contract is
at-least-once — over-replay is always legal because the aggregator
dedups by per-lane seq — so these tests pin ordering, bounded loss
(counted, never silent), and torn-tail recovery rather than
exactly-once delivery.
"""

import struct
import time

import pytest

from traceml_tpu.transport import TCPClient, TCPServer, UDSClient
from traceml_tpu.transport.shm_ring import (
    MIN_RING_BYTES,
    ShmRingClient,
    ShmRingRegistry,
)
from traceml_tpu.transport.spool import _HEADER, DurableSender, ReplaySpool, SPOOL_MAGIC
from traceml_tpu.utils import msgpack_codec

pytestmark = pytest.mark.skipif(
    msgpack_codec.preencode({}).raw is None,
    reason="JSON-fallback host: no splice-able raw bodies to spool",
)


def _payload(seq, rank=0, sampler="step_time"):
    return {
        "meta": {"seq": seq, "session_id": "s", "sampler": sampler},
        "global_rank": rank,
        "data": {"step": seq},
    }


def _enc(seq, **kw):
    return msgpack_codec.preencode(_payload(seq, **kw))


# -- ReplaySpool ---------------------------------------------------------


def test_append_iter_roundtrip_across_segments(tmp_path):
    # tiny segments force rotation mid-stream; iter order must stay
    # append order across the segment boundary
    spool = ReplaySpool(tmp_path, max_bytes=1 << 20, segment_bytes=128)
    bodies = {}
    for seq in range(100, 120):
        raw = _enc(seq).raw
        bodies[seq] = raw
        assert spool.append(seq, raw)
    assert spool.pending_frames() == 20
    assert spool.max_seq() == 119
    got = list(spool.iter_frames())
    assert [s for s, _ in got] == list(range(100, 120))
    assert all(body == bodies[s] for s, body in got)
    assert len(list(tmp_path.glob("*.seg"))) > 1  # rotation actually happened
    spool.close()


def test_size_bound_evicts_oldest_whole_segments(tmp_path):
    spool = ReplaySpool(tmp_path, max_bytes=600, segment_bytes=128)
    for seq in range(50):
        spool.append(seq, _enc(seq).raw)
    assert spool.pending_bytes() <= 600 + 128  # bound ± one tail segment
    assert spool.evicted_frames > 0  # loss is counted, never silent
    assert spool.evicted_bytes > 0
    remaining = [s for s, _ in spool.iter_frames()]
    # eviction drops the OLDEST prefix; the newest frames always survive
    assert remaining == list(range(50 - len(remaining), 50))
    assert spool.appended_frames == 50
    spool.close()


def test_restart_recovers_frames_in_order(tmp_path):
    spool = ReplaySpool(tmp_path, segment_bytes=128)
    for seq in range(5):
        spool.append(seq, _enc(seq).raw)
    spool.close()

    reopened = ReplaySpool(tmp_path, segment_bytes=128)
    assert reopened.torn_tails == 0
    assert [s for s, _ in reopened.iter_frames()] == [0, 1, 2, 3, 4]
    # post-restart appends land in a FRESH segment (recovered tails are
    # never appended to) and keep global order
    for seq in range(5, 8):
        reopened.append(seq, _enc(seq).raw)
    assert [s for s, _ in reopened.iter_frames()] == list(range(8))
    reopened.close()


def test_torn_tail_truncates_cleanly(tmp_path):
    spool = ReplaySpool(tmp_path, segment_bytes=1 << 20)
    for seq in range(4):
        spool.append(seq, _enc(seq).raw)
    spool.close()
    # simulate dying mid-append: a valid header promising more body
    # bytes than exist, exactly what a torn write leaves behind
    seg = sorted(tmp_path.glob("*.seg"))[-1]
    with seg.open("ab") as f:
        f.write(_HEADER.pack(SPOOL_MAGIC, 8 + 1000, 99) + b"partial")

    reopened = ReplaySpool(tmp_path, segment_bytes=1 << 20)
    assert reopened.torn_tails == 1
    assert [s for s, _ in reopened.iter_frames()] == [0, 1, 2, 3]
    reopened.close()


def test_corrupt_magic_stops_scan_at_boundary(tmp_path):
    spool = ReplaySpool(tmp_path)
    spool.append(1, _enc(1).raw)
    spool.close()
    seg = sorted(tmp_path.glob("*.seg"))[-1]
    with seg.open("ab") as f:
        f.write(struct.pack(">4sIQ", b"XXXX", 16, 7) + b"\x00" * 8)
    reopened = ReplaySpool(tmp_path)
    assert reopened.torn_tails == 1
    assert [s for s, _ in reopened.iter_frames()] == [1]
    reopened.close()


def test_consume_through_keeps_partial_segment(tmp_path):
    spool = ReplaySpool(tmp_path, segment_bytes=128)
    for seq in range(20):
        spool.append(seq, _enc(seq).raw)
    segs = sorted(tmp_path.glob("*.seg"))
    assert len(segs) >= 3
    # consume through the middle of the stream: fully-covered segments
    # drop, the segment straddling the cut survives WHOLE (its prefix
    # replays again and dedups server-side)
    spool.consume_through(10)
    remaining = [s for s, _ in spool.iter_frames()]
    assert remaining and remaining[-1] == 19
    assert remaining[0] <= 10 + 1  # at most one partial segment's prefix
    assert remaining == sorted(remaining)
    spool.consume_through(19)
    assert spool.pending_frames() == 0
    spool.close()


def test_clear_removes_everything(tmp_path):
    spool = ReplaySpool(tmp_path)
    for seq in range(3):
        spool.append(seq, _enc(seq).raw)
    spool.clear()
    assert spool.pending_frames() == 0
    assert spool.pending_bytes() == 0
    assert list(tmp_path.glob("*.seg")) == []


# -- DurableSender -------------------------------------------------------


class _FakeClient:
    """Link double: `ok` flips the wire up/down instantly."""

    def __init__(self):
        self.ok = True
        self.batches = []  # via send_batch (fresh sends)
        self.bodies = []  # via send_encoded_body (replay groups)

    def send_batch(self, batch):
        if not self.ok:
            return False
        self.batches.append(list(batch))
        return True

    def send_encoded_body(self, body):
        if not self.ok:
            return False
        self.bodies.append(bytes(body))
        return True


def _decode_replayed(client):
    out = []
    for body in client.bodies:
        decoded = msgpack_codec.decode(body)
        assert isinstance(decoded, list)
        out.extend(decoded)
    return out


def test_send_failure_spools_then_replays(tmp_path):
    client = _FakeClient()
    sender = DurableSender(client, ReplaySpool(tmp_path))
    assert sender.send([_enc(1), _enc(2)])  # healthy path: straight through

    client.ok = False
    assert not sender.send([_enc(3), _enc(4)])
    stats = sender.stats()
    # the failed batch AND the sent-but-maybe-uncommitted ring (1, 2)
    # both hit the spool: TCP success is not aggregator commit
    assert stats["spooled_envelopes"] == 4
    assert stats["spool_frames"] == 4

    client.ok = True
    assert sender.send([_enc(5)])
    replayed = _decode_replayed(client)
    assert [p["meta"]["seq"] for p in replayed] == [1, 2, 3, 4]
    assert sender.stats()["replayed_envelopes"] == 4
    assert sender.stats()["spool_frames"] == 0  # drained clean
    # the fresh batch went out as a normal send, after the backlog
    assert client.batches[-1][0].obj["meta"]["seq"] == 5
    sender.close()


def test_replay_batches_and_partial_failure_resumes(tmp_path):
    client = _FakeClient()
    client.ok = False
    sender = DurableSender(
        client, ReplaySpool(tmp_path, segment_bytes=64), replay_batch=3
    )
    sender.send([_enc(s) for s in range(8)])
    assert sender.stats()["spool_frames"] == 8

    # link heals for exactly one replay group, then dies again
    sends = {"n": 0}
    real = client.send_encoded_body

    def one_shot(body):
        sends["n"] += 1
        client.ok = sends["n"] <= 1
        return real(body)

    client.send_encoded_body = one_shot
    client.ok = True
    assert not sender.replay()
    assert sender.stats()["replayed_envelopes"] == 3
    # the un-replayed suffix is still pending (consume_through per group)
    assert sender.stats()["spool_frames"] >= 5

    client.send_encoded_body = real
    client.ok = True
    assert sender.replay()
    replayed = [p["meta"]["seq"] for p in _decode_replayed(client)]
    # over-replay of a partial segment's prefix is legal; the full
    # suffix must be present and ordering preserved per group
    assert replayed[:3] == [0, 1, 2]
    assert replayed[-1] == 7
    assert set(range(8)) <= set(replayed)
    sender.close()


def test_rawless_payload_counts_send_failure(tmp_path):
    client = _FakeClient()
    client.ok = False
    sender = DurableSender(client, ReplaySpool(tmp_path))
    # JSON-fallback envelope: no splice-able bytes, legacy drop-on-
    # failure but counted
    sender.send([msgpack_codec.EncodedPayload(_payload(1), None)])
    assert sender.stats()["spool_send_failures"] == 1
    assert sender.stats()["spool_frames"] == 0
    sender.close()


def test_send_transient_never_spooled(tmp_path):
    client = _FakeClient()
    client.ok = False
    sender = DurableSender(client, ReplaySpool(tmp_path))
    assert not sender.send_transient([_enc(1)])
    assert sender.stats()["spool_frames"] == 0  # stale heartbeats are worthless

    # but a transient send DOES kick the backlog when the link is up
    sender.send([_enc(2)])
    assert sender.stats()["spool_frames"] == 1
    client.ok = True
    sender.send_transient([_enc(3)])
    assert sender.stats()["spool_frames"] == 0
    assert sender.stats()["replayed_envelopes"] == 1
    sender.close()


class _ComesBackClient(_FakeClient):
    """Link double that is down until its ``up_after``-th redial."""

    def __init__(self, up_after):
        super().__init__()
        self.ok = False
        self.redials = 0
        self._up_after = up_after

    def redial_now(self):
        self.redials += 1
        self.ok = self.redials >= self._up_after


def test_drain_before_exit_waits_for_the_link(tmp_path):
    client = _ComesBackClient(up_after=3)
    sender = DurableSender(client, ReplaySpool(tmp_path))
    assert not sender.send([_enc(1), _enc(2)])
    assert sender.drain_before_exit(wait_s=5.0, poll_s=0.01)
    assert client.redials == 3
    assert [p["meta"]["seq"] for p in _decode_replayed(client)] == [1, 2]
    assert sender.stats()["spool_frames"] == 0
    sender.close()


def test_drain_before_exit_gives_up_after_its_wait(tmp_path):
    client = _ComesBackClient(up_after=10**9)
    sender = DurableSender(client, ReplaySpool(tmp_path))
    sender.send([_enc(1)])
    t0 = time.monotonic()
    assert not sender.drain_before_exit(wait_s=0.2, poll_s=0.05)
    assert time.monotonic() - t0 < 2.0
    assert client.redials >= 2
    assert sender.stats()["spool_frames"] == 1  # kept, never dropped
    sender.close()


# -- link flap through a real TCP server ---------------------------------


def test_link_flap_replay_end_to_end(tmp_path):
    """Server dies mid-run and comes back on the SAME port (the
    launcher's restart path pins it): everything sent into the outage
    must arrive after the link heals — duplicates allowed (writer-side
    dedup), silent loss not."""
    server = TCPServer()
    server.start()
    port = server.port
    client = TCPClient("127.0.0.1", port, reconnect_backoff=0.01)
    sender = DurableSender(client, ReplaySpool(tmp_path / "spool"))
    got = []

    def drain(n, timeout=10.0):
        deadline = time.monotonic() + timeout
        while len(got) < n and time.monotonic() < deadline:
            server.wait_for_data(0.1)
            got.extend(server.drain_decoded())

    try:
        assert sender.send([_enc(0), _enc(1)])
        drain(2)
        assert len(got) == 2

        server.stop()
        deadline = time.monotonic() + 5.0
        while sender.send([_enc(2), _enc(3)]) and time.monotonic() < deadline:
            time.sleep(0.05)  # until the dead peer surfaces as a send error
        sender.send([_enc(4)])
        assert sender.stats()["spool_frames"] >= 3

        server = TCPServer(port=port)  # SO_REUSEADDR: rebinds immediately
        server.start()
        deadline = time.monotonic() + 10.0
        while sender.stats()["spool_frames"] and time.monotonic() < deadline:
            sender.send([_enc(5)])
            time.sleep(0.05)
        assert sender.stats()["spool_frames"] == 0, sender.stats()
        drain(6)
        seqs = {p["meta"]["seq"] for p in got}
        assert set(range(6)) <= seqs, sorted(seqs)  # nothing silently lost
    finally:
        sender.close()
        client.close()
        server.stop()


def test_exit_drain_dials_past_the_backoff(tmp_path):
    """A rank that finishes while its aggregator is down and then comes
    back on the same port: its last sends sit in the spool behind a
    long backoff window, and the exit drain delivers them anyway."""
    server = TCPServer()
    server.start()
    port = server.port
    client = TCPClient("127.0.0.1", port, reconnect_backoff=60.0)
    sender = DurableSender(client, ReplaySpool(tmp_path / "spool"))
    got = []
    try:
        assert sender.send([_enc(0)])
        server.stop()
        deadline = time.monotonic() + 5.0
        while sender.send([_enc(1)]) and time.monotonic() < deadline:
            time.sleep(0.05)  # until the dead peer surfaces as a send error
        assert not sender.send([_enc(2)])  # the dial fails: backoff armed
        server = TCPServer(port=port)
        server.start()
        assert not sender.send([_enc(3)])  # inside the backoff: no dial
        assert sender.stats()["spool_frames"] >= 3
        assert sender.drain_before_exit(wait_s=5.0)
        deadline = time.monotonic() + 10.0
        while {1, 2, 3} - {p["meta"]["seq"] for p in got} and time.monotonic() < deadline:
            server.wait_for_data(0.1)
            got.extend(server.drain_decoded())
        assert {1, 2, 3} <= {p["meta"]["seq"] for p in got}
    finally:
        sender.close()
        client.close()
        server.stop()


@pytest.mark.parametrize("kind", ["tcp", "uds", "shm"])
def test_durable_replay_over_each_transport(tmp_path, kind):
    """The durable-send contract is transport-independent: everything
    sent into an aggregator outage/restart must arrive after it heals —
    duplicates allowed (writer-side seq dedup), silent loss not.

    The outage differs per transport: tcp/uds see a dead then rebound
    listener; shm sees the restarted consumer re-attach the segment
    (generation flip → one failed send → spooled replay window), with
    the ring itself doubling as a replay buffer across the restart.
    """
    session = tmp_path / "session"
    sock = str(tmp_path / "u.sock")
    state = {"port": 0}

    def start_server():
        if kind == "tcp":
            srv = TCPServer(port=state["port"])
        elif kind == "uds":
            srv = TCPServer(uds_path=sock)
        else:
            srv = TCPServer()
            srv.attach_ring_registry(ShmRingRegistry(session))
        srv.start()
        state["port"] = srv.port
        return srv

    server = start_server()
    if kind == "tcp":
        client = TCPClient("127.0.0.1", state["port"], reconnect_backoff=0.01)
    elif kind == "uds":
        client = UDSClient(sock, reconnect_backoff=0.01)
    else:
        client = ShmRingClient(
            tmp_path / "seg.ring",
            capacity=MIN_RING_BYTES,
            session_dir=session,
            global_rank=0,
        )
    sender = DurableSender(client, ReplaySpool(tmp_path / "spool"))
    got = []

    def drain(n, timeout=10.0):
        deadline = time.monotonic() + timeout
        while len(got) < n and time.monotonic() < deadline:
            server.wait_for_data(0.1)
            got.extend(server.drain_decoded())

    try:
        assert sender.send([_enc(0), _enc(1)])
        drain(2)
        assert len(got) >= 2

        server.stop()  # the outage (shm: consumer detaches too)
        deadline = time.monotonic() + 3.0
        while sender.send([_enc(2), _enc(3)]) and time.monotonic() < deadline:
            # tcp/uds exit on the first surfaced send error; the shm
            # ring happily buffers until the restart below
            time.sleep(0.02)
        sender.send([_enc(4)])

        server = start_server()
        deadline = time.monotonic() + 10.0
        while sender.stats()["spool_frames"] and time.monotonic() < deadline:
            sender.send([_enc(5)])
            time.sleep(0.05)
        assert sender.stats()["spool_frames"] == 0, sender.stats()
        sender.send([_enc(5)])  # shm: past the gen-flip failed send
        drain(6)
        seqs = {p["meta"]["seq"] for p in got}
        assert set(range(6)) <= seqs, sorted(seqs)  # nothing silently lost
    finally:
        sender.close()
        client.close()
        server.stop()
