"""TelemetryPublisher single-encode path: idle gate, wire/disk byte
agreement, shutdown flush completeness, producer self-observability."""

import time
from pathlib import Path

from traceml_tpu.database.database_writer import ENVELOPE_FILE, iter_backup_tables
from traceml_tpu.runtime.sender import TelemetryPublisher
from traceml_tpu.samplers.base_sampler import BaseSampler
from traceml_tpu.telemetry.control import CONTROL_KEY, PRODUCER_STATS
from traceml_tpu.telemetry.envelope import SenderIdentity, normalize_telemetry_envelope
from traceml_tpu.utils import msgpack_codec


class FakeSampler(BaseSampler):
    name = "fake"

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._i = 0

    def _sample(self):
        self.db.add_record("t", {"i": self._i})
        self._i += 1


class CapturingClient:
    """Stands in for TCPClient: records the exact frame bodies."""

    def __init__(self):
        self.bodies = []

    def send_batch(self, payloads):
        self.bodies.append(msgpack_codec.encode_batch(payloads))
        return True


def test_idle_tick_is_free_no_payload_no_disk(tmp_path):
    s = FakeSampler(disk_backup_dir=tmp_path)
    pub = TelemetryPublisher([s], None, SenderIdentity())
    for _ in range(50):
        assert pub.publish() == 0
    assert pub.idle_ticks == 50
    assert pub.stats()["idle_ratio"] == 1.0
    # no disk artifacts at all: nothing was collected or buffered
    assert not (tmp_path / "fake").exists()


def test_single_encode_wire_and_disk_share_bytes(tmp_path):
    s = FakeSampler(disk_backup_dir=tmp_path)
    client = CapturingClient()
    pub = TelemetryPublisher([s], client, SenderIdentity(global_rank=3))
    s.sample()
    s.sample()
    # 2 = one telemetry envelope + the one-shot transport_hello announce
    assert pub.publish() == 2
    pub.publish(final=True)  # force the backup buffer out
    # wire: one batch frame decoding to one envelope with both rows
    payloads, errors = msgpack_codec.decode_batch(client.bodies)
    assert errors == 0
    envs = [e for e in map(normalize_telemetry_envelope, payloads) if e]
    assert len(envs) == 1
    assert envs[0].tables["t"] == [{"i": 0}, {"i": 1}]
    assert envs[0].global_rank == 3
    # disk: the same envelope, same rows
    got = list(iter_backup_tables(tmp_path / "fake" / ENVELOPE_FILE))
    assert got == [("t", {"i": 0}), ("t", {"i": 1})]


def test_publisher_marks_envelope_mode_no_legacy_double_write(tmp_path):
    s = FakeSampler(disk_backup_dir=tmp_path)
    pub = TelemetryPublisher([s], None, SenderIdentity())
    assert s.writer.envelope_mode  # committed at construction
    s.sample()
    pub.publish(final=True)
    # only the envelope file exists — no per-row t.msgpack alongside it
    files = sorted(p.name for p in (tmp_path / "fake").iterdir())
    assert files == [ENVELOPE_FILE]


def test_midwindow_kill_backup_has_all_rows(tmp_path):
    """Regression (r10 satellite): rows published but throttled out of
    the backup buffer, plus rows never published at all, must BOTH reach
    disk when the sampler is stopped mid-window."""
    s = FakeSampler(disk_backup_dir=tmp_path)
    pub = TelemetryPublisher([s], None, SenderIdentity())
    s.sample()
    pub.publish()  # envelope buffered; flush_every=20 throttle → not on disk
    assert s.writer.has_pending()
    s.sample()  # lands AFTER the last publish; the publisher never sees it
    s.stop()  # kill: no final drain, no final publish
    got = list(iter_backup_tables(tmp_path / "fake" / ENVELOPE_FILE))
    assert got == [("t", {"i": 0}), ("t", {"i": 1})]


def test_base_sampler_stop_idempotent_after_final_publish(tmp_path):
    s = FakeSampler(disk_backup_dir=tmp_path)
    pub = TelemetryPublisher([s], None, SenderIdentity())
    s.sample()
    pub.publish(final=True)
    s.stop()  # nothing dirty, nothing pending — must not duplicate
    got = list(iter_backup_tables(tmp_path / "fake" / ENVELOPE_FILE))
    assert got == [("t", {"i": 0})]


def test_final_publish_force_flushes_every_sampler(tmp_path):
    a, b = FakeSampler(disk_backup_dir=tmp_path), FakeSampler(disk_backup_dir=tmp_path / "b")
    pub = TelemetryPublisher([a, b], None, SenderIdentity())
    a.sample()
    b.sample()
    pub.publish(final=True)
    assert not a.writer.has_pending() and not b.writer.has_pending()
    assert (tmp_path / "fake" / ENVELOPE_FILE).exists()
    assert (tmp_path / "b" / "fake" / ENVELOPE_FILE).exists()


def test_producer_stats_message_on_final():
    # no disk backup: the tick after a publish is genuinely idle (a
    # pending backup buffer intentionally keeps ticks non-idle until
    # the flush throttle writes it)
    s = FakeSampler(disk_backup_dir=None)
    client = CapturingClient()
    pub = TelemetryPublisher([s], client, SenderIdentity(global_rank=1))
    s.sample()
    pub.publish()
    pub.publish()  # idle
    pub.publish(final=True, extra_payloads=[{"hello": 1}])
    payloads, _ = msgpack_codec.decode_batch(client.bodies)
    stats_msgs = [p for p in payloads if p.get(CONTROL_KEY) == PRODUCER_STATS]
    assert stats_msgs, payloads
    st = stats_msgs[-1]["stats"]
    assert st["samplers"]["fake"]["envelopes"] == 1
    assert st["idle_ticks"] == 1
    assert st["samplers"]["fake"]["collect_us"] >= 0
    assert stats_msgs[-1]["meta"]["global_rank"] == 1


def test_stats_not_emitted_every_batch(tmp_path):
    s = FakeSampler(disk_backup_dir=tmp_path)
    client = CapturingClient()
    pub = TelemetryPublisher(
        [s], client, SenderIdentity(), stats_interval_s=3600.0
    )
    for _ in range(5):
        s.sample()
        pub.publish()
    payloads, _ = msgpack_codec.decode_batch(client.bodies)
    assert not any(p.get(CONTROL_KEY) == PRODUCER_STATS for p in payloads)


class OtherSampler(FakeSampler):
    name = "other"


def test_publish_units_ships_only_named_units_and_counts_lane(tmp_path):
    fake, other = FakeSampler(disk_backup_dir=tmp_path), OtherSampler(disk_backup_dir=tmp_path)
    client = CapturingClient()
    pub = TelemetryPublisher([fake, other], client, SenderIdentity(), stats_interval_s=3600.0)
    fake.sample()
    fake.sample()
    other.sample()
    # 2 = the fake sampler's envelope + the one-shot transport_hello
    assert pub.publish_units(("fake",)) == 2
    assert pub.publish_units(("fake",)) == 0  # nothing new: no frame
    payloads, _ = msgpack_codec.decode_batch(client.bodies)
    envs = [e for e in map(normalize_telemetry_envelope, payloads) if e]
    assert [e.sampler for e in envs] == ["fake"]
    assert envs[0].tables["t"] == [{"i": 0}, {"i": 1}]
    assert other.sender.dirty()  # left for the full tick
    st = pub.stats()
    assert (st["lane_publishes"], st["lane_rows"]) == (1, 0)  # rows of table "fake" only
    assert st["ticks"] == 0 and st["samplers"]["fake"]["envelopes"] == 1
    pub.publish()  # the full tick ships the rest, once
    payloads, _ = msgpack_codec.decode_batch(client.bodies)
    envs = [e for e in map(normalize_telemetry_envelope, payloads) if e]
    assert [e.sampler for e in envs] == ["fake", "other"]
    assert pub.stats()["lane_publishes"] == 1


def test_publish_units_counts_rows_of_the_samplers_own_table():
    class StepLike(FakeSampler):
        name = "step_time"

        def _sample(self):
            self.db.add_record("step_time", {"step": self._i})
            self.db.add_record("model_stats", {"flops": 1.0})
            self._i += 1

    s = StepLike(disk_backup_dir=None)
    pub = TelemetryPublisher([s], CapturingClient(), SenderIdentity())
    for _ in range(3):
        s.sample()
    pub.publish_units(("step_time",))
    s.sample()
    pub.publish_units(("step_time",))
    st = pub.stats()
    assert (st["lane_publishes"], st["lane_rows"]) == (2, 4)


def test_publish_units_leaves_the_backup_throttle_to_full_ticks(tmp_path):
    """Lane publishes buffer their envelope for the disk backup without
    advancing its flush_every throttle, which counts full ticks only."""
    s = FakeSampler(disk_backup_dir=tmp_path)
    pub = TelemetryPublisher([s], None, SenderIdentity())
    for _ in range(25):
        s.sample()
        pub.publish_units(("fake",))
    assert s.writer.has_pending()
    assert not (tmp_path / "fake" / ENVELOPE_FILE).exists()
    for _ in range(19):
        pub.publish()  # pending backup keeps these ticks non-idle
    assert not (tmp_path / "fake" / ENVELOPE_FILE).exists()
    pub.publish()  # the 20th full tick writes every buffered frame
    got = list(iter_backup_tables(tmp_path / "fake" / ENVELOPE_FILE))
    assert got == [("t", {"i": i}) for i in range(25)]


class ReturningClient(CapturingClient):
    """Down until its first redial, then records replayed bodies too."""

    def __init__(self):
        super().__init__()
        self.up = False

    def redial_now(self):
        self.up = True

    def send_batch(self, payloads):
        return self.up and super().send_batch(payloads)

    def send_encoded_body(self, body):
        if self.up:
            self.bodies.append(bytes(body))
        return self.up


def test_drain_spool_delivers_the_final_publish(tmp_path):
    s = FakeSampler(disk_backup_dir=tmp_path / "backup")
    client = ReturningClient()
    pub = TelemetryPublisher(
        [s], client, SenderIdentity(global_rank=1), spool_dir=tmp_path / "spool"
    )
    s.sample()
    pub.publish(final=True, extra_payloads=[{"marker": 1}])
    assert client.bodies == []  # the link was down: all of it spooled
    assert pub.drain_spool(wait_s=1.0)
    payloads, errors = msgpack_codec.decode_batch(client.bodies)
    assert errors == 0
    assert any(p.get("marker") == 1 for p in payloads)
    envs = [e for e in map(normalize_telemetry_envelope, payloads) if e]
    assert [e.tables["t"] for e in envs] == [[{"i": 0}]]
    assert pub.stats()["transport"]["spool_frames"] == 0


def test_drain_spool_without_a_spool_is_a_no_op(tmp_path):
    pub = TelemetryPublisher([FakeSampler(disk_backup_dir=tmp_path)], None, SenderIdentity())
    assert pub.drain_spool(wait_s=5.0)
