"""Chaos harness: the detector's OWN dependencies misbehave.

The qualbench measures time-to-detect for all eleven shop-side flagd
faults; this suite injects the faults *underneath the detector* —
broker kill/restart mid-fetch, truncated wire frames, poison protobufs
on ``orders``, corrupt snapshots at boot, half-open sockets — and
asserts the supervised runtime's contract: the process stays alive,
the corresponding Prometheus counter moves, and detection quality is
unchanged after recovery.

Fault → expected behavior matrix (mirrored in README.md):

==========================  =========================================
injected fault              observed behavior / metric
==========================  =========================================
broker kill + restart       pump reconnects with backoff; offset
                            continuity (at-least-once, no span lost,
                            none double-counted)
poison ``orders`` record    quarantined + ``anomaly_quarantined_
                            records_total``; batch pump never stalls
truncated OTLP body         400 + ``anomaly_ingest_rejected_total
                            {reason="truncated"}``; server lives
oversized OTLP body         413 + ``…{reason="oversized"}``
malformed OTLP body         400 + ``…{reason="malformed"}``
corrupt checkpoint at boot  cold start + ``anomaly_checkpoint_
                            corrupt_total``; bad file moved aside
mid-frame truncation / RST  (FaultWire) consumer drops + reconnects;
                            daemon survives, resumes on clear
crash-looping component     DEGRADED state, ``anomaly_degraded`` 1,
                            per-component gRPC health NOT_SERVING
==========================  =========================================
"""

from __future__ import annotations

import http.client
import json
import socket
import time

import numpy as np
import pytest

from opentelemetry_demo_tpu.models import DetectorConfig
from opentelemetry_demo_tpu.runtime import checkpoint, qualbench
from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
from opentelemetry_demo_tpu.runtime.faultwire import FaultWire
from opentelemetry_demo_tpu.runtime.kafka_broker import KafkaBroker
from opentelemetry_demo_tpu.runtime.kafka_orders import Order, encode_order
from opentelemetry_demo_tpu.runtime import supervision
from opentelemetry_demo_tpu.telemetry import metrics as tele_metrics
from opentelemetry_demo_tpu.telemetry.metrics import MetricRegistry

pytestmark = pytest.mark.chaos

SMALL = dict(num_services=8, hll_p=8, cms_width=512)


def _order_payload(i: int) -> bytes:
    return encode_order(Order(
        order_id=f"ord-{i}", tracking_id=f"trk-{i}",
        shipping_cost_units=9.5, item_count=1,
        product_ids=("EYE-PLO-25",), total_quantity=2,
    ))


def _daemon_env(monkeypatch, tmp_path, broker_port=None, **extra):
    monkeypatch.setenv("ANOMALY_OTLP_PORT", "0")
    monkeypatch.setenv("ANOMALY_OTLP_GRPC_PORT", "-1")  # HTTP leg suffices
    monkeypatch.setenv("ANOMALY_METRICS_PORT", "0")
    monkeypatch.setenv("ANOMALY_BATCH", "256")
    monkeypatch.setenv("ANOMALY_CHECKPOINT", str(tmp_path / "ckpt"))
    monkeypatch.delenv("KAFKA_ADDR", raising=False)
    if broker_port is not None:
        monkeypatch.setenv("KAFKA_ADDR", f"127.0.0.1:{broker_port}")
    for k, v in extra.items():
        monkeypatch.setenv(k, v)


def _scrape(daemon) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", daemon.exporter.port)
    conn.request("GET", "/metrics")
    return conn.getresponse().read().decode()


def _pump_until(daemon, cond, timeout_s=15.0, poll_s=0.02):
    deadline = time.monotonic() + timeout_s
    t = 0.0
    while time.monotonic() < deadline:
        daemon.step(t)
        if cond():
            return
        t += 0.25
        time.sleep(poll_s)
    raise AssertionError("condition not reached before timeout")


# --- supervisor unit behavior ----------------------------------------


class TestSupervisor:
    def _clock(self):
        state = {"t": 0.0}

        def advance(dt):
            state["t"] += dt

        return (lambda: state["t"]), advance

    def test_backoff_grows_bounded_with_jitter(self):
        now, advance = self._clock()
        reg = MetricRegistry()
        sup = supervision.Supervisor(registry=reg, time_fn=now)
        sup.register("flaky", base_backoff_s=1.0, max_backoff_s=8.0,
                     restart_budget=100, budget_window_s=1e9)
        waits = []
        for _ in range(6):
            sup.run_step("flaky", lambda: 1 / 0)
            c = sup._components["flaky"]
            waits.append(c.next_attempt_at - now())
            advance(waits[-1] + 0.01)  # sit out the backoff window
        # Jittered exponential: each wait sits in [0.5, 1.5)x its base
        # rung, bases doubling 1,2,4,8 then clamped at 8.
        for wait, base in zip(waits, (1, 2, 4, 8, 8, 8)):
            assert 0.5 * base <= wait < 1.5 * base
        # Restarts counted into the Prometheus family.
        text = reg.render()
        assert 'anomaly_component_restarts_total{component="flaky"} 6.0' in text
        assert 'anomaly_component_up{component="flaky"} 0.0' in text

    def test_run_step_skips_during_backoff_and_recovers(self):
        now, advance = self._clock()
        sup = supervision.Supervisor(time_fn=now)
        sup.register("c", base_backoff_s=2.0)
        calls = []

        def boom():
            calls.append("x")
            raise RuntimeError("transient")

        assert sup.run_step("c", boom) is None
        assert sup.state("c") == supervision.BACKOFF
        # Inside the backoff window the function is NOT invoked.
        assert sup.run_step("c", boom) is None
        assert calls == ["x"]
        advance(4.0)
        assert sup.run_step("c", lambda: 42) == 42
        assert sup.state("c") == supervision.UP

    def test_crash_loop_degrades_but_keeps_retrying(self):
        now, advance = self._clock()
        reg = MetricRegistry()
        sup = supervision.Supervisor(registry=reg, time_fn=now)
        sup.register("loop", base_backoff_s=0.1, max_backoff_s=1.0,
                     restart_budget=3, budget_window_s=60.0)
        for _ in range(5):
            sup.run_step("loop", lambda: 1 / 0)
            advance(2.0)
        assert sup.state("loop") == supervision.DEGRADED
        assert sup.degraded()
        assert "anomaly_degraded 1.0" in reg.render()
        # Degraded ≠ abandoned: the component still answers retries and
        # recovers the moment the fault clears.
        advance(2.0)
        assert sup.run_step("loop", lambda: "ok") == "ok"
        assert sup.state("loop") == supervision.UP
        assert not sup.degraded()
        assert "anomaly_degraded 0.0" in reg.render()

    def test_probe_failure_triggers_restart(self):
        now, advance = self._clock()
        sup = supervision.Supervisor(time_fn=now)
        healthy = {"v": False}
        restarts = []
        sup.register(
            "svc",
            restart=lambda: restarts.append(1) or healthy.update(v=True),
            probe=lambda: healthy["v"],
            base_backoff_s=0.1,
        )
        advance(0.01)
        sup.tick()  # probe fails → crash recorded
        assert sup.state("svc") == supervision.BACKOFF
        advance(1.0)
        sup.tick()  # due → restart() runs and succeeds
        assert restarts == [1]
        assert sup.state("svc") == supervision.UP

    def test_health_status_per_component(self):
        sup = supervision.Supervisor()
        sup.register("kafka-orders")
        assert sup.health_status("anomaly.component.kafka-orders") == \
            supervision.SERVING
        sup.report_crash("kafka-orders", RuntimeError("down"))
        assert sup.health_status("anomaly.component.kafka-orders") == \
            supervision.NOT_SERVING
        assert sup.health_status("anomaly.component.nope") is None
        assert sup.health_status("oteldemo.CartService") is None


# --- checkpoint corruption -------------------------------------------


class TestCorruptCheckpoint:
    def test_truncated_snapshot_cold_starts_with_metric(
        self, monkeypatch, tmp_path
    ):
        _daemon_env(monkeypatch, tmp_path)
        config = DetectorConfig(**SMALL)
        d1 = DetectorDaemon(config)
        try:
            d1.pipeline.tensorizer.service_id("payment")
        finally:
            d1.shutdown()  # writes the snapshot
        ckpt = tmp_path / "ckpt.ckpt"
        blob = ckpt.read_bytes()
        assert len(blob) > 64
        ckpt.write_bytes(blob[: len(blob) // 3])  # torn write / truncation

        d2 = DetectorDaemon(config)  # must NOT raise
        try:
            # Cold start: nothing restored from the torn file.
            assert d2.pipeline.tensorizer.service_names == []
            assert int(np.asarray(d2.detector.state.step_idx)) == 0
            d2.start()
            text = _scrape(d2)
            assert "anomaly_checkpoint_corrupt_total 1.0" in text
            # The frame family counts the same event by hop.
            assert 'anomaly_frame_corrupt_total{hop="checkpoint"} 1.0' in text
        finally:
            d2.shutdown()
        # Evidence moved aside; the daemon's own shutdown snapshot owns
        # the canonical path again (next boot restores normally).
        assert (tmp_path / "ckpt.ckpt.corrupt").exists()
        d3 = DetectorDaemon(config)
        try:
            assert checkpoint.exists(str(tmp_path / "ckpt"))
        finally:
            d3.shutdown()

    def test_array_blob_corrupt_midstream_meta_intact(
        self, monkeypatch, tmp_path
    ):
        """The partial-write gap: the frame header (and the meta block
        inside it — offsets, epoch, config) reads FINE but a state
        column's payload bytes were scribbled in place — the shape a
        torn flush leaves inside a structurally-valid file, and exactly
        what the per-column CRC32C + trailer exist to catch.
        load_resilient must cold-start, move the file aside, and the
        boot must count anomaly_checkpoint_corrupt_total."""
        from opentelemetry_demo_tpu.runtime import frame

        config = DetectorConfig(**SMALL)
        _daemon_env(monkeypatch, tmp_path)
        d1 = DetectorDaemon(config)
        try:
            d1.pipeline.tensorizer.service_id("payment")
        finally:
            d1.shutdown()  # writes the snapshot
        ckpt = tmp_path / "ckpt.ckpt"
        blob = bytearray(ckpt.read_bytes())
        # Zero a stretch strictly INSIDE the column payload region
        # (past the header, short of the trailer): the header — and the
        # meta it carries — stays byte-for-byte intact.
        _version, _flags, hlen = (
            int.from_bytes(blob[4:6], "little"),
            int.from_bytes(blob[6:8], "little"),
            int.from_bytes(blob[16:20], "little"),
        )
        payload_start = 20 + hlen
        assert payload_start + 64 < len(blob) - 4
        for i in range(payload_start + 16, payload_start + 48):
            blob[i] ^= 0xFF
        ckpt.write_bytes(bytes(blob))
        # The header is still readable — the corruption is strictly
        # inside a column payload, the case whole-file truncation
        # tests can't see (peek_file_meta is the header-only read the
        # fencing path uses).
        meta_peek = frame.peek_file_meta(str(ckpt)).meta
        assert meta_peek["config"]  # meta decodes fine
        det, meta, corrupt = checkpoint.load_resilient(
            str(tmp_path / "ckpt"), config
        )
        assert det is None and meta is None and corrupt is True
        assert (tmp_path / "ckpt.ckpt.corrupt").exists()
        # And the daemon boot path surfaces it as a counter (the file
        # was already moved aside, so re-create the corruption).
        (tmp_path / "ckpt.ckpt.corrupt").rename(ckpt)
        d2 = DetectorDaemon(config)  # must NOT raise
        try:
            assert d2.pipeline.tensorizer.service_names == []
            d2.start()
            assert "anomaly_checkpoint_corrupt_total 1.0" in _scrape(d2)
        finally:
            d2.shutdown()

    def test_restore_metrics_feed_logs_mismatching_key(self, caplog):
        """Satellite: a metrics-leg geometry mismatch is a LOGGED
        partial restore naming the offending config field, not a
        silent False."""
        import logging as _logging

        from opentelemetry_demo_tpu.models.metrics_head import (
            MetricsHeadConfig,
        )
        from opentelemetry_demo_tpu.runtime.metrics_feed import MetricsFeed

        feed = MetricsFeed(MetricsHeadConfig(num_services=8))
        saved_cfg = MetricsHeadConfig(num_services=16)
        meta = {
            "_metrics_arrays": {"dummy": np.zeros(1)},
            "metrics_config": list(saved_cfg),
        }
        with caplog.at_level(_logging.WARNING):
            assert checkpoint.restore_metrics_feed(meta, feed) is False
        assert any(
            "num_services" in rec.message for rec in caplog.records
        ), caplog.records

    def test_digest_catches_silent_bit_rot(self, tmp_path):
        from opentelemetry_demo_tpu.models import AnomalyDetector

        det = AnomalyDetector(DetectorConfig(**SMALL))
        path = str(tmp_path / "snap")
        checkpoint.save(path, det, offsets={0: 5}, dispatch_lock=None)
        # Flip bytes mid-file without breaking the structure (the
        # corruption a torn-write check can't see): the frame's
        # per-column CRC32C / trailer is what catches it — the role
        # the retired sha256 sidecar digest used to play.
        f = tmp_path / ("snap" + checkpoint.SUFFIX)
        blob = bytearray(f.read_bytes())
        mid = len(blob) // 2
        for i in range(mid, mid + 8):
            blob[i] ^= 0xFF
        f.write_bytes(bytes(blob))
        det2, meta2, corrupt = checkpoint.load_resilient(
            path, DetectorConfig(**SMALL)
        )
        assert det2 is None and meta2 is None and corrupt is True
        assert (tmp_path / ("snap" + checkpoint.SUFFIX + ".corrupt")).exists()

    def test_config_mismatch_still_refuses(self, tmp_path):
        from opentelemetry_demo_tpu.models import AnomalyDetector

        det = AnomalyDetector(DetectorConfig(**SMALL))
        path = str(tmp_path / "snap")
        checkpoint.save(path, det, dispatch_lock=None)
        with pytest.raises(ValueError):
            checkpoint.load_resilient(path, DetectorConfig(num_services=16))

    def test_elastic_meta_carries_clock(self, tmp_path):
        """Cross-topology resume keeps window-clock continuity: the
        meta returned by load_onto_mesh-style readers carries
        clock_t_prev (ADVICE r5 satellite; the mesh variant is covered
        in test_parallel.py's elastic-restore test)."""
        from opentelemetry_demo_tpu.models import AnomalyDetector

        det = AnomalyDetector(DetectorConfig(**SMALL))
        det.clock._t_prev = 41.75
        path = str(tmp_path / "snap")
        checkpoint.save(path, det, dispatch_lock=None)
        det2, meta = checkpoint.load(path, DetectorConfig(**SMALL))
        assert meta["clock_t_prev"] == 41.75
        assert det2.clock._t_prev == 41.75


# --- OTLP ingest hardening -------------------------------------------


class TestOtlpIngestFaults:
    @pytest.fixture
    def daemon(self, monkeypatch, tmp_path):
        _daemon_env(monkeypatch, tmp_path, ANOMALY_OTLP_MAX_BODY="4096")
        d = DetectorDaemon(DetectorConfig(**SMALL))
        d.start()
        yield d
        d.shutdown()

    def _raw(self, port: int, data: bytes, recv: bool = True) -> bytes:
        s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
        try:
            s.sendall(data)
            s.shutdown(socket.SHUT_WR)
            if not recv:
                return b""
            out = b""
            s.settimeout(5.0)
            while True:
                chunk = s.recv(4096)
                if not chunk:
                    return out
                out += chunk
        finally:
            s.close()

    def _post(self, port: int, body: bytes) -> int:
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.request("POST", "/v1/traces", body=body,
                     headers={"Content-Type": "application/x-protobuf"})
        resp = conn.getresponse()
        resp.read()
        return resp.status

    def test_truncated_body_answers_400_and_server_lives(self, daemon):
        port = daemon.receiver.port
        resp = self._raw(
            port,
            b"POST /v1/traces HTTP/1.1\r\nHost: t\r\n"
            b"Content-Type: application/x-protobuf\r\n"
            b"Content-Length: 512\r\n\r\n" + b"\x0a\x08partial",
        )
        assert b"400" in resp.split(b"\r\n", 1)[0]
        assert daemon.receiver.rejects.get("truncated") == 1
        # The NEXT export proceeds normally: the fault was contained.
        assert self._post(port, b"") == 200
        daemon.step(0.0)
        assert (
            'anomaly_ingest_rejected_total{reason="truncated",'
            'transport="http"} 1.0'
        ) in _scrape(daemon)

    def test_oversized_body_answers_413_without_reading(self, daemon):
        port = daemon.receiver.port
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.request("POST", "/v1/traces", body=b"x" * 8192,
                     headers={"Content-Type": "application/x-protobuf"})
        assert conn.getresponse().status == 413
        assert daemon.receiver.rejects.get("oversized") == 1
        assert self._post(port, b"") == 200

    def test_malformed_body_answers_400_with_counter(self, daemon):
        port = daemon.receiver.port
        assert self._post(port, b"\xff\xff\xff\xff garbage") == 400
        assert daemon.receiver.rejects.get("malformed") == 1
        assert self._post(port, b"") == 200

    def test_abrupt_disconnect_mid_body_survives(self, daemon):
        """Client promises a body then RSTs: the handler thread is
        released and the server keeps serving."""
        port = daemon.receiver.port
        s = socket.create_connection(("127.0.0.1", port), timeout=5.0)
        s.sendall(
            b"POST /v1/traces HTTP/1.1\r\nHost: t\r\n"
            b"Content-Length: 100000\r\n\r\n" + b"y" * 10
        )
        import struct

        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()  # RST
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if daemon.receiver.rejects:
                break
            time.sleep(0.05)
        # Either counted as disconnect or as truncated read — both are
        # contained faults; the live server is the real assertion.
        assert self._post(port, b"") == 200


# --- Kafka orders chaos ----------------------------------------------


class TestOrdersChaos:
    def test_poison_record_quarantined_pump_not_stalled(
        self, monkeypatch, tmp_path
    ):
        broker = KafkaBroker()
        broker.start()
        try:
            broker.ensure_topic("orders")
            broker.append("orders", _order_payload(0))
            broker.append("orders", b"\xff\xff\xff\xff")  # poison pill
            broker.append("orders", _order_payload(1))
            _daemon_env(monkeypatch, tmp_path, broker_port=broker.port)
            daemon = DetectorDaemon(DetectorConfig(**SMALL))
            daemon.start()
            try:
                _pump_until(
                    daemon, lambda: daemon._offsets.get(0, 0) >= 3
                )
                daemon.pipeline.drain()
                # Both good records crossed; the pill was quarantined
                # with its coordinates and payload head kept for triage.
                assert daemon.pipeline.stats.spans == 2
                assert daemon._orders.decode_failures == 1
                part, off, etype, head = daemon._orders.quarantine[0]
                assert (part, off) == (0, 1)
                assert head == b"\xff\xff\xff\xff"
                daemon.step(10.0)  # flush quarantine metrics
                text = _scrape(daemon)
                assert (
                    'anomaly_quarantined_records_total{source="orders"} 1.0'
                ) in text
                assert "anomaly_quarantine_last_error_ts_seconds" in text
            finally:
                daemon.shutdown()
        finally:
            broker.stop()

    def test_broker_kill_restart_offset_continuity(
        self, monkeypatch, tmp_path
    ):
        """Broker dies mid-run and comes back WITH its log (the durable
        restart the compose broker performs): the consumer reconnects
        with backoff, resumes at its position — every order counted
        exactly once, none lost, none replayed."""
        broker = KafkaBroker()
        broker.start()
        port = broker.port
        broker.ensure_topic("orders")
        for i in range(5):
            broker.append("orders", _order_payload(i))
        _daemon_env(monkeypatch, tmp_path, broker_port=port)
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        daemon.start()
        try:
            _pump_until(daemon, lambda: daemon._offsets.get(0, 0) >= 5)
            broker.stop()  # kill mid-run: consumer holds a dead socket
            for k in range(10):  # polls against the dead broker
                daemon.step(100.0 + k)  # must not raise
            # Durable restart: same port, same logs, same group offsets.
            broker2 = KafkaBroker(port=port)
            broker2._topics = broker._topics
            broker2._group_offsets = dict(broker._group_offsets)
            broker2.start()
            try:
                for i in range(5, 8):
                    broker2.append("orders", _order_payload(i))
                _pump_until(
                    daemon, lambda: daemon._offsets.get(0, 0) >= 8,
                    timeout_s=30.0, poll_s=0.1,
                )
                daemon.pipeline.drain()
                # Exactly-once accounting across the bounce: 8 orders
                # in, 8 spans counted — at-least-once delivery with
                # seek-past-checkpoint dedup means no double count.
                assert daemon.pipeline.stats.spans == 8
                assert daemon._orders.decode_failures == 0
            finally:
                broker2.stop()
        finally:
            daemon.shutdown()

    def test_faultwire_truncation_and_rst_survived(
        self, monkeypatch, tmp_path
    ):
        """The wire itself misbehaves: mid-frame truncation + RST on
        every connection for a while. The consumer drops + reconnects
        (bounded backoff) and delivery resumes once the wire heals."""
        broker = KafkaBroker()
        broker.start()
        proxy = FaultWire("127.0.0.1", broker.port)
        proxy.start()
        try:
            broker.ensure_topic("orders")
            for i in range(3):
                broker.append("orders", _order_payload(i))
            _daemon_env(monkeypatch, tmp_path, broker_port=proxy.port)
            daemon = DetectorDaemon(DetectorConfig(**SMALL))
            daemon.start()
            try:
                _pump_until(daemon, lambda: daemon._offsets.get(0, 0) >= 3)
                # Chaos on: every new connection dies 20 bytes in,
                # mid-frame; live ones are RST both ways.
                proxy.truncate_after = 20
                proxy.kill_connections()
                # Deadline-polled condition, not a fixed sleep window
                # (the PR 11 in-suite flake): conns_killed only moves
                # when kill_connections() catches a LIVE pair, and
                # under full-suite load the consumer can be between
                # polls — holding a dead socket, no pair to kill — at
                # the single kill moment, leaving the counter at 0 no
                # matter how long a fixed window sleeps. Step the
                # daemon (driving reconnects through the truncating
                # proxy) and re-kill until a session has provably been
                # RST mid-life, bounded by a generous deadline.
                deadline = time.monotonic() + 30.0
                t = 200.0
                while (
                    proxy.conns_killed < 1
                    and time.monotonic() < deadline
                ):
                    daemon.step(t)  # must not raise
                    proxy.kill_connections()
                    t += 0.25
                    time.sleep(0.02)
                assert proxy.conns_killed >= 1
                # Wire heals: delivery resumes through the same proxy.
                proxy.clear()
                for i in range(3, 6):
                    broker.append("orders", _order_payload(i))
                _pump_until(
                    daemon, lambda: daemon._offsets.get(0, 0) >= 6,
                    timeout_s=30.0, poll_s=0.1,
                )
                daemon.pipeline.drain()
                assert daemon.pipeline.stats.spans == 6
            finally:
                daemon.shutdown()
        finally:
            proxy.stop()
            broker.stop()


# --- supervised daemon components ------------------------------------


class TestSupervisedDaemon:
    def test_component_health_on_grpc_surface(self, monkeypatch, tmp_path):
        """Per-component health rides the existing grpc.health.v1
        ingress: anomaly.component.<name> answers SERVING while UP,
        NOT_SERVING in backoff, NOT_FOUND for unknown components."""
        pytest.importorskip("grpc")
        from opentelemetry_demo_tpu.runtime.health_probe import probe

        _daemon_env(monkeypatch, tmp_path)
        monkeypatch.setenv("ANOMALY_OTLP_GRPC_PORT", "0")
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        daemon.start()
        try:
            addr = f"127.0.0.1:{daemon.grpc_receiver.port}"
            assert probe(addr)  # server-wide
            assert probe(addr, "anomaly.component.pump")
            assert not probe(addr, "anomaly.component.nope")  # NOT_FOUND
            daemon._supervisor.report_crash("pump", RuntimeError("boom"))
            assert not probe(addr, "anomaly.component.pump")
            # The server-wide status is unaffected by one component.
            assert probe(addr)
        finally:
            daemon.shutdown()

    def test_dead_http_receiver_restarted_same_port(
        self, monkeypatch, tmp_path
    ):
        _daemon_env(monkeypatch, tmp_path)
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        daemon.start()
        try:
            port = daemon.receiver.port
            daemon.receiver.stop()  # the serve thread dies
            assert not daemon.receiver.alive()
            deadline = time.monotonic() + 10.0
            t = 0.0
            while time.monotonic() < deadline:
                daemon.step(t)
                t += 0.25
                if daemon.receiver.alive():
                    break
                time.sleep(0.05)
            assert daemon.receiver.alive(), "receiver not revived"
            # Same resolved port: the collector's exporter keeps working.
            assert daemon.receiver.port == port
            conn = http.client.HTTPConnection("127.0.0.1", port)
            conn.request("POST", "/v1/traces", body=b"",
                         headers={"Content-Type": "application/x-protobuf"})
            assert conn.getresponse().status == 200
        finally:
            daemon.shutdown()


# --- detection quality across recovery --------------------------------


def test_ttd_unchanged_after_checkpoint_recovery(tmp_path):
    """The acceptance bar: post-recovery TTD equals the uninterrupted
    run's. A crash + restore mid-warmup (snapshot → corrupt-free
    reload, the recovery path the chaos cases exercise) must leave the
    detector's math bit-identical — measured on the paymentFailure
    shape from qualbench."""
    from opentelemetry_demo_tpu.models import AnomalyDetector
    from opentelemetry_demo_tpu.runtime.tensorize import SpanTensorizer

    WARM, WINDOW, RESTART_AT = 100, 40, 50
    config = DetectorConfig(**SMALL)

    def run(with_restart: bool):
        rng = np.random.default_rng(11)
        frng = np.random.default_rng(7)
        det = AnomalyDetector(config)
        tz = SpanTensorizer(
            num_services=qualbench.S, batch_size=qualbench.B
        )
        mutate = qualbench.error_burst(frng, 5, 1.0)
        for step in range(WARM):
            det.observe(qualbench._batch(rng, tz), step * qualbench.DT_S)
            if with_restart and step == RESTART_AT:
                path = str(tmp_path / f"reco-{with_restart}")
                checkpoint.save(path, det, dispatch_lock=None)
                det, _meta = checkpoint.load(path, config)
        for k in range(WINDOW):
            report = det.observe(
                qualbench._batch(rng, tz, mutate=mutate, step=k),
                (WARM + k) * qualbench.DT_S,
            )
            if bool(np.asarray(report.flags)[5]):
                return k + 1
        return None

    baseline = run(with_restart=False)
    recovered = run(with_restart=True)
    assert baseline is not None, "fault must be detectable at all"
    assert recovered == baseline, (
        f"recovery changed detection quality: TTD {recovered} != {baseline}"
    )
