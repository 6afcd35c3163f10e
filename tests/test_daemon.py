"""Daemon lifecycle: env-config boot, ingest→metrics, flags, resume.

Drives the deployable sidecar (runtime.daemon) the way the compose
overlay does — OTLP over HTTP in, Prometheus text out, flagd file
gating, checkpoint on shutdown and resume on reboot.
"""

import http.client
import json
import logging
import os
import threading

import numpy as np
import pytest

from opentelemetry_demo_tpu.models import DetectorConfig
from opentelemetry_demo_tpu.runtime import wire
from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
from opentelemetry_demo_tpu.runtime.pipeline import DetectorPipeline
from opentelemetry_demo_tpu.telemetry import metrics as tele_metrics


def _payload(service, n, rng, lat_ns=10**6):
    def kv(k, v):
        return wire.encode_len(1, k.encode()) + wire.encode_len(
            2, wire.encode_len(1, v.encode())
        )

    spans = b""
    for _ in range(n):
        start = 10**18
        spans += wire.encode_len(
            2,
            wire.encode_len(1, bytes(rng.integers(0, 256, 16, dtype=np.uint8)))
            + wire.encode_fixed64(7, start)
            + wire.encode_fixed64(8, start + lat_ns),
        )
    rs = wire.encode_len(
        1, wire.encode_len(1, kv("service.name", service))
    ) + wire.encode_len(2, spans)
    return wire.encode_len(1, rs)


@pytest.fixture
def env(tmp_path, monkeypatch):
    flags = {
        "flags": {
            "anomalyDetectorEnabled": {
                "state": "ENABLED",
                "variants": {"on": True, "off": False},
                "defaultVariant": "on",
            }
        }
    }
    flag_path = tmp_path / "flags.json"
    flag_path.write_text(json.dumps(flags))
    monkeypatch.setenv("ANOMALY_OTLP_PORT", "0")
    monkeypatch.setenv("ANOMALY_OTLP_GRPC_PORT", "0")
    monkeypatch.setenv("ANOMALY_METRICS_PORT", "0")
    monkeypatch.setenv("ANOMALY_BATCH", "256")
    monkeypatch.setenv("FLAGD_FILE", str(flag_path))
    monkeypatch.setenv("ANOMALY_CHECKPOINT", str(tmp_path / "ckpt"))
    monkeypatch.delenv("KAFKA_ADDR", raising=False)
    return flag_path


def _post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port)
    conn.request(
        "POST",
        "/v1/traces",
        body=body,
        headers={"Content-Type": "application/x-protobuf"},
    )
    resp = conn.getresponse()
    resp.read()
    return resp.status


def _scrape(port):
    conn = http.client.HTTPConnection("127.0.0.1", port)
    conn.request("GET", "/metrics")
    return conn.getresponse().read().decode()


def test_daemon_end_to_end(env):
    config = DetectorConfig(num_services=8, hll_p=8, cms_width=512)
    daemon = DetectorDaemon(config)
    daemon.start()
    rng = np.random.default_rng(0)
    try:
        for step in range(30):
            assert _post(daemon.receiver.port, _payload("payment", 50, rng)) == 200
            daemon.step(step * 0.05)
        daemon.pipeline.drain()
        daemon._on_report  # report callback ran via drain
        text = _scrape(daemon.exporter.port)
        assert tele_metrics.ANOMALY_Z_SCORE in text
        assert 'service="payment"' in text
        assert tele_metrics.ANOMALY_SPANS_TOTAL in text

        # Disable via the flag file: pending work drains and drops.
        env.write_text(
            json.dumps(
                {
                    "flags": {
                        "anomalyDetectorEnabled": {
                            "state": "ENABLED",
                            "variants": {"on": True, "off": False},
                            "defaultVariant": "off",
                        }
                    }
                }
            )
        )
        os.utime(env)  # ensure mtime moves even on coarse clocks
        before = daemon.pipeline.stats.spans
        _post(daemon.receiver.port, _payload("payment", 50, rng))
        daemon.step(2.0)
        assert daemon.pipeline.stats.spans == before
        assert daemon.pipeline.stats.dropped_disabled >= 50
    finally:
        daemon.shutdown()

    # Reboot: state and intern table come back from the checkpoint.
    daemon2 = DetectorDaemon(config)
    try:
        assert "payment" in daemon2.pipeline.tensorizer.service_names
        assert int(daemon2.detector.state.step_idx) > 0
    finally:
        daemon2.exporter.stop()
        daemon2.receiver.stop()


def test_ladder_width_failure_is_logged_and_counted(env, monkeypatch, caplog):
    """A ladder width that does not compile is logged with its error and
    counted (outcome="error") while the other widths still warm and the
    daemon stays up; build_info names the device that serves."""
    refused = 512

    def warm_width(self, width):
        if width == refused:
            raise RuntimeError("refused by the compiler")

    monkeypatch.setattr(DetectorPipeline, "warm_width", warm_width)
    config = DetectorConfig(num_services=8, hll_p=8, cms_width=512)
    with caplog.at_level(logging.ERROR):
        daemon = DetectorDaemon(config)
        try:
            for t in threading.enumerate():
                if t.name == "width-ladder-warmup":
                    t.join(timeout=30)
            text = daemon.registry.render()
        finally:
            daemon.exporter.stop()
            daemon.receiver.stop()
    widths = [256, 512, 1024, 2048]  # ANOMALY_BATCH=256, growth 8×
    for w in widths:
        outcome = "error" if w == refused else "ok"
        assert (
            f'{tele_metrics.ANOMALY_WIDTH_WARMUPS}{{outcome="{outcome}",'
            f'width="{w}"}} 1.0' in text
        ), text
    assert f"adaptive width {refused} failed to compile" in caplog.text
    assert "refused by the compiler" in caplog.text
    assert 'platform="cpu"' in text and 'device_kind="cpu"' in text


def test_daemon_metrics_leg_flags_surge(env):
    """The /v1/metrics ingestion leg end to end: a counter-rate surge
    (the kafkaQueueProblems/flood failure shape on the metric stream)
    raises a metric-driven flag, visible on the Prometheus surface."""
    from opentelemetry_demo_tpu.runtime.otlp_metrics import (
        encode_metrics_request,
    )

    daemon = DetectorDaemon(DetectorConfig(num_services=8, hll_p=8, cms_width=512))
    daemon.start()
    rng = np.random.default_rng(5)
    try:
        def post_counter(total, t):
            body = encode_metrics_request(
                [("kafka", [("queue_depth_total", total, True)])],
                t_ns=int(t * 1e9),
            )
            conn = http.client.HTTPConnection("127.0.0.1", daemon.receiver.port)
            conn.request(
                "POST",
                "/v1/metrics",
                body=body,
                headers={"Content-Type": "application/x-protobuf"},
            )
            resp = conn.getresponse()
            resp.read()
            return resp.status

        total = 0.0
        t = 0.0
        for i in range(60):
            t += 5.0
            rate = 30.0 * (1.0 + 0.05 * rng.standard_normal())
            if i >= 50:
                rate = 300.0  # the queue-problems surge
            total += rate * 5.0
            assert post_counter(total, t) == 200
            daemon.step(t)
        text = _scrape(daemon.exporter.port)
        assert tele_metrics.ANOMALY_METRIC_Z in text
        assert 'metric="queue_depth_total"' in text
        assert tele_metrics.ANOMALY_METRIC_FLAG_TOTAL in text
        assert 'app_anomaly_metric_flags_total{service="kafka"}' in text
    finally:
        daemon.shutdown()

    # Reboot: the metrics head's warm state and intern tables come back
    # (a restart must not forget which rate is "normal").
    daemon2 = DetectorDaemon(DetectorConfig(num_services=8, hll_p=8, cms_width=512))
    try:
        assert daemon2.metrics_feed.service_names == ["kafka"]
        assert daemon2.metrics_feed.metric_names == ["queue_depth_total"]
        obs = np.asarray(daemon2.metrics_feed.head.state.obs)
        assert obs[0, 0] > 30  # warm, not reset
    finally:
        daemon2.shutdown()
