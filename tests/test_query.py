"""Live query plane: HTTP/gRPC reads over live sketch state.

The acceptance bars this suite proves (ISSUE 7):

- **Live answers** (``TestLiveDaemon``): a real daemon answers top-k,
  cardinality(+timeline), z-score state and anomalies-with-exemplars
  over HTTP, with role/epoch/seq/staleness on every response and the
  ``anomaly_query_*`` self-observability on /metrics.
- **Grafana datasource** (``test_grafana_datasource_contract``): the
  simple-JSON contract — GET /, /search, /query (timeseries + table),
  /annotations — against the same live daemon.
- **Read-replica consistency**
  (``test_replica_answers_bit_identical_at_same_seq``): a standby in
  read-replica mode answers BIT-IDENTICALLY to a direct primary read
  at the same replicated sequence — one snapshot contract, one numpy
  read path (ops.*_np helpers), no fork.
- **Queries fail over with the role**
  (``test_read_replica_survives_primary_sigkill``): the replica keeps
  answering through a SIGKILL of the primary and across its own
  promotion, on the same port.
- **Exemplars** (``test_exemplars_round_trip_to_ingested_traces``):
  anomaly exemplar trace ids round-trip to the exact ids ingested.
- **No donation race** (``test_queries_never_race_dispatch_donation``):
  concurrent query refreshes against live dispatch never observe a
  deleted donated buffer (the dispatch-lock snapshot discipline).
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from opentelemetry_demo_tpu.models import AnomalyDetector, DetectorConfig
from opentelemetry_demo_tpu.ops import cms, hll
from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
from opentelemetry_demo_tpu.runtime.ingestbench import make_columns
from opentelemetry_demo_tpu.runtime.pipeline import DetectorPipeline
from opentelemetry_demo_tpu.runtime.query import (
    QueryEngine,
    QueryError,
    dispatch,
)
from opentelemetry_demo_tpu.runtime.querybench import _snapshot_fn
from opentelemetry_demo_tpu.utils.config import ConfigError, query_config

pytestmark = pytest.mark.query

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(num_services=8, hll_p=8, cms_width=512)
NAMES = ("frontend", "cart", "checkout", "currency", "payment", "email")


# --- plumbing ---------------------------------------------------------


@contextmanager
def _env(**overrides):
    """Set/clear env vars for a daemon constructor, restore after."""
    saved: dict[str, str | None] = {}
    base = {
        "ANOMALY_OTLP_PORT": "0",
        "ANOMALY_OTLP_GRPC_PORT": "-1",
        "ANOMALY_METRICS_PORT": "0",
        "ANOMALY_BATCH": "128",
        "ANOMALY_ADAPTIVE_BATCH": "0",
        "ANOMALY_QUERY_PORT": "0",
        "ANOMALY_QUERY_GRPC_PORT": "-1",
        "ANOMALY_QUERY_MAX_STALENESS_S": "0.2",
    }
    clear = (
        "ANOMALY_CHECKPOINT", "KAFKA_ADDR", "ANOMALY_ROLE",
        "ANOMALY_REPLICATION_PORT", "ANOMALY_REPLICATION_TARGET",
        "ANOMALY_REPLICATION_INTERVAL_S", "ANOMALY_FAILOVER_TIMEOUT_S",
        "ANOMALY_PRIMARY_HEALTH_ADDR", "ANOMALY_QUERY_READ_REPLICA",
        "ANOMALY_QUERY_EXEMPLARS", "ANOMALY_QUERY_TIMELINE",
        "ANOMALY_QUERY_TOPK",
    )
    merged = dict(base)
    merged.update(overrides)
    for key in set(merged) | set(clear):
        saved[key] = os.environ.get(key)
        os.environ.pop(key, None)
    for key, val in merged.items():
        if val is not None:
            os.environ[key] = val
    try:
        yield
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


def _get(port: int, path: str) -> tuple[int, object]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10.0)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


def _post(port: int, path: str, body: dict) -> tuple[int, object]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10.0)
    try:
        conn.request(
            "POST", path, body=json.dumps(body),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


def _feed(daemon, rng, steps: int, t0: float = 0.0, anomaly_from=None):
    """Steady columnar load; from ``anomaly_from`` on, service 3's
    latency explodes 1000x (flags via the latency/CUSUM heads)."""
    t = t0
    for i in range(steps):
        cols = make_columns(rng, 128)
        cols = cols._replace(svc=(cols.svc % len(NAMES)).astype(np.int32))
        if anomaly_from is not None and i >= anomaly_from:
            cols.lat_us[cols.svc == 3] *= 1000.0
        daemon.pipeline.submit_columns(cols)
        daemon.step(t)
        t += 0.25
    return t


def _intern(daemon) -> None:
    for name in NAMES:
        daemon.pipeline.tensorizer.service_id(name)


# --- numpy read helpers match the device ops --------------------------


class TestReadHelpers:
    def test_cms_query_np_matches_device(self):
        rng = np.random.default_rng(0)
        table = rng.integers(0, 1000, size=(3, 4, 512)).astype(np.int32)
        hi = rng.integers(0, 2**32, size=64, dtype=np.uint64).astype(np.uint32)
        lo = rng.integers(0, 2**32, size=64, dtype=np.uint64).astype(np.uint32)
        import jax.numpy as jnp

        idx_np = cms.cms_indices_np(hi, lo, 4, 512)
        idx_dev = np.asarray(cms.cms_indices(
            jnp.asarray(hi), jnp.asarray(lo), 4, 512
        ))
        assert (idx_np == idx_dev).all()
        out_np = cms.cms_query_np(table, idx_np)
        out_dev = np.asarray(cms.cms_query(jnp.asarray(table), jnp.asarray(idx_np)))
        assert (out_np == out_dev).all()

    def test_hll_estimate_np_matches_device(self):
        rng = np.random.default_rng(1)
        regs = rng.integers(0, 20, size=(3, 8, 256)).astype(np.int32)
        regs[0, 0] = 0  # linear-counting branch too
        np_est = hll.hll_estimate_np(regs)
        dev_est = np.asarray(hll.hll_estimate(regs))
        assert np.allclose(np_est, dev_est, rtol=1e-5)


# --- knob validation --------------------------------------------------


class TestQueryConfig:
    def test_defaults_resolve(self):
        with _env():
            cfg = query_config()
        assert cfg["ANOMALY_QUERY_TOPK"] == 10
        assert cfg["ANOMALY_QUERY_READ_REPLICA"] == 1

    @pytest.mark.parametrize("knob,bad", [
        ("ANOMALY_QUERY_TOPK", "0"),
        ("ANOMALY_QUERY_TIMELINE", "0"),
        ("ANOMALY_QUERY_MAX_STALENESS_S", "0"),
    ])
    def test_bad_shapes_refuse_boot(self, knob, bad):
        with _env(**{knob: bad}):
            with pytest.raises(ConfigError):
                query_config()


# --- engine unit ------------------------------------------------------


class TestEngine:
    def test_no_state_yet_is_503(self):
        engine = QueryEngine(snapshot_fn=lambda: ({}, {}))
        status, doc = dispatch(engine, "/query/services", {})
        assert status == 503 and "error" in doc

    def test_unknown_service_and_endpoint(self):
        det = AnomalyDetector(DetectorConfig(**SMALL))
        pipe = DetectorPipeline(det, batch_size=64)
        pipe.tensorizer.service_id("frontend")
        engine = QueryEngine(snapshot_fn=_snapshot_fn(det, pipe))
        status, _doc = dispatch(
            engine, "/query/topk", {"service": "nope"}
        )
        assert status == 404
        status, _doc = dispatch(engine, "/nope", {})
        assert status == 404
        status, _doc = dispatch(engine, "/query/topk", {})
        assert status == 400

    def test_topk_counts_match_direct_cms_reads(self):
        """Oracle: the top-k counts equal direct cms_query_np point
        reads for the same folded keys — the query is the sketch
        estimate, nothing resampled."""
        from opentelemetry_demo_tpu.ops.hashing import (
            split_hi_lo_np,
            splitmix64_np,
        )

        config = DetectorConfig(**SMALL)
        det = AnomalyDetector(config)
        pipe = DetectorPipeline(det, batch_size=128)
        for n in NAMES:
            pipe.tensorizer.service_id(n)
        rng = np.random.default_rng(2)
        t = 0.0
        for _ in range(20):
            cols = make_columns(rng, 128)
            cols = cols._replace(
                svc=(cols.svc % len(NAMES)).astype(np.int32)
            )
            pipe.submit_columns(cols)
            pipe.pump(t)
            t += 0.25
        pipe.drain()
        engine = QueryEngine(snapshot_fn=_snapshot_fn(det, pipe))
        status, doc = dispatch(
            engine, "/query/topk", {"service": "cart", "k": "5"}
        )
        assert status == 200
        data = doc["data"]
        assert data["top"], "candidates must have been captured"
        svc_id = 1  # cart
        arrays, _meta = _snapshot_fn(det, pipe)()
        cur = arrays["cms_bank"][:, 0]
        for row in data["top"]:
            crc = np.asarray([int(row["attr_crc"], 16)], np.uint64)
            key = crc | (np.uint64(svc_id) << np.uint64(32))
            hi, lo = split_hi_lo_np(splitmix64_np(key))
            idx = cms.cms_indices_np(
                hi, lo, cur.shape[-2], cur.shape[-1]
            )
            direct = cms.cms_query_np(cur, idx)  # [W#, 1]
            assert row["counts"] == [int(c) for c in direct[:, 0]]
        counts = [row["count"] for row in data["top"]]
        assert counts == sorted(counts, reverse=True)

    def test_timeline_accretes_per_sequence(self):
        det = AnomalyDetector(DetectorConfig(**SMALL))
        pipe = DetectorPipeline(det, batch_size=64)
        pipe.tensorizer.service_id("frontend")
        engine = QueryEngine(
            snapshot_fn=_snapshot_fn(det, pipe), timeline_depth=4
        )
        rng = np.random.default_rng(3)
        t = 0.0
        for _ in range(7):
            cols = make_columns(rng, 64)
            cols = cols._replace(svc=np.zeros(64, np.int32))
            pipe.submit_columns(cols)
            pipe.pump(t)
            pipe.drain()
            t += 1.0
            engine.refresh()
        engine.refresh()  # same seq: must NOT append a duplicate
        status, doc = dispatch(
            engine, "/query/cardinality", {"service": "frontend"}
        )
        assert status == 200
        timeline = doc["data"]["timeline"]
        assert len(timeline) == 4  # ring depth bounds it
        seqs = [e["seq"] for e in timeline]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


# --- pipeline exemplar capture ----------------------------------------


def test_exemplars_round_trip_to_ingested_traces():
    """Flag an anomaly and check every exemplar is the 8-byte hex
    prefix of a trace id that was actually ingested for that service
    — the Jaeger link is real, not synthesized."""
    config = DetectorConfig(
        **SMALL, warmup_batches=2.0, z_warmup_batches=3.0
    )
    det = AnomalyDetector(config)
    pipe = DetectorPipeline(det, batch_size=64, exemplar_ring=4)
    for n in NAMES:
        pipe.tensorizer.service_id(n)
    rng = np.random.default_rng(4)
    submitted: set[str] = set()
    t = 0.0
    for i in range(30):
        cols = make_columns(rng, 64)
        cols = cols._replace(svc=(cols.svc % len(NAMES)).astype(np.int32))
        if i >= 15:
            cols.lat_us[cols.svc == 3] *= 10_000.0
        for v in cols.trace_key[cols.svc == 3]:
            submitted.add(int(v).to_bytes(8, "little").hex())
        pipe.submit_columns(cols)
        pipe.pump(t)
        pipe.drain()
        t += 0.25
    meta = pipe.query_meta()
    assert pipe.exemplars_captured > 0
    ring = meta["exemplars"].get("3")
    assert ring, "flagged service must hold exemplars"
    assert len(ring) <= 4  # bounded per-service ring
    for entry in ring:
        assert entry["trace_id"] in submitted
        assert entry["signal"]
    events = [e for e in meta["anomalies"] if e["service"] == 3]
    assert events and all(
        tid in submitted for e in events for tid in e["exemplars"]
    )
    # The whole block must survive a JSON round trip unchanged — it
    # rides the replication meta.
    assert json.loads(json.dumps(meta)) == meta


def _fake_flag_report(num_services: int = 8, windows: int = 3):
    """A report shape whose latency z exceeds any sane threshold."""
    from types import SimpleNamespace

    return SimpleNamespace(
        lat_z=np.full((num_services, windows), 9.0, np.float32),
        err_z=np.zeros((num_services, windows), np.float32),
        rate_z=np.zeros((num_services, windows), np.float32),
        card_z=np.zeros((num_services, windows), np.float32),
        cusum=np.zeros((num_services, 3), np.float32),
    )


def test_anomaly_events_recorded_with_exemplar_capture_disabled():
    """ANOMALY_QUERY_EXEMPLARS=0 is the privacy knob: it must disable
    only trace-id capture — anomaly EVENTS still record, or
    /query/anomalies and the Grafana annotations go dark."""
    det = AnomalyDetector(DetectorConfig(**SMALL))
    pipe = DetectorPipeline(det, batch_size=64, exemplar_ring=0)
    cols = make_columns(np.random.default_rng(11), 64)
    cols = cols._replace(svc=np.full(64, 3, np.int32))
    flags = np.zeros(8, bool)
    flags[3] = True
    pipe._capture_exemplars(1.0, cols, _fake_flag_report(), flags, 6.0)
    meta = pipe.query_meta()
    assert meta["exemplars"] == {}
    assert pipe.exemplars_captured == 0
    events = [e for e in meta["anomalies"] if e["service"] == 3]
    assert events, "event recording must survive exemplar_ring=0"
    assert events[0]["signals"] == ["latency"]
    assert events[0]["exemplars"] == []


def test_restore_query_meta_round_trip():
    """Promotion hydration: a fresh pipeline fed a replicated
    query_meta() block answers exemplar/anomaly/top-k queries from the
    same data — the history must survive the role flip. The capture
    counter stays local (it backs this process's Prometheus delta)."""
    det = AnomalyDetector(DetectorConfig(**SMALL))
    src = DetectorPipeline(
        det, batch_size=64, exemplar_ring=4, hh_candidates=16
    )
    cols = make_columns(np.random.default_rng(12), 64)
    cols = cols._replace(svc=(cols.svc % 6).astype(np.int32))
    src._capture_candidates(cols)
    flags = np.zeros(8, bool)
    flags[2] = True
    src._capture_exemplars(1.0, cols, _fake_flag_report(), flags, 6.0)
    block = src.query_meta()
    assert block["exemplars"] and block["anomalies"]
    assert block["hh_candidates"]

    det2 = AnomalyDetector(DetectorConfig(**SMALL))
    dst = DetectorPipeline(
        det2, batch_size=64, exemplar_ring=4, hh_candidates=16
    )
    dst.restore_query_meta(json.loads(json.dumps(block)))
    restored = dst.query_meta()
    assert restored["exemplars"] == block["exemplars"]
    assert restored["anomalies"] == block["anomalies"]
    assert restored["hh_candidates"] == block["hh_candidates"]
    assert dst.exemplars_captured == 0
    dst.restore_query_meta({})  # empty block is a no-op, not a crash


# --- live daemon over HTTP (the curl surface) -------------------------


@pytest.fixture(scope="module")
def live_daemon():
    with _env(ANOMALY_QUERY_GRPC_PORT="0"):
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
    daemon.start()
    _intern(daemon)
    rng = np.random.default_rng(5)
    _feed(daemon, rng, steps=90, anomaly_from=55)
    daemon.query_engine.refresh()
    yield daemon
    daemon.shutdown()


class TestLiveDaemon:
    def test_topk_cardinality_zscore_anomalies_over_http(self, live_daemon):
        port = live_daemon.query_service.port
        status, doc = _get(port, "/query/services")
        assert status == 200
        assert set(NAMES) <= set(doc["data"]["services"])
        assert doc["meta"]["role"] == "primary"
        assert doc["meta"]["seq"] > 0
        assert doc["meta"]["staleness_s"] < 5.0

        status, doc = _get(port, "/query/topk?service=frontend&k=3")
        assert status == 200
        assert len(doc["data"]["top"]) <= 3
        assert doc["data"]["top"][0]["count"] > 0

        status, doc = _get(port, "/query/cardinality?service=cart")
        assert status == 200
        assert len(doc["data"]["estimate"]) == 3
        assert max(doc["data"]["estimate"]) > 0
        assert doc["data"]["timeline"]

        status, doc = _get(port, "/query/zscore?service=currency")
        assert status == 200
        z = doc["data"]
        assert len(z["latency"]["mean"]) == 3
        assert z["cusum"]["thresholds"] == [5.0, 5.0, 8.0]

        status, doc = _get(port, "/query/anomalies")
        assert status == 200
        events = doc["data"]["events"]
        assert events, "latency x1000 must have flagged"
        assert any(e["service"] == "currency" for e in events)
        flagged = next(e for e in events if e["exemplars"])
        assert re.fullmatch(r"[0-9a-f]{16}", flagged["exemplars"][0])

    def test_error_statuses(self, live_daemon):
        port = live_daemon.query_service.port
        assert _get(port, "/query/topk")[0] == 400
        assert _get(port, "/query/topk?service=ghost")[0] == 404
        assert _get(port, "/nope")[0] == 404

    def test_oversized_post_refused_unread(self, live_daemon):
        """An attacker-sized Content-Length gets a 413 WITHOUT the
        server reading (and buffering) the body — the OTLP receiver's
        discipline, reused on the query port."""
        conn = http.client.HTTPConnection(
            "127.0.0.1", live_daemon.query_service.port, timeout=5.0
        )
        try:
            conn.putrequest("POST", "/query")
            conn.putheader("Content-Length", str(64 << 20))
            conn.endheaders()
            assert conn.getresponse().status == 413
        finally:
            conn.close()

    def test_malformed_content_length_closes_keepalive(self, live_daemon):
        """A Content-Length the server cannot parse leaves the body's
        extent unknowable — the 400 must CLOSE the keep-alive stream
        (else the unread body bytes desync every later request on the
        connection)."""
        conn = http.client.HTTPConnection(
            "127.0.0.1", live_daemon.query_service.port, timeout=5.0
        )
        try:
            conn.putrequest("POST", "/query")
            conn.putheader("Content-Length", "12abc")
            conn.endheaders()
            resp = conn.getresponse()
            assert resp.status == 400
            assert b"Content-Length" in resp.read()
            assert resp.headers.get("Connection") == "close"
        finally:
            conn.close()

    def test_negative_content_length_rejected_unread(self, live_daemon):
        """Content-Length: -1 must 400 without calling read(-1) —
        read-until-EOF on a held-open connection would pin the handler
        thread forever."""
        conn = http.client.HTTPConnection(
            "127.0.0.1", live_daemon.query_service.port, timeout=5.0
        )
        try:
            conn.putrequest("POST", "/query")
            conn.putheader("Content-Length", "-1")
            conn.endheaders()
            resp = conn.getresponse()
            assert resp.status == 400
            assert resp.headers.get("Connection") == "close"
        finally:
            conn.close()

    def test_negative_svc_fallback_is_404_not_wraparound(self, live_daemon):
        """svc--1 must 404: a negative parsed id would wrap-index into
        the LAST service's state and answer with the wrong data."""
        port = live_daemon.query_service.port
        assert _get(port, "/query/zscore?service=svc--1")[0] == 404
        assert _get(port, "/query/topk?service=svc--1")[0] == 404
        assert _get(port, "/query/cardinality?service=svc--1")[0] == 404

    def test_error_responses_carry_cors_header(self, live_daemon):
        """Grafana is a cross-origin browser client: without the CORS
        header on ERROR responses too, the browser blocks the JSON
        error document and the UI shows an opaque network failure."""
        conn = http.client.HTTPConnection(
            "127.0.0.1", live_daemon.query_service.port, timeout=5.0
        )
        try:
            conn.putrequest("POST", "/query")
            conn.putheader("Content-Length", str(64 << 20))
            conn.endheaders()
            resp = conn.getresponse()
            assert resp.status == 413
            assert resp.headers.get("Access-Control-Allow-Origin") == "*"
        finally:
            conn.close()

    def test_grpc_twin_answers_same_documents(self, live_daemon):
        pytest.importorskip("grpc")
        from opentelemetry_demo_tpu.runtime.query import grpc_query

        target = f"127.0.0.1:{live_daemon.query_grpc.port}"
        doc = grpc_query(target, "/query/cardinality", {"service": "cart"})
        _status, http_doc = _get(
            live_daemon.query_service.port, "/query/cardinality?service=cart"
        )
        assert doc["data"]["estimate"] == http_doc["data"]["estimate"]

    def test_self_observability_on_metrics(self, live_daemon):
        live_daemon.step(999.0)  # export pass
        conn = http.client.HTTPConnection(
            "127.0.0.1", live_daemon.exporter.port, timeout=5.0
        )
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        assert 'anomaly_query_requests_total{code="200"' in text
        assert "anomaly_query_latency_seconds_bucket" in text
        assert "anomaly_query_staleness_seconds" in text
        assert "anomaly_exemplars_captured_total" in text
        captured = re.search(
            r"anomaly_exemplars_captured_total (\d+\.\d+)", text
        )
        assert captured and float(captured.group(1)) > 0

    def test_grafana_datasource_contract(self, live_daemon):
        port = live_daemon.query_service.port
        # Test connection.
        status, doc = _get(port, "/")
        assert status == 200 and doc["status"] == "ok"
        # /search: the target vocabulary.
        status, targets = _post(port, "/search", {})
        assert status == 200
        assert "anomalies" in targets
        assert "cardinality:frontend" in targets
        assert "topk:frontend" in targets
        # /query: timeseries shape.
        status, out = _post(port, "/query", {
            "range": {
                "from": "2020-01-01T00:00:00Z",
                "to": "2099-01-01T00:00:00Z",
            },
            "targets": [{"target": "cardinality:frontend"}],
        })
        assert status == 200
        assert out[0]["target"] == "cardinality:frontend"
        assert out[0]["datapoints"], "timeline must have points"
        value, ts_ms = out[0]["datapoints"][0]
        assert value >= 0 and ts_ms > 1e12  # epoch millis
        # /query: table shape.
        status, out = _post(port, "/query", {
            "targets": [{"target": "anomalies", "type": "table"}],
        })
        assert status == 200
        assert out[0]["type"] == "table"
        cols = [c["text"] for c in out[0]["columns"]]
        assert cols == ["time", "service", "signals", "exemplar"]
        assert out[0]["rows"]
        # /annotations.
        status, anns = _post(port, "/annotations", {
            "annotation": {"name": "anomalies", "query": "anomalies"},
        })
        assert status == 200 and anns
        assert {"annotation", "time", "title", "text", "tags"} <= set(anns[0])
        assert any("trace:" in a["text"] for a in anns)
        # Unknown target is a clean 400, not a 500.
        status, _ = _post(
            port, "/query", {"targets": [{"target": "bogus:x"}]}
        )
        assert status == 400


# --- read replica: bit-consistency + failover -------------------------


def _quiesce_converged(primary, standby, timeout=30.0) -> None:
    """Step both daemons until the standby's mirror equals the
    primary's live state (same step_idx, same sketch banks)."""
    deadline = time.monotonic() + timeout
    t = 1000.0
    while time.monotonic() < deadline:
        primary.step(t)
        standby.step(t)
        t += 0.25
        arrays, _meta = standby.repl_standby.snapshot()
        if arrays:
            live, _ = primary._replication_snapshot()
            if (
                int(arrays["step_idx"]) == int(live["step_idx"])
                and (arrays["cms_bank"] == live["cms_bank"]).all()
                and (arrays["hll_bank"] == live["hll_bank"]).all()
                and np.array_equal(arrays["lat_mean"], live["lat_mean"])
            ):
                return
        time.sleep(0.05)
    raise AssertionError("standby never converged to the primary state")


def test_replica_answers_bit_identical_at_same_seq():
    """THE consistency bar: at the same replicated sequence, every
    point query answered by the read replica is byte-identical to a
    direct primary read — same snapshot contract, same numpy path.
    (The cardinality timeline is per-process sampling and explicitly
    outside the contract; everything else must match exactly.)"""
    with _env(ANOMALY_REPLICATION_PORT="0",
              ANOMALY_REPLICATION_INTERVAL_S="0.1"):
        primary = DetectorDaemon(DetectorConfig(**SMALL))
    primary.start()
    standby = None
    try:
        _intern(primary)
        rng = np.random.default_rng(6)
        _feed(primary, rng, steps=60, anomaly_from=35)
        with _env(
            ANOMALY_ROLE="standby",
            ANOMALY_REPLICATION_TARGET=(
                f"127.0.0.1:{primary.repl_primary.port}"
            ),
            ANOMALY_FAILOVER_TIMEOUT_S="3600",
            ANOMALY_QUERY_READ_REPLICA="1",
        ):
            standby = DetectorDaemon(DetectorConfig(**SMALL))
        standby.start()
        assert standby.repl_standby.wait_for_state(20.0)
        # A little more load (including flags) AFTER attach, then
        # quiesce so the final delta ships.
        _feed(primary, rng, steps=10, t0=500.0, anomaly_from=0)
        _quiesce_converged(primary, standby)
        primary.query_engine.refresh()
        standby.query_engine.refresh()
        p_port = primary.query_service.port
        s_port = standby.query_service.port
        for path in (
            "/query/services",
            "/query/topk?service=currency&k=8",
            "/query/topk?service=frontend&k=8",
            "/query/cardinality?service=cart",
            "/query/zscore?service=currency",
            "/query/anomalies?limit=50",
            # Evidence bundles ride the replicated query_meta block
            # verbatim — the replica's explanation IS the primary's.
            "/query/explain?limit=50",
        ):
            ps, pdoc = _get(p_port, path)
            ss, sdoc = _get(s_port, path)
            assert (ps, ss) == (200, 200), path
            assert pdoc["meta"]["seq"] == sdoc["meta"]["seq"], path
            assert pdoc["meta"]["role"] == "primary"
            assert sdoc["meta"]["role"] == "standby"
            pdoc["data"].pop("timeline", None)
            sdoc["data"].pop("timeline", None)
            assert (
                json.dumps(pdoc["data"], sort_keys=True)
                == json.dumps(sdoc["data"], sort_keys=True)
            ), f"replica answer diverged on {path}"
            if path.startswith("/query/explain"):
                # The pin must compare real evidence, not two empty
                # rings agreeing about nothing.
                assert pdoc["data"]["bundles"], "no bundles built"
        # The replica's staleness reports the replication-lag bound.
        _s, sdoc = _get(s_port, "/query/services")
        assert sdoc["meta"]["staleness_s"] >= 0.0
    finally:
        if standby is not None:
            standby.shutdown()
        primary.shutdown()


def test_read_replica_survives_primary_sigkill(tmp_path):
    """Queries fail over WITH the role: the read replica answers while
    the primary lives, keeps answering through its SIGKILL, and still
    answers (as the new primary) after promotion — same port."""
    from opentelemetry_demo_tpu.runtime.otlp_export import (
        encode_export_request,
    )
    from opentelemetry_demo_tpu.runtime.tensorize import SpanRecord

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update({
        "ANOMALY_OTLP_PORT": "0",
        "ANOMALY_OTLP_GRPC_PORT": "-1",
        "ANOMALY_METRICS_PORT": "0",
        "ANOMALY_BATCH": "128",
        "ANOMALY_PUMP_INTERVAL_S": "0.05",
        "ANOMALY_ADAPTIVE_BATCH": "0",
        "ANOMALY_NUM_SERVICES": "8",
        "ANOMALY_CMS_WIDTH": "512",
        "ANOMALY_HLL_P": "8",
        "ANOMALY_INGEST_WORKERS": "0",
        "ANOMALY_ROLE": "primary",
        "ANOMALY_REPLICATION_PORT": "0",
        "ANOMALY_REPLICATION_INTERVAL_S": "0.1",
        "ANOMALY_QUERY_PORT": "0",
        "ANOMALY_QUERY_GRPC_PORT": "-1",
        "ANOMALY_CHECKPOINT": str(tmp_path / "primary"),
    })
    proc = subprocess.Popen(
        [sys.executable, "-m", "opentelemetry_demo_tpu.runtime.daemon"],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    standby = None
    try:
        line = None
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            out = proc.stdout.readline()
            if not out:
                if proc.poll() is not None:
                    raise RuntimeError(f"primary exited rc={proc.returncode}")
                time.sleep(0.05)
                continue
            if "anomaly-detector:" in out:
                line = out
                break
        assert line, "primary never announced"
        otlp_port = int(re.search(r"otlp-http :(\d+)", line).group(1))
        repl_port = int(re.search(r"repl :(\d+)", line).group(1))
        assert int(re.search(r"query :(\d+)", line).group(1)) > 0

        # Load at the primary so replicated state is non-trivial.
        body = encode_export_request([
            SpanRecord(
                service="payment", duration_us=900.0,
                trace_id=os.urandom(8), is_error=False, attr="p",
            )
            for _ in range(64)
        ])
        conn = http.client.HTTPConnection(
            "127.0.0.1", otlp_port, timeout=10.0
        )
        conn.request(
            "POST", "/v1/traces", body=body,
            headers={"Content-Type": "application/x-protobuf"},
        )
        assert conn.getresponse().status == 200
        conn.close()

        with _env(
            ANOMALY_ROLE="standby",
            ANOMALY_REPLICATION_TARGET=f"127.0.0.1:{repl_port}",
            # Generous watchdog: under full-suite CPU contention the
            # primary's first jit compile can stall its ship loop for
            # seconds, and a premature promotion would break the
            # "replica answers AS A STANDBY first" half of this drill.
            ANOMALY_FAILOVER_TIMEOUT_S="8.0",
            ANOMALY_QUERY_READ_REPLICA="1",
            ANOMALY_CHECKPOINT=str(tmp_path / "standby"),
        ):
            standby = DetectorDaemon(DetectorConfig(**SMALL))
        standby.start()
        q_port = standby.query_service.port
        deadline = time.monotonic() + 60.0
        doc = None
        while time.monotonic() < deadline:
            standby.step(0.0)
            status, doc = _get(q_port, "/query/services")
            if (
                status == 200
                and "payment" in doc["data"]["services"]
                and doc["meta"]["seq"] > 0  # first batch replicated
            ):
                break
            time.sleep(0.1)
        assert doc and doc["meta"]["role"] == "standby"
        seq_before = doc["meta"]["seq"]
        assert seq_before > 0

        # SIGKILL the primary; the replica must keep answering
        # throughout the watchdog window, from the replicated mirror.
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        for _ in range(5):
            standby.step(1.0)
            status, doc = _get(
                q_port, "/query/cardinality?service=payment"
            )
            assert status == 200
            assert doc["meta"]["seq"] >= seq_before
            time.sleep(0.1)

        # ...and across the promotion, on the SAME port.
        deadline = time.monotonic() + 30.0
        t = 2.0
        while time.monotonic() < deadline and standby.role != "primary":
            standby.step(t)
            t += 0.25
            time.sleep(0.02)
        assert standby.role == "primary"
        status, doc = _get(q_port, "/query/cardinality?service=payment")
        assert status == 200
        assert doc["meta"]["role"] == "primary"
        assert doc["meta"]["epoch"] >= 1
        assert doc["meta"]["seq"] >= seq_before
    finally:
        if standby is not None:
            standby.shutdown()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=15)


# --- concurrency: queries vs dispatch donation ------------------------


def test_queries_never_race_dispatch_donation():
    """Hammer snapshot refreshes + point queries from several threads
    while the pipeline dispatches (donating the state buffers) on the
    main thread. The dispatch-lock snapshot makes this safe; without
    it, np.asarray on a just-donated array raises 'Array has been
    deleted'. refresh_errors is the canary and must stay 0."""
    det = AnomalyDetector(DetectorConfig(**SMALL))
    pipe = DetectorPipeline(det, batch_size=256)
    for n in NAMES:
        pipe.tensorizer.service_id(n)
    engine = QueryEngine(
        snapshot_fn=_snapshot_fn(det, pipe), max_staleness_s=0.0
    )
    rng = np.random.default_rng(7)
    stop = threading.Event()
    failures: list[str] = []

    def reader(idx: int) -> None:
        while not stop.is_set():
            try:
                assert engine.refresh()
                status, _doc = dispatch(
                    engine, "/query/cardinality",
                    {"service": NAMES[idx % len(NAMES)]},
                )
                assert status == 200
                dispatch(engine, "/query/topk", {"service": "frontend"})
            except Exception as e:  # noqa: BLE001 — collected, asserted
                failures.append(repr(e))
                return

    threads = [
        threading.Thread(target=reader, args=(i,), daemon=True)
        for i in range(4)
    ]
    for th in threads:
        th.start()
    t = 0.0
    try:
        for _ in range(150):
            cols = make_columns(rng, 256)
            cols = cols._replace(
                svc=(cols.svc % len(NAMES)).astype(np.int32)
            )
            pipe.submit_columns(cols)
            pipe.pump(t)
            t += 0.05
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
        pipe.drain()
    assert not failures, failures
    assert engine.refresh_errors == 0


# --- misc -------------------------------------------------------------


def test_query_error_is_not_a_crash():
    e = QueryError(404, "nope")
    assert e.status == 404 and str(e) == "nope"


def test_dispatch_maps_internal_errors_to_500():
    """A handler bug answers a counted 500 on BOTH transports — the
    gRPC leg has no blanket except of its own, so an escape here would
    surface as a raw UNKNOWN with a traceback while HTTP said 500."""

    class Boom:
        def services(self):
            raise KeyError("cms_bank")

    status, doc = dispatch(Boom(), "/query/services", {})
    assert status == 500
    assert doc == {"error": "internal query error"}


def test_endpoint_label_bounds_metric_cardinality():
    """Arbitrary client paths must never mint new Prometheus series —
    anything outside the endpoint vocabulary collapses to 'other'."""
    from opentelemetry_demo_tpu.runtime.query import endpoint_label

    assert endpoint_label("/query/topk") == "/query/topk"
    assert endpoint_label("/") == "/"
    for probe in ("/admin", "/query/topk/../x", "/%2e%2e", "/etc/passwd"):
        assert endpoint_label(probe) == "other"


def test_candidate_ring_keeps_recent_not_largest():
    """The top-k candidate ring is recency-ordered: a small-valued CRC
    arriving late must displace an earlier one, and the numerically
    largest CRCs must hold no privileged slot (np.unique sorts by
    value; slicing that kept high CRCs forever)."""
    from opentelemetry_demo_tpu.runtime.tensorize import SpanColumns

    det = AnomalyDetector(DetectorConfig(**SMALL))
    pipe = DetectorPipeline(det, batch_size=64, hh_candidates=4)
    pipe.tensorizer.service_id("frontend")

    def batch(crcs):
        n = len(crcs)
        return SpanColumns(
            svc=np.zeros(n, np.int32),
            lat_us=np.ones(n, np.float32),
            is_error=np.zeros(n, np.float32),
            trace_key=np.arange(n, dtype=np.uint64),
            attr_crc=np.asarray(crcs, np.uint64),
        )

    t = 0.0
    # Old, numerically-huge CRCs first; then fresh SMALL ones.
    for crcs in ([900, 901, 902, 903], [1, 2], [3, 4]):
        pipe.submit_columns(batch(crcs))
        pipe.pump(t)
        pipe.drain()
        t += 0.25
    cands = pipe.query_meta()["hh_candidates"]["0"]
    assert set(cands) == {1, 2, 3, 4}, cands  # recency, not magnitude
    assert cands[0] in (3, 4)  # most-recent-first
