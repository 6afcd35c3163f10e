"""Verdict provenance plane: every anomaly explains itself (ISSUE 18).

The acceptance bars this suite proves:

- **Deterministic ids** (``TestBundleId``): a bundle id is a pure
  function of the replicated (epoch, seq, service) coordinates — the
  property that lets primary, replica, and a replay mint the SAME id.
- **Bundle assembly** (``TestEngine``): the engine builds a complete
  JSON-able bundle from already-harvested host state (trajectory ring,
  closed head vocabulary, graceful degradation without a state
  snapshot), and ``log_doc`` encodes through the real OTLP logs
  encoder.
- **Live answers** (``TestLiveExplain``): a flagged daemon serves the
  full bundle on ``/query/explain`` — heads, trajectory, EWMA/CUSUM
  state, exemplar trace ids with Jaeger deep links — and the anomaly
  events + Grafana annotations cite the same bundle id.
- **Time travel** (``test_explain_survives_daemon_restart``): bundles
  persist through the retention ladder as meta-only frames; after a
  full daemon restart a ranged ``/query/explain`` answers the SAME
  bundle from disk.

(The replica half of the contract — bit-identical ``/query/explain``
from a read replica at matched seq — is pinned where the other parity
paths live: ``test_query.test_replica_answers_bit_identical_at_same_seq``.)
"""

from __future__ import annotations

import json
import re
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opentelemetry_demo_tpu.models import DetectorConfig
from opentelemetry_demo_tpu.models.detector import (
    AnomalyDetector,
    detector_init,
)
from opentelemetry_demo_tpu.ops.cms import cms_indices_np, cms_query_np
from opentelemetry_demo_tpu.ops.hashing import split_hi_lo_np, splitmix64_np
from opentelemetry_demo_tpu.ops.hll import hll_estimate_np
from opentelemetry_demo_tpu.runtime import history
from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
from opentelemetry_demo_tpu.runtime.pipeline import DetectorPipeline
from opentelemetry_demo_tpu.telemetry import metrics as tele_metrics
from opentelemetry_demo_tpu.runtime.provenance import (
    HEAD_CUSUM,
    HEAD_EWMA_Z,
    HEAD_FOR_REASON,
    REASON_CUSUM,
    REASON_LATENCY,
    ProvenanceEngine,
    bundle_id,
    log_doc,
)

from .test_query import NAMES, SMALL, _env, _feed, _get, _intern, _post

pytestmark = pytest.mark.provenance


# --- deterministic ids ------------------------------------------------


class TestBundleId:
    def test_pure_function_of_replicated_coordinates(self):
        assert bundle_id(1, 42, 3) == bundle_id(1, 42, 3)
        assert re.fullmatch(r"[0-9a-f]{16}", bundle_id(1, 42, 3))

    def test_nearby_triples_do_not_collide(self):
        ids = {
            bundle_id(e, s, v)
            for e in range(3)
            for s in range(16)
            for v in range(8)
        }
        assert len(ids) == 3 * 16 * 8


# --- engine unit ------------------------------------------------------


def _fake_report(k: float, n: int = 8):
    return SimpleNamespace(
        lat_z=np.full(n, k, np.float32),
        cusum=np.zeros((n, 3), np.float32),
    )


class TestEngine:
    def test_build_without_state_degrades_not_refuses(self):
        """A failed flag-time snapshot costs the state block only:
        trajectory, heads, exemplars and the id still land."""
        eng = ProvenanceEngine(
            DetectorConfig(**SMALL), topk=5, trajectory_windows=4,
            epoch_fn=lambda: 7,
        )
        for k in range(6):
            eng.observe_report(float(k), _fake_report(0.5 + k))
        b = eng.build(
            t_batch=5.0, seq=9, service=3, label="currency",
            signals=[REASON_LATENCY, REASON_CUSUM],
            exemplars=["aa" * 8], state=None, hh_candidates=[],
            trace_id=None,
        )
        assert b["id"] == bundle_id(7, 9, 3)
        assert b["service"] == "currency" and b["service_id"] == 3
        assert b["heads"] == sorted({HEAD_EWMA_Z, HEAD_CUSUM})
        # Ring bounded at trajectory_windows, oldest first, the
        # per-service slice of what observe_report rang.
        assert len(b["trajectory"]) == 4
        assert b["trajectory"][-1]["lat_z"] == [pytest.approx(5.5)]
        assert "ewma" not in b and "top_keys" not in b
        json.dumps(b)  # the bundle contract: plain JSON-able

    def test_head_mapping_is_total_over_reasons(self):
        eng = ProvenanceEngine(DetectorConfig(**SMALL))
        b = eng.build(
            t_batch=0.0, seq=0, service=0, label="frontend",
            signals=list(HEAD_FOR_REASON), exemplars=[], state=None,
            hh_candidates=[], trace_id=None,
        )
        assert b["heads"] == sorted(set(HEAD_FOR_REASON.values()))
        # An unknown reason maps to NO head rather than a guessed one.
        b2 = eng.build(
            t_batch=0.0, seq=1, service=0, label="frontend",
            signals=["not-a-reason"], exemplars=[], state=None,
            hh_candidates=[], trace_id=None,
        )  # staticcheck: ok[provenance-vocabulary] deliberately-unknown reason exercising the closed-mapping fallback
        assert b2["heads"] == []

    def test_log_doc_encodes_through_the_real_otlp_encoder(self):
        from opentelemetry_demo_tpu.runtime.otlp_export import (
            encode_logs_request,
        )

        eng = ProvenanceEngine(DetectorConfig(**SMALL))
        b = eng.build(
            t_batch=3.0, seq=2, service=1, label="cart",
            signals=[REASON_LATENCY], exemplars=["ab" * 8],
            state=None, hh_candidates=[], trace_id="cd" * 8,
        )
        doc = log_doc(b)
        assert doc.attrs["anomaly.bundle_id"] == b["id"]
        assert doc.trace_id == bytes.fromhex("cd" * 8)
        assert b["id"] in doc.body and "cart" in doc.body
        blob = encode_logs_request([doc])
        assert blob and b["id"].encode() in blob

    def test_build_latency_samples_drain_once(self):
        eng = ProvenanceEngine(DetectorConfig(**SMALL))
        eng.build(
            t_batch=0.0, seq=0, service=0, label="a", signals=[],
            exemplars=[], state=None, hh_candidates=[], trace_id=None,
        )
        samples = eng.take_build_samples()
        assert len(samples) == 1 and samples[0] >= 0.0
        assert eng.take_build_samples() == []


# --- per-key evidence gather -----------------------------------------

CAP = 16


def evidence_bytes(config: DetectorConfig, k: int) -> int:
    """Bytes one key's evidence gather moves: 4-byte words for its five
    EWMA rows (one per tau), card_mean and span_total (one per window),
    3 CUSUM cells, the step, W# x R HLL registers and W# x D x K CMS
    cells."""
    t, nw = config.num_taus, config.num_windows
    words = (
        5 * t + 2 * nw + 3 + 1
        + nw * (1 << config.hll_p) + nw * config.cms_depth * k
    )
    return 4 * words


def _random_state(config: DetectorConfig, seed: int):
    """A detector state with every leaf filled from ``seed``: HLL rows
    from near-empty (linear counting) to dense, CMS counts, EWMA and
    CUSUM rows, span totals and a step index."""
    rng = np.random.default_rng(seed)
    st = detector_init(config)
    hll = rng.integers(0, 12, st.hll_bank.shape)
    keep = rng.uniform(size=st.hll_bank.shape[:-1])[..., None]
    hll = np.where(rng.uniform(size=hll.shape) < keep, hll, 0)

    def f32(leaf):
        return jnp.asarray(
            rng.normal(size=leaf.shape).astype(np.float32) * 10.0
        )

    return st._replace(
        hll_bank=jnp.asarray(hll.astype(np.int32)),
        cms_bank=jnp.asarray(
            rng.integers(0, 5000, st.cms_bank.shape).astype(np.int32)
        ),
        span_total=jnp.asarray(
            rng.uniform(0.0, 1e5, st.span_total.shape).astype(np.float32)
        ),
        lat_mean=f32(st.lat_mean), lat_var=f32(st.lat_var),
        err_mean=f32(st.err_mean), rate_mean=f32(st.rate_mean),
        rate_var=f32(st.rate_var), card_mean=f32(st.card_mean),
        card_var=f32(st.card_var), cusum=f32(st.cusum),
        step_idx=jnp.int32(int(rng.integers(1, 10_000))),
    )


def _reference_state_block(config, full, svc: int, cands, topk: int):
    """The bundle's state block from a full host copy of the state:
    ``hll_estimate_np`` over the whole current bank, ``cms_query_np``
    over the whole current CMS slot."""
    def row(leaf):
        return [float(x) for x in np.asarray(leaf)[svc]]

    est = hll_estimate_np(np.asarray(full.hll_bank)[:, 0])  # [W#, S]
    base = np.asarray(full.card_mean)[svc]
    nw = config.num_windows
    cus = np.asarray(full.cusum)[svc]
    block = {
        "ewma": {
            "latency": {"mean": row(full.lat_mean), "var": row(full.lat_var)},
            "error_rate": {"mean": row(full.err_mean)},
            "throughput": {
                "mean": row(full.rate_mean), "var": row(full.rate_var),
            },
        },
        "cusum": {
            "latency_up": float(cus[0]), "error_up": float(cus[1]),
            "rate_down": float(cus[2]),
            "thresholds": [float(x) for x in config.cusum_thresholds],
        },
        "cardinality": {
            "estimate": [float(est[w, svc]) for w in range(nw)],
            "baseline_mean": [float(base[w]) for w in range(nw)],
            "delta": [
                float(est[w, svc]) - float(base[w]) for w in range(nw)
            ],
        },
        "top_keys": [],
    }
    if cands:
        cur = np.asarray(full.cms_bank)[:, 0]  # [W#, D, C]
        crc = np.asarray(cands, dtype=np.uint64)
        hi, lo = split_hi_lo_np(
            splitmix64_np(crc | (np.uint64(svc) << np.uint64(32)))
        )
        counts = cms_query_np(
            cur, cms_indices_np(hi, lo, config.cms_depth, config.cms_width)
        )
        sel = counts[-1]
        order = sorted(
            range(len(cands)), key=lambda i: (-int(sel[i]), int(crc[i]))
        )[:topk]
        denom = max(float(np.asarray(full.span_total)[-1, 0]), 1.0)
        block["top_keys"] = [
            {
                "attr_crc": f"0x{int(crc[i]):08x}",
                "count": int(sel[i]),
                "counts": [int(c) for c in counts[:, i]],
                "share": float(np.float32(int(sel[i]) / denom)),
            }
            for i in order
        ]
    return block


@pytest.mark.parametrize("n_cands", [0, 1, CAP])
@pytest.mark.parametrize("n_keys", [1, 3])
def test_gathered_evidence_builds_the_full_copy_bundle(n_keys, n_cands):
    """Bundles built from the per-key device gather equal, field for
    field and bit for bit, bundles built from a full host copy of the
    same state; the fetch moves the per-key size, not the banks."""
    config = DetectorConfig(**SMALL)
    det = AnomalyDetector(config)
    det.state = _random_state(config, seed=100 * n_keys + n_cands)
    full = jax.device_get(det.state)
    engine = ProvenanceEngine(config, topk=5)
    pipe = DetectorPipeline(
        det, batch_size=128, provenance=engine, hh_candidates=CAP
    )
    rng = np.random.default_rng(n_keys + n_cands)
    keys = [int(k) for k in rng.choice(config.num_services, n_keys, False)]
    cands = {
        k: [int(c) for c in rng.choice(2**32, n_cands, replace=False)]
        for k in keys
    }
    evidence = pipe._provenance_evidence(cands)
    assert evidence is not None and set(evidence) == set(keys)
    assert pipe.provenance_fetch_bytes == n_keys * evidence_bytes(config, CAP)
    assert pipe.provenance_fetch_bytes < n_keys * full.hll_bank.nbytes
    for k in keys:
        assert int(evidence[k]["step_idx"]) == int(full.step_idx)
        b = engine.build(
            t_batch=1.0, seq=int(full.step_idx), service=k, label=f"s{k}",
            signals=[REASON_LATENCY], exemplars=[], state=evidence[k],
            hh_candidates=cands[k], trace_id=None,
        )
        want = _reference_state_block(config, full, k, cands[k], topk=5)
        assert {name: b[name] for name in want} == want
        assert len(b["top_keys"]) == min(n_cands, 5)
        json.dumps(b)


def test_gather_reaches_the_device_under_its_own_name():
    """The detector step is a bare partial and reaches the device as
    ``jit__unknown``; a trace that reads the step by that name must not
    count the gather, so the gather's program carries its own name."""
    config = DetectorConfig(**SMALL)
    det = AnomalyDetector(config)
    lowered = det._evidence.lower(
        det.state, np.int32(0), np.zeros((config.cms_depth, CAP), np.int32)
    )
    module = lowered.as_text().split("\n", 1)[0]
    assert module.startswith("module @jit_evidence_gather "), module


# --- live daemon ------------------------------------------------------


def _flagged_daemon():
    """A primary fed past a latency explosion on service 3."""
    with _env():
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
    daemon.start()
    _intern(daemon)
    rng = np.random.default_rng(11)
    _feed(daemon, rng, steps=60, anomaly_from=35)
    daemon.query_engine.refresh()
    return daemon


class TestLiveExplain:
    def test_flagged_daemon_serves_complete_bundles(self):
        daemon = _flagged_daemon()
        try:
            port = daemon.query_service.port
            status, doc = _get(port, "/query/explain?limit=50")
            assert status == 200
            bundles = doc["data"]["bundles"]
            assert bundles and doc["data"]["built"] >= len(bundles)
            b = next(
                (x for x in bundles if x["service"] == NAMES[3]), None
            )
            assert b is not None, "flagged service has no bundle"
            assert re.fullmatch(r"[0-9a-f]{16}", b["id"])
            assert b["signals"] and set(b["heads"]) <= set(
                HEAD_FOR_REASON.values()
            )
            # Flag-time evidence gather landed: EWMA baselines,
            # CUSUM accumulators vs thresholds, cardinality-vs-baseline.
            assert b["ewma"]["latency"]["mean"]
            assert len(b["cusum"]["thresholds"]) == 3
            assert b["cardinality"]["estimate"]
            # Trajectory over recent harvested windows, detector
            # coordinates, and the Jaeger deep links derived from the
            # bundle's own exemplar trace ids.
            assert b["trajectory"]
            assert b["seq"] >= 0 and b["epoch"] >= 0
            assert b["windows_s"] and b["z_threshold"] > 0
            for tid, url in zip(b["exemplars"], b["trace_urls"]):
                assert url == f"/jaeger/trace/{tid}"
            # Filters: by service, and by id.
            _s, by_svc = _get(
                port, f"/query/explain?service={NAMES[3]}&limit=50"
            )
            assert {x["service"] for x in by_svc["data"]["bundles"]} == {
                NAMES[3]
            }
            _s, by_id = _get(port, f"/query/explain?id={b['id']}")
            assert [x["id"] for x in by_id["data"]["bundles"]] == [b["id"]]
            # Anomaly events cite the bundle ids they were built with.
            _s, anom = _get(port, "/query/anomalies?limit=50")
            cited = {
                ev["bundle"]
                for ev in anom["data"]["events"]
                if ev.get("bundle")
            }
            assert b["id"] in cited
            # Grafana annotations carry the citation + deep links.
            _s, anns = _post(port, "/annotations", {
                "annotation": {"name": "anomalies", "query": "anomalies"},
            })
            assert any("bundle:" in a["text"] for a in anns)
            assert any("/jaeger/trace/" in a["text"] for a in anns)
            # The build metrics export beside the bundles.
            text = daemon.registry.render()
            assert "anomaly_explanations_built_total" in text
            assert "anomaly_explain_latency_seconds_bucket" in text
            assert "anomaly_build_info{" in text
            # healthz carries the process birth timestamp.
            _state, detail = daemon._healthz()
            assert 0 < detail["start_ts"] <= time.time()
        finally:
            daemon.shutdown()

    def test_disabled_provenance_still_flags(self):
        """Bundles are explanation, not detection: with the plane off,
        anomaly events land (bundle: None) and /query/explain answers
        an empty ring, not an error."""
        with _env(ANOMALY_PROVENANCE_ENABLE="0"):
            daemon = DetectorDaemon(DetectorConfig(**SMALL))
        daemon.start()
        try:
            assert daemon.provenance is None
            _intern(daemon)
            rng = np.random.default_rng(11)
            _feed(daemon, rng, steps=60, anomaly_from=35)
            daemon.query_engine.refresh()
            port = daemon.query_service.port
            _s, anom = _get(port, "/query/anomalies?limit=50")
            assert anom["data"]["events"]
            assert all(
                ev["bundle"] is None for ev in anom["data"]["events"]
            )
            status, doc = _get(port, "/query/explain")
            assert status == 200 and doc["data"]["bundles"] == []
        finally:
            daemon.shutdown()


def test_first_flag_compiles_nothing_and_fetches_per_key_bytes():
    """With the boot warm-up done, the report that first flags compiles
    nothing, and the provenance fetch counter rises by one key's
    evidence per bundle, far below one HLL bank."""
    with _env():
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
    for t in threading.enumerate():
        if t.name == "width-ladder-warmup":
            t.join(timeout=60)
    daemon.start()
    try:
        _intern(daemon)
        rng = np.random.default_rng(11)
        t = _feed(daemon, rng, steps=35)
        pipe = daemon.pipeline
        assert pipe.stats.flag_events == 0
        for _ in range(40):
            compiles = daemon.compiles.count
            built = pipe.explanations_built
            fetched = pipe.provenance_fetch_bytes
            t = _feed(daemon, rng, steps=1, t0=t, anomaly_from=0)
            if pipe.stats.flag_events:
                break
        assert pipe.stats.flag_events, "the fault never flagged"
        assert daemon.compiles.count == compiles
        bundles = pipe.explanations_built - built
        per_key = evidence_bytes(daemon.detector.config, pipe._evidence_k)
        assert bundles >= 1
        assert pipe.provenance_fetch_bytes - fetched == bundles * per_key
        assert per_key < daemon.detector.state.hll_bank.nbytes
        daemon._export_provenance_stats()
        counters, _ = daemon.registry.snapshot()
        assert counters[
            (tele_metrics.ANOMALY_PROVENANCE_FETCH_BYTES, ())
        ] == float(pipe.provenance_fetch_bytes)
    finally:
        daemon.shutdown()


# --- restart survival through the retention ladder --------------------


def test_explain_survives_daemon_restart(tmp_path):
    """Record a flagged run with the history tier on, restart the
    daemon on the same volume, and answer a ranged /query/explain with
    the SAME bundle — id included — from disk."""
    hist_env = dict(
        ANOMALY_HISTORY_DIR=str(tmp_path / "history"),
        ANOMALY_HISTORY_COMPACT_INTERVAL_S="0.05",
    )
    with _env(**hist_env):
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
    daemon.start()
    recorded: dict = {}
    try:
        _intern(daemon)
        rng = np.random.default_rng(11)
        _feed(daemon, rng, steps=60, anomaly_from=35)
        daemon.query_engine.refresh()
        port = daemon.query_service.port
        _s, doc = _get(port, "/query/explain?limit=1")
        assert doc["data"]["bundles"], "no bundle to record"
        recorded = doc["data"]["bundles"][0]
        # The writer's own thread drains the bundle queue into
        # KIND_EXPLAIN records; wait for the first to seal.
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if daemon.history_store.records(kind=history.KIND_EXPLAIN):
                break
            time.sleep(0.05)
        assert daemon.history_store.records(kind=history.KIND_EXPLAIN)
    finally:
        daemon.shutdown()

    with _env(**hist_env):
        reborn = DetectorDaemon(DetectorConfig(**SMALL))
    reborn.start()
    try:
        port = reborn.query_service.port
        status, doc = _get(
            port, "/query/explain?from=0&to=100000&limit=100"
        )
        assert status == 200
        assert doc["meta"]["source"] == "history"
        by_id = {
            b["id"]: b for b in doc["data"]["bundles"]
        }
        assert recorded["id"] in by_id
        # The disk answer is the recorded bundle, field for field
        # (trace_urls are derived per answer from the same exemplars).
        assert json.dumps(
            by_id[recorded["id"]], sort_keys=True
        ) == json.dumps(recorded, sort_keys=True)
        # A range that predates the incident answers empty, from disk.
        _s, empty = _get(port, "/query/explain?from=-200&to=-100")
        assert empty["data"]["bundles"] == []
    finally:
        reborn.shutdown()
