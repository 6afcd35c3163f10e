"""Metrics: counters/histograms + Prometheus text exposition.

The detector surfaces its results the way the reference's services
surface theirs — app-level OTel metrics scraped into Prometheus and
graphed in Grafana (SURVEY.md §5 "Metrics"; custom metric examples:
``app.frontend.requests`` InstrumentationMiddleware.ts:10,
``app.payment.transactions`` charge.js:15). The ``app.anomaly.*`` family
exported here drives deploy/grafana-anomaly-dashboard.json.
"""

from __future__ import annotations

import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Iterable


def _fmt_le(le: float) -> str:
    """Prometheus-style bucket bound rendering (ints without .0)."""
    return str(int(le)) if float(le).is_integer() else repr(float(le))


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class MetricRegistry:
    """Thread-safe counters and gauges with Prometheus text output."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[tuple[str, tuple], float] = {}
        self._gauges: dict[tuple[str, tuple], float] = {}
        self._help: dict[str, str] = {}
        # (name, labels, buckets) -> a histogram's series keys, bounded
        # by the label sets the counters themselves hold.
        self._histo_keys: dict[tuple, tuple] = {}

    def describe(self, name: str, help_text: str) -> None:
        self._help[name] = help_text

    def counter_add(self, name: str, value: float = 1.0, **labels: str) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def gauge_set(self, name: str, value: float, **labels: str) -> None:
        key = (name, tuple(sorted(labels.items())))
        with self._lock:
            self._gauges[key] = value

    def histogram_observe(
        self,
        name: str,
        value: float,
        buckets: tuple[float, ...],
        **labels: str,
    ) -> None:
        """Explicit-bucket histogram as Prometheus counter series.

        Emits cumulative ``{name}_bucket{le=...}`` (including ``+Inf``),
        ``{name}_sum`` and ``{name}_count`` — the representation the
        reference's cart latency histograms with bucket advice take on
        the Prometheus side (ValkeyCartStore.cs:30-43) and the shape the
        spanmetrics connector's duration histograms export.
        """
        # This runs per span in the spanmetrics hot path and per phase
        # on the detector's served path: the series keys of one
        # (name, labels, buckets) are built once and reused.
        base_key = tuple(sorted(labels.items()))
        if not isinstance(buckets, tuple):
            buckets = tuple(buckets)
        keys = self._histo_keys.get((name, base_key, buckets))
        if keys is None:
            keys = self._histo_series(name, base_key, buckets)
        bucket_keys, inf_key, sum_key, count_key = keys
        counters = self._counters
        with self._lock:
            for le, key in zip(buckets, bucket_keys):
                if value <= le:
                    counters[key] = counters.get(key, 0.0) + 1.0
            counters[inf_key] = counters.get(inf_key, 0.0) + 1.0
            counters[sum_key] = counters.get(sum_key, 0.0) + value
            counters[count_key] = counters.get(count_key, 0.0) + 1.0

    def _histo_series(self, name: str, base_key: tuple, buckets: tuple) -> tuple:
        """The ``_bucket`` keys (one per bound), the ``+Inf`` bucket,
        ``_sum`` and ``_count`` keys of one histogram series."""
        base = list(base_key)
        i = 0
        while i < len(base) and base[i][0] < "le":
            i += 1

        def with_le(le_str: str) -> tuple:
            return tuple(base[:i] + [("le", le_str)] + base[i:])

        keys = (
            [(name + "_bucket", with_le(_fmt_le(le))) for le in buckets],
            (name + "_bucket", with_le("+Inf")),
            (name + "_sum", base_key),
            (name + "_count", base_key),
        )
        self._histo_keys[(name, base_key, buckets)] = keys
        return keys

    def snapshot(self) -> tuple[dict, dict]:
        """Point-in-time copy of (counters, gauges) — the scrape surface
        the TSDB's virtual-clock scraper reads (telemetry.tsdb.Scraper),
        the in-proc analogue of Prometheus GETting ``/metrics``."""
        with self._lock:
            return dict(self._counters), dict(self._gauges)

    def render(self) -> str:
        lines: list[str] = []
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
        seen: set[str] = set()

        def emit(store: dict, kind: str) -> Iterable[str]:
            for (name, labels), value in sorted(store.items()):
                if name not in seen:
                    seen.add(name)
                    if name in self._help:
                        yield f"# HELP {name} {self._help[name]}"
                    yield f"# TYPE {name} {kind}"
                yield f"{name}{_fmt_labels(dict(labels))} {value}"

        lines.extend(emit(counters, "counter"))
        lines.extend(emit(gauges, "gauge"))
        return "\n".join(lines) + "\n"


class PrometheusExporter:
    """Serves a registry at ``/metrics`` (the scrape surface).

    With ``health`` (a callable returning ``(status, detail)``), also
    serves ``GET /healthz``: JSON ``{"status": ..., **detail}``, HTTP
    200 for ``ok``/``saturated`` (a deliberately-shedding daemon is
    ALIVE — k8s must not restart its way out of overload) and 503 for
    ``degraded`` (a crash-looping component is a real readiness fail).
    """

    def __init__(self, registry: MetricRegistry, host: str = "0.0.0.0",
                 port: int = 9464, health=None):
        reg = registry

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                if self.path == "/healthz" and health is not None:
                    import json as _json

                    try:
                        status, detail = health()
                    except Exception:  # noqa: BLE001 — health must answer
                        status, detail = "degraded", {"error": "health probe raised"}
                    body = _json.dumps(
                        {"status": status, **detail}
                    ).encode()
                    self.send_response(503 if status == "degraded" else 200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if self.path != "/metrics":
                    self.send_response(404)
                    self.end_headers()
                    return
                body = reg.render().encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="prom-exporter", daemon=True
        )

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        # BaseServer.shutdown() blocks on an event only serve_forever sets;
        # calling it on a never-started server would wait forever.
        if self._thread.is_alive():
            self._server.shutdown()
        self._server.server_close()


# Metric names for the anomaly detector's export family.
ANOMALY_FLAG_TOTAL = "app_anomaly_flags_total"
ANOMALY_Z_SCORE = "app_anomaly_z_score"
ANOMALY_CARDINALITY = "app_anomaly_distinct_traces"
ANOMALY_HEAVY_HITTER = "app_anomaly_heavy_hitter_ratio"
ANOMALY_SPANS_TOTAL = "app_anomaly_spans_processed_total"
# The exact verdict counter: rows whose batch's report was harvested
# (the processed counter advances at dispatch, up to two batches ahead).
ANOMALY_SPANS_HARVESTED = "app_anomaly_spans_harvested_total"
# Reports dropped unfetched (their batches still folded into state):
# the in-flight bound, or a harvest interval, superseded them.
ANOMALY_REPORTS_SKIPPED = "anomaly_reports_skipped_total"
ANOMALY_LAG_P99 = "app_anomaly_detection_lag_p99_ms"
ANOMALY_CUSUM = "app_anomaly_cusum"
# The metrics-ingestion leg (OTLP /v1/metrics → metrics head).
ANOMALY_METRIC_Z = "app_anomaly_metric_z_score"
ANOMALY_METRIC_FLAG_TOTAL = "app_anomaly_metric_flags_total"
ANOMALY_METRIC_POINTS_TOTAL = "app_anomaly_metric_points_processed_total"
ANOMALY_LOG_RECORDS_TOTAL = "app_anomaly_log_records_processed_total"
# Self-telemetry gauges the daemon exports on a 1 s cadence (ingest/
# batch/backlog visibility before the first detector report — the
# otelcol_* habit). Declared here, not inline at the export site: the
# staticcheck metric-surface pass fences anomaly-family names to this
# table so a typo'd inline literal can never mint an undocumented
# series.
ANOMALY_PENDING_ROWS = "app_anomaly_pending_rows"
ANOMALY_BATCHES_DISPATCHED = "app_anomaly_batches_dispatched"
ANOMALY_SPANS_INGESTED = "app_anomaly_spans_ingested"
ANOMALY_LOG_DOCS_STORED = "app_anomaly_log_docs_stored"
# The fault-tolerant runtime's own health family (runtime.supervision):
# the sidecar's job is to stay up while everything around it misbehaves,
# so its component restarts/degradation are first-class metrics.
ANOMALY_COMPONENT_RESTARTS = "anomaly_component_restarts_total"
ANOMALY_COMPONENT_UP = "anomaly_component_up"
ANOMALY_DEGRADED = "anomaly_degraded"
ANOMALY_QUARANTINE_TOTAL = "anomaly_quarantined_records_total"
ANOMALY_QUARANTINE_LAST_ERROR_TS = "anomaly_quarantine_last_error_ts_seconds"
ANOMALY_INGEST_REJECTED = "anomaly_ingest_rejected_total"
ANOMALY_CHECKPOINT_CORRUPT = "anomaly_checkpoint_corrupt_total"
# Overload-protection family (bounded admission / brownout — the
# memory_limiter + sending_queue analogue; runtime.pipeline): the
# flow-control loop is only trustworthy if every shed/throttle/backoff
# decision leaves a number behind.
ANOMALY_SHED_ROWS = "anomaly_shed_rows_total"  # {lane=, cause=} (+ tenant= on the per-tenant quota shed)
ANOMALY_QUEUE_ROWS = "anomaly_queue_rows"
ANOMALY_QUEUE_WATERMARK = "anomaly_queue_watermark_rows"  # {mark=high|low}
ANOMALY_BROWNOUT_LEVEL = "anomaly_brownout_level"
ANOMALY_SATURATED = "anomaly_saturated"
ANOMALY_KAFKA_PAUSED = "anomaly_kafka_paused"
# Parallel host-ingest engine (runtime.ingest_pool): queue depth,
# flush/coalesce counters and worker utilization — how an operator
# sees whether the decode pool, the pipeline, or neither is the
# bottleneck at the current offered load.
ANOMALY_INGEST_POOL_DEPTH = "anomaly_ingest_pool_depth"
ANOMALY_INGEST_POOL_FLUSHES = "anomaly_ingest_pool_flushes_total"
ANOMALY_INGEST_POOL_SPANS = "anomaly_ingest_pool_spans_total"
ANOMALY_INGEST_POOL_REQUESTS = "anomaly_ingest_pool_requests_total"
ANOMALY_INGEST_POOL_UTILIZATION = "anomaly_ingest_pool_worker_utilization"
ANOMALY_INGEST_POOL_BUSY = "anomaly_ingest_pool_busy_seconds_total"  # summed over workers
# Device-put spine (runtime.spine: the staging ring between batch
# assembly and the donated device step): whether host→device transfer
# is actually hidden behind compute, and how deep the ring runs.
ANOMALY_SPINE_PUT_OVERLAP = "anomaly_spine_put_overlap_ratio"
ANOMALY_SPINE_RING_DEPTH = "anomaly_spine_ring_depth"
# Staged batches the pump waited for with the device idle and
# dispatched in the pass that staged them (outside the overlap ratio).
ANOMALY_SPINE_IDLE_WAITS = "anomaly_spine_idle_waits_total"
# Serve-loop passes by what ended the loop's wait: admitted rows, or
# the pump interval elapsing with none.
ANOMALY_PUMP_WAKES = "anomaly_pump_wakes_total"  # {cause=rows|interval}
# Sender-queue visibility for the OTLP exporters (otlp_export.py):
# the drop-oldest path and its backlog, per signal.
ANOMALY_EXPORT_DROPPED = "anomaly_export_dropped_total"  # {signal=}
ANOMALY_EXPORT_QUEUE_DEPTH = "anomaly_export_queue_depth"  # {signal=}
# Hot-standby replication family (runtime.replication + the daemon's
# role state machine): who is serving, at what epoch, how far behind
# the standby is, and every fenced write a resurrected stale primary
# attempted — the split-brain audit trail.
ANOMALY_ROLE = "anomaly_role"  # {role=primary|standby|promoting|fenced}
ANOMALY_EPOCH = "anomaly_epoch"
ANOMALY_REPLICATION_DELTAS = "anomaly_replication_deltas_total"  # {direction=}
ANOMALY_REPLICATION_SNAPSHOTS = "anomaly_replication_snapshots_total"  # {direction=}
ANOMALY_REPLICATION_LAG = "anomaly_replication_lag_seconds"
ANOMALY_REPLICATION_FENCED = "anomaly_replication_fenced_total"  # {path=}
ANOMALY_FAILOVERS = "anomaly_failovers_total"
# Deferred-confirmation offset list (daemon orders pump): entries shed
# when the bounded list overflows — each one is a bounded replay on
# restart, never silent loss.
ANOMALY_OFFSET_DEFER_DROPPED = "anomaly_offset_defer_dropped_total"
# Partial restores (checkpoint.restore_metrics_feed): a snapshot whose
# metrics leg could not be hydrated (geometry change) — the span leg
# restored, the metrics head cold-started.
ANOMALY_RESTORE_PARTIAL = "anomaly_restore_partial_total"
# Verified-frame family (runtime.frame — the ONE columnar wire format
# every state byte moves in): frames that failed verification at each
# hop (ingest scratch→pipeline, replication link, checkpoint file) —
# each one is corruption CAUGHT at a boundary and quarantined instead
# of merged into live sketches — plus the format version this process
# writes (a fleet mid-rolling-upgrade shows a mixed gauge).
ANOMALY_FRAME_CORRUPT = "anomaly_frame_corrupt_total"  # {hop=}
ANOMALY_FRAME_VERSION = "anomaly_frame_version"
# Live query plane (runtime.query: HTTP/gRPC reads over live sketch
# state, the Grafana JSON datasource, read-replica serving): request
# rate/latency per endpoint, the staleness bound every answer carries,
# and the exemplar trace ids captured at flag time.
ANOMALY_QUERY_REQUESTS = "anomaly_query_requests_total"  # {endpoint=, code=}
ANOMALY_QUERY_LATENCY = "anomaly_query_latency_seconds"  # histogram
ANOMALY_QUERY_STALENESS = "anomaly_query_staleness_seconds"
ANOMALY_EXEMPLARS_CAPTURED = "anomaly_exemplars_captured_total"
# Detector self-telemetry (runtime.selftrace batch-lifecycle tracer,
# runtime.flightrec flight recorder, and the phase timers PROMOTED
# from bench-only pool/spine counters into real histograms): where a
# dispatched batch's wall time goes per lifecycle phase, whether the
# device put actually hid behind compute THIS window, how far behind
# harvest runs, and the tracer/recorder's own output rates.
ANOMALY_PHASE_SECONDS = "anomaly_phase_seconds"  # histogram {phase=}
ANOMALY_SPINE_PUT_WAIT = "anomaly_spine_put_wait_seconds"  # histogram
ANOMALY_HARVEST_LAG = "anomaly_harvest_lag_seconds"  # histogram
# Windowed histogram companion to the lifetime-ratio gauge
# anomaly_spine_put_overlap_ratio: one observation per scrape window,
# so overlap quantiles come from Prometheus instead of bench-only math.
ANOMALY_SPINE_OVERLAP_WINDOW = "anomaly_spine_put_overlap_window_ratio"
# Per-answer histogram companion to the anomaly_query_staleness_seconds
# gauge (same Prometheus-owns-the-p99 promotion).
ANOMALY_QUERY_STALENESS_HIST = "anomaly_query_answer_staleness_seconds"
# Time-travel history tier (runtime.history: compaction thread folding
# expiring window banks into the on-disk retention ladder; the query
# plane's range-read backend): how much history exists, how far back it
# reaches, how often the ladder folds, and what a range read costs —
# plus corrupt records surfacing on the shared frame-corruption family
# as anomaly_frame_corrupt_total{hop="history"}.
ANOMALY_HISTORY_SEGMENTS = "anomaly_history_segments"
ANOMALY_HISTORY_BYTES = "anomaly_history_bytes"
ANOMALY_HISTORY_COMPACTIONS = "anomaly_history_compactions_total"
ANOMALY_HISTORY_OLDEST = "anomaly_history_oldest_seconds"
ANOMALY_HISTORY_READ_LATENCY = "anomaly_history_read_latency_seconds"
ANOMALY_SELFTRACE_TRACES = "anomaly_selftrace_traces_total"
ANOMALY_SELFTRACE_SPANS = "anomaly_selftrace_spans_total"
ANOMALY_FLIGHT_EVENTS = "anomaly_flight_events_total"  # {kind=}
ANOMALY_FLIGHT_DUMPS = "anomaly_flight_dumps_total"  # {reason=}
# Closed-loop auto-mitigation (runtime.remediation: the supervised
# controller driving flagd mitigation flags + the sampling policy, then
# verifying its own action recovered the system): every act / verified
# recovery / rollback / failed mitigation leaves a number behind, the
# active gauge shows what is currently mitigated, and the TTM histogram
# is the loop's headline — time-to-mitigate beside time-to-detect.
ANOMALY_MITIGATION_ACTIONS = "anomaly_mitigation_actions_total"  # {actuator=}
ANOMALY_MITIGATION_ROLLBACKS = "anomaly_mitigation_rollbacks_total"
ANOMALY_MITIGATION_VERIFIED = "anomaly_mitigation_verified_total"
ANOMALY_MITIGATION_FAILED = "anomaly_mitigation_failed_total"
ANOMALY_MITIGATION_ACTIVE = "anomaly_mitigation_active"
ANOMALY_TIME_TO_MITIGATE = "anomaly_time_to_mitigate_seconds"  # histogram
# Counterfactual pre-flight (runtime.shadow gating the controller's
# acts on a shadow replay of recorded history) + the collector-steering
# actuator: every verdict by direction, every refusal by reason (the
# fail-closed audit trail), the act→verdict wall interval, and the
# storage fraction the currently pushed tail-sampling policy implies.
ANOMALY_PREFLIGHT_VERDICTS = "anomaly_preflight_verdicts_total"  # {verdict=}
ANOMALY_PREFLIGHT_REFUSED = "anomaly_preflight_refused_total"  # {reason=}
ANOMALY_PREFLIGHT_SECONDS = "anomaly_preflight_seconds"  # histogram
ANOMALY_COLLECTOR_KEEP_RATIO = "anomaly_collector_keep_ratio"
# Sharded detector fleet (runtime.fleet membership + guardrailed
# reshard; runtime.aggregator scatter-gather reads): who is on the
# ring, how often the keyspace moved, how often a move was REFUSED by
# the reshard budget (a flapping shard exhausting its bucket freezes
# the ring — refusals are the audit trail), and each shard's own
# ingest rate (the per-shard panel beside the fleet-global view).
ANOMALY_FLEET_SHARDS_LIVE = "anomaly_fleet_shards_live"
ANOMALY_FLEET_RING_VERSION = "anomaly_fleet_ring_version"
ANOMALY_FLEET_FROZEN = "anomaly_fleet_ring_frozen"
ANOMALY_RESHARDS = "anomaly_reshards_total"
ANOMALY_RESHARDS_REFUSED = "anomaly_reshards_refused_total"
ANOMALY_FLEET_SHARD_SPANS = "anomaly_fleet_shard_ingest_spans_total"  # {shard=}
# Elastic fleet (runtime.autoscale + in-daemon frame adoption): the
# saturation-driven split/join proposer's decision trail — proposals,
# gated refusals by reason (budget / fenced / bounds / role /
# disabled), the live saturation score and last proposed size — plus
# the adoption side: automatic keyspace merges performed by a
# ring-heir when membership declared its pair dead (zero operator
# action), refusals (intern-table drift, no mirror state), and the
# measured time-to-adopt (heartbeat death declaration → merged frame
# serving), the elastic fleet's headline beside TTD and TTM.
ANOMALY_AUTOSCALE_PROPOSALS = "anomaly_autoscale_proposals_total"  # {action=}
ANOMALY_AUTOSCALE_REFUSED = "anomaly_autoscale_refused_total"  # {reason=}
ANOMALY_AUTOSCALE_TARGET = "anomaly_autoscale_target_shards"
ANOMALY_AUTOSCALE_SCORE = "anomaly_autoscale_saturation_score"
ANOMALY_FLEET_ADOPTIONS = "anomaly_fleet_adoptions_total"
ANOMALY_FLEET_ADOPTIONS_REFUSED = "anomaly_fleet_adoptions_refused_total"  # {reason=}
ANOMALY_FLEET_ADOPTION_TTA = "anomaly_fleet_adoption_seconds"

# Verdict provenance plane (runtime.provenance): evidence bundles
# built at flag time (per flagged service), bundles exported as OTLP
# log records through the background poster, and what a flag-time
# build costs at harvest — plus the fleet build-identity
# gauge (one 1-valued series per process, labeled with the package
# version, the wire frame version and the jax build) a rolling resize
# checks for mixed-build shards.
ANOMALY_EXPLANATIONS_BUILT = "anomaly_explanations_built_total"
ANOMALY_EXPLANATIONS_EXPORTED = "anomaly_explanations_exported_total"
ANOMALY_EXPLAIN_LATENCY = "anomaly_explain_latency_seconds"  # histogram
# Bytes the flag-time evidence gathers moved device → host (one key's
# rows and candidate cells per flagged key, not the banks).
ANOMALY_PROVENANCE_FETCH_BYTES = "anomaly_provenance_fetch_bytes_total"
# {version=, frame_version=, jax=, platform=, device_kind=}
ANOMALY_BUILD_INFO = "anomaly_build_info"
# Adaptive-width ladder precompile at boot, one count per width:
# outcome="error" means that width's step did not compile (the daemon
# stays up and would pay the failure again at escalation time).
ANOMALY_WIDTH_WARMUPS = "anomaly_width_warmups_total"  # {width=, outcome=}
# The device's own counts, on the 1 s cadence: backend compiles since
# boot (a compile while serving is a shape off the warmed ladder) and
# device memory where the backend reports it.
ANOMALY_COMPILES = "anomaly_compiles_total"
ANOMALY_DEVICE_MEMORY = "anomaly_device_memory_bytes"  # {kind=in_use|peak}

# Key lifecycle plane (runtime.keyspace): the budget watchdog's RSS
# sample (first-class — the soak bench's VmRSS read, promoted to a
# scrapeable gauge), the intern-table occupancy trio the fill fraction
# is computed from, the keyspace degradation-ladder level (0 normal ·
# 1 evict idle · 2 throttle new keys · 3 collapse to overflow · 4 shed
# ingest), the eviction/generation counters, and the per-tenant
# admission outcomes under ladder pressure.
ANOMALY_PROCESS_RSS = "anomaly_process_rss_bytes"
ANOMALY_KEYSPACE_ROWS = "anomaly_keyspace_rows"  # live interned keys
ANOMALY_KEYSPACE_CAPACITY = "anomaly_keyspace_capacity_rows"
ANOMALY_KEYSPACE_FILL = "anomaly_keyspace_fill_ratio"
ANOMALY_KEYSPACE_LEVEL = "anomaly_keyspace_level"
ANOMALY_KEYSPACE_GENERATION = "anomaly_keyspace_generation"
ANOMALY_KEYSPACE_EVICTED = "anomaly_keyspace_evicted_total"
ANOMALY_KEYSPACE_FREE_IDS = "anomaly_keyspace_free_ids"
ANOMALY_KEYSPACE_THROTTLED = "anomaly_keyspace_newkeys_throttled_total"  # {tenant=}
ANOMALY_KEYSPACE_OVERFLOW = "anomaly_keyspace_overflow_keys_total"  # {tenant=}


def export_metrics_report(
    registry: MetricRegistry,
    service_names: list[str],
    metric_names: list[str],
    report,
    flagged: list[str],
    seen: set | None = None,
) -> None:
    """Publish one MetricsHeadReport into the registry (host-side).

    ``seen`` (caller-owned, persisted across reports) tracks which
    (service, metric) series were ever exported: quiet cells never mint
    a series, but a series that HAS been minted keeps updating — its z
    masks to 0 when the stream stops, and freezing the last anomalous
    value on the Prometheus surface would show a permanent incident.
    """
    import numpy as np

    z = np.asarray(report.z)  # [S, M, T]
    cell = np.asarray(report.cell_flags)  # [S, M]
    for i, sname in enumerate(service_names[: z.shape[0]]):
        zi = np.abs(z[i]).max(axis=1)  # [M]
        for j, mname in enumerate(metric_names[: z.shape[1]]):
            key = (sname, mname)
            minted = seen is not None and key in seen
            if zi[j] > 0.0 or cell[i, j] or minted:
                registry.gauge_set(
                    ANOMALY_METRIC_Z, float(zi[j]), service=sname, metric=mname
                )
                if seen is not None:
                    seen.add(key)
    for name in flagged:
        registry.counter_add(ANOMALY_METRIC_FLAG_TOTAL, 1.0, service=name)


def export_report(
    registry: MetricRegistry,
    service_names: list[str],
    report,
    flagged: list[str],
) -> None:
    """Publish one DetectorReport into the registry (host-side, cheap)."""
    import numpy as np

    lat_z = np.asarray(report.lat_z)
    err_z = np.asarray(report.err_z)
    rate_z = np.asarray(report.rate_z)
    card_z = np.asarray(report.card_z)
    card = np.asarray(report.card_est)
    hh = np.asarray(report.hh_ratio)
    cusum = np.asarray(report.cusum)
    # The intern table can outgrow the sketch's service axis (overflow
    # names share the last id but keep their own table entries), so cap
    # at the report's actual row count.
    for i, name in enumerate(service_names[: lat_z.shape[0]]):
        registry.gauge_set(ANOMALY_Z_SCORE, float(np.abs(lat_z[i]).max()),
                           service=name, signal="latency")
        registry.gauge_set(ANOMALY_Z_SCORE, float(np.abs(err_z[i]).max()),
                           service=name, signal="error_rate")
        registry.gauge_set(ANOMALY_Z_SCORE, float(np.abs(rate_z[i]).max()),
                           service=name, signal="throughput")
        registry.gauge_set(ANOMALY_Z_SCORE, float(np.abs(card_z[i]).max()),
                           service=name, signal="cardinality")
        registry.gauge_set(ANOMALY_CARDINALITY, float(card[i].max()), service=name)
        registry.gauge_set(ANOMALY_HEAVY_HITTER, float(hh[i].max()), service=name)
        for j, signal in enumerate(("latency_up", "error_up", "rate_down")):
            registry.gauge_set(
                ANOMALY_CUSUM, float(cusum[i, j]), service=name, signal=signal
            )
    for name in flagged:
        registry.counter_add(ANOMALY_FLAG_TOTAL, 1.0, service=name)
