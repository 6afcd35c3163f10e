"""OTLP/HTTP span ingestion: the collector-export seam.

The reference collector fans traces out to exporters
(/root/reference/src/otel-collector/otelcol-config.yml:120-123); wiring
the detector in means adding one more ``otlphttp`` exporter pointing at
this receiver (deploy/otelcol-config-anomaly.yml does exactly that, the
pattern of the Jaeger exporter at :85-88). The receiver accepts
``POST /v1/traces`` with either protobuf (``application/x-protobuf``,
decoded by the schema-projection below) or JSON OTLP bodies, and turns
every span into a :class:`~..runtime.tensorize.SpanRecord`.

Field numbers follow the public OTLP protocol (opentelemetry-proto
trace/v1): ExportTraceServiceRequest{resource_spans=1},
ResourceSpans{resource=1, scope_spans=2}, Resource{attributes=1},
KeyValue{key=1, value=2}, AnyValue{string_value=1},
ScopeSpans{spans=2}, Span{trace_id=1, name=5, start_time_unix_nano=7,
end_time_unix_nano=8, attributes=9, events=11, status=15},
Span.Event{time_unix_nano=1, name=2, attributes=3}, Status{code=3}.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable

from . import native, selftrace, wire
from .selftrace import PHASE_DOOR, SPAN_DOOR
from .tensorize import SpanEvent, SpanRecord

_STATUS_ERROR = 2  # opentelemetry.proto.trace.v1.Status.StatusCode.ERROR

# Attribute keys worth monitoring for heavy hitters, in priority order —
# the ids the shop attaches to its spans (e.g. checkout's app.product.id,
# session ids from baggage; SURVEY.md §5 "Tracing").
MONITORED_ATTR_KEYS = (
    "app.product.id",
    "app.order.id",
    "app.session.id",
    "session.id",
)


def _as_msg(val) -> bytes:
    """An embedded-message field must arrive length-delimited: a
    corrupted tag can flip its wire type so the scanner hands back an
    int where sub-scan expects bytes. That is malformed wire data — a
    clean 400 verdict (WireError is a ValueError) — not a TypeError
    crash in the receiver; the fuzz suite pins this."""
    if not isinstance(val, bytes):
        raise wire.WireError(
            f"embedded message field carries wire type of {type(val).__name__}"
        )
    return val


def _anyvalue_str(buf: bytes) -> str | None:
    f = wire.scan_fields(buf)
    sv = wire.first(f, 1)
    if isinstance(sv, bytes):
        return sv.decode("utf-8", "replace")
    return None


def _attrs_to_dict(attr_bufs: list[bytes]) -> dict[str, str]:
    out: dict[str, str] = {}
    for kv_buf in attr_bufs:
        kv = wire.scan_fields(_as_msg(kv_buf))
        key = wire.first(kv, 1, b"")
        val_buf = wire.first(kv, 2)
        if key and isinstance(key, bytes) and isinstance(val_buf, bytes):
            sval = _anyvalue_str(val_buf)
            if sval is not None:
                out[key.decode("utf-8", "replace")] = sval
    return out


def _pick_attr(attrs: dict[str, str]) -> str | None:
    for key in MONITORED_ATTR_KEYS:
        if key in attrs:
            return attrs[key]
    return None


def decode_export_request(payload: bytes) -> list[SpanRecord]:
    """ExportTraceServiceRequest protobuf → SpanRecords."""
    records: list[SpanRecord] = []
    req = wire.scan_fields(payload)
    for rs_buf in req.get(1, []):
        rs = wire.scan_fields(_as_msg(rs_buf))
        service = "unknown"
        res_buf = wire.first(rs, 1)
        if res_buf:
            res = wire.scan_fields(_as_msg(res_buf))
            res_attrs = _attrs_to_dict(res.get(1, []))
            service = res_attrs.get("service.name", service)
        for ss_buf in rs.get(2, []):
            ss = wire.scan_fields(_as_msg(ss_buf))
            for span_buf in ss.get(2, []):
                records.append(_decode_span(_as_msg(span_buf), service))
    return records


def _decode_event(ev_buf: bytes, span_start_ns: int) -> SpanEvent:
    ev = wire.scan_fields(_as_msg(ev_buf))
    t_ns = int(wire.first(ev, 1, 0) or 0)
    name_raw = wire.first(ev, 2)
    name = (
        name_raw.decode("utf-8", "replace")
        if isinstance(name_raw, bytes) else ""
    )
    attrs = _attrs_to_dict(ev.get(3, []))
    return SpanEvent(
        name=name,
        ts_offset_us=max(t_ns - span_start_ns, 0) / 1000.0,
        attrs=tuple(attrs.items()),
    )


def _decode_span(span_buf: bytes, service: str) -> SpanRecord:
    sp = wire.scan_fields(span_buf)
    trace_id = wire.first(sp, 1, b"\0") or b"\0"
    start = int(wire.first(sp, 7, 0) or 0)
    end = int(wire.first(sp, 8, 0) or 0)
    duration_us = max(end - start, 0) / 1000.0
    attrs = _attrs_to_dict(sp.get(9, []))
    is_error = False
    status_buf = wire.first(sp, 15)
    if status_buf:
        st = wire.scan_fields(_as_msg(status_buf))
        is_error = int(wire.first(st, 3, 0) or 0) == _STATUS_ERROR
    name_raw = wire.first(sp, 5)
    return SpanRecord(
        service=service,
        duration_us=duration_us,
        trace_id=trace_id,
        is_error=is_error,
        attr=_pick_attr(attrs),
        name=name_raw.decode("utf-8", "replace") if isinstance(name_raw, bytes) else None,
        events=tuple(
            _decode_event(ev_buf, start) for ev_buf in sp.get(11, [])
        ),
    )


def decode_export_request_json(payload: bytes) -> list[SpanRecord]:
    """JSON-encoded OTLP (the collector's otlphttp json mode)."""
    doc = json.loads(payload)
    records: list[SpanRecord] = []
    for rs in doc.get("resourceSpans", []):
        service = "unknown"
        for attr in rs.get("resource", {}).get("attributes", []):
            if attr.get("key") == "service.name":
                service = attr.get("value", {}).get("stringValue", service)
        for ss in rs.get("scopeSpans", []):
            for sp in ss.get("spans", []):
                attrs = {
                    a.get("key"): a.get("value", {}).get("stringValue")
                    for a in sp.get("attributes", [])
                }
                start = int(sp.get("startTimeUnixNano", 0))
                end = int(sp.get("endTimeUnixNano", 0))
                events = tuple(
                    SpanEvent(
                        # str() guard: an explicit null/non-string name
                        # must not poison the store (obsui joins names).
                        name=str(ev.get("name") or ""),
                        ts_offset_us=max(
                            int(ev.get("timeUnixNano", 0) or 0) - start, 0
                        ) / 1000.0,
                        attrs=tuple(
                            (a.get("key"), a.get("value", {}).get("stringValue"))
                            for a in ev.get("attributes", [])
                            if a.get("key")
                            and a.get("value", {}).get("stringValue") is not None
                        ),
                    )
                    for ev in sp.get("events", [])
                )
                records.append(
                    SpanRecord(
                        service=service,
                        duration_us=max(end - start, 0) / 1000.0,
                        trace_id=bytes.fromhex(sp.get("traceId", "00")),
                        is_error=sp.get("status", {}).get("code") in (2, "STATUS_CODE_ERROR"),
                        attr=_pick_attr({k: v for k, v in attrs.items() if v}),
                        name=sp.get("name"),
                        events=events,
                    )
                )
    return records


def _severity_from_number(num: int) -> str | None:
    """OTLP SeverityNumber enum → the store's scale (None if unset).

    Spec bands: 1-4 TRACE, 5-8 DEBUG, 9-12 INFO, 13-16 WARN,
    17-20 ERROR, 21-24 FATAL."""
    if num <= 0:
        return None
    if num <= 8:
        return "DEBUG"
    if num <= 12:
        return "INFO"
    if num <= 16:
        return "WARN"
    if num <= 20:
        return "ERROR"
    return "FATAL"


def decode_logs_request(payload: bytes) -> list:
    """ExportLogsServiceRequest protobuf → LogDocs.

    The collector's third signal (otelcol-config.yml:128-131, logs →
    OpenSearch): ResourceLogs{resource=1, scope_logs=2},
    ScopeLogs{log_records=2}, LogRecord{time_unix_nano=1,
    severity_number=2, severity_text=3, body=5, attributes=6,
    trace_id=9, observed_time_unix_nano=11} per the public
    opentelemetry-proto logs/v1 field numbers. Spec fallbacks: severity
    text is optional (severity_number alone is valid), and
    time_unix_nano=0 means "use ObservedTimestamp".
    """
    from ..telemetry.logstore import LogDoc, normalize_severity

    docs: list = []
    req = wire.scan_fields(payload)
    for rl_buf in req.get(1, []):
        rl = wire.scan_fields(rl_buf)
        service = "unknown"
        res_buf = wire.first(rl, 1)
        if res_buf:
            res = wire.scan_fields(res_buf)
            service = _attrs_to_dict(res.get(1, [])).get("service.name", service)
        for sl_buf in rl.get(2, []):
            sl = wire.scan_fields(sl_buf)
            for lr_buf in sl.get(2, []):
                lr = wire.scan_fields(lr_buf)
                sev_raw = wire.first(lr, 3)
                sev_text = (
                    sev_raw.decode("utf-8", "replace")
                    if isinstance(sev_raw, bytes) and sev_raw else None
                )
                if sev_text is None:  # text optional: number-only is valid
                    sev_text = _severity_from_number(
                        int(wire.first(lr, 2, 0) or 0)
                    )
                body_buf = wire.first(lr, 5)
                body = _anyvalue_str(body_buf) if isinstance(body_buf, bytes) else None
                trace_id = wire.first(lr, 9)
                t_ns = int(wire.first(lr, 1, 0) or 0)
                if t_ns == 0:  # spec: fall back to ObservedTimestamp
                    t_ns = int(wire.first(lr, 11, 0) or 0)
                docs.append(LogDoc(
                    ts=t_ns / 1e9,
                    service=service,
                    severity=normalize_severity(sev_text),
                    body=body or "",
                    attrs=_attrs_to_dict(lr.get(6, [])),
                    trace_id=trace_id if isinstance(trace_id, bytes) and trace_id else None,
                ))
    return docs


def decode_logs_request_json(payload: bytes) -> list:
    """JSON-encoded OTLP logs (the collector's otlphttp json mode)."""
    from ..telemetry.logstore import LogDoc, normalize_severity

    doc = json.loads(payload)
    docs: list = []
    for rl in doc.get("resourceLogs", []):
        service = "unknown"
        for attr in rl.get("resource", {}).get("attributes", []):
            if attr.get("key") == "service.name":
                service = attr.get("value", {}).get("stringValue", service)
        for sl in rl.get("scopeLogs", []):
            for lr in sl.get("logRecords", []):
                attrs = {
                    a.get("key"): a.get("value", {}).get("stringValue")
                    for a in lr.get("attributes", [])
                }
                trace_hex = lr.get("traceId") or ""
                sev_text = lr.get("severityText") or _severity_from_number(
                    int(lr.get("severityNumber", 0) or 0)
                )
                t_ns = int(lr.get("timeUnixNano", 0) or 0)
                if t_ns == 0:  # spec: fall back to ObservedTimestamp
                    t_ns = int(lr.get("observedTimeUnixNano", 0) or 0)
                docs.append(LogDoc(
                    ts=t_ns / 1e9,
                    service=service,
                    severity=normalize_severity(sev_text),
                    body=lr.get("body", {}).get("stringValue", ""),
                    attrs={k: v for k, v in attrs.items() if v is not None},
                    trace_id=bytes.fromhex(trace_hex) if trace_hex else None,
                ))
    return docs


def decode_export_request_columnar(payload: bytes):
    """Protobuf request → native columnar batch, or None to fall back.

    Returns a ``runtime.native.ColumnarSpans`` when the C++ decoder is
    available (feed it to ``DetectorPipeline.submit_columnar``); None
    when the native library can't load — callers then take the
    record-level Python path with identical results.
    """
    if not native.available():
        return None
    return native.decode_otlp(payload, MONITORED_ATTR_KEYS)


class OtlpHttpReceiver:
    """Threaded OTLP/HTTP receiver feeding callbacks, one per signal.

    ``POST /v1/traces`` (and any unrecognised path, for compatibility)
    decodes spans: ``on_records`` is called from the server thread with
    each request's SpanRecords; the callback enqueues into the pipeline
    (which owns batching/tensorization on its own thread). When
    ``on_columnar`` is provided and the native decoder is available,
    protobuf bodies skip Python record objects entirely: C++ wire decode
    → columnar arrays → ``on_columnar`` (the pipeline's fast path).

    When ``on_payload`` is provided (the parallel ingest engine,
    ``runtime.ingest_pool``), protobuf trace bodies take the fastest
    path of all: the RAW body is handed to the decode pool and the
    handler blocks only on the request's :class:`DecodeTicket` —
    batched C++ decode, pooled buffers, coalesced tensorize all happen
    on the pool's workers. The verdicts are unchanged: malformed still
    answers 400 (the ticket carries the per-request decode error, even
    when the request was decoded in a batch), success still means the
    rows are enqueued, and a full pool queue answers the same
    retryable 429 as pipeline saturation — the bounded-admission
    contract has no unbounded buffer ahead of the pool.

    ``POST /v1/metrics`` decodes OTLP metrics/v1 (runtime.otlp_metrics)
    into ``on_metric_records`` — the collector's metrics-pipeline leg
    (otelcol-config.yml:124-126). ``POST /v1/logs`` decodes OTLP
    logs/v1 into ``on_log_records`` — the third signal
    (otelcol-config.yml:128-131). Absent the respective callback,
    exports are acknowledged and dropped (an ingest-side null sink,
    matching a collector with that pipeline unconfigured).

    Ingest hardening (the fault-tolerant-runtime contract, proven by
    tests/test_chaos.py): a malformed body answers 400, a truncated
    body (client died mid-upload) 400, an oversized body 413 — each
    tallied in ``rejects[reason]`` and reported through ``on_reject`` —
    and an abrupt client disconnect (half-open socket, reset mid-
    response) releases the handler thread via the per-connection
    ``timeout`` instead of pinning it. None of these ever kill the
    server: the next well-formed export proceeds normally.

    Backpressure (``retry_after``, tests/test_overload.py): while the
    pipeline sits above its high watermark, trace exports answer the
    OTLP retryable-error contract — ``429`` with an integer
    ``Retry-After`` (delta-seconds, rounded up — real SDKs parse it as
    an int), tallied as ``rejects["saturated"]``. The body is drained
    (bounded by the oversized check) but never decoded: a 429 sent
    over unread bytes would RST the client mid-send and the exporter
    would see a connection error instead of the retryable status.
    Metrics/logs exports stay admitted: they arrive at scrape cadence,
    orders of magnitude below the span path the budget protects.
    """

    # Half-open-socket bound: StreamRequestHandler applies this to the
    # connection in setup(), so a client that stops sending mid-request
    # frees the thread instead of pinning it forever.
    CONNECTION_TIMEOUT_S = 10.0

    def __init__(
        self,
        on_records: Callable[[list[SpanRecord]], None],
        host: str = "0.0.0.0",
        port: int = 4318,
        on_columnar: Callable | None = None,
        on_metric_records: Callable | None = None,
        on_log_records: Callable | None = None,
        on_reject: Callable[[str], None] | None = None,
        max_body_bytes: int = 16 << 20,
        retry_after: Callable[[], float | None] | None = None,
        on_payload: Callable | None = None,
        phase_observe: Callable[[str, float], None] | None = None,
    ):
        receiver = self

        class Handler(BaseHTTPRequestHandler):
            timeout = receiver.CONNECTION_TIMEOUT_S

            def do_POST(self):  # noqa: N802 (http.server API)
                path = self.path.split("?", 1)[0]
                # The door span: handler entry to the answer written,
                # the decode-ticket wait included. Its phase counts
                # accepted trace exports only; refusals and failures
                # are counted by their own reject reasons.
                with selftrace.span(SPAN_DOOR) as door:
                    accepted = self._post(path)
                if accepted and receiver.phase_observe is not None:
                    receiver.phase_observe(PHASE_DOOR, door.seconds)

            def _post(self, path: str) -> bool:
                """Answer one POST; True once a trace export got its
                200."""
                try:
                    length = int(self.headers.get("Content-Length", 0))
                except ValueError:
                    receiver._reject("bad_length")
                    self.send_response(400)
                    self.end_headers()
                    return False
                if length > receiver.max_body_bytes:
                    # Oversized: refuse WITHOUT reading — draining a
                    # multi-GB body to politely answer 413 is itself a
                    # resource fault. Close so the pipelined remainder
                    # can't be parsed as a next request.
                    receiver._reject("oversized")
                    self.send_response(413)
                    self.send_header("Connection", "close")
                    self.end_headers()
                    self.close_connection = True
                    return False
                if retry_after is not None and not (
                    path.endswith("/v1/metrics") or path.endswith("/v1/logs")
                ):
                    hint = retry_after()
                    if hint is not None:
                        # Saturated: retryable refusal. The body IS
                        # drained first (it's already bounded by the
                        # oversized check above): answering 429 with
                        # unread bytes queued would RST a client still
                        # blocked in send(), and an exporter that sees
                        # a reset instead of the 429 never learns to
                        # back off — the exact failure this gate
                        # exists to prevent. Decode is skipped; the
                        # drain is the whole price of admission
                        # control. Retry-After is integer
                        # delta-seconds (RFC 7231 — real OTLP SDKs
                        # parse it as an int), rounded UP so the hint
                        # never undershoots the configured pace.
                        try:
                            self.rfile.read(length)
                        except OSError:
                            receiver._reject("disconnect")
                            self.close_connection = True
                            return False
                        receiver._reject("saturated")
                        self.send_response(429)
                        self.send_header(
                            "Retry-After", str(max(int(-(-hint // 1)), 1))
                        )
                        self.end_headers()
                        return False
                try:
                    body = self.rfile.read(length)
                except OSError:
                    # Timeout or reset mid-body: the client is gone —
                    # nothing to answer, just release the thread.
                    receiver._reject("disconnect")
                    self.close_connection = True
                    return False
                if len(body) < length:
                    # Truncated frame: the client promised more bytes
                    # than it sent (died mid-upload). 4xx, not a crash.
                    receiver._reject("truncated")
                    self.send_response(400)
                    self.end_headers()
                    return False
                is_json = "json" in (self.headers.get("Content-Type") or "")
                is_traces = not (
                    path.endswith("/v1/metrics") or path.endswith("/v1/logs")
                )
                if (
                    is_traces
                    and not is_json
                    and receiver.on_payload is not None
                ):
                    # Parallel ingest engine: hand the raw body to the
                    # decode pool; block only on THIS request's ticket.
                    from .ingest_pool import (
                        IngestPoolSaturated,
                        IngestWorkerError,
                    )

                    try:
                        ticket = receiver.on_payload(body)
                    except IngestPoolSaturated:
                        # Same retryable refusal as pipeline
                        # saturation: the pool queue is bounded by
                        # design, and a full one means "come back".
                        receiver._reject("saturated")
                        self.send_response(429)
                        self.send_header("Retry-After", "1")
                        self.end_headers()
                        return False
                    try:
                        ticket.result()
                    except TimeoutError:
                        # Wedged flush (supervisor territory): the
                        # request MAY still land, but the client must
                        # not treat it as accepted — 503 is the OTLP
                        # retryable status, never a 4xx that would
                        # make an exporter discard the batch.
                        self.send_response(503)
                        self.send_header("Retry-After", "1")
                        self.end_headers()
                        return False
                    except IngestWorkerError:
                        # Server-side flush failure: our bug, not the
                        # client's bytes — must surface as 5xx, never
                        # masquerade as "malformed".
                        self.send_response(500)
                        self.end_headers()
                        return False
                    except Exception:
                        # The pool's per-request DECODE verdict (any
                        # exception the payload raised while being
                        # picked apart): malformed wire data is the
                        # client's fault — 400, the serial path's
                        # answer.
                        receiver._reject("malformed")
                        self.send_response(400)
                        self.end_headers()
                        return False
                    try:
                        self.send_response(200)
                        self.send_header(
                            "Content-Type", "application/x-protobuf"
                        )
                        self.end_headers()
                        self.wfile.write(b"")
                    except OSError:
                        receiver._reject("disconnect")
                        self.close_connection = True
                        return False
                    return True
                columnar = None
                metric_records = None
                log_records = None
                try:
                    if path.endswith("/v1/logs"):
                        if is_json:
                            log_records = decode_logs_request_json(body)
                        else:
                            log_records = decode_logs_request(body)
                    elif path.endswith("/v1/metrics"):
                        from . import otlp_metrics

                        if is_json:
                            metric_records = (
                                otlp_metrics.decode_metrics_request_json(body)
                            )
                        else:
                            metric_records = (
                                otlp_metrics.decode_metrics_request(body)
                            )
                    elif is_json:
                        records = decode_export_request_json(body)
                    elif receiver.on_columnar is not None:
                        columnar = decode_export_request_columnar(body)
                        if columnar is None:
                            records = decode_export_request(body)
                    else:
                        records = decode_export_request(body)
                except Exception:
                    # Anything a malformed body can raise while being
                    # picked apart (WireError, JSONDecodeError, but also
                    # TypeError/AttributeError from structurally-wrong
                    # shapes) is the client's fault: answer 400 rather
                    # than letting http.server abort the connection.
                    # Only decoding is in scope — a failure in the ingest
                    # callback below is a server bug and must surface,
                    # not masquerade as a client error.
                    receiver._reject("malformed")
                    self.send_response(400)
                    self.end_headers()
                    return False
                if log_records is not None:
                    if receiver.on_log_records is not None:
                        receiver.on_log_records(log_records)
                elif metric_records is not None:
                    if receiver.on_metric_records is not None:
                        receiver.on_metric_records(metric_records)
                elif columnar is not None:
                    receiver.on_columnar(columnar)
                else:
                    receiver.on_records(records)
                try:
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "application/x-protobuf"
                    )
                    self.end_headers()
                    self.wfile.write(b"")  # empty Export*ServiceResponse
                except OSError:
                    # Client reset between upload and ack: the data is
                    # already ingested (at-least-once), only the ack was
                    # lost — count it, release the thread.
                    receiver._reject("disconnect")
                    self.close_connection = True
                    return False
                return is_traces

            def log_message(self, *args):  # silence per-request stderr spam
                pass

        self.on_records = on_records
        self.on_columnar = on_columnar
        self.on_payload = on_payload
        self.on_metric_records = on_metric_records
        self.on_log_records = on_log_records
        self.on_reject = on_reject
        self.max_body_bytes = max_body_bytes
        self.retry_after = retry_after
        # The door's phase hook (``anomaly_phase_seconds{phase="door"}``).
        self.phase_observe = phase_observe
        # reason → count; the daemon mirrors these into
        # anomaly_ingest_rejected_total{transport="http",reason=...}.
        self.rejects: dict[str, int] = {}
        self._server = ThreadingHTTPServer((host, port), Handler)
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="otlp-receiver", daemon=True
        )

    def _reject(self, reason: str) -> None:
        self.rejects[reason] = self.rejects.get(reason, 0) + 1
        if self.on_reject is not None:
            try:
                self.on_reject(reason)
            except Exception:  # noqa: BLE001 — metrics must not kill ingest
                pass

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def alive(self) -> bool:
        """Liveness for the supervisor: the serve thread is running."""
        return self._thread.is_alive()

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        # BaseServer.shutdown() blocks on an event only serve_forever sets;
        # calling it on a never-started server would wait forever.
        if self._thread.is_alive():
            self._server.shutdown()
        self._server.server_close()
