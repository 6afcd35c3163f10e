"""OTLP/gRPC receiver (:4317): the collector's primary telemetry ingress.

The reference collector listens for OTLP gRPC first and HTTP second
(/root/reference/src/otel-collector/otelcol-config.yml:5-8), and every
reference SDK defaults to gRPC export — so the sidecar speaks it too.
The transport is grpcio with *generic* raw-bytes handlers: no generated
stubs, no proto runtime on the hot path — request bytes go straight into
the same hand-rolled wire decoders the HTTP receiver uses
(runtime.otlp / runtime.otlp_metrics), and the response is the empty
Export*ServiceResponse (zero bytes is a valid empty proto3 message).

Service/method names are the public OTLP protocol's:
``opentelemetry.proto.collector.{trace,metrics}.v1``. Any OTLP gRPC
exporter (otel-go/java/python SDKs, another collector's ``otlp``
exporter) interoperates unchanged.
"""

from __future__ import annotations

from typing import Callable

from . import otlp, otlp_metrics, selftrace
from .selftrace import PHASE_DOOR, SPAN_DOOR
from .tensorize import SpanRecord

TRACE_EXPORT = "/opentelemetry.proto.collector.trace.v1.TraceService/Export"
METRICS_EXPORT = (
    "/opentelemetry.proto.collector.metrics.v1.MetricsService/Export"
)
LOGS_EXPORT = "/opentelemetry.proto.collector.logs.v1.LogsService/Export"


class OtlpGrpcReceiver:
    """gRPC twin of :class:`~.otlp.OtlpHttpReceiver` — same callbacks.

    ``on_records`` receives decoded SpanRecords per Export call;
    ``on_columnar`` (with the native decoder available) takes the C++
    columnar fast path; ``on_payload`` (the parallel ingest engine,
    ``runtime.ingest_pool``) hands the RAW request bytes to the decode
    pool and blocks only on the per-RPC ticket — malformed still
    answers ``INVALID_ARGUMENT``, a full pool queue the same retryable
    ``RESOURCE_EXHAUSTED`` as pipeline saturation;
    ``on_metric_records`` receives MetricRecords
    from the MetricsService. Malformed payloads answer
    ``INVALID_ARGUMENT`` (the client's fault) and are tallied in
    ``rejects``/``on_reject``; oversized messages are bounced by grpc
    itself (``RESOURCE_EXHAUSTED`` via ``max_receive_message_length``)
    before our handler runs; abrupt disconnects are absorbed by the
    transport. Callback failures propagate as ``INTERNAL`` — server
    bugs must surface.

    ``component_status`` (optional, from
    ``supervision.Supervisor.health_status``) lets the attached
    grpc.health.v1 service answer per-component Check requests
    (``anomaly.component.<name>``) beside the server-wide status.

    Backpressure (``retry_after``, the HTTP leg's 429 twin): while the
    pipeline is saturated, trace Exports abort with
    ``RESOURCE_EXHAUSTED`` — the OTLP spec's retryable status — with a
    ``retry-after-s`` trailing-metadata hint, tallied as
    ``rejects["saturated"]``. Metrics/logs Exports stay admitted (scrape
    cadence, not the flood the budget protects against).
    """

    def __init__(
        self,
        on_records: Callable[[list[SpanRecord]], None],
        host: str = "0.0.0.0",
        port: int = 4317,
        on_columnar: Callable | None = None,
        on_metric_records: Callable | None = None,
        on_log_records: Callable | None = None,
        max_workers: int = 4,
        on_reject: Callable[[str], None] | None = None,
        max_body_bytes: int = 16 << 20,
        component_status: Callable[[str], int | None] | None = None,
        retry_after: Callable[[], float | None] | None = None,
        on_payload: Callable | None = None,
        phase_observe: Callable[[str, float], None] | None = None,
    ):
        import grpc
        from concurrent import futures

        self.on_records = on_records
        self.on_columnar = on_columnar
        self.on_payload = on_payload
        self.on_metric_records = on_metric_records
        self.on_log_records = on_log_records
        self.on_reject = on_reject
        self.retry_after = retry_after
        # The door's phase hook (``anomaly_phase_seconds{phase="door"}``).
        self.phase_observe = phase_observe
        self.rejects: dict[str, int] = {}
        receiver = self

        def _reject(reason: str) -> None:
            receiver.rejects[reason] = receiver.rejects.get(reason, 0) + 1
            if receiver.on_reject is not None:
                try:
                    receiver.on_reject(reason)
                except Exception:  # noqa: BLE001 — metrics must not kill ingest
                    pass

        def export_traces(request: bytes, context) -> bytes:
            # The door span, as on the HTTP leg: entry to the answer,
            # the decode-ticket wait included; an aborted RPC raises
            # out of it and is not observed as a door pass.
            with selftrace.span(SPAN_DOOR) as door:
                out = admit_traces(request, context)
            if receiver.phase_observe is not None:
                receiver.phase_observe(PHASE_DOOR, door.seconds)
            return out

        def admit_traces(request: bytes, context) -> bytes:
            if receiver.retry_after is not None:
                hint = receiver.retry_after()
                if hint is not None:
                    _reject("saturated")
                    # The OTLP/gRPC retryable contract: clients treat
                    # RESOURCE_EXHAUSTED as retry-with-backoff; the
                    # trailing metadata carries the server's hint
                    # (grpc_send honors it on the exporter side).
                    context.set_trailing_metadata(
                        (("retry-after-s", f"{hint:g}"),)
                    )
                    context.abort(
                        grpc.StatusCode.RESOURCE_EXHAUSTED,
                        f"pipeline saturated; retry after {hint:g}s",
                    )
            if receiver.on_payload is not None:
                # Parallel ingest engine (runtime.ingest_pool): raw
                # body to the decode pool, block on this RPC's ticket.
                from .ingest_pool import (
                    IngestPoolSaturated,
                    IngestWorkerError,
                )

                try:
                    ticket = receiver.on_payload(request)
                except IngestPoolSaturated:
                    _reject("saturated")
                    context.set_trailing_metadata((("retry-after-s", "1"),))
                    context.abort(
                        grpc.StatusCode.RESOURCE_EXHAUSTED,
                        "ingest pool saturated; retry",
                    )
                try:
                    ticket.result()
                except TimeoutError:
                    # Wedged flush: retryable, never a client fault.
                    context.abort(
                        grpc.StatusCode.UNAVAILABLE,
                        "ingest flush timed out; retry",
                    )
                except IngestWorkerError:
                    # Server-side flush failure — surface as INTERNAL
                    # exactly like a raising callback on the serial
                    # path, never as INVALID_ARGUMENT.
                    raise
                except Exception:
                    # Per-request DECODE verdict: the client's bytes.
                    _reject("malformed")
                    context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        "malformed OTLP payload",
                    )
                return b""  # empty ExportTraceServiceResponse
            columnar = None
            try:
                if receiver.on_columnar is not None:
                    columnar = otlp.decode_export_request_columnar(request)
                if columnar is None:
                    records = otlp.decode_export_request(request)
            except Exception:  # noqa: BLE001 — decoding the
                # client's bytes: whatever malformed protobuf raises is
                # the client's INVALID_ARGUMENT, never our crash.
                _reject("malformed")
                context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT, "malformed OTLP payload"
                )
            if columnar is not None:
                receiver.on_columnar(columnar)
            else:
                receiver.on_records(records)
            return b""  # empty ExportTraceServiceResponse

        def export_metrics(request: bytes, context) -> bytes:
            try:
                records = otlp_metrics.decode_metrics_request(request)
            except Exception:  # noqa: BLE001 — decoding the
                # client's bytes: whatever malformed protobuf raises is
                # the client's INVALID_ARGUMENT, never our crash.
                _reject("malformed")
                context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT, "malformed OTLP payload"
                )
            if receiver.on_metric_records is not None:
                receiver.on_metric_records(records)
            return b""  # empty ExportMetricsServiceResponse

        def export_logs(request: bytes, context) -> bytes:
            try:
                docs = otlp.decode_logs_request(request)
            except Exception:  # noqa: BLE001 — decoding the
                # client's bytes: whatever malformed protobuf raises is
                # the client's INVALID_ARGUMENT, never our crash.
                _reject("malformed")
                context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT, "malformed OTLP payload"
                )
            if receiver.on_log_records is not None:
                receiver.on_log_records(docs)
            return b""  # empty ExportLogsServiceResponse

        # grpc.health.v1 beside the OTLP ingress: the registration every
        # reference service performs (main.go:223-224, server.cpp:92-102),
        # and what the compose/k8s healthchecks probe on this daemon.
        # One watcher slot: the ingress pool is small (4 workers) and
        # Export throughput must never queue behind parked watchers.
        import threading

        from .grpc_health import HealthService

        self._stop_event = threading.Event()
        self._health = HealthService(
            {m.split("/")[1] for m in (TRACE_EXPORT, METRICS_EXPORT, LOGS_EXPORT)},
            self._stop_event,
            watcher_slots=1,
            component_status=component_status,
        )

        handlers = {
            TRACE_EXPORT: export_traces,
            METRICS_EXPORT: export_metrics,
            LOGS_EXPORT: export_logs,
        }

        class Handler(grpc.GenericRpcHandler):
            def service(self, details):
                health = receiver._health.add_to_generic_handlers(
                    grpc, details.method
                )
                if health is not None:
                    return health
                fn = handlers.get(details.method)
                if fn is None:
                    return None
                return grpc.unary_unary_rpc_method_handler(
                    fn, request_deserializer=None, response_serializer=None
                )

        self._server = grpc.server(
            futures.ThreadPoolExecutor(
                max_workers=max_workers, thread_name_prefix="otlp-grpc"
            ),
            options=[
                # Oversized exports bounce at the transport with
                # RESOURCE_EXHAUSTED (the HTTP leg's 413 analogue)
                # instead of ballooning the decoder's heap.
                ("grpc.max_receive_message_length", max_body_bytes),
            ],
        )
        self._server.add_generic_rpc_handlers((Handler(),))
        self.port = self._server.add_insecure_port(f"{host}:{port}")
        if self.port == 0:
            # grpc reports bind failure by returning port 0 instead of
            # raising; a daemon that silently boots with a dead primary
            # ingress is worse than one that refuses to boot.
            raise OSError(f"OTLP/gRPC receiver failed to bind {host}:{port}")

    def start(self) -> None:
        self._server.start()

    def alive(self) -> bool:
        """Liveness for the supervisor: started and not stopped. The
        grpc core owns its own threads (no Python thread to watch), so
        this reflects lifecycle state; the supervisor's deeper probe is
        a real health-check RPC on its own cadence."""
        return not self._stop_event.is_set()

    def stop(self, grace: float = 1.0) -> None:
        # NOT_SERVING reaches health watchers before the teardown.
        self._stop_event.set()
        self._server.stop(grace).wait()


def export_client(target: str):
    """(traces_fn, metrics_fn) raw-bytes unary callables for tests and
    the collector's gRPC exporter — each takes a serialized request and
    returns the (empty) response bytes."""
    import grpc

    channel = grpc.insecure_channel(target)
    traces = channel.unary_unary(
        TRACE_EXPORT, request_serializer=None, response_deserializer=None
    )
    metrics = channel.unary_unary(
        METRICS_EXPORT, request_serializer=None, response_deserializer=None
    )
    return traces, metrics
