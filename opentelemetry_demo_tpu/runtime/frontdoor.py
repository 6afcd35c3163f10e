"""Python control plane for the native OTLP front door.

The data plane lives in native/frontdoor.cc: accept → HTTP/1.1
framing → body bytes recv'd DIRECTLY into a recycled native buffer →
(id, kind, ptr, len) ticket → verdict → canned response — zero Python
in the per-payload loop. This module is everything that rightly stays
Python, because it needs pipeline state:

- the pump threads that drain tickets in BATCHES (one GIL-released
  ``native.frontdoor_next`` call per batch) and route them: trace
  bodies go to the decode pool's POINTER path (``pool.submit`` of a
  zero-copy ctypes view — ``decode_otlp_many`` scans the native buffer
  in place), metrics/logs take the Python decoders at scrape cadence;
- the verdict vocabulary, bit-compatible with ``runtime/otlp.py``'s
  receiver: pipeline saturation → 429 + integer Retry-After (rounded
  up), pool saturation → 429 + Retry-After: 1, a server-side flush
  failure → 500, and the per-request DECODE verdict carried by the
  :class:`DecodeTicket` → 400 for exactly the bad request while its
  batchmates proceed. A WEDGED flush gets its verdict DEFERRED, not
  short-circuited to 503: the pool still holds a zero-copy view of
  the ticket's native buffer, and ``frontdoor_respond`` is what hands
  the buffer back to the connection thread for recycling — responding
  early would let the decode scan freed/reused memory. The ticket is
  parked on a stalled list the pump re-polls each drain, and the
  eventual REAL verdict (200/400/500) goes out when the flush lands;
  Metrics/logs stay exempt from the saturation gate (they arrive at
  scrape cadence — the same exemption the Python receiver applies);
- reject bookkeeping: the natively-decided verdicts (bad_length,
  oversized, chunked, truncated, disconnect) are counted by
  frontdoor.cc and mirrored into ``rejects``/``on_reject`` here, so
  ``anomaly_ingest_rejected_total{transport="frontdoor"}`` tells one
  honest story regardless of which side decided;
- graceful drain: quiesce (stop accepting; in-flight verdicts keep
  flowing) → wait for quiescence → full native stop → join pumps.

Deliberately ABSENT from this module: ``http.server``,
``socketserver``, and any per-request Python object on the trace
path — scripts/sanitycheck.py pins both (the zero-Python-HTTP
monopoly), and tests/test_frontdoor.py proves the vocabulary against
the Python receiver on a shared corpus.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Callable

from . import native
from .ingest_pool import IngestPool, IngestPoolSaturated, IngestWorkerError

# Native reject slots mirrored into the receiver-style rejects dict
# (same reason spellings as runtime/otlp.py where the verdict exists
# there; "chunked" is native-only — the Python receiver never sees a
# chunked body as such).
_NATIVE_REJECT_REASONS = (
    "bad_length", "oversized", "chunked", "truncated", "disconnect",
)


class FrontDoorServer:
    """Own one native front door + its pump threads.

    ``pool`` is the shared :class:`~.ingest_pool.IngestPool` — the
    front door is a second producer into the same bounded queue, so
    the bounded-admission contract (nothing unbounded ahead of the
    pipeline) is inherited, not re-implemented.
    """

    def __init__(
        self,
        pool: IngestPool,
        port: int = 0,
        max_body_bytes: int = 16 << 20,
        pumps: int = 1,
        batch_max: int = 64,
        max_conns: int = 64,
        header_timeout_ms: int = 10000,
        retry_after: Callable[[], float | None] | None = None,
        on_reject: Callable[[str], None] | None = None,
        on_metric_records: Callable | None = None,
        on_log_records: Callable | None = None,
        ticket_timeout_s: float = 30.0,
    ):
        self._pool = pool
        self._retry_after = retry_after
        self._on_reject = on_reject
        self._on_metric_records = on_metric_records
        self._on_log_records = on_log_records
        self._ticket_timeout_s = ticket_timeout_s
        self.max_body_bytes = max_body_bytes
        self.rejects: dict[str, int] = {}
        self._rejects_lock = threading.Lock()
        self._native_seen = {r: 0 for r in _NATIVE_REJECT_REASONS}
        self._handle = native.frontdoor_start(
            port, max_body_bytes, max_conns, header_timeout_ms
        )
        self.port = native.frontdoor_port(self._handle)
        self._batch_max = max(int(batch_max), 1)
        self._stopped = False
        self._pumps = [
            threading.Thread(
                target=self._pump, name=f"frontdoor-pump-{i}", daemon=True
            )
            for i in range(max(int(pumps), 1))
        ]
        for t in self._pumps:
            t.start()

    # -- reject bookkeeping --------------------------------------------

    def _reject(self, reason: str, n: int = 1) -> None:
        with self._rejects_lock:
            self.rejects[reason] = self.rejects.get(reason, 0) + n
        if self._on_reject is not None:
            for _ in range(n):
                self._on_reject(reason)

    def _sync_native_rejects(self) -> None:
        """Fold frontdoor.cc's natively-decided reject counters into
        the receiver-style dict (delta since last sync, so calling
        this from stats() and the pump keeps one honest total)."""
        raw = native.frontdoor_stats(self._handle)
        for reason in _NATIVE_REJECT_REASONS:
            delta = raw[reason] - self._native_seen[reason]
            if delta > 0:
                self._native_seen[reason] = raw[reason]
                self._reject(reason, delta)

    # -- the pump -------------------------------------------------------

    def _pump(self) -> None:
        batch = native.frontdoor_alloc_batch(self._batch_max)
        pending: list[tuple[int, object]] = []
        # Tickets whose flush outlived _ticket_timeout_s: the pool
        # STILL holds a zero-copy view of their native buffers, so the
        # verdict (and with it the buffer hand-back) is deferred until
        # the flush actually resolves — see _sweep_stalled.
        stalled: list[tuple[int, object]] = []
        h = self._handle
        while True:
            n = native.frontdoor_next(h, batch, timeout_ms=100)
            if n < 0:
                # Server stopping, queue drained. Give any still-
                # stalled flush one last bounded wait so its verdict
                # (a no-op respond by now — native stop already
                # answered the conn 503) marks the buffer released
                # before this thread exits.
                self._sweep_stalled(stalled, final=True)
                return
            for i in range(n):
                rid = int(batch.ids[i])
                kind = int(batch.kinds[i])
                ptr = int(batch.ptrs[i])
                ln = int(batch.lens[i])
                if kind == native.FD_KIND_TRACES:
                    self._admit_trace(rid, ptr, ln, pending)
                else:
                    self._serve_signal(rid, kind, ptr, ln)
            # Resolve this drain's tickets in order: each carries its
            # OWN decode verdict (the 400-for-exactly-the-bad-request
            # contract), resolved together by the pool's batched flush.
            for rid, ticket in pending:
                try:
                    ticket.result(timeout=self._ticket_timeout_s)
                    status, ra = 200, 0
                except TimeoutError:
                    # Wedged flush: the ticket's buffer is STILL queued
                    # in the pool. Responding now would return the
                    # buffer to the connection thread for resize/
                    # recycle while the decode worker can still scan
                    # it (use-after-free) — frontdoor_body's contract
                    # is respond only AFTER the decode consumed the
                    # bytes. Park it; the verdict goes out on a later
                    # sweep, when the flush has really resolved.
                    stalled.append((rid, ticket))
                    continue
                except IngestWorkerError:
                    # Server-side flush failure: our bug, not the
                    # client's bytes — 5xx, never "malformed".
                    status, ra = 500, 0
                except Exception:  # noqa: BLE001 — the decode verdict
                    self._reject("malformed")
                    status, ra = 400, 0
                native.frontdoor_respond(h, rid, status, ra)
            pending.clear()
            if stalled:
                self._sweep_stalled(stalled)
            if n > 0:
                self._sync_native_rejects()

    def _sweep_stalled(
        self, stalled: list[tuple[int, object]], final: bool = False
    ) -> None:
        """Respond to parked wedged-flush tickets whose flush has since
        landed (non-blocking poll per ticket; ``final`` blocks one
        ticket-timeout each — the pump's exit path). Unresolved tickets
        stay parked: their native buffers are still borrowed by the
        pool, and ``pending`` in the native stats stays >0 for them,
        which is what makes ``stop()``'s drain wait cover them too."""
        kept: list[tuple[int, object]] = []
        for rid, ticket in stalled:
            if not final and not ticket.done():
                kept.append((rid, ticket))
                continue
            try:
                ticket.result(
                    timeout=self._ticket_timeout_s if final else 0.0
                )
                status, ra = 200, 0
            except TimeoutError:
                if final:
                    # Truly wedged past the exit grace: nothing safe
                    # left to do — dropping the respond keeps our side
                    # of the never-release-a-borrowed-buffer contract.
                    continue
                kept.append((rid, ticket))
                continue
            except IngestWorkerError:
                status, ra = 500, 0
            except Exception:  # noqa: BLE001 — the decode verdict
                self._reject("malformed")
                status, ra = 400, 0
            native.frontdoor_respond(self._handle, rid, status, ra)
        stalled[:] = kept

    def _admit_trace(
        self, rid: int, ptr: int, ln: int, pending: list
    ) -> None:
        # Saturation gate first (the PR 2 Retry-After contract): the
        # native side already read the whole body — the drain that
        # keeps a 429 from RSTing a mid-send client happened on the C
        # side by construction.
        if self._retry_after is not None:
            hint = self._retry_after()
            if hint is not None:
                self._reject("saturated")
                native.frontdoor_respond(
                    self._handle, rid, 429, max(int(-(-hint // 1)), 1)
                )
                return
        body = native.frontdoor_body(ptr, ln)
        try:
            ticket = self._pool.submit(body)
        except IngestPoolSaturated:
            self._reject("saturated")
            native.frontdoor_respond(self._handle, rid, 429, 1)
            return
        pending.append((rid, ticket))

    def _serve_signal(self, rid: int, kind: int, ptr: int, ln: int) -> None:
        # Metrics/logs: scrape-cadence traffic — one bytes copy here
        # is noise, and the Python decoders are the single source of
        # truth for these signals (same as the Python receiver).
        data = ctypes.string_at(ptr, ln) if ln else b""
        try:
            if kind == native.FD_KIND_METRICS:
                if self._on_metric_records is not None:
                    from . import otlp_metrics

                    self._on_metric_records(
                        otlp_metrics.decode_metrics_request(data)
                    )
            elif kind == native.FD_KIND_LOGS:
                if self._on_log_records is not None:
                    from .otlp import decode_logs_request

                    self._on_log_records(decode_logs_request(data))
            native.frontdoor_respond(self._handle, rid, 200, 0)
        except Exception:  # noqa: BLE001 — malformed exports answer 400
            self._reject("malformed")
            native.frontdoor_respond(self._handle, rid, 400, 0)

    # -- observability --------------------------------------------------

    def stats(self) -> dict:
        self._sync_native_rejects()
        raw = native.frontdoor_stats(self._handle)
        with self._rejects_lock:
            rejects = dict(self.rejects)
        return {**raw, "rejects": rejects, "port": self.port}

    # -- lifecycle ------------------------------------------------------

    def stop(self, drain_timeout_s: float = 5.0) -> None:
        """Graceful drain: quiesce, let in-flight verdicts land, full
        native stop, join pumps. Idempotent."""
        if self._stopped:
            return
        self._stopped = True
        import time

        native.frontdoor_quiesce(self._handle)
        # "pending" counts every ticket whose conn has not received a
        # verdict — including pump-parked wedged-flush tickets whose
        # buffers the pool still borrows — so this wait also keeps the
        # hard stop (which frees conn buffers) away from live views
        # for as long as the drain budget allows.
        deadline = time.monotonic() + drain_timeout_s
        while time.monotonic() < deadline:
            if native.frontdoor_stats(self._handle)["pending"] == 0:
                break
            time.sleep(0.02)
        native.frontdoor_stop(self._handle)
        for t in self._pumps:
            t.join(timeout=5.0)
        self._sync_native_rejects()
