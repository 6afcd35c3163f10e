"""Native front-door suite: verdict parity, framing fuzz/chaos,
column bit-identity, and intern-table scale.

The tentpole's claims, each pinned:

- **Vocabulary parity** — the native acceptor and the Python receiver
  answer the SAME status for every request in a shared seed corpus
  (valid, malformed, bad/oversized Content-Length, empty, odd paths,
  metrics, logs). One vocabulary, two doors.
- **Column bit-identity** — the same payloads through either door land
  in the pipeline as bit-identical columns (the front door is a
  transport, never a second decoder).
- **Framing fuzz/chaos** — truncation at every framing boundary,
  slowloris header trickle, pipelined requests, oversized and chunked
  refusals, and faultwire RST/corrupt between client and acceptor:
  the server survives all of it and keeps serving.
- **Zero Python in the per-payload loop** — a static pin (mirrored in
  scripts/sanitycheck.py) that runtime/frontdoor.py imports no Python
  HTTP machinery; bodies go socket → native buffer → decode ticket.
- **Intern scale** (the satellite): ≥100k distinct services in ONE
  flush with dense first-appearance ids, lock-free known-batch reads,
  and fleet drift refusal with large tables.
"""

from __future__ import annotations

import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from opentelemetry_demo_tpu.runtime import native
from opentelemetry_demo_tpu.runtime.ingest_pool import (
    IngestPool,
    IngestPoolSaturated,
)
from opentelemetry_demo_tpu.runtime.ingestbench import make_payloads
from opentelemetry_demo_tpu.runtime.otlp import OtlpHttpReceiver
from opentelemetry_demo_tpu.runtime.tensorize import SpanTensorizer

pytestmark = pytest.mark.frontdoor

needs_frontdoor = pytest.mark.skipif(
    not (native.available() and native.frontdoor_available()),
    reason="native front-door library unavailable",
)

MAX_BODY = 1 << 20


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _frontdoor(pool=None, **kw):
    from opentelemetry_demo_tpu.runtime.frontdoor import FrontDoorServer

    if pool is None:
        tz = SpanTensorizer(num_services=32)
        pool = IngestPool(lambda cols: None, tz, workers=1)
        kw.setdefault("_own_pool", None)
    kw.pop("_own_pool", None)
    return FrontDoorServer(pool, port=0, max_body_bytes=MAX_BODY, **kw), pool


def _raw_request(port: int, data: bytes, timeout: float = 10.0) -> bytes:
    """Send raw bytes, read until the peer closes or one full
    header-only response arrived."""
    s = socket.create_connection(("127.0.0.1", port))
    s.settimeout(timeout)
    try:
        if data:
            s.sendall(data)
        buf = b""
        while b"\r\n\r\n" not in buf:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
        return buf
    finally:
        s.close()


def _http(
    method: bytes, path: bytes, body: bytes = b"",
    headers: dict[bytes, bytes] | None = None,
    content_length: bytes | None = None,
) -> bytes:
    hdrs = {b"Host": b"test"}
    if method == b"POST":
        hdrs[b"Content-Length"] = (
            content_length
            if content_length is not None
            else str(len(body)).encode()
        )
    hdrs.update(headers or {})
    head = b"".join(b"%s: %s\r\n" % (k, v) for k, v in hdrs.items())
    return b"%s %s HTTP/1.1\r\n%s\r\n" % (method, path, head) + body


def _status(resp: bytes) -> int | None:
    try:
        return int(resp.split(b" ", 2)[1])
    except (IndexError, ValueError):
        return None


def _header(resp: bytes, name: bytes) -> bytes | None:
    for line in resp.split(b"\r\n\r\n", 1)[0].split(b"\r\n")[1:]:
        k, _, v = line.partition(b":")
        if k.strip().lower() == name.lower():
            return v.strip()
    return None


def _post_python(port: int, path: str, body: bytes) -> int:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body,
        headers={"Content-Type": "application/x-protobuf"},
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


def _columns_fields(cols) -> dict:
    items = (
        cols._asdict().items() if hasattr(cols, "_asdict")
        else vars(cols).items()
    )
    return {k: v for k, v in items if isinstance(v, np.ndarray)}


# The shared seed corpus: (label, path, body, content_length_override).
# Chunked transfer and GETs are deliberately absent — the Python
# receiver never sees a chunked body as such (http.server frames it
# away) and serves no GET routes, so there is no Python verdict to be
# in parity WITH; both get their own directed native tests below.
def _seed_corpus() -> list[tuple[str, str, bytes, bytes | None]]:
    valid = make_payloads(n_requests=2, spans_per_request=16, seed=3)
    return [
        ("valid_traces", "/v1/traces", valid[0], None),
        ("valid_traces_2", "/v1/traces", valid[1], None),
        ("malformed_traces", "/v1/traces", b"\xff\xfe\xfd\xfc", None),
        ("empty_body", "/v1/traces", b"", None),
        ("odd_path_is_traces", "/weird/route", valid[0], None),
        ("bad_content_length", "/v1/traces", b"xx", b"banana"),
        (
            "oversized",
            "/v1/traces",
            b"",
            str(MAX_BODY + 1).encode(),
        ),
        ("malformed_metrics", "/v1/metrics", b"\xff\xff\xff", None),
        ("empty_metrics", "/v1/metrics", b"", None),
        ("empty_logs", "/v1/logs", b"", None),
    ]


# ---------------------------------------------------------------------------
# parity + bit-identity
# ---------------------------------------------------------------------------

@needs_frontdoor
class TestParity:
    def test_frontdoor_status_parity_shared_corpus(self):
        """Native and Python doors answer the SAME status for every
        corpus request (oversized is compared without sending a body —
        both refuse on the declared length alone)."""
        corpus = _seed_corpus()

        # Python side: receiver + pool, raw sockets so the corpus's
        # broken Content-Length values can actually go on the wire.
        tz_py = SpanTensorizer(num_services=32)
        pool_py = IngestPool(lambda cols: None, tz_py, workers=1)
        rx = OtlpHttpReceiver(
            lambda r: None, host="127.0.0.1", port=0,
            on_payload=pool_py.submit,
            on_metric_records=lambda recs: None,
            on_log_records=lambda recs: None,
            max_body_bytes=MAX_BODY,
        )
        rx.start()
        py_status = {}
        try:
            for label, path, body, cl in corpus:
                resp = _raw_request(
                    rx.port,
                    _http(b"POST", path.encode(), body, content_length=cl),
                )
                py_status[label] = _status(resp)
        finally:
            rx.stop()
            pool_py.close()

        fd, pool = _frontdoor(on_metric_records=lambda recs: None,
                              on_log_records=lambda recs: None)
        fd_status = {}
        try:
            for label, path, body, cl in corpus:
                resp = _raw_request(
                    fd.port,
                    _http(b"POST", path.encode(), body, content_length=cl),
                )
                fd_status[label] = _status(resp)
        finally:
            fd.stop()
            pool.close()

        assert fd_status == py_status, (
            f"verdict vocabulary drift: native={fd_status} "
            f"python={py_status}"
        )
        # And the vocabulary is the one the contract names, not merely
        # self-consistent.
        assert py_status["valid_traces"] == 200
        assert py_status["malformed_traces"] == 400
        assert py_status["bad_content_length"] == 400
        assert py_status["oversized"] == 413

    def test_frontdoor_columns_byte_identical(self):
        """Same payloads, either door, bit-identical pipeline columns
        (one payload per flush: workers=1 + drain per request keeps
        flush boundaries deterministic on both sides)."""
        payloads = make_payloads(n_requests=4, spans_per_request=64, seed=9)

        def run_python() -> list:
            tz = SpanTensorizer(num_services=32)
            got: list = []
            pool = IngestPool(got.append, tz, workers=1)
            rx = OtlpHttpReceiver(
                lambda r: None, host="127.0.0.1", port=0,
                on_payload=pool.submit, max_body_bytes=MAX_BODY,
            )
            rx.start()
            try:
                for p in payloads:
                    assert _post_python(rx.port, "/v1/traces", p) == 200
                    pool.drain()
            finally:
                rx.stop()
                pool.close()
            return got

        def run_frontdoor() -> list:
            tz = SpanTensorizer(num_services=32)
            got: list = []
            pool = IngestPool(got.append, tz, workers=1)
            fd, _ = _frontdoor(pool=pool)
            try:
                for p in payloads:
                    resp = _raw_request(
                        fd.port, _http(b"POST", b"/v1/traces", p)
                    )
                    assert _status(resp) == 200
                    pool.drain()
            finally:
                fd.stop()
                pool.close()
            return got

        py_cols = run_python()
        fd_cols = run_frontdoor()
        assert len(py_cols) == len(fd_cols) == len(payloads)
        for a, b in zip(py_cols, fd_cols):
            fa, fb = _columns_fields(a), _columns_fields(b)
            assert fa.keys() == fb.keys()
            for k in fa:
                assert fa[k].dtype == fb[k].dtype, k
                assert np.array_equal(fa[k], fb[k]), (
                    f"column {k} differs between doors"
                )


# ---------------------------------------------------------------------------
# framing fuzz / chaos
# ---------------------------------------------------------------------------

@needs_frontdoor
class TestFraming:
    def test_frontdoor_truncation_every_boundary(self):
        """Close the connection at EVERY byte of the framing prefix
        (request line + headers + blank line) and at body boundaries:
        the acceptor must survive each cut and keep serving."""
        payload = make_payloads(n_requests=1, spans_per_request=8)[0]
        req = _http(b"POST", b"/v1/traces", payload)
        head_len = req.index(b"\r\n\r\n") + 4
        cuts = list(range(head_len + 1)) + [
            head_len + 1,
            head_len + len(payload) // 2,
            len(req) - 1,
        ]
        fd, pool = _frontdoor()
        try:
            for cut in cuts:
                s = socket.create_connection(("127.0.0.1", fd.port))
                s.sendall(req[:cut])
                s.close()
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if fd.stats()["live_conns"] == 0:
                    break
                time.sleep(0.02)
            # Still serving after every cut.
            resp = _raw_request(fd.port, req)
            assert _status(resp) == 200
            stats = fd.stats()
            # Cuts inside the body are "truncated" verdicts (framing
            # promised more bytes than arrived); cuts before the blank
            # line just end a header read. Either way: nothing leaks.
            assert stats["truncated"] >= 1
            assert stats["live_conns"] <= 1
        finally:
            fd.stop()
            pool.close()

    def test_frontdoor_slowloris(self):
        """A header trickled one byte at a time hits the header
        deadline and gets the connection closed — the acceptor's slot
        is not hostage to a slow client."""
        fd, pool = _frontdoor(header_timeout_ms=400)
        try:
            s = socket.create_connection(("127.0.0.1", fd.port))
            s.settimeout(10.0)
            closed = False
            t0 = time.monotonic()
            try:
                for ch in b"POST /v1/traces HTTP/1.1\r\nHost: x\r\n":
                    s.sendall(bytes([ch]))
                    time.sleep(0.05)
                    if time.monotonic() - t0 > 5.0:
                        break
                # Server should have given up by now.
                got = s.recv(1024)
                closed = got == b""
            except (ConnectionError, BrokenPipeError, OSError):
                closed = True
            finally:
                s.close()
            assert closed, "slowloris connection was never shed"
            # And the door still serves promptly.
            payload = make_payloads(n_requests=1, spans_per_request=4)[0]
            resp = _raw_request(
                fd.port, _http(b"POST", b"/v1/traces", payload)
            )
            assert _status(resp) == 200
        finally:
            fd.stop()
            pool.close()

    def test_frontdoor_pipelined_requests(self):
        """Three requests in one write: three responses, in order,
        each with its OWN verdict (the middle one is malformed)."""
        good = make_payloads(n_requests=1, spans_per_request=8)[0]
        wire = (
            _http(b"POST", b"/v1/traces", good)
            + _http(b"POST", b"/v1/traces", b"\xff\xfe\xfd")
            + _http(b"POST", b"/v1/traces", good)
        )
        fd, pool = _frontdoor()
        try:
            s = socket.create_connection(("127.0.0.1", fd.port))
            s.settimeout(15.0)
            try:
                s.sendall(wire)
                buf = b""
                statuses = []
                while len(statuses) < 3:
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
                    while b"\r\n\r\n" in buf and len(statuses) < 3:
                        head, buf = buf.split(b"\r\n\r\n", 1)
                        statuses.append(_status(head + b"\r\n\r\n"))
            finally:
                s.close()
            assert statuses == [200, 400, 200]
        finally:
            fd.stop()
            pool.close()

    def test_frontdoor_oversized_413(self):
        """An oversized Content-Length is refused WITHOUT reading the
        body, with Connection: close — the unread remainder must never
        be parsed as a next request."""
        fd, pool = _frontdoor()
        try:
            s = socket.create_connection(("127.0.0.1", fd.port))
            s.settimeout(10.0)
            try:
                s.sendall(
                    b"POST /v1/traces HTTP/1.1\r\nHost: x\r\n"
                    b"Content-Length: %d\r\n\r\n" % (MAX_BODY + 1)
                )
                buf = b""
                while b"\r\n\r\n" not in buf:
                    chunk = s.recv(65536)
                    if not chunk:
                        break
                    buf += chunk
                assert _status(buf) == 413
                assert (_header(buf, b"Connection") or b"").lower() == b"close"
                # The server closes without waiting for the body.
                assert s.recv(1024) == b""
            finally:
                s.close()
            assert fd.stats()["oversized"] == 1
        finally:
            fd.stop()
            pool.close()

    def test_frontdoor_chunked_rejected(self):
        """Transfer-Encoding: chunked is refused 400 + close: the
        zero-copy body read frames on Content-Length alone, and the
        chunked bytes must not be parsed as a next request."""
        fd, pool = _frontdoor()
        try:
            resp = _raw_request(
                fd.port,
                b"POST /v1/traces HTTP/1.1\r\nHost: x\r\n"
                b"Transfer-Encoding: chunked\r\n\r\n"
                b"4\r\nwxyz\r\n0\r\n\r\n",
            )
            assert _status(resp) == 400
            assert fd.stats()["chunked"] == 1
            # Still serving.
            payload = make_payloads(n_requests=1, spans_per_request=4)[0]
            resp = _raw_request(
                fd.port, _http(b"POST", b"/v1/traces", payload)
            )
            assert _status(resp) == 200
        finally:
            fd.stop()
            pool.close()

    def test_frontdoor_faultwire_chaos(self):
        """The chaos proxy between client and acceptor: mid-stream
        truncation kills requests, seeded corruption mangles framing —
        the acceptor answers its vocabulary (or sheds the conn) and
        keeps serving clean traffic throughout."""
        from opentelemetry_demo_tpu.runtime.faultwire import FaultWire

        payload = make_payloads(n_requests=1, spans_per_request=8)[0]
        req = _http(b"POST", b"/v1/traces", payload)
        fd, pool = _frontdoor()
        proxy = FaultWire("127.0.0.1", fd.port)
        proxy.start()
        try:
            # Clean through the proxy first: the path works.
            assert _status(_raw_request(proxy.port, req)) == 200
            # Truncate every connection mid-request.
            proxy.truncate_after = 30
            for _ in range(4):
                try:
                    _raw_request(proxy.port, req, timeout=5.0)
                except OSError:
                    pass
            proxy.clear()
            # Seeded corruption: responses may be garbage or 400s;
            # the server must neither crash nor wedge.
            proxy.corrupt_rate = 0.02
            proxy.corrupt_seed = 1234
            for _ in range(4):
                try:
                    _raw_request(proxy.port, req, timeout=5.0)
                except OSError:
                    pass
            proxy.clear()
            # Direct (no proxy): still healthy, still serving.
            assert _status(_raw_request(fd.port, req)) == 200
            assert _raw_request(
                fd.port, b"GET /healthz HTTP/1.1\r\nHost: x\r\n\r\n"
            ).startswith(b"HTTP/1.1 200")
        finally:
            proxy.stop()
            fd.stop()
            pool.close()


# ---------------------------------------------------------------------------
# control plane: saturation, drain, the zero-Python pin
# ---------------------------------------------------------------------------

class _StubTicket:
    def __init__(self, delay_s: float = 0.0, exc: Exception | None = None):
        self._delay = delay_s
        self._exc = exc

    def result(self, timeout: float | None = None):
        time.sleep(self._delay)
        if self._exc is not None:
            raise self._exc
        return object()


class _StubPool:
    """Duck-typed IngestPool: scripted verdicts for the control-plane
    tests (the real pool's vocabulary is covered by TestParity)."""

    def __init__(self):
        self.mode = "ok"
        self.submitted = 0

    def submit(self, payload):
        self.submitted += 1
        if self.mode == "saturated":
            raise IngestPoolSaturated("full")
        if self.mode == "slow":
            return _StubTicket(delay_s=0.3)
        return _StubTicket()


@needs_frontdoor
class TestControlPlane:
    def test_frontdoor_saturation_retry_after(self):
        """Pipeline saturation → 429 with the admission hint rounded
        UP to an integer Retry-After (the PR 2 contract); pool
        saturation → 429 with Retry-After: 1."""
        from opentelemetry_demo_tpu.runtime.frontdoor import FrontDoorServer

        hint = [None]
        pool = _StubPool()
        fd = FrontDoorServer(
            pool, port=0, max_body_bytes=MAX_BODY,
            retry_after=lambda: hint[0],
        )
        try:
            req = _http(b"POST", b"/v1/traces", b"\x0a\x00")
            assert _status(_raw_request(fd.port, req)) == 200

            hint[0] = 2.3
            resp = _raw_request(fd.port, req)
            assert _status(resp) == 429
            assert _header(resp, b"Retry-After") == b"3"

            hint[0] = None
            pool.mode = "saturated"
            resp = _raw_request(fd.port, req)
            assert _status(resp) == 429
            assert _header(resp, b"Retry-After") == b"1"
            assert fd.rejects.get("saturated", 0) == 2
        finally:
            fd.stop()

    def test_frontdoor_graceful_drain(self):
        """stop() quiesces the listener, lets the in-flight verdict
        land (the client gets its real 200, not a RST), then tears
        down; new connections are refused after."""
        from opentelemetry_demo_tpu.runtime.frontdoor import FrontDoorServer

        pool = _StubPool()
        pool.mode = "slow"
        fd = FrontDoorServer(pool, port=0, max_body_bytes=MAX_BODY)
        port = fd.port
        req = _http(b"POST", b"/v1/traces", b"\x0a\x00")
        got: dict = {}

        def client():
            got["resp"] = _raw_request(port, req, timeout=15.0)

        t = threading.Thread(target=client, daemon=True)
        t.start()
        # Let the request reach the pump before draining.
        deadline = time.monotonic() + 5.0
        while pool.submitted == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        fd.stop(drain_timeout_s=10.0)
        t.join(timeout=15.0)
        assert not t.is_alive()
        assert _status(got.get("resp", b"")) == 200
        with pytest.raises(OSError):
            socket.create_connection(("127.0.0.1", port), timeout=2.0)

    def test_frontdoor_wedged_flush_defers_verdict(self):
        """A flush that outlives the pump's ticket timeout must NOT be
        short-circuited to an early 503: responding is what returns the
        native body buffer to the connection thread for recycling, and
        the pool still holds a zero-copy view of it (use-after-free).
        The pump parks the ticket and the REAL verdict goes out when
        the flush finally lands."""
        from opentelemetry_demo_tpu.runtime.frontdoor import FrontDoorServer

        class _WedgedTicket:
            def __init__(self):
                self._ev = threading.Event()

            def done(self):
                return self._ev.is_set()

            def result(self, timeout=None):
                if not self._ev.wait(timeout):
                    raise TimeoutError("wedged flush")

        class _WedgedPool:
            def __init__(self):
                self.tickets = []

            def submit(self, payload):
                t = _WedgedTicket()
                self.tickets.append(t)
                return t

        pool = _WedgedPool()
        fd = FrontDoorServer(
            pool, port=0, max_body_bytes=MAX_BODY, ticket_timeout_s=0.15
        )
        try:
            req = _http(b"POST", b"/v1/traces", b"\x0a\x00")
            got: dict = {}

            def client():
                got["resp"] = _raw_request(fd.port, req, timeout=15.0)

            t = threading.Thread(target=client, daemon=True)
            t.start()
            deadline = time.monotonic() + 5.0
            while not pool.tickets and time.monotonic() < deadline:
                time.sleep(0.01)
            assert pool.tickets, "request never reached the pool"
            # Well past the ticket timeout: no premature verdict may
            # have landed while the buffer is still borrowed.
            time.sleep(0.6)
            assert "resp" not in got
            pool.tickets[0]._ev.set()  # the flush finally resolves
            t.join(timeout=10.0)
            assert _status(got.get("resp", b"")) == 200
        finally:
            fd.stop()

    def test_frontdoor_no_python_http_in_payload_path(self):
        """The zero-Python pin, enforced from inside the suite as well
        as sanitycheck: the front door's module may not import any
        Python HTTP machinery — the per-payload loop is native, and a
        convenience import here would silently rebuild the old wall."""
        import ast
        import inspect

        from opentelemetry_demo_tpu.runtime import frontdoor as fd_mod

        tree = ast.parse(inspect.getsource(fd_mod))
        imported: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported.update(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom) and node.module:
                imported.add(node.module)
                imported.update(
                    f"{node.module}.{a.name}" for a in node.names
                )
        banned = (
            "http", "http.server", "http.client", "socketserver",
            "urllib", "urllib.request", "wsgiref", "asyncio",
        )
        for mod in imported:
            top = mod.split(".", 1)[0]
            assert top not in banned and mod not in banned, (
                f"frontdoor.py imports {mod!r}: Python HTTP "
                "machinery has no business in the per-payload path"
            )


# ---------------------------------------------------------------------------
# intern-table scale (satellite: ≥100k distinct services, one flush)
# ---------------------------------------------------------------------------

class TestInternScale:
    def test_intern_100k_one_flush_bit_identity(self):
        """100k distinct services in ONE batched intern: dense
        first-appearance ids (bit-identical to the serial assignment
        rule), stable on re-intern, overflow bucket only past
        capacity."""
        n = 100_000
        names = [f"svc-{i:06d}" for i in range(n)]

        # Capacity above the batch: ids are exactly the dense ranks.
        tz = SpanTensorizer(num_services=n + 1)
        ids = tz.intern_many(names)
        assert ids == list(range(n))
        assert tz.intern_many(names) == ids  # re-intern: stable
        assert len(tz.service_names) == n

        # The serial twin (the ONE assignment rule) agrees on a
        # sampled prefix — service_id publishes per miss, so the twin
        # stays small while still pinning the shared rule.
        twin = SpanTensorizer(num_services=n + 1)
        assert [twin.service_id(nm) for nm in names[:2000]] == ids[:2000]

        # Capacity far below the batch: everything past num_services-1
        # folds into the overflow bucket, ids below stay dense.
        cap = 1024
        tz_small = SpanTensorizer(num_services=cap)
        small_ids = tz_small.intern_many(names)
        assert small_ids == [min(i, cap - 1) for i in range(n)]
        # The table stays BOUNDED at the key budget: overflow names
        # are counted, never memorized (the key lifecycle plane's
        # contract — the sketch axis saturating must not grow host
        # memory either).
        assert len(tz_small.service_names) == cap - 1
        assert tz_small.overflow_assigns_total == n - (cap - 1)
        # Re-intern: dense ids stable, overflow stable but re-counted
        # (unmemorized keys re-apply on every sighting).
        assert tz_small.intern_many(names) == small_ids
        assert tz_small.overflow_assigns_total == 2 * (n - (cap - 1))

    def test_intern_known_batch_lock_free(self):
        """A batch of already-known names resolves from the published
        snapshot WITHOUT touching the intern lock: hold the lock from
        another thread and the known-batch read must still complete."""
        n = 10_000
        names = [f"svc-{i:05d}" for i in range(n)]
        tz = SpanTensorizer(num_services=n + 1)
        expected = tz.intern_many(names)

        got: dict = {}
        with tz._intern_lock:  # noqa: SLF001 — the property under test
            t = threading.Thread(
                target=lambda: got.__setitem__(
                    "ids", tz.intern_many(names)
                ),
                daemon=True,
            )
            t.start()
            t.join(timeout=5.0)
            assert not t.is_alive(), (
                "known-batch intern blocked on the lock: the "
                "lock-free snapshot path regressed"
            )
        assert got["ids"] == expected

    def test_fleet_drift_refusal_large_tables(self):
        """merge_shard_arrays refuses a drifted geometry when the
        tables are large (1<<17 rows), and still merges exactly when
        geometry matches — drift refusal is not a small-table
        artifact."""
        from opentelemetry_demo_tpu.runtime.fleet import (
            ShardMergeError,
            merge_shard_arrays,
        )

        rows = 1 << 17
        rng = np.random.default_rng(42)
        a = {
            "cms_bank": rng.integers(0, 50, (rows, 8), dtype=np.int64),
            "hll_bank": rng.integers(0, 30, (rows, 4), dtype=np.int8),
        }
        b_ok = {
            "cms_bank": rng.integers(0, 50, (rows, 8), dtype=np.int64),
            "hll_bank": rng.integers(0, 30, (rows, 4), dtype=np.int8),
        }
        merged = merge_shard_arrays(a, b_ok)
        assert np.array_equal(
            merged["cms_bank"], a["cms_bank"] + b_ok["cms_bank"]
        )
        assert np.array_equal(
            merged["hll_bank"], np.maximum(a["hll_bank"], b_ok["hll_bank"])
        )

        for drifted in (
            {"cms_bank": np.zeros((rows + 1, 8), np.int64)},
            {"hll_bank": np.zeros((rows, 5), np.int8)},
        ):
            src = {**b_ok, **drifted}
            with pytest.raises(ShardMergeError):
                merge_shard_arrays(a, src)
