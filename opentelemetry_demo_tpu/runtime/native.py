"""ctypes bindings for the native C++ libraries (native/*.cc).

Two kernels live behind this module:

- **ingest** — protobuf payloads → columnar numpy arrays, the host
  half of the ≥200k spans/sec budget (SURVEY.md §7 hard part (a):
  "protobuf decode and hashing must be vectorized/C-accelerated and
  batched"). Semantics pinned to the Python reference decoders by
  tests/test_native_ingest.py.
- **currency** — Money conversion/sum carry arithmetic (the reference
  keeps currency native in C++, server.cpp; so does this framework).
  Semantics pinned by tests/test_native_currency.py.

Build-on-demand: each library is one translation unit compiled with
``g++ -O3`` (~1 s). The output is named by a hash of the compiler, its
flags and the source bytes, so a library built from other source or
with other flags (a copy of ``_build/`` from elsewhere, a stale build)
is never loaded. Environments without a compiler simply report
``available() == False`` and callers fall back to the pure-Python
paths — same results, less throughput. ``python -m
opentelemetry_demo_tpu.runtime.native`` builds all of them (the images
do this at build time).

**GIL contract** (the ingest pool's scaling story): libraries load via
``ctypes.CDLL`` — NOT ``ctypes.PyDLL`` — so ctypes RELEASES the GIL
for the duration of every foreign call and re-acquires it on return.
While one decode worker is inside ``otd_decode_otlp_many``, other
workers run Python (or their own native calls) in true parallel; N
decode workers therefore scale until they saturate cores, not the
interpreter lock. The C code touches no Python objects (payload bytes
pass as borrowed ``c_char_p`` pointers kept alive by the caller's
references; outputs are raw numpy-owned memory), which is what makes
the GIL-free window safe. Pinned by
tests/test_ingest_pool.py::test_native_decode_releases_gil — a Python
counter thread must keep making progress while a big decode call is
in flight.

The r15 two-pass scanner extends the same contract INSIDE the window:
``otd_decode_otlp_many`` may spawn ``threads`` native OS threads to
shard its pass-2 extraction. Those threads live entirely within the
GIL-free foreign call (spawned and joined before ctypes re-acquires),
touch only the raw C buffers, and never see a Python object — so the
safety argument is unchanged and the sharding is invisible to the
interpreter beyond the call returning sooner.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import NamedTuple, Sequence

import numpy as np

_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")

_lock = threading.Lock()
_libs: dict[str, ctypes.CDLL] = {}
_errors: dict[str, str] = {}


class ColumnarSpans(NamedTuple):
    """Decoded OTLP spans as columns (one row per span, document order).

    ``svc_idx`` points into ``services`` (one entry per resource-spans
    block). ``None`` means the resource had no service.name — the
    record-level decoder's "unknown" — which is distinct from a
    present-but-empty name (interned as ``""``, exactly as the record
    path does).
    """

    duration_us: np.ndarray  # float32[N]
    trace_key: np.ndarray  # uint64[N] — first 8 bytes of trace_id, LE
    is_error: np.ndarray  # uint8[N]
    attr_crc: np.ndarray  # uint32[N] — CRC32 of the chosen attr value
    attr_present: np.ndarray  # uint8[N]
    svc_idx: np.ndarray  # int32[N]
    event_count: np.ndarray  # int32[N] — span events on the span
    has_exception: np.ndarray  # uint8[N] — exception/error event present
    services: list[str | None]


class ColumnarOrders(NamedTuple):
    """Decoded OrderResult batch as columns (one row per message)."""

    value_units: np.ndarray  # float32[N] — shipping cost (value lane)
    order_key: np.ndarray  # uint64[N] — first 8 bytes of order id
    attr_crc: np.ndarray  # uint32[N] — CRC32 of first product id


# ingest.cc's sharded decode_many spawns std::thread workers; -pthread
# is required for that on Linux and harmless for the single-threaded
# translation units sharing this build rule.
_CXXFLAGS = (
    "-O3", "-std=c++17", "-fPIC", "-Wall", "-Wextra", "-pthread", "-shared",
)


def _lib_path(name: str) -> str:
    """Where native/<name>.cc's library lives for THIS source, compiler
    and flags: the file name carries their hash."""
    cxx = os.environ.get("CXX", "g++")
    h = hashlib.sha256("\0".join((cxx,) + _CXXFLAGS).encode())
    with open(os.path.join(_DIR, f"{name}.cc"), "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, "_build", f"libotd_{name}-{h.hexdigest()[:16]}.so")


def _build(name: str, out: str) -> str | None:
    """Compile native/<name>.cc to ``out`` unless it exists; returns an
    error string on failure."""
    if os.path.exists(out):
        return None
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # Build beside, then rename: concurrent builders (test workers,
    # several daemons) never load a half-written library.
    tmp = f"{out}.{os.getpid()}.{threading.get_ident()}.tmp"
    cmd = [
        os.environ.get("CXX", "g++"), *_CXXFLAGS, "-o", tmp,
        os.path.join(_DIR, f"{name}.cc"),
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{cmd[0]}: {e}"
    if proc.returncode != 0:
        if os.path.exists(tmp):
            os.remove(tmp)
        return proc.stderr.strip() or f"{cmd[0]} exited {proc.returncode}"
    os.replace(tmp, out)
    return None


def _lib_for(name: str) -> ctypes.CDLL | None:
    """Build+load native/<name>.cc on first use (cached, thread-safe)."""
    lib = _libs.get(name)  # lock-free hot path (GIL-safe dict read)
    if lib is not None:
        return lib
    with _lock:
        if name in _libs:
            return _libs[name]
        if name in _errors:
            return None
        out = _lib_path(name)
        err = _build(name, out)
        if err is not None:
            _errors[name] = err
            return None
        lib = ctypes.CDLL(out)
        _CONFIGURE[name](lib)
        _libs[name] = lib
        return lib


def _configure_ingest(lib: ctypes.CDLL) -> None:
    # Payload pointers are declared c_char_p so Python bytes pass
    # zero-copy (the C side only reads; lengths travel separately,
    # so embedded NULs are fine).
    lib.otd_decode_otlp.restype = ctypes.c_int
    lib.otd_decode_otlp.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,           # buf, len
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,  # keys
        ctypes.c_int,                               # cap
        ctypes.c_void_p, ctypes.c_void_p,           # duration, trace
        ctypes.c_void_p, ctypes.c_void_p,           # err, crc
        ctypes.c_void_p, ctypes.c_void_p,           # present, svc_idx
        ctypes.c_void_p, ctypes.c_void_p,           # event_count, has_exc
        ctypes.c_char_p, ctypes.c_size_t,           # svc_buf, cap
        ctypes.c_void_p, ctypes.c_int,              # svc_len, rs_cap
        ctypes.POINTER(ctypes.c_int32),             # n_services
    ]
    lib.otd_decode_otlp_many.restype = ctypes.c_int
    lib.otd_decode_otlp_many.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_void_p,  # bufs, lens
        ctypes.c_int,                               # n_payloads
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,  # keys
        ctypes.c_int,                               # cap
        ctypes.c_void_p, ctypes.c_void_p,           # duration, trace
        ctypes.c_void_p, ctypes.c_void_p,           # err, crc
        ctypes.c_void_p, ctypes.c_void_p,           # present, svc_idx
        ctypes.c_void_p, ctypes.c_void_p,           # event_count, has_exc
        ctypes.c_char_p, ctypes.c_size_t,           # svc_buf, cap
        ctypes.c_void_p, ctypes.c_int,              # svc_len, rs_cap
        ctypes.POINTER(ctypes.c_int32),             # n_services
        ctypes.c_void_p,                            # payload_rows
        ctypes.c_int, ctypes.c_longlong,            # n_threads, shard_min
        ctypes.POINTER(ctypes.c_double),            # scan_s
        ctypes.POINTER(ctypes.c_double),            # extract_s
    ]
    # Two-pass split, exposed raw for the decodebench microbench and
    # the boundary-adversarial fuzz suite: pass 1 (structural scan →
    # span index) and pass 2 (index → columns).
    lib.otd_scan_otlp.restype = ctypes.c_int
    lib.otd_scan_otlp.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,           # buf, len
        ctypes.c_void_p, ctypes.c_void_p,           # span_off, span_len
        ctypes.c_void_p, ctypes.c_int,              # span_svc, span_cap
        ctypes.c_char_p, ctypes.c_size_t,           # svc_buf, cap
        ctypes.c_void_p, ctypes.c_int,              # svc_len, rs_cap
        ctypes.POINTER(ctypes.c_int32),             # n_services
    ]
    lib.otd_extract_otlp.restype = ctypes.c_int
    lib.otd_extract_otlp.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,           # buf, len
        ctypes.c_void_p, ctypes.c_void_p,           # span_off, span_len
        ctypes.c_void_p, ctypes.c_int,              # span_svc, n_spans
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,  # keys
        ctypes.c_void_p, ctypes.c_void_p,           # duration, trace
        ctypes.c_void_p, ctypes.c_void_p,           # err, crc
        ctypes.c_void_p, ctypes.c_void_p,           # present, svc_idx
        ctypes.c_void_p, ctypes.c_void_p,           # event_count, has_exc
    ]
    lib.otd_decode_orders.restype = ctypes.c_int
    lib.otd_decode_orders.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_void_p, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.otd_crc32.restype = ctypes.c_uint32
    lib.otd_crc32.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    # CRC-32C (frame checksum): void* so ndarray memory passes by
    # address without a tobytes copy — checksumming the SOURCE view is
    # what makes frame.encode's scratch-race detection work.
    lib.otd_crc32c.restype = ctypes.c_uint32
    lib.otd_crc32c.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint32
    ]
    # Install the USD-normalization table for the order value lane once
    # per load — the same factors kafka_orders.order_to_record applies
    # on the Python path (currency_data is a leaf module; no cycle).
    lib.otd_set_order_rates.restype = None
    lib.otd_set_order_rates.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_double), ctypes.c_int
    ]
    from ..currency_data import EUR_RATES, to_usd_factor

    # The C side clamps at 64 entries SILENTLY — growing EUR_RATES past
    # that would diverge native (factor 1.0) from Python (real factor).
    assert len(EUR_RATES) <= 64, "EUR_RATES exceeds native rate-table cap"
    codes = b"".join(
        code.encode().ljust(8, b"\0")[:8] for code in EUR_RATES
    )
    factors = (ctypes.c_double * len(EUR_RATES))(
        *(to_usd_factor(code) for code in EUR_RATES)
    )
    lib.otd_set_order_rates(codes, factors, len(EUR_RATES))


def _configure_currency(lib: ctypes.CDLL) -> None:
    for fn in (lib.otd_money_convert, lib.otd_money_sum):
        fn.restype = ctypes.c_int
    lib.otd_money_convert.argtypes = [
        ctypes.c_double, ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.otd_money_sum.argtypes = [
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
    ]


def _configure_shipping(lib: ctypes.CDLL) -> None:
    lib.otd_quote_money.restype = ctypes.c_int
    lib.otd_quote_money.argtypes = [
        ctypes.c_double, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.otd_tracking_id.restype = ctypes.c_int
    lib.otd_tracking_id.argtypes = [
        ctypes.c_char_p, ctypes.c_int32, ctypes.c_char_p
    ]


def _configure_frontdoor(lib: ctypes.CDLL) -> None:
    # The native HTTP front door (frontdoor.cc): acceptor + per-conn
    # threads live entirely on the C side; these entry points are the
    # pump's batch drain and verdict write-back. otd_fd_next blocks
    # with the GIL released (ctypes.CDLL — the same contract as the
    # decode calls), so a waiting pump costs the interpreter nothing.
    lib.otd_fd_start.restype = ctypes.c_int64
    lib.otd_fd_start.argtypes = [
        ctypes.c_int32, ctypes.c_int64,             # port, max_body
        ctypes.c_int32, ctypes.c_int64,             # max_conns, hdr_timeout
    ]
    lib.otd_fd_port.restype = ctypes.c_int32
    lib.otd_fd_port.argtypes = [ctypes.c_int64]
    lib.otd_fd_next.restype = ctypes.c_int64
    lib.otd_fd_next.argtypes = [
        ctypes.c_int64,                             # handle
        ctypes.c_void_p, ctypes.c_void_p,           # ids, kinds
        ctypes.c_void_p, ctypes.c_void_p,           # ptrs, lens
        ctypes.c_int64, ctypes.c_int64,             # max_n, timeout_ms
    ]
    lib.otd_fd_respond.restype = ctypes.c_int32
    lib.otd_fd_respond.argtypes = [
        ctypes.c_int64, ctypes.c_int64,             # handle, req id
        ctypes.c_int32, ctypes.c_int32,             # status, retry_after
    ]
    lib.otd_fd_stats.restype = None
    lib.otd_fd_stats.argtypes = [ctypes.c_int64, ctypes.c_void_p]
    lib.otd_fd_quiesce.restype = None
    lib.otd_fd_quiesce.argtypes = [ctypes.c_int64]
    lib.otd_fd_stop.restype = None
    lib.otd_fd_stop.argtypes = [ctypes.c_int64]


_CONFIGURE = {
    "ingest": _configure_ingest,
    "frontdoor": _configure_frontdoor,
    "currency": _configure_currency,
    "shipping": _configure_shipping,
}


def _load() -> ctypes.CDLL | None:
    return _lib_for("ingest")


def available() -> bool:
    return _load() is not None


def load_error() -> str | None:
    """Why the ingest library is unavailable (None when it loaded)."""
    _load()
    return _errors.get("ingest")


def currency_available() -> bool:
    return _lib_for("currency") is not None


def frontdoor_available() -> bool:
    return _lib_for("frontdoor") is not None


def frontdoor_load_error() -> str | None:
    _lib_for("frontdoor")
    return _errors.get("frontdoor")


# Signal kinds a front-door ticket carries (frontdoor.cc constants):
# the pump routes traces to the decode pool's pointer path and
# metrics/logs — scrape-cadence traffic — to the Python decoders.
FD_KIND_TRACES = 0
FD_KIND_METRICS = 1
FD_KIND_LOGS = 2

# otd_fd_stats slot names, in the C enum's order (frontdoor.cc
# StatIdx) — keep in sync.
FD_STAT_NAMES = (
    "accepted", "live_conns", "enqueued", "pending", "bad_length",
    "oversized", "chunked", "truncated", "disconnect", "overcap",
    "health", "notfound", "bytes_in", "responded",
)


class FrontDoorBatch(NamedTuple):
    """Reusable drain buffers for :func:`frontdoor_next` — allocated
    once per pump so the steady-state drain performs zero numpy
    allocations."""

    ids: np.ndarray  # int64[max_n] — ticket ids
    kinds: np.ndarray  # int32[max_n] — FD_KIND_*
    ptrs: np.ndarray  # uint64[max_n] — native body addresses
    lens: np.ndarray  # int64[max_n] — body lengths


def frontdoor_alloc_batch(max_n: int) -> FrontDoorBatch:
    return FrontDoorBatch(
        np.empty(max_n, np.int64), np.empty(max_n, np.int32),
        np.empty(max_n, np.uint64), np.empty(max_n, np.int64),
    )


def frontdoor_start(
    port: int, max_body: int, max_conns: int = 64,
    header_timeout_ms: int = 10000,
) -> int:
    """Start a native front door; returns the server handle.

    Raises ``RuntimeError`` when the library is unavailable or the
    port cannot be bound (the daemon surfaces either as a boot error —
    an opt-in front door that silently didn't bind would make the
    operator think the fast path is serving).
    """
    lib = _lib_for("frontdoor")
    if lib is None:
        raise RuntimeError(
            f"native frontdoor unavailable: {frontdoor_load_error()}"
        )
    h = lib.otd_fd_start(
        int(port), int(max_body), int(max_conns), int(header_timeout_ms)
    )
    if h < 0:
        raise RuntimeError(f"frontdoor bind failed on port {port}")
    return int(h)


def frontdoor_port(handle: int) -> int:
    lib = _lib_for("frontdoor")
    assert lib is not None
    return int(lib.otd_fd_port(int(handle)))


def frontdoor_next(
    handle: int, batch: FrontDoorBatch, timeout_ms: int = 100
) -> int:
    """Drain up to ``len(batch.ids)`` complete request tickets into
    ``batch`` (blocking up to ``timeout_ms`` with the GIL released).
    Returns the count, 0 on timeout, or -1 once the server is stopping
    and the queue is empty — the pump's exit signal."""
    lib = _lib_for("frontdoor")
    assert lib is not None
    return int(lib.otd_fd_next(
        int(handle), batch.ids.ctypes.data, batch.kinds.ctypes.data,
        batch.ptrs.ctypes.data, batch.lens.ctypes.data,
        batch.ids.shape[0], int(timeout_ms),
    ))


def frontdoor_body(ptr: int, length: int) -> ctypes.Array:
    """Borrow a ticket's native body buffer as a ctypes view — len()
    and the decode pointer path both work on it, with ZERO copy. The
    buffer stays valid until :func:`frontdoor_respond` for its id (the
    frontdoor.cc ownership rule); callers must respond only after the
    decode consumed the bytes."""
    return (ctypes.c_char * int(length)).from_address(int(ptr))


def frontdoor_respond(
    handle: int, req_id: int, status: int, retry_after: int = 0
) -> None:
    """Deliver the verdict for a ticket: the native side writes the
    canned response and recycles the body buffer."""
    lib = _lib_for("frontdoor")
    assert lib is not None
    lib.otd_fd_respond(
        int(handle), int(req_id), int(status), int(retry_after)
    )


def frontdoor_stats(handle: int) -> dict[str, int]:
    lib = _lib_for("frontdoor")
    assert lib is not None
    out = np.zeros(len(FD_STAT_NAMES), np.int64)
    lib.otd_fd_stats(int(handle), out.ctypes.data)
    return {k: int(v) for k, v in zip(FD_STAT_NAMES, out)}


def frontdoor_quiesce(handle: int) -> None:
    """Graceful-drain phase 1: stop accepting; queued tickets keep
    flowing to the pump, new requests answer 503."""
    lib = _lib_for("frontdoor")
    assert lib is not None
    lib.otd_fd_quiesce(int(handle))


def frontdoor_stop(handle: int) -> None:
    """Full stop: 503 every still-queued ticket, wake the pump
    (frontdoor_next returns -1), join every native thread."""
    lib = _lib_for("frontdoor")
    assert lib is not None
    lib.otd_fd_stop(int(handle))


_INT64_MIN, _INT64_MAX = -(1 << 63), (1 << 63) - 1

# Monitored-key ctypes arrays, cached per key tuple: the key set is a
# process-lifetime constant (otlp.MONITORED_ATTR_KEYS), so rebuilding
# the encoded array per decode call was pure per-flush overhead.
_keys_cache: dict[tuple, ctypes.Array] = {}


def _keys_array(attr_keys: Sequence[str]) -> ctypes.Array:
    t = tuple(attr_keys)
    arr = _keys_cache.get(t)
    if arr is None:
        arr = (ctypes.c_char_p * len(t))(*[k.encode() for k in t])
        _keys_cache[t] = arr
    return arr


def money_convert(
    rate: float, units: int, nanos: int
) -> tuple[int, int, int]:
    """(code, units, nanos): code 0 ok, -2 invalid money, -3 overflow.

    The facade (services.currency) maps -2 to MoneyError and falls back
    to Python arithmetic on -3 (arbitrary-precision territory). Inputs
    outside int64 report -3 here — ctypes would otherwise truncate them
    to their low 64 bits before the C++ guard could see them.
    """
    if not (_INT64_MIN <= units <= _INT64_MAX):
        return -3, 0, 0
    lib = _lib_for("currency")
    assert lib is not None
    ou = ctypes.c_int64(0)
    on = ctypes.c_int32(0)
    code = lib.otd_money_convert(
        rate, units, nanos, ctypes.byref(ou), ctypes.byref(on)
    )
    return code, ou.value, on.value


def money_sum(
    u1: int, n1: int, u2: int, n2: int
) -> tuple[int, int, int]:
    """(code, units, nanos) — same code contract as money_convert."""
    if not (
        _INT64_MIN <= u1 <= _INT64_MAX and _INT64_MIN <= u2 <= _INT64_MAX
    ):
        return -3, 0, 0
    lib = _lib_for("currency")
    assert lib is not None
    ou = ctypes.c_int64(0)
    on = ctypes.c_int32(0)
    code = lib.otd_money_sum(
        u1, n1, u2, n2, ctypes.byref(ou), ctypes.byref(on)
    )
    return code, ou.value, on.value


def shipping_available() -> bool:
    return _lib_for("shipping") is not None


def quote_money(per_item: float, count: int) -> tuple[int, int, int]:
    """(code, units, nanos): code 0 ok, -1 bad count, -3 overflow.

    Quote total = round(per_item * count, 2), split from_float-style —
    the native half of services.shipping (see native/shipping.cc)."""
    lib = _lib_for("shipping")
    assert lib is not None
    ou = ctypes.c_int64(0)
    on = ctypes.c_int32(0)
    code = lib.otd_quote_money(per_item, count, ctypes.byref(ou), ctypes.byref(on))
    return code, ou.value, on.value


def tracking_id(name: bytes) -> str:
    """UUID v5 (URL namespace) over ``name`` — uuid.uuid5 parity."""
    lib = _lib_for("shipping")
    assert lib is not None
    out = ctypes.create_string_buffer(36)
    lib.otd_tracking_id(name, len(name), out)
    return out.raw.decode("ascii")


def crc32(data: bytes) -> int:
    lib = _load()
    assert lib is not None
    return int(lib.otd_crc32(data, len(data)))


def crc32c(data, crc: int = 0) -> int:
    """CRC-32C (Castagnoli) — the frame checksum (runtime/frame.py).

    Accepts bytes, bytearray, or a C-contiguous ndarray; array memory
    is checksummed in place (no copy). ``crc`` seeds a running
    checksum (0 to start). Slicing-by-8 in C, GIL released.
    """
    lib = _load()
    assert lib is not None
    if isinstance(data, np.ndarray):
        a = data if data.flags.c_contiguous else np.ascontiguousarray(data)
        return int(lib.otd_crc32c(a.ctypes.data, a.nbytes, crc))
    if isinstance(data, bytearray):
        n = len(data)
        buf = (ctypes.c_char * n).from_buffer(data) if n else b""
        return int(lib.otd_crc32c(buf, n, crc))
    return int(lib.otd_crc32c(bytes(data), len(data), crc))


def decode_otlp(
    payload: bytes, attr_keys: Sequence[str]
) -> ColumnarSpans:
    """Columnar decode of an ExportTraceServiceRequest.

    Raises ``ValueError`` on malformed wire data — the same verdicts as
    ``otlp.decode_export_request`` (the HTTP receiver maps either to a
    400).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native ingest unavailable: {load_error()}")
    keys = _keys_array(attr_keys)
    cap = len(payload) // 16 + 64
    # One name byte per payload byte is the ceiling (names are payload
    # substrings); one resource-spans entry needs ≥2 payload bytes.
    svc_cap = len(payload) + 1
    rs_cap = len(payload) // 2 + 2
    svc_buf = ctypes.create_string_buffer(svc_cap)
    svc_len = np.empty(rs_cap, np.int32)
    n_services = ctypes.c_int32(0)
    retried = False
    while True:
        duration = np.empty(cap, np.float32)
        trace = np.empty(cap, np.uint64)
        err = np.empty(cap, np.uint8)
        crc = np.empty(cap, np.uint32)
        present = np.empty(cap, np.uint8)
        svc_idx = np.empty(cap, np.int32)
        event_count = np.empty(cap, np.int32)
        has_exc = np.empty(cap, np.uint8)
        n = lib.otd_decode_otlp(
            payload, len(payload), keys, len(attr_keys), cap,
            duration.ctypes.data, trace.ctypes.data,
            err.ctypes.data, crc.ctypes.data,
            present.ctypes.data, svc_idx.ctypes.data,
            event_count.ctypes.data, has_exc.ctypes.data,
            svc_buf, svc_cap,
            svc_len.ctypes.data, rs_cap,
            ctypes.byref(n_services),
        )
        if n == -2 and not retried:  # pathological tiny-span payloads
            cap = len(payload) // 2 + 64
            retried = True
            continue
        if n < 0:
            raise ValueError(f"malformed OTLP payload (code {n})")
        services: list[str | None] = []
        pos = 0
        for ln in svc_len[: n_services.value]:
            if ln < 0:
                services.append(None)
            else:
                services.append(
                    svc_buf.raw[pos : pos + ln].decode("utf-8", "replace")
                )
                pos += ln
        return ColumnarSpans(
            duration[:n].copy(), trace[:n].copy(), err[:n].copy(),
            crc[:n].copy(), present[:n].copy(), svc_idx[:n].copy(),
            event_count[:n].copy(), has_exc[:n].copy(),
            services,
        )


class DecodeScratch(NamedTuple):
    """Reusable output buffers for :func:`decode_otlp_many`.

    One scratch set services one in-flight decode; the ingest pool
    keeps a freelist of them (``ingest_pool.ScratchPool``) sized by
    high-watermark so steady-state decode performs ZERO numpy
    allocations — the per-request ``np.empty``×8 churn of the serial
    path was a measured ~2× of its span budget. The decode RESULT
    returned to callers is views into these arrays, so a scratch must
    not be released back to its pool until the caller has copied the
    rows out (the pool's coalesce step does exactly that).
    """

    cap: int
    svc_cap: int
    rs_cap: int
    duration: np.ndarray  # float32[cap]
    trace: np.ndarray  # uint64[cap]
    err: np.ndarray  # uint8[cap]
    crc: np.ndarray  # uint32[cap]
    present: np.ndarray  # uint8[cap]
    svc_idx: np.ndarray  # int32[cap]
    event_count: np.ndarray  # int32[cap]
    has_exc: np.ndarray  # uint8[cap]
    svc_buf: ctypes.Array  # char[svc_cap]
    svc_len: np.ndarray  # int32[rs_cap]


def alloc_scratch(cap: int, svc_cap: int, rs_cap: int) -> DecodeScratch:
    return DecodeScratch(
        cap, svc_cap, rs_cap,
        np.empty(cap, np.float32), np.empty(cap, np.uint64),
        np.empty(cap, np.uint8), np.empty(cap, np.uint32),
        np.empty(cap, np.uint8), np.empty(cap, np.int32),
        np.empty(cap, np.int32), np.empty(cap, np.uint8),
        ctypes.create_string_buffer(svc_cap), np.empty(rs_cap, np.int32),
    )


def scratch_dims(
    payload_bytes: int, n_payloads: int, retry: bool = False
) -> tuple[int, int, int]:
    """(cap, svc_cap, rs_cap) for a coalesced batch — the per-payload
    heuristics of :func:`decode_otlp` summed (``retry`` switches to the
    len/2 span ceiling the single-payload path retries with)."""
    denom = 2 if retry else 16
    return (
        payload_bytes // denom + 64 * max(n_payloads, 1),
        payload_bytes + 1,
        payload_bytes // 2 + 2 * max(n_payloads, 1),
    )


# Default byte floor below which decode_otlp_many never shards a batch
# across native threads: under ~256 KiB the extraction wall is small
# enough that a std::thread spawn/join costs more than it hides.
# Overridden per call (the pool passes ANOMALY_INGEST_SHARD_MIN_BYTES).
SHARD_MIN_BYTES_DEFAULT = 262144


def decode_otlp_many(
    payloads: Sequence[bytes],
    attr_keys: Sequence[str],
    scratch: DecodeScratch | None = None,
    threads: int = 0,
    shard_min_bytes: int = SHARD_MIN_BYTES_DEFAULT,
    phases: dict | None = None,
) -> tuple[ColumnarSpans, np.ndarray]:
    """Batched columnar decode: many requests, ONE ctypes round trip.

    Returns ``(columns, payload_rows)`` where ``columns`` spans every
    well-formed payload (rows append in argument order, ``svc_idx``
    into a batch-wide service list) and ``payload_rows[i]`` is payload
    i's row count or ``-1`` when that payload was malformed — the
    per-request verdict the receivers turn into a 400 for exactly the
    bad request while its batchmates proceed.

    Two-pass under the hood (ingest.cc): a structural scan builds the
    batch-wide span index, then extraction fills the columns — sharded
    across up to ``threads`` native OS threads at span-record
    boundaries (mid-payload included, so ONE oversized export spreads
    over cores) whenever the batch carries ≥ ``shard_min_bytes``.
    ``threads<=1`` keeps the serial extraction. ``phases`` (optional
    dict) receives the per-pass wall seconds as ``{"scan": s,
    "extract": s}`` — the ingest pool feeds them to the
    anomaly_phase_seconds histograms.

    With ``scratch`` provided the returned arrays are VIEWS into it
    (zero-copy — the ingest pool's hot path; copy before releasing the
    scratch). Without, fresh copies are returned, matching
    :func:`decode_otlp`. Raises ``ValueError`` only for errors that
    poison the whole batch (over-limit key count); per-payload wire
    garbage never raises here.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native ingest unavailable: {load_error()}")
    n_payloads = len(payloads)
    try:
        bufs = (ctypes.c_char_p * max(n_payloads, 1))(*payloads)
    except TypeError:
        # Buffer-backed payloads (the front door's native body views):
        # cast the address instead of copying — the borrowed-pointer
        # contract is identical, the owner (frontdoor.cc) keeps the
        # buffer alive until its ticket is answered.
        bufs = (ctypes.c_char_p * max(n_payloads, 1))()
        for i, p in enumerate(payloads):
            bufs[i] = (
                p if isinstance(p, bytes)
                else ctypes.cast(p, ctypes.c_char_p)
            )
    lens = np.fromiter(
        map(len, payloads), np.uint64, count=n_payloads
    ) if n_payloads else np.zeros(1, np.uint64)
    total = int(lens.sum()) if n_payloads else 0
    payload_rows = np.empty(max(n_payloads, 1), np.int32)
    keys = _keys_array(attr_keys)
    scan_s = ctypes.c_double(0.0)
    extract_s = ctypes.c_double(0.0)
    retried = False
    while True:
        need = scratch_dims(total, n_payloads, retried)
        s = scratch
        if s is None or s.cap < need[0] or s.svc_cap < need[1] or s.rs_cap < need[2]:
            s = alloc_scratch(*need)
        n_services = ctypes.c_int32(0)
        n = lib.otd_decode_otlp_many(
            bufs, lens.ctypes.data, n_payloads,
            keys, len(attr_keys), s.cap,
            s.duration.ctypes.data, s.trace.ctypes.data,
            s.err.ctypes.data, s.crc.ctypes.data,
            s.present.ctypes.data, s.svc_idx.ctypes.data,
            s.event_count.ctypes.data, s.has_exc.ctypes.data,
            s.svc_buf, s.svc_cap,
            s.svc_len.ctypes.data, s.rs_cap,
            ctypes.byref(n_services), payload_rows.ctypes.data,
            int(threads), int(shard_min_bytes),
            ctypes.byref(scan_s), ctypes.byref(extract_s),
        )
        if n in (-2, -3) and not retried:
            # Pathological tiny-span payloads overflowed the heuristic
            # capacity: retry once at the hard ceiling (decode_otlp's
            # same ladder), bypassing the too-small caller scratch.
            retried = True
            scratch = None
            continue
        if n < 0:
            raise ValueError(f"otlp batch decode failed (code {n})")
        if phases is not None:
            phases["scan"] = scan_s.value
            phases["extract"] = extract_s.value
        # Copy ONLY the used name-byte prefix, once: `svc_buf.raw` would
        # copy the whole (payload-sized) buffer per access — measured at
        # ~90% of a big flush's wall time before this went string_at.
        lens_list = s.svc_len[: n_services.value].tolist()
        used = sum(ln for ln in lens_list if ln > 0)
        blob = ctypes.string_at(s.svc_buf, used)
        services: list[str | None] = []
        pos = 0
        for ln in lens_list:
            if ln < 0:
                services.append(None)
            else:
                services.append(
                    blob[pos : pos + ln].decode("utf-8", "replace")
                )
                pos += ln
        cols = ColumnarSpans(
            s.duration[:n], s.trace[:n], s.err[:n], s.crc[:n],
            s.present[:n], s.svc_idx[:n], s.event_count[:n],
            s.has_exc[:n], services,
        )
        if scratch is None:  # no caller-owned buffers: hand out copies
            cols = ColumnarSpans(
                *(a[:n].copy() for a in cols[:8]), services
            )
        return cols, payload_rows[:n_payloads]


class SpanIndex(NamedTuple):
    """Pass-1 structural index over ONE payload (`scan_otlp`): span
    record boundaries plus the resource-spans service table — exactly
    what pass 2 (`extract_otlp`) consumes. Offsets are relative to the
    scanned payload's first byte."""

    span_off: np.ndarray  # int32[N] — span submessage offset
    span_len: np.ndarray  # int32[N] — span submessage length
    span_svc: np.ndarray  # int32[N] — resource-spans entry per span
    services: list[str | None]


def scan_otlp(payload: bytes) -> SpanIndex:
    """Pass 1 alone: structural scan → span index (no column work).

    The raw-scanner surface `make decodebench` prices and the fuzz
    suite's boundary oracle (truncation exactly at a pass-1 boundary).
    Raises ``ValueError`` on malformed framing — span-interior damage
    is invisible to the scan by design (pass 2's verdict).
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native ingest unavailable: {load_error()}")
    cap = len(payload) // 2 + 64  # hard ceiling: a span costs ≥2 bytes
    rs_cap = len(payload) // 2 + 2
    svc_cap = len(payload) + 1
    span_off = np.empty(cap, np.int32)
    span_len = np.empty(cap, np.int32)
    span_svc = np.empty(cap, np.int32)
    svc_buf = ctypes.create_string_buffer(svc_cap)
    svc_len = np.empty(rs_cap, np.int32)
    n_services = ctypes.c_int32(0)
    n = lib.otd_scan_otlp(
        payload, len(payload),
        span_off.ctypes.data, span_len.ctypes.data, span_svc.ctypes.data,
        cap, svc_buf, svc_cap, svc_len.ctypes.data, rs_cap,
        ctypes.byref(n_services),
    )
    if n < 0:
        raise ValueError(f"malformed OTLP payload (code {n})")
    services: list[str | None] = []
    pos = 0
    blob = ctypes.string_at(
        svc_buf, sum(int(ln) for ln in svc_len[: n_services.value] if ln > 0)
    )
    for ln in svc_len[: n_services.value]:
        if ln < 0:
            services.append(None)
        else:
            services.append(blob[pos : pos + ln].decode("utf-8", "replace"))
            pos += ln
    return SpanIndex(
        span_off[:n].copy(), span_len[:n].copy(), span_svc[:n].copy(),
        services,
    )


def extract_otlp(
    payload: bytes, index: SpanIndex, attr_keys: Sequence[str]
) -> ColumnarSpans:
    """Pass 2 alone: a `scan_otlp` index → columns (no re-scan).

    Completes the decode the way `decode_otlp` would have — the
    decodebench pairing that isolates extract throughput from scan
    throughput. Raises ``ValueError`` on a malformed span interior.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native ingest unavailable: {load_error()}")
    n = index.span_off.shape[0]
    duration = np.empty(n, np.float32)
    trace = np.empty(n, np.uint64)
    err = np.empty(n, np.uint8)
    crc = np.empty(n, np.uint32)
    present = np.empty(n, np.uint8)
    svc_idx = np.empty(n, np.int32)
    event_count = np.empty(n, np.int32)
    has_exc = np.empty(n, np.uint8)
    keys = _keys_array(attr_keys)
    rc = lib.otd_extract_otlp(
        payload, len(payload),
        index.span_off.ctypes.data, index.span_len.ctypes.data,
        index.span_svc.ctypes.data, n,
        keys, len(attr_keys),
        duration.ctypes.data, trace.ctypes.data,
        err.ctypes.data, crc.ctypes.data,
        present.ctypes.data, svc_idx.ctypes.data,
        event_count.ctypes.data, has_exc.ctypes.data,
    )
    if rc < 0:
        raise ValueError(f"malformed OTLP payload (code {rc})")
    return ColumnarSpans(
        duration, trace, err, crc, present, svc_idx, event_count, has_exc,
        list(index.services),
    )


def decode_orders(payloads: Sequence[bytes]) -> ColumnarOrders:
    """Columnar decode of a batch of OrderResult payloads."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native ingest unavailable: {load_error()}")
    n = len(payloads)
    bufs = (ctypes.c_char_p * max(n, 1))(*payloads) if n else (
        ctypes.c_char_p * 1
    )()
    lens = np.asarray([len(p) for p in payloads] or [0], np.uint64)
    value = np.empty(max(n, 1), np.float32)
    key = np.empty(max(n, 1), np.uint64)
    crc = np.empty(max(n, 1), np.uint32)
    rc = lib.otd_decode_orders(
        bufs, lens.ctypes.data, n,
        value.ctypes.data, key.ctypes.data, crc.ctypes.data,
    )
    if rc < 0:
        raise ValueError(f"malformed OrderResult payload (code {rc})")
    return ColumnarOrders(value[:n], key[:n], crc[:n])


if __name__ == "__main__":
    for _name in _CONFIGURE:
        if _lib_for(_name) is None:
            raise SystemExit(f"native {_name}: {_errors[_name]}")
        print(f"native {_name}: {_lib_path(_name)}")
