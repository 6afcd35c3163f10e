"""Native C++ ingest decoder vs the Python reference decoders.

The C++ library (native/ingest.cc) must produce exactly the columns the
Python record path produces — same service interning, same first/last
occurrence semantics, same CRC32 hashes, same error verdicts on
malformed payloads. These tests are the parity pin; throughput is
scripts/bench_ingest.py's job.
"""

import zlib

import numpy as np
import pytest

from opentelemetry_demo_tpu.runtime import native, wire
from opentelemetry_demo_tpu.runtime.kafka_orders import (
    Order,
    decode_order,
    decode_orders_columnar,
    encode_order,
    order_to_record,
)
from opentelemetry_demo_tpu.runtime.otlp import (
    MONITORED_ATTR_KEYS,
    decode_export_request,
)
from opentelemetry_demo_tpu.runtime.tensorize import SpanTensorizer

pytestmark = pytest.mark.skipif(
    not native.available(),
    reason=f"native ingest unavailable: {native.load_error()}",
)


def _anyval(s):
    return wire.encode_len(1, s.encode())


def _kv(k, v):
    return wire.encode_len(1, k.encode()) + wire.encode_len(2, _anyval(v))


def _span(trace_id, start, end, attrs=(), err=False, extra=b""):
    span = (
        wire.encode_len(1, trace_id)
        + wire.encode_len(5, b"op")
        + wire.encode_fixed64(7, start)
        + wire.encode_fixed64(8, end)
    )
    for k, v in attrs:
        span += wire.encode_len(9, _kv(k, v))
    if err:
        span += wire.encode_len(15, wire.encode_int(3, 2))
    return span + extra


def _rs(service, span_bufs, with_resource=True):
    rs = b""
    if with_resource:
        resource = wire.encode_len(1, _kv("service.name", service))
        rs += wire.encode_len(1, resource)
    rs += wire.encode_len(2, b"".join(wire.encode_len(2, s) for s in span_bufs))
    return wire.encode_len(1, rs)


def _parity(payload: bytes):
    """Decode both ways and compare the resulting columns."""
    tz_py = SpanTensorizer(num_services=16)
    tz_nat = SpanTensorizer(num_services=16)
    ref = tz_py.columns_from_records(decode_export_request(payload))
    got = tz_nat.columns_from_columnar(
        native.decode_otlp(payload, MONITORED_ATTR_KEYS)
    )
    assert tz_py.service_names == tz_nat.service_names
    np.testing.assert_array_equal(ref.svc, got.svc)
    np.testing.assert_allclose(ref.lat_us, got.lat_us, rtol=1e-6)
    np.testing.assert_array_equal(ref.is_error, got.is_error)
    np.testing.assert_array_equal(ref.trace_key, got.trace_key)
    np.testing.assert_array_equal(ref.attr_crc, got.attr_crc)
    return got


class TestOtlpParity:
    def test_basic_request(self):
        payload = _rs(
            "payment",
            [
                _span(b"\x01" * 16, 10**9, 10**9 + 250 * 10**6,
                      [("app.product.id", "P-7")], err=True),
                _span(b"\x02" * 16, 10**9, 10**9 + 10**6),
            ],
        )
        got = _parity(payload)
        assert got.rows == 2
        assert got.is_error.tolist() == [1.0, 0.0]

    def test_multi_resource_spans_and_missing_resource(self):
        payload = (
            _rs("checkout", [_span(b"\x03" * 16, 0, 5000)])
            + _rs("ignored", [], with_resource=True)
            + _rs("", [_span(b"\x04" * 16, 0, 1000)], with_resource=False)
            + _rs("cart", [_span(b"\x05" * 16, 7, 7)])
        )
        got = _parity(payload)
        assert got.rows == 3  # middle rs has no spans

    def test_attr_priority_and_last_wins(self):
        # session.id present but app.product.id should win; duplicate
        # keys: the LAST occurrence's value is hashed (dict semantics).
        payload = _rs(
            "ad",
            [
                _span(
                    b"\x06" * 16, 0, 10,
                    [("session.id", "s-1"),
                     ("app.product.id", "P-old"),
                     ("app.product.id", "P-new")],
                )
            ],
        )
        got = _parity(payload)
        assert got.attr_crc[0] == zlib.crc32(b"P-new")

    def test_unknown_fields_skipped(self):
        # Unknown span field 99 (LEN) containing garbage must be skipped
        # without descent — and unknown top-level fields too.
        junk = wire.encode_len(99, b"\xff\xff\xff")
        payload = (
            _rs("quote", [_span(b"\x07" * 16, 0, 10, extra=junk)])
            + wire.encode_len(9, b"\xde\xad")
        )
        got = _parity(payload)
        assert got.rows == 1

    def test_short_and_empty_trace_ids(self):
        payload = _rs(
            "email",
            [_span(b"abc", 0, 10), _span(b"", 0, 10)],
        )
        got = _parity(payload)
        assert got.trace_key[0] == int.from_bytes(
            b"abc".ljust(8, b"\0"), "little"
        )
        assert got.trace_key[1] == 0

    @pytest.mark.parametrize(
        "bad",
        [
            b"\x0a\xff",  # truncated length
            wire.encode_len(1, wire.encode_len(2, b"\x12\x7f")),  # bad span
            b"\x00\x01",  # field number 0
            b"\x0b",  # SGROUP wire type
        ],
    )
    def test_malformed_raises_both_ways(self, bad):
        with pytest.raises(wire.WireError):
            decode_export_request(bad)
        with pytest.raises(ValueError):
            native.decode_otlp(bad, MONITORED_ATTR_KEYS)

    def test_empty_payload(self):
        got = _parity(b"")
        assert got.rows == 0

    def test_nul_byte_in_service_name(self):
        # Length-prefixed name transport: a NUL inside one name must not
        # shift later names (the record path has no separator to confuse).
        payload = _rs("a\0b", [_span(b"\x08" * 16, 0, 1)]) + _rs(
            "c", [_span(b"\x09" * 16, 0, 1)]
        )
        _parity(payload)

    def test_empty_vs_missing_service_name(self):
        # service.name present-but-empty interns as ""; absent interns
        # as "unknown" — two different services, both ways.
        payload = _rs("", [_span(b"\x0a" * 16, 0, 1)]) + _rs(
            "x", [_span(b"\x0b" * 16, 0, 1)], with_resource=False
        )
        got = _parity(payload)
        assert got.rows == 2

    def test_wrong_wire_type_verdicts_match(self):
        # Known fields with a wire type the Python path chokes on must
        # be errors natively too (400, never 200-and-drop) — and the
        # cases Python tolerates (falsy zeros) must decode natively.
        span = _span(b"\x0c" * 16, 0, 10)
        rs_body = wire.encode_len(2, wire.encode_len(2, span))
        cases_error = [
            wire.encode_int(1, 5),  # resource_spans as varint
            wire.encode_len(1, wire.encode_int(2, 1)),  # scope_spans int
            wire.encode_len(1, wire.encode_int(1, 7) + rs_body),  # resource int
            wire.encode_len(  # attributes as varint inside a span
                1,
                wire.encode_len(
                    2,
                    wire.encode_len(2, span + wire.encode_int(9, 3)),
                ),
            ),
        ]
        for bad in cases_error:
            with pytest.raises(Exception):
                decode_export_request(bad)
            with pytest.raises(ValueError):
                native.decode_otlp(bad, MONITORED_ATTR_KEYS)
        # Falsy zeros: resource=0 (varint) is "no resource", not an error.
        ok = wire.encode_len(1, wire.encode_int(1, 0) + rs_body)
        got = _parity(ok)
        assert got.rows == 1

    def test_span_events_parity_and_exception_fold(self):
        # Span events (field 11): the native decoder surfaces a count +
        # has_exception flag; the record path carries full SpanEvents.
        # Both must agree, and the exception fold must reach the error
        # lane identically (tensorize.EXCEPTION_EVENT_NAMES).
        def _event(t_ns, name, attrs=()):
            body = wire.encode_fixed64(1, t_ns) + wire.encode_len(2, name)
            for k, v in attrs:
                body += wire.encode_len(3, _kv(k, v))
            return wire.encode_len(11, body)

        payload = _rs("checkout", [
            _span(b"\x21" * 16, 0, 5_000_000, extra=(
                _event(1_000_000, b"prepared")
                + _event(2_000_000, b"charged",
                         [("app.payment.transaction.id", "tx")])
                + _event(3_000_000, b"shipped")
            )),
            # status OK + exception event: error evidence via the event.
            _span(b"\x22" * 16, 0, 1_000_000, extra=_event(
                500_000, b"exception", [("exception.message", "boom")]
            )),
            # deferred "error" event (checkout main.go:257) counts too,
            # and the ad service's capitalized "Error" (AdService.java:219).
            _span(b"\x23" * 16, 0, 1_000_000, extra=_event(0, b"error")),
            _span(b"\x28" * 16, 0, 1_000_000, extra=_event(
                0, b"Error", [("exception.message", "ad fail")]
            )),
            _span(b"\x24" * 16, 0, 1_000_000),
        ])
        got = _parity(payload)  # includes the is_error lane comparison
        assert got.is_error.tolist() == [0.0, 1.0, 1.0, 1.0, 0.0]
        cols = native.decode_otlp(payload, MONITORED_ATTR_KEYS)
        records = decode_export_request(payload)
        assert cols.event_count.tolist() == [len(r.events) for r in records]
        assert cols.has_exception.tolist() == [0, 1, 1, 1, 0]
        assert [e.name for e in records[0].events] == [
            "prepared", "charged", "shipped"]

    def test_span_event_edge_verdicts_match(self):
        # events as varint → error both ways (submessage-list); numeric
        # event name → claims the slot with an EMPTY name, no error;
        # empty-LEN event time → default 0, no error.
        bad = _rs("s", [_span(b"\x25" * 16, 0, 10,
                              extra=wire.encode_int(11, 3))])
        with pytest.raises(Exception):
            decode_export_request(bad)
        with pytest.raises(ValueError):
            native.decode_otlp(bad, MONITORED_ATTR_KEYS)
        ok = _rs("s", [_span(b"\x26" * 16, 0, 10, extra=wire.encode_len(
            11, wire.encode_int(2, 7) + wire.encode_len(1, b"")
        ))])
        got = _parity(ok)
        assert got.rows == 1
        cols = native.decode_otlp(ok, MONITORED_ATTR_KEYS)
        records = decode_export_request(ok)
        assert cols.event_count.tolist() == [1]
        assert records[0].events[0].name == ""
        # malformed event ATTRS (varint where KeyValue expected) → error
        bad_attr = _rs("s", [_span(b"\x27" * 16, 0, 10, extra=wire.encode_len(
            11, wire.encode_len(2, b"ev") + wire.encode_int(3, 1)
        ))])
        with pytest.raises(Exception):
            decode_export_request(bad_attr)
        with pytest.raises(ValueError):
            native.decode_otlp(bad_attr, MONITORED_ATTR_KEYS)

    def test_large_request_many_services(self):
        rng = np.random.default_rng(3)
        payload = b""
        for i in range(12):
            spans = [
                _span(
                    bytes(rng.integers(0, 256, 16, dtype=np.uint8)),
                    0,
                    int(rng.integers(1, 10**9)),
                    [("app.session.id", f"sess-{int(rng.integers(0, 50))}")],
                    err=bool(rng.random() < 0.3),
                )
                for _ in range(40)
            ]
            payload += _rs(f"svc-{i % 5}", spans)
        got = _parity(payload)
        assert got.rows == 480


class TestOrdersParity:
    def _payloads(self):
        orders = [
            Order("ord-1", "trk", 3.5, 2, ("P-A", "P-B"), 3),
            Order("", "", 0.0, 0, (), 0),
            Order("ord-with-long-id-123456", "t", 19.99, 1, ("P-Z",), 1),
            # Non-USD wires: the value lane must USD-normalize
            # identically on both decode paths.
            Order("ord-jpy", "t", 1500.0, 1, ("P-J",), 1, currency="JPY"),
            Order("ord-eur", "t", 9.5, 1, ("P-E",), 1, currency="EUR"),
            Order("ord-xxx", "t", 7.0, 1, ("P-X",), 1, currency="XXX"),
        ]
        return [encode_order(o) for o in orders]

    def test_columnar_matches_record_path(self):
        payloads = self._payloads()
        tz_py = SpanTensorizer(num_services=8)
        tz_nat = SpanTensorizer(num_services=8)
        ref = tz_py.columns_from_records(
            [order_to_record(decode_order(p)) for p in payloads]
        )
        got = decode_orders_columnar(payloads, tz_nat)
        np.testing.assert_array_equal(ref.svc, got.svc)
        np.testing.assert_allclose(ref.lat_us, got.lat_us, rtol=1e-6)
        np.testing.assert_array_equal(ref.trace_key, got.trace_key)
        np.testing.assert_array_equal(ref.attr_crc, got.attr_crc)

    def test_empty_batch(self):
        got = decode_orders_columnar([], SpanTensorizer())
        assert got.rows == 0

    def test_value_lane_usd_normalized(self):
        # A JPY shipping cost must not land ~150x a USD one in the
        # detector's order-value lane (currency-dependent units would
        # make non-USD traffic bursts fire false value anomalies).
        from opentelemetry_demo_tpu.currency_data import to_usd_factor

        jpy = encode_order(
            Order("o-j", "t", 1500.0, 1, ("P",), 1, currency="JPY")
        )
        usd = encode_order(Order("o-u", "t", 1500.0, 1, ("P",), 1))
        rec_jpy = order_to_record(decode_order(jpy))
        rec_usd = order_to_record(decode_order(usd))
        assert rec_usd.duration_us == pytest.approx(1500.0)
        assert rec_jpy.duration_us == pytest.approx(
            1500.0 * to_usd_factor("JPY")
        )
        assert rec_jpy.duration_us < 20.0  # ~9.5 USD, not 1500
        got = decode_orders_columnar([jpy, usd], SpanTensorizer())
        np.testing.assert_allclose(
            got.lat_us[:2],
            [rec_jpy.duration_us, rec_usd.duration_us],
            rtol=1e-6,
        )

    def test_numeric_currency_code_falls_back_to_usd_both_ways(self):
        # Money.currency_code encoded as a VARINT (malformed producer):
        # Python's isinstance(code, bytes) guard maps it to "USD"
        # (_money_units) rather than raising — the native decoder must
        # take the same lenient branch instead of failing the batch.
        money = wire.encode_int(1, 5) + wire.encode_int(2, 3)
        payload = wire.encode_len(1, b"ord-n") + wire.encode_len(3, money)
        order = decode_order(payload)
        assert order.currency == "USD"
        assert order.shipping_cost_units == pytest.approx(3.0)
        rec = order_to_record(decode_order(payload))
        got = decode_orders_columnar([payload], SpanTensorizer())
        assert got.rows == 1
        np.testing.assert_allclose(
            got.lat_us[:1], [rec.duration_us], rtol=1e-6
        )

    def test_empty_money_units_bytes_raise_both_ways(self):
        # Money.units as an EMPTY length-delimited field: float(b"")
        # raises on the Python path, so the native path must error too
        # (error verdicts are part of the parity contract).
        money = wire.encode_len(1, b"USD") + wire.encode_len(2, b"")
        payload = wire.encode_len(1, b"ord-e") + wire.encode_len(3, money)
        with pytest.raises(Exception):
            decode_order(payload)
        from opentelemetry_demo_tpu.runtime import native

        if native.available():
            with pytest.raises(ValueError):
                native.decode_orders([payload])

    def test_empty_product_id_skipped(self):
        # decode_order skips falsy product ids; the first NON-empty one
        # is the heavy-hitter attribute.
        items = (
            wire.encode_len(5, wire.encode_len(1, wire.encode_len(1, b"")))
            + wire.encode_len(
                5, wire.encode_len(1, wire.encode_len(1, b"P1"))
            )
        )
        payload = wire.encode_len(1, b"ord-9") + items
        rec = order_to_record(decode_order(payload))
        assert rec.attr == "P1"
        got = decode_orders_columnar([payload], SpanTensorizer())
        assert got.attr_crc[0] == zlib.crc32(b"P1")


class TestBuildKey:
    def test_library_name_keys_source_compiler_and_flags(
        self, tmp_path, monkeypatch
    ):
        """A library is only ever loaded from the name its source bytes,
        compiler and flags hash to: a copied-in or stale ``_build/``
        file built from anything else is never picked up."""
        monkeypatch.setattr(native, "_DIR", str(tmp_path))
        monkeypatch.delenv("CXX", raising=False)
        src = tmp_path / "x.cc"
        src.write_text("int f() { return 1; }\n")
        first = native._lib_path("x")
        assert native._lib_path("x") == first
        src.write_text("int f() { return 2; }\n")
        edited = native._lib_path("x")
        monkeypatch.setenv("CXX", "clang++")
        other_cxx = native._lib_path("x")
        assert len({first, edited, other_cxx}) == 3
        for path in (first, edited, other_cxx):
            assert path.startswith(str(tmp_path / "_build" / "libotd_x-"))


class TestCrc32:
    def test_matches_zlib(self):
        for s in (b"", b"P-7", b"abcdefgh" * 100, bytes(range(256))):
            assert native.crc32(s) == zlib.crc32(s)


class TestCrc32c:
    def test_known_answer_and_python_parity(self):
        """CRC-32C (the frame checksum): the RFC 3720 check value, and
        bit-parity between the native slicing-by-8 kernel and frame.py's
        portable fallback — a primary with a compiler and a standby
        without one MUST agree on every checksum."""
        from opentelemetry_demo_tpu.runtime import frame

        assert native.crc32c(b"123456789") == 0xE3069283
        assert frame._py_crc32c(b"123456789") == 0xE3069283
        rng = np.random.default_rng(7)
        for n in (0, 1, 7, 8, 9, 63, 64, 1000):
            data = rng.integers(0, 256, n, dtype=np.uint8)
            assert native.crc32c(data) == frame._py_crc32c(data.tobytes())
        # Running-seed composition (how the trailer could be streamed).
        a, b = b"abcdefgh", b"ijklm"
        assert native.crc32c(b, native.crc32c(a)) == native.crc32c(a + b)


@pytest.mark.fuzz
class TestDecodeFuzz:
    """Satellite: deterministic seeded byte-mutation fuzz. A mutated
    OTLP payload through the batched native decoder must NEVER crash
    the worker — every payload gets either a clean per-payload -1
    verdict (the receivers' 400) or a successful parse, and a valid
    batchmate always survives. Seeds are fixed: any failure reproduces
    byte-for-byte."""

    SEEDS = range(40)

    def _base_payloads(self):
        spans = [
            _span(bytes([i + 1]) * 16, 1_000, 5_000 + i * 997,
                  attrs=[("app.product.id", f"P{i}")], err=bool(i % 2))
            for i in range(6)
        ]
        return [
            _rs("checkout", spans),
            _rs("cart", spans[:2]) + _rs("frontend", spans[2:4]),
            _rs("", spans[:1], with_resource=False),
        ]

    def test_seeded_mutations_clean_verdict_or_parse(self):
        from opentelemetry_demo_tpu.runtime.faultwire import corrupt_bytes

        bases = self._base_payloads()
        witness = bases[0]  # rides UNMUTATED in every batch
        for seed in self.SEEDS:
            rate = 0.002 + (seed % 8) * 0.01  # light nicks → heavy damage
            batch = [
                corrupt_bytes(p, seed=seed, rate=rate)[0] for p in bases
            ]
            cols, rows = native.decode_otlp_many(
                batch + [witness], MONITORED_ATTR_KEYS
            )
            assert rows.shape[0] == len(batch) + 1
            # Every verdict is clean: parsed (>=0) or rejected (-1);
            # the decoder never wrote more rows than it reported.
            assert all(int(r) >= -1 for r in rows), (seed, rows)
            assert cols.duration_us.shape[0] == sum(
                int(r) for r in rows if r > 0
            )
            # The valid batchmate is never poisoned by its neighbors.
            assert int(rows[-1]) == 6, (seed, rows)
            # And whatever parsed feeds the tensorizer without fault.
            tz = SpanTensorizer(num_services=16)
            out = tz.columns_from_columnar(cols, copy=True)
            assert out.rows == cols.duration_us.shape[0]

    def test_python_decoder_same_contract(self):
        """The no-compiler fallback path (otlp.decode_export_request)
        under the same corpus: parse or ValueError, never a crash —
        the serial receivers' 400 contract."""
        from opentelemetry_demo_tpu.runtime.faultwire import corrupt_bytes

        for seed in self.SEEDS:
            for p in self._base_payloads():
                mutated = corrupt_bytes(p, seed=seed, rate=0.01)[0]
                try:
                    records = decode_export_request(mutated)
                except ValueError:
                    continue  # the clean 400 verdict
                for r in records:
                    assert isinstance(r.service, str)
                    float(r.duration_us)

    def test_native_and_python_verdicts_agree_on_every_seed(self):
        """ONE verdict vocabulary across engines: for every mutated
        payload the native scanner's per-payload verdict (-1 vs rows)
        and the Python fallback's (ValueError vs parse) must AGREE —
        a deployment can swap decode engines without a single request
        changing its 400-vs-200 answer."""
        from opentelemetry_demo_tpu.runtime.faultwire import corrupt_bytes

        for seed in self.SEEDS:
            rate = 0.002 + (seed % 8) * 0.01
            for p in self._base_payloads():
                mutated = corrupt_bytes(p, seed=seed, rate=rate)[0]
                _, rows = native.decode_otlp_many(
                    [mutated], MONITORED_ATTR_KEYS
                )
                native_ok = int(rows[0]) >= 0
                try:
                    decode_export_request(mutated)
                    python_ok = True
                except ValueError:
                    python_ok = False
                assert native_ok == python_ok, (seed, native_ok, python_ok)


@pytest.mark.fuzz
class TestScannerBoundaryFuzz:
    """Boundary-adversarial cases for the two-pass scanner: varints
    straddling shard-split points, max-nesting submessages, truncation
    exactly at a pass-1 boundary. Native (serial AND thread-sharded)
    and the Python fallback must agree — clean -1/400 verdict or
    parse — on every case."""

    def _varied_spans_payload(self, n_spans=4096, seed=5):
        """Spans with deliberately varied sizes so submessage-length
        varints cross the 1-byte/2-byte boundary, trace ids vary in
        length, and shard splits land mid-payload at every alignment."""
        rng = np.random.default_rng(seed)
        bufs = []
        for i in range(n_spans):
            tid = bytes(rng.integers(0, 256, int(rng.integers(0, 17)),
                                     dtype=np.uint8))
            extra = b""
            if i % 7 == 0:
                # pad with an unknown LEN field so the span length
                # varint needs 2 bytes (>127) for some spans
                extra = wire.encode_len(14, b"x" * int(rng.integers(0, 160)))
            bufs.append(
                _span(tid, 1_000 + i, 5_000 + i * 31,
                      attrs=[("app.product.id", f"P{i % 13}")],
                      err=bool(i % 3 == 0), extra=extra)
            )
        return _rs("checkout", bufs)

    def test_shard_split_varints_bit_exact(self):
        """One fat payload, every thread count: the sharded extraction
        (splits at span-record boundaries, mid-payload) must reproduce
        the serial columns bit-for-bit — a varint straddling a shard
        split cannot exist BY CONSTRUCTION (shards split the pass-1
        index, never the byte stream), and this pins it."""
        payload = self._varied_spans_payload()
        ref, ref_rows = native.decode_otlp_many(
            [payload], MONITORED_ATTR_KEYS, threads=1
        )
        for threads in (2, 3, 4):
            got, rows = native.decode_otlp_many(
                [payload], MONITORED_ATTR_KEYS, threads=threads,
                shard_min_bytes=0,
            )
            assert rows.tolist() == ref_rows.tolist()
            for name, a, b in zip(ref._fields, ref, got):
                if hasattr(a, "dtype"):
                    np.testing.assert_array_equal(a, b, err_msg=name)
            assert got.services == ref.services

    def test_sharded_decode_mutation_fuzz_agrees_with_python(self):
        """The fuzz corpus through the THREADED path: per-payload
        verdicts equal the serial path's and the Python fallback's on
        every seed — compaction under sharding never leaks a row."""
        from opentelemetry_demo_tpu.runtime.faultwire import corrupt_bytes

        base = self._varied_spans_payload(n_spans=2048, seed=9)
        witness = self._varied_spans_payload(n_spans=600, seed=11)
        for seed in range(12):
            mutated = corrupt_bytes(base, seed=seed, rate=0.004)[0]
            batch = [mutated, witness]
            ser_cols, ser_rows = native.decode_otlp_many(
                batch, MONITORED_ATTR_KEYS, threads=1
            )
            thr_cols, thr_rows = native.decode_otlp_many(
                batch, MONITORED_ATTR_KEYS, threads=3, shard_min_bytes=0
            )
            assert ser_rows.tolist() == thr_rows.tolist(), seed
            for name, a, b in zip(ser_cols._fields, ser_cols, thr_cols):
                if hasattr(a, "dtype"):
                    np.testing.assert_array_equal(a, b, err_msg=(seed, name))
            assert int(thr_rows[1]) == 600  # witness always survives
            try:
                decode_export_request(mutated)
                python_ok = True
            except ValueError:
                python_ok = False
            assert (int(ser_rows[0]) >= 0) == python_ok, seed

    def test_truncation_at_every_pass1_boundary(self):
        """Truncate the payload at EXACTLY each span-record boundary
        the pass-1 scan discovered (start and end of every span):
        native and Python must agree on every cut — the adversarial
        alignment for an index-driven decoder."""
        payload = self._varied_spans_payload(n_spans=64, seed=13)
        idx = native.scan_otlp(payload)
        cuts = sorted(
            {int(o) for o in idx.span_off}
            | {int(o) + int(ln)
               for o, ln in zip(idx.span_off, idx.span_len)}
        )
        assert len(cuts) >= 64
        for cut in cuts:
            m = payload[:cut]
            _, rows = native.decode_otlp_many([m], MONITORED_ATTR_KEYS)
            native_ok = int(rows[0]) >= 0
            try:
                decode_export_request(m)
                python_ok = True
            except ValueError:
                python_ok = False
            assert native_ok == python_ok, cut

    def test_max_nesting_submessages(self):
        """Pathologically deep submessage nesting (1000 levels) in an
        unknown span field and inside an attribute AnyValue: both
        decoders skip unknown LEN fields by length (no recursion), so
        the payload must PARSE on both engines with identical columns
        — and a deep blob must never smash a stack."""
        deep = b"z"
        for _ in range(1000):
            deep = wire.encode_len(13, deep)  # links: unknown to both
        # Attr value stays ASCII (the Python fallback utf-8-decodes
        # attr strings, so a non-UTF-8 value is out of parity scope);
        # the deep blob itself rides the unknown field.
        nested_attr = wire.encode_len(
            9,
            wire.encode_len(1, b"app.product.id")
            + wire.encode_len(2, wire.encode_len(1, b"P-deep")),
        )
        span = _span(b"\x01" * 16, 1_000, 9_000, extra=deep + nested_attr)
        payload = _rs("checkout", [span])
        _parity(payload)
        # And through the batched/threaded entry point.
        cols, rows = native.decode_otlp_many(
            [payload], MONITORED_ATTR_KEYS, threads=2, shard_min_bytes=0
        )
        assert rows.tolist() == [1]
        idx = native.scan_otlp(payload)
        assert idx.span_off.shape[0] == 1
