"""Device-resident ingest spine: ring, overlap, donation safety.

What must hold (the r9 spine tentpole):

- **Bit parity** (``test_spine_parity_with_inline_path``): the staged
  ring + async puts change WHEN bytes move, never WHAT the detector
  computes — spine-on and spine-off runs produce identical report
  sequences over the same virtual-time stream.
- **Pack parity** (``test_pack_columns_into_matches_pack_columns``):
  the zero-allocation slot pack is bit-identical to ``pack_columns``,
  chunked or not, including the padded tail's zero-key hashes.
- **No donation race** (``test_dispatch_vs_put_hammer_under_donation``):
  the stager's puts run concurrently with donated dispatches and
  state-snapshot readers (the PR 6 refresh-vs-dispatch shape) at ring
  depth 2 — no "Array has been deleted", no corrupted reports.
- **Ring discipline** (``test_ring_slots_are_reused``): slot buffers
  are allocated once per (slot, width) and reused — the staging pack
  performs zero width-sized allocations in steady state.
- **Overlap discipline** (``TestIdleDevice``): the pump asks the
  device whether a step is running. With the device idle it waits for
  every batch staged, the one it just staged included, and dispatches
  them in the same pump (idle waits); with a step running it never
  blocks on a put, and the batch dispatches on a later pump once its
  put is done (an overlap hit).
- **Lifecycle** (``test_drain_flushes_staged_batches``, flag-off drop,
  knob validation): nothing staged is ever lost on drain, the off
  switch drops staged rows with the queue, and malformed spine knobs
  refuse to boot.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opentelemetry_demo_tpu.models import AnomalyDetector, DetectorConfig
from opentelemetry_demo_tpu.runtime.ingestbench import make_columns
from opentelemetry_demo_tpu.runtime.pipeline import DetectorPipeline
from opentelemetry_demo_tpu.runtime.spine import DevicePutSpine
from opentelemetry_demo_tpu.runtime.tensorize import SpanTensorizer
from opentelemetry_demo_tpu.utils.config import ConfigError, spine_config

pytestmark = pytest.mark.spine

SMALL = dict(num_services=8, hll_p=8, cms_width=512)


class _StepReport:
    """Report stand-in: the real report array, whose step reads as
    running while ``running()`` is True (a CPU step finishes before the
    next pump, so a running step has to be made observable)."""

    def __init__(self, arr, running):
        self._arr = arr
        self._running = running

    def is_ready(self) -> bool:
        return not self._running() and self._arr.is_ready()

    def copy_to_host_async(self) -> None:
        self._arr.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._arr, dtype=dtype)


def _report_steps(det, running) -> None:
    """Wrap ``det.observe_packed`` so each report is a ``_StepReport``;
    ``running(n)`` says whether the n-th dispatched step reads as
    still running."""
    observe = det.observe_packed
    count = itertools.count()

    def wrapped(batch, t_now):
        n = next(count)
        return _StepReport(observe(batch, t_now), lambda: running(n))

    det.observe_packed = wrapped


def _run_stream(spine_ring: int, n_batches: int = 40, seed: int = 7):
    det = AnomalyDetector(DetectorConfig(**SMALL))
    reports = []
    pipe = DetectorPipeline(
        det,
        on_report=lambda t, r, flagged: reports.append((t, r, tuple(flagged))),
        batch_size=256,
        spine_ring=spine_ring,
    )
    rng = np.random.default_rng(seed)
    t = 0.0
    for _ in range(n_batches):
        pipe.submit_columns(make_columns(rng, 256))
        pipe.pump(t)
        t += 0.05
    pipe.close()
    return reports, pipe


class TestParity:
    def test_spine_parity_with_inline_path(self):
        ref, p0 = _run_stream(spine_ring=0)
        got, p1 = _run_stream(spine_ring=2)
        assert p0.stats.batches == p1.stats.batches
        assert len(ref) == len(got) > 0
        for (ta, ra, fa), (tb, rb, fb) in zip(ref, got):
            assert ta == tb and fa == fb
            for name, x, y in zip(ra._fields, ra, rb):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y), err_msg=name
                )

    @pytest.mark.parametrize("chunk_rows", [0, 7, 64, 1000])
    def test_pack_columns_into_matches_pack_columns(self, chunk_rows):
        tz = SpanTensorizer(num_services=8, batch_size=256)
        rng = np.random.default_rng(3)
        cols = make_columns(rng, 200)
        ref = tz.pack_columns(cols, width=256)
        slot = tz.alloc_batch(256)
        got = tz.pack_columns_into(slot, cols, chunk_rows=chunk_rows)
        for name, x, y in zip(ref._fields, ref, got):
            np.testing.assert_array_equal(x, y, err_msg=name)
        # The slot really is the output (no hidden allocation).
        assert got.svc is slot.svc and got.valid is slot.valid

    def test_pack_into_overflow_refused(self):
        tz = SpanTensorizer(num_services=8, batch_size=64)
        slot = tz.alloc_batch(64)
        cols = make_columns(np.random.default_rng(0), 65)
        with pytest.raises(ValueError, match="exceeds batch width"):
            tz.pack_columns_into(slot, cols)


class TestRing:
    def test_ring_slots_are_reused(self):
        tz = SpanTensorizer(num_services=8, batch_size=128)
        spine = DevicePutSpine(tz, depth=2)
        rng = np.random.default_rng(5)
        try:
            seen_hosts = set()
            for i in range(8):
                spine.stage(make_columns(rng, 128), 128, float(i), float(i))
                staged = spine.take(wait=True)
                assert staged is not None and staged.batch is not None
            for slot in spine._slots:
                assert list(slot) == [128]  # one width, allocated once
                seen_hosts.add(id(slot[128].svc))
            assert len(seen_hosts) == 2  # depth distinct host buffers
            st = spine.stats()
            assert st["puts_total"] == 8 and st["ring_depth"] == 2
        finally:
            spine.close()

    def test_take_nonblocking_returns_none_until_ready(self):
        # A spine with a wedged device_put must not block a
        # non-waiting take (the overlap regime's contract).
        gate = threading.Event()

        def slow_put(a):
            gate.wait(5.0)
            return jax.device_put(a)

        tz = SpanTensorizer(num_services=8, batch_size=64)
        spine = DevicePutSpine(tz, depth=2, device_put=slow_put)
        try:
            spine.stage(
                make_columns(np.random.default_rng(0), 64), 64, 0.0, 0.0
            )
            assert spine.take(wait=False) is None
            gate.set()
            staged = spine.take(wait=True)
            assert staged is not None and staged.batch is not None
            st = spine.stats()
            assert st["overlap_misses"] >= 1
        finally:
            gate.set()
            spine.close()

    def test_spine_knob_validation(self):
        with pytest.raises(ValueError):
            DevicePutSpine(SpanTensorizer(), depth=0)
        import os

        os.environ["ANOMALY_SPINE_RING"] = "-1"
        try:
            with pytest.raises(ConfigError):
                spine_config()
        finally:
            del os.environ["ANOMALY_SPINE_RING"]
        assert spine_config()["ANOMALY_SPINE_RING"] == 2  # registry default


class TestDonationSafety:
    def test_dispatch_vs_put_hammer_under_donation(self):
        """Hammer the spine path (stager thread putting batch k+1)
        against donated dispatches on the main thread WHILE background
        readers snapshot detector state under the dispatch lock — the
        PR 6 refresh-vs-dispatch shape extended with the put thread.
        Without the lock discipline (or with a ring slot recycled
        under an in-flight transfer) this dies with 'Array has been
        deleted' or a corrupted report."""
        det = AnomalyDetector(DetectorConfig(**SMALL))
        harvested = []
        pipe = DetectorPipeline(
            det,
            on_report=lambda t, r, f: harvested.append(r),
            batch_size=256,
            spine_ring=2,
        )
        # Odd steps read as still running at the next pump (a CPU step
        # finishes before it), so the overlap branch stays engaged;
        # even ones finish, so idle waits interleave with it.
        _report_steps(det, lambda n: n % 2 == 1)
        rng = np.random.default_rng(11)
        stop = threading.Event()
        failures: list[str] = []

        def snapshot_reader() -> None:
            # The replication/warm-widths shape: tree-copy the live
            # state under the dispatch lock, never unlocked.
            while not stop.is_set():
                try:
                    with pipe._dispatch_lock:
                        copied = jax.tree_util.tree_map(
                            jnp.copy, det.state
                        )
                    jax.block_until_ready(copied.step_idx)
                except Exception as e:  # noqa: BLE001 — collected
                    failures.append(repr(e))
                    return

        readers = [
            threading.Thread(target=snapshot_reader, daemon=True)
            for _ in range(3)
        ]
        for th in readers:
            th.start()
        t = 0.0
        try:
            for _ in range(150):
                # Two chunks per pump keeps a backlog: the overlap
                # path (dispatch k while putting k+1) stays engaged.
                pipe.submit_columns(make_columns(rng, 256))
                pipe.submit_columns(make_columns(rng, 256))
                pipe.pump(t)
                t += 0.05
        finally:
            stop.set()
            for th in readers:
                th.join(timeout=10.0)
            pipe.close()
        assert not failures, failures
        st = pipe.spine_stats()
        assert st["puts_total"] == pipe.stats.batches == 300
        # The hammer must actually have exercised the overlap regime.
        assert st["overlap_hits"] > 0
        assert st["idle_waits"] > 0
        # Every harvested report is finite — a scribbled staging slot
        # would surface as garbage z-scores long before a crash.
        for rep in harvested:
            assert np.isfinite(np.asarray(rep.lat_z)).all()


class TestIdleDevice:
    @staticmethod
    def _pipe(det, put, on_report=None):
        pipe = DetectorPipeline(
            det, on_report=on_report, batch_size=128, spine_ring=2
        )
        pipe._spine._device_put = put
        return pipe

    # The tests hold the harvest: the reports stay in flight, so the
    # pump has to tell a finished step from a running one.

    def test_idle_device_dispatches_in_the_pump_that_staged(
        self, hold_harvest
    ):
        def slow_put(a):
            time.sleep(0.005)  # the put outlasts the pump's own pass
            return jax.device_put(a)

        det = AnomalyDetector(DetectorConfig(**SMALL))
        pipe = self._pipe(det, slow_put)
        rng = np.random.default_rng(8)
        try:
            with hold_harvest(pipe):
                for k in (1, 2, 3):
                    pipe.submit_columns(make_columns(rng, 100))
                    pipe.pump(0.05 * k)
                    # Dispatched within the call that staged it.
                    assert pipe.stats.batches == k
                    assert pipe._spine.pending() == 0
                    st = pipe.spine_stats()
                    assert st["idle_waits"] == k
                    assert st["overlap_hits"] == st["overlap_misses"] == 0
                    # The previous step finishes before the next pump,
                    # its report still unharvested in flight.
                    jax.block_until_ready(pipe._inflight[-1][2])
                assert len(pipe._inflight) == 2  # the in-flight bound
        finally:
            pipe.close()
        assert pipe.stats.spans == 300

    def test_running_step_never_blocks_the_pump_on_a_put(
        self, hold_harvest
    ):
        gate = threading.Event()
        gate.set()

        def gated_put(a):
            gate.wait(10.0)
            return jax.device_put(a)

        finished = threading.Event()
        det = AnomalyDetector(DetectorConfig(**SMALL))
        _report_steps(det, lambda n: not finished.is_set())
        times = []
        pipe = self._pipe(
            det, gated_put, on_report=lambda t, r, f: times.append(t)
        )
        rng = np.random.default_rng(9)
        try:
            with hold_harvest(pipe):
                pipe.submit_columns(make_columns(rng, 100))
                pipe.pump(0.05)  # nothing in flight: an idle wait
                assert pipe.stats.batches == 1
                gate.clear()  # the next put wedges while the step runs
                pipe.submit_columns(make_columns(rng, 100))
                t0 = time.monotonic()
                pipe.pump(0.10)
                # Did not wait on the put.
                assert time.monotonic() - t0 < 5.0
                assert pipe.stats.batches == 1
                assert pipe._spine.pending() == 1
                gate.set()
                staged = pipe._spine._staged[0]
                assert staged.ready.wait(10.0)
                pipe.pump(0.15)  # the step still runs; the put is done
                assert pipe.stats.batches == 2
                assert pipe._spine.pending() == 0
                st = pipe.spine_stats()
                assert st["overlap_hits"] == 1
                assert st["overlap_misses"] == 0
                assert st["idle_waits"] == 1
                assert st["overlap_ratio"] == 1.0
        finally:
            gate.set()
            finished.set()
            pipe.close()
        # Each batch keeps the t_now of the pump that staged it.
        assert times == [0.05, 0.10]
        assert pipe.stats.spans == 200

    def test_a_batch_left_staged_goes_with_the_next_pump(
        self, hold_harvest
    ):
        """A pump that meets a running step leaves its batch staged.
        The next pump, which stages one of its own, finds the device
        idle and dispatches both, oldest first: no batch waits a pump
        period in the ring behind each later one."""
        gate = threading.Event()
        gate.set()

        def gated_put(a):
            gate.wait(10.0)
            return jax.device_put(a)

        finished = threading.Event()
        det = AnomalyDetector(DetectorConfig(**SMALL))
        _report_steps(det, lambda n: n == 0 and not finished.is_set())
        pipe = self._pipe(det, gated_put)
        rng = np.random.default_rng(11)
        try:
            with hold_harvest(pipe):
                pipe.submit_columns(make_columns(rng, 100))
                pipe.pump(0.05)  # nothing in flight: an idle wait
                gate.clear()  # step 0 runs and the next put is slow
                pipe.submit_columns(make_columns(rng, 100))
                pipe.pump(0.10)
                assert pipe.stats.batches == 1
                assert pipe._spine.pending() == 1
                gate.set()
                finished.set()
                for k in (3, 4, 5):
                    # The previous step finishes before the next pump.
                    jax.block_until_ready(pipe._inflight[-1][2]._arr)
                    pipe.submit_columns(make_columns(rng, 100))
                    pipe.pump(0.05 * k)
                    assert pipe.stats.batches == k
                    assert pipe._spine.pending() == 0
                assert pipe.spine_stats()["idle_waits"] == 5
        finally:
            gate.set()
            finished.set()
            pipe.close()
        assert pipe.stats.spans == 500

    def test_daemon_exports_idle_waits_apart_from_the_overlap_ratio(
        self, monkeypatch, tmp_path
    ):
        from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
        from opentelemetry_demo_tpu.telemetry import metrics as tm

        from .test_selftrace import _daemon_env

        _daemon_env(monkeypatch, tmp_path, ANOMALY_SPINE_RING="2")
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        try:
            pipe = daemon.pipeline
            rng = np.random.default_rng(10)
            for i in range(3):
                pipe.submit_columns(make_columns(rng, 50))
                pipe.pump(float(i))
            daemon._export_spine_stats()
            counters, gauges = daemon.registry.snapshot()
            assert pipe.stats.batches == 3
            assert counters[(tm.ANOMALY_SPINE_IDLE_WAITS, ())] == 3.0
            # No take was made while a step ran: no ratio to report.
            assert (tm.ANOMALY_SPINE_PUT_OVERLAP, ()) not in gauges
            assert gauges[(tm.ANOMALY_SPINE_RING_DEPTH, ())] == 2.0
        finally:
            daemon.shutdown()


class TestLifecycle:
    def test_drain_flushes_staged_batches(self):
        det = AnomalyDetector(DetectorConfig(**SMALL))
        pipe = DetectorPipeline(det, batch_size=128, spine_ring=3)
        rng = np.random.default_rng(2)
        for _ in range(5):
            pipe.submit_columns(make_columns(rng, 128))
        pipe.drain()
        assert pipe.pending_rows() == 0
        assert pipe._spine.pending() == 0
        assert pipe.stats.batches == 5
        assert pipe.stats.spans == 5 * 128
        pipe.close()

    def test_flag_off_drops_staged_rows(self):
        from opentelemetry_demo_tpu.utils.flags import FlagEvaluator

        flags = FlagEvaluator()
        det = AnomalyDetector(DetectorConfig(**SMALL))
        pipe = DetectorPipeline(
            det, flags=flags, batch_size=128, spine_ring=2
        )
        rng = np.random.default_rng(4)
        # Stage one batch (dispatched or held staged — both count).
        pipe.submit_columns(make_columns(rng, 128))
        pipe.pump(0.0)
        pipe.submit_columns(make_columns(rng, 128))
        flags.replace({
            "flags": {
                "anomalyDetectorEnabled": {
                    "state": "ENABLED",
                    "variants": {"on": True, "off": False},
                    "defaultVariant": "off",
                }
            }
        })
        pipe.pump(0.05)
        assert pipe._spine.pending() == 0
        dispatched = pipe.stats.spans
        assert dispatched + pipe.stats.dropped_disabled == 2 * 128
        pipe.close()

    def test_spine_stats_surface(self):
        det = AnomalyDetector(DetectorConfig(**SMALL))
        pipe = DetectorPipeline(det, batch_size=128)  # spine off
        assert pipe.spine_stats() is None
        pipe.close()


class TestStaleReadbackEnv:
    """The benchmark's config files still set the readback switches
    the daemon no longer has; it ignores them."""

    # The dropped variables, by the name after the ``ANOMALY_`` prefix:
    # the registry holds none of them, and this test is their one
    # mention outside the records.
    STALE = [("HARVEST_ASYNC", "1"), ("HARVEST_INTERVAL", "3600"),
             ("SPINE_OVERLAP", "0")]

    @pytest.mark.parametrize(
        "name,value", STALE, ids=[f"ANOMALY_{n}={v}" for n, v in STALE]
    )
    def test_stale_readback_env_is_ignored(
        self, monkeypatch, tmp_path, name, value
    ):
        from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon

        from .test_selftrace import _daemon_env

        _daemon_env(
            monkeypatch, tmp_path, ANOMALY_SPINE_RING="2",
            **{f"ANOMALY_{name}": value},
        )
        gate = threading.Event()
        gate.set()
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        try:
            pipe = daemon.pipeline
            rng = np.random.default_rng(12)
            for i in range(4):
                pipe.submit_columns(make_columns(rng, 50))
                pipe.pump(float(i))
                # Harvested on the pump thread, in the pump that
                # dispatched it.
                assert not pipe._inflight
                assert pipe.stats.spans_harvested == 50 * (i + 1)
            assert pipe.stats.reports_skipped == 0
            names = {t.name for t in threading.enumerate()}
            assert not names & {"report-harvester", "rtt-probe"}
            # With a step running, a batch whose put is not done stays
            # staged: the pump does not wait on it.
            pipe._device_idle = lambda: False

            def gated_put(a):
                gate.wait(10.0)
                return jax.device_put(a)

            pipe._spine._device_put = gated_put
            gate.clear()
            pipe.submit_columns(make_columns(rng, 50))
            t0 = time.monotonic()
            pipe.pump(4.0)
            assert time.monotonic() - t0 < 5.0
            assert pipe._spine.pending() == 1
            gate.set()
            assert pipe._spine._staged[0].ready.wait(10.0)
            pipe.pump(5.0)
            assert pipe._spine.pending() == 0
            assert pipe.spine_stats()["overlap_hits"] == 1
            assert pipe.stats.spans_harvested == 250
        finally:
            gate.set()
            daemon.shutdown()
