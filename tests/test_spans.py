"""The served path's own spans: the selftrace span helper, the waits
between layers, the exact verdict counter and the daemon's spans.

- The helper observes its phase, rides a sampled batch trace and, under
  a JAX profiler session, lands on the /host: plane under its table
  name, in the thread that opened it.
- queue_wait + ring_wait + dispatch + report_wait + harvest tile each
  report's harvest lag (the spine on).
- app_anomaly_spans_harvested_total credits exactly the rows harvested
  reports cover; the processed counter keeps its semantics.
- The door, loop-step, self-report and checkpoint phases are observed
  where they happen, and the decode pool's busy seconds are counted.
"""

from __future__ import annotations

import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from opentelemetry_demo_tpu.models.detector import (
    AnomalyDetector,
    DetectorConfig,
)
from opentelemetry_demo_tpu.runtime import selftrace
from opentelemetry_demo_tpu.runtime.pipeline import DetectorPipeline

from .test_selftrace import SMALL, _daemon_env, make_columns

pytestmark = pytest.mark.selftrace



def _recorder():
    """A phase_observe hook that keeps (phase, seconds) in call order."""
    seen: list[tuple[str, float]] = []
    return seen, lambda phase, dt: seen.append((phase, dt))


def _phase_counts(daemon) -> dict[str, float]:
    counters, _ = daemon.registry.snapshot()
    return {
        dict(k[1])["phase"]: v for k, v in counters.items()
        if k[0] == "anomaly_phase_seconds_count"
    }


class TestSpanHelper:
    def test_span_observes_its_phase_and_rides_the_batch_trace(self):
        seen, observe = _recorder()
        trace = selftrace.BatchTrace(0)
        with selftrace.span(
            selftrace.SPAN_DISPATCH, observe=observe, trace=trace,
            attrs=(("k", "v"),),
        ) as sp:
            time.sleep(0.002)
        assert sp.seconds >= 0.002
        assert seen == [(selftrace.PHASE_DISPATCH, sp.seconds)]
        name, start, dur, attrs, _links = trace.spans[-1]
        assert name == selftrace.SPAN_DISPATCH and attrs == (("k", "v"),)
        assert dur == pytest.approx(sp.seconds)
        assert start == pytest.approx(sp.t0 - trace.t0_perf)
        # A span with no phase of its own (the loop's sleep) only
        # annotates and times.
        with selftrace.span(selftrace.SPAN_LOOP_WAIT, observe=observe):
            pass
        assert len(seen) == 1

    def test_span_records_through_an_exception(self):
        seen, observe = _recorder()
        with pytest.raises(KeyError):
            with selftrace.span(selftrace.SPAN_CHECKPOINT, observe=observe):
                raise KeyError("disk")
        assert [p for p, _ in seen] == [selftrace.PHASE_CHECKPOINT]

    def test_spans_land_on_the_profiler_host_plane(self, tmp_path):
        """Under a JAX profiler session the spans are TraceAnnotations:
        read back from the .xplane.pb, each sits on the /host: plane
        under its table name, in the thread that opened it."""
        import threading

        import jax
        from jax.profiler import ProfileData

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with selftrace.span(selftrace.SPAN_STEP):
                time.sleep(0.001)

            def worker():
                with selftrace.span(selftrace.SPAN_DECODE):
                    time.sleep(0.001)

            t = threading.Thread(target=worker, name="span-worker")
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        finally:
            jax.profiler.stop_trace()
        (path,) = list(tmp_path.rglob("*.xplane.pb"))
        lines: dict[str, set] = {}
        for plane in ProfileData.from_file(str(path)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("detector."):
                        lines.setdefault(ev.name, set()).add((plane.name, i))
        assert set(lines) == {selftrace.SPAN_STEP, selftrace.SPAN_DECODE}
        # Two threads, two lines of the host plane.
        assert lines[selftrace.SPAN_STEP].isdisjoint(lines[selftrace.SPAN_DECODE])


class TestWaitTiling:
    def test_waits_tile_each_reports_harvest_lag(self):
        """With the spine on, queue_wait + ring_wait + dispatch +
        report_wait + harvest equal each report's harvest lag: one
        clock, shared stamps, no gaps."""
        seen, observe = _recorder()
        config = DetectorConfig(**SMALL)
        pipe = DetectorPipeline(
            AnomalyDetector(config), batch_size=64, spine_ring=2,
            phase_observe=observe,
        )
        rng = np.random.default_rng(5)
        t = 0.0
        try:
            for i in range(12):
                pipe.submit_columns(make_columns(rng, 40 + 20 * (i % 3)))
                time.sleep(0.002)
                pipe.pump(t)
                t += 0.05
            pipe.drain()
        finally:
            pipe.close()
        assert pipe.stats.reports_skipped == 0
        by: dict[str, list[float]] = {}
        for phase, dt in seen:
            by.setdefault(phase, []).append(dt)
        # Every stage is FIFO (queue, ring, in-flight deque), so the
        # k-th sample of each phase belongs to the k-th batch.
        parts = [
            selftrace.PHASE_QUEUE_WAIT, selftrace.PHASE_RING_WAIT,
            selftrace.PHASE_DISPATCH, selftrace.PHASE_REPORT_WAIT,
            selftrace.PHASE_HARVEST,
        ]
        lags = by[selftrace.PHASE_HARVEST_LAG]
        assert len(lags) == pipe.stats.batches > 1
        for name in parts:
            assert len(by[name]) == len(lags), name
            assert min(by[name]) >= 0.0, name
        for k, lag in enumerate(lags):
            tiled = sum(by[name][k] for name in parts)
            assert abs(tiled - lag) < 1e-3, (k, tiled, lag)
        assert min(by[selftrace.PHASE_QUEUE_WAIT]) >= 0.002


def _counter(daemon, name) -> float:
    counters, _ = daemon.registry.snapshot()
    return counters.get((name, ()), 0.0)


class TestHarvestedCounter:
    def test_harvested_counter_covers_skipped_and_in_flight_rows(
        self, monkeypatch, tmp_path, hold_harvest
    ):
        """Three batches dispatched with the harvest held: the oldest
        report is skipped, two stay in flight. The harvested counter
        credits exactly the rows each harvested report covers (the
        skipped batch's with the next one); the processed counter keeps
        its semantics: every dispatched row at the first harvest."""
        from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
        from opentelemetry_demo_tpu.telemetry import metrics as tm

        _daemon_env(monkeypatch, tmp_path, ANOMALY_SPINE_RING="0")
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        try:
            pipe = daemon.pipeline
            rng = np.random.default_rng(9)
            rows = [40, 50, 60]
            with hold_harvest(pipe):
                for i, n in enumerate(rows):
                    pipe.submit_columns(make_columns(rng, n))
                    pipe.pump(float(i))
            assert pipe.stats.reports_skipped == 1
            assert _counter(daemon, tm.ANOMALY_SPANS_HARVESTED) == 0.0
            assert pipe._harvest_one(keep=1)
            assert _counter(daemon, tm.ANOMALY_SPANS_HARVESTED) == 90.0
            assert _counter(daemon, tm.ANOMALY_SPANS_TOTAL) == 150.0
            assert pipe._harvest_one(keep=0)
            assert _counter(daemon, tm.ANOMALY_SPANS_HARVESTED) == 150.0
            assert _counter(daemon, tm.ANOMALY_SPANS_TOTAL) == 150.0
            assert not pipe._harvest_one(keep=0)
            daemon._export_selftrace_stats()
            assert _counter(daemon, tm.ANOMALY_REPORTS_SKIPPED) == 1.0
        finally:
            daemon.shutdown()


class TestDaemonSpans:
    def test_door_observed_per_accepted_post_and_pool_busy_counted(
        self, monkeypatch, tmp_path
    ):
        from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
        from opentelemetry_demo_tpu.runtime.ingestbench import make_payloads
        from opentelemetry_demo_tpu.telemetry import metrics as tm

        _daemon_env(monkeypatch, tmp_path, ANOMALY_INGEST_WORKERS="1")
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        try:
            daemon.start()
            url = f"http://127.0.0.1:{daemon.receiver.port}/v1/traces"

            def post(body: bytes) -> int:
                req = urllib.request.Request(
                    url, data=body, method="POST",
                    headers={"Content-Type": "application/x-protobuf"},
                )
                try:
                    with urllib.request.urlopen(req, timeout=10) as resp:
                        return resp.status
                except urllib.error.HTTPError as e:
                    return e.code

            statuses = [post(p) for p in make_payloads(3, 16, seed=2)]
            statuses.append(post(b"\x0a\xff"))  # malformed: 400
            assert statuses == [200, 200, 200, 400]
            # The door observes after its answer is written: give the
            # handler thread a moment past the client's read.
            deadline = time.monotonic() + 5.0
            while (
                _phase_counts(daemon).get(selftrace.PHASE_DOOR) != 3
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            assert _phase_counts(daemon).get(selftrace.PHASE_DOOR) == 3
            daemon.step()
            assert _counter(daemon, tm.ANOMALY_INGEST_POOL_BUSY) > 0.0
        finally:
            daemon.shutdown()

    def test_daemon_step_observes_loop_step_and_checkpoint(
        self, monkeypatch, tmp_path
    ):
        from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon

        _daemon_env(
            monkeypatch, tmp_path,
            ANOMALY_CHECKPOINT=str(tmp_path / "detector"),
            ANOMALY_CHECKPOINT_INTERVAL_S="0.001",
        )
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        try:
            time.sleep(0.01)
            daemon.step()
            counts = _phase_counts(daemon)
            assert counts.get(selftrace.PHASE_LOOP_STEP) == 1
            assert counts.get(selftrace.PHASE_CHECKPOINT) == 1
            assert counts.get(selftrace.PHASE_SELF_REPORT) == 1
        finally:
            daemon.shutdown()

    def test_compile_counter_counts_backend_compiles(self):
        import jax
        import jax.numpy as jnp

        counter = selftrace.CompileCounter()
        counter.start()
        try:
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
            assert counter.count >= 1
        finally:
            counter.close()
        seen = counter.count
        jax.jit(lambda x: x * 5 - 2)(jnp.arange(7)).block_until_ready()
        assert counter.count == seen  # closed: no longer listening
        counter.close()  # idempotent
