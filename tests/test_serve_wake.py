"""The serve loop wakes on admitted rows, edge-triggered.

``DetectorDaemon.run`` waits for the first of two things: new rows
(``DetectorPipeline.wake``, set by every admission in
``submit_columns``) or ``ANOMALY_PUMP_INTERVAL_S``. The interval is
only the longest idle sleep. These tests run the real loop on a thread
and read ``anomaly_pump_wakes_total{cause=}``:

- rows admitted through each ingest path are dispatched well inside a
  long interval, and the pass counts as ``cause="rows"``;
- ``stop()`` ends a long wait at once, with no pass after it;
- rows the pump holds on purpose (the adaptive hold at an escalated
  width) never spin the loop: the wake is an edge, not a level;
- a standby, which pumps no spans, keeps the fixed wait.

Time bounds are generous: the full suite runs beside other workers.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from opentelemetry_demo_tpu.models.detector import DetectorConfig
from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
from opentelemetry_demo_tpu.runtime.replication import ROLE_STANDBY
from opentelemetry_demo_tpu.runtime.tensorize import SpanRecord
from opentelemetry_demo_tpu.telemetry import metrics as tm

from .test_daemon import _payload, _post
from .test_selftrace import SMALL, _daemon_env, make_columns

LONG_S = 5.0  # an interval no test waits out


def _wakes(daemon) -> dict[str, float]:
    counters, _ = daemon.registry.snapshot()
    return {
        cause: counters.get((tm.ANOMALY_PUMP_WAKES, (("cause", cause),)), 0.0)
        for cause in ("rows", "interval")
    }


def _serve(daemon) -> threading.Thread:
    ready = threading.Event()
    serve = threading.Thread(
        target=daemon.run, kwargs={"on_ready": lambda _d: ready.set()},
        name="test-serve", daemon=True,
    )
    serve.start()
    assert ready.wait(60.0)
    return serve


def _stop(daemon, serve) -> None:
    daemon.stop()
    serve.join(timeout=60.0)
    assert not serve.is_alive()


def _wait_for(cond, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def _submit(daemon, path: str, rng) -> None:
    if path == "otlp-http":
        assert _post(daemon.receiver.port, _payload("cart", 20, rng)) == 200
    elif path == "records":
        daemon.pipeline.submit(
            [SpanRecord("cart", 1000.0, i) for i in range(20)]
        )
    else:
        daemon.pipeline.submit_columns(make_columns(rng, 20))


@pytest.mark.parametrize("path", ["otlp-http", "records", "columns"])
def test_admitted_rows_wake_the_loop(monkeypatch, tmp_path, path):
    """With a 5 s interval, rows are dispatched well inside it."""
    _daemon_env(
        monkeypatch, tmp_path, ANOMALY_PUMP_INTERVAL_S=str(LONG_S)
    )
    daemon = DetectorDaemon(DetectorConfig(**SMALL))
    rng = np.random.default_rng(28)
    serve = _serve(daemon)
    try:
        pipe = daemon.pipeline
        # The first batch compiles the step; it is not timed.
        _submit(daemon, path, rng)
        assert _wait_for(lambda: pipe.stats.batches >= 1, 120.0)
        before = _wakes(daemon)
        batches = pipe.stats.batches
        t0 = time.monotonic()
        _submit(daemon, path, rng)
        assert _wait_for(lambda: pipe.stats.batches > batches, 2.0), (
            "rows waited for the interval"
        )
        assert time.monotonic() - t0 < LONG_S / 2
        after = _wakes(daemon)
        assert after["rows"] >= before["rows"] + 1
        assert after["interval"] == before["interval"]
    finally:
        _stop(daemon, serve)


def test_stop_ends_a_long_wait_with_no_further_pass(monkeypatch, tmp_path):
    _daemon_env(monkeypatch, tmp_path, ANOMALY_PUMP_INTERVAL_S="30")
    daemon = DetectorDaemon(DetectorConfig(**SMALL))
    serve = _serve(daemon)
    time.sleep(0.2)  # the loop is inside its 30 s wait
    passes = sum(_wakes(daemon).values())
    t0 = time.monotonic()
    _stop(daemon, serve)
    assert time.monotonic() - t0 < 10.0
    assert sum(_wakes(daemon).values()) == passes


def test_rows_held_pending_do_not_spin_the_loop(monkeypatch, tmp_path):
    """An escalated adaptive hold leaves rows pending with nothing new
    arriving: the loop sleeps out its interval, it does not spin."""
    _daemon_env(monkeypatch, tmp_path, ANOMALY_PUMP_INTERVAL_S="0.1")
    daemon = DetectorDaemon(DetectorConfig(**SMALL))
    pipe = daemon.pipeline
    # The controller's escalated regime, with a hold that never ends.
    pipe.adaptive_batching = True
    pipe._width = 2 * pipe.tensorizer.batch_size
    pipe.max_wait_s = 3600.0
    pipe._last_dispatch = time.monotonic()
    serve = _serve(daemon)
    try:
        pipe.submit_columns(make_columns(np.random.default_rng(0), 20))
        assert _wait_for(lambda: _wakes(daemon)["rows"] >= 1, 10.0)
        before = _wakes(daemon)
        time.sleep(0.5)
        after = _wakes(daemon)
        assert pipe.pending_rows() == 20  # held, not dispatched
        assert pipe.stats.batches == 0
        assert after["rows"] == before["rows"]
        # About 5 interval passes; a level-triggered wake makes hundreds.
        assert after["interval"] - before["interval"] <= 10
    finally:
        _stop(daemon, serve)


def test_standby_keeps_the_fixed_wait(monkeypatch, tmp_path):
    _daemon_env(
        monkeypatch, tmp_path,
        ANOMALY_PUMP_INTERVAL_S="0.2",
        ANOMALY_ROLE="standby",
        ANOMALY_REPLICATION_TARGET="127.0.0.1:1",  # nothing there
        ANOMALY_FAILOVER_TIMEOUT_S="3600",
    )
    daemon = DetectorDaemon(DetectorConfig(**SMALL))
    assert daemon.role == ROLE_STANDBY
    serve = _serve(daemon)
    try:
        rng = np.random.default_rng(1)
        before = _wakes(daemon)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.6:
            daemon.pipeline.submit_columns(make_columns(rng, 4))
            time.sleep(0.01)
        after = _wakes(daemon)
        assert after["rows"] == before["rows"] == 0
        # About 3 passes at 0.2 s; one per arrival would be about 50.
        assert after["interval"] - before["interval"] <= 6
    finally:
        _stop(daemon, serve)
