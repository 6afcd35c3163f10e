"""Hot-standby replication: epoch-fenced failover, mergeable-sketch
anti-entropy, and the failover drill.

The acceptance bars this suite proves (ISSUE 5):

- **Failover drill** (``test_failover_drill_sigkill_primary``): SIGKILL
  a real primary daemon subprocess under live Kafka + OTLP load → the
  in-process standby promotes, no committed offset regresses, delivery
  resumes from the replicated offset map (at-least-once), and the
  promoted process answers OTLP ingest.
- **Fencing** (``test_stale_primary_fenced_on_all_three_paths``): a
  stale primary attempting a checkpoint save, a Kafka offset commit,
  or a replication frame after promotion is rejected on all three.
- **Anti-entropy** (``test_blackholed_standby_converges_by_merge``): a
  standby deprived of N deltas converges after reconnect via sketch
  merge (no snapshot re-bootstrap) — HLL/CMS bit-identical to an
  unpartitioned replica's, EWMA exact at quiescence (the documented
  tolerance: replace-latest lags by at most one replication interval
  during flow, equal once the final delta lands).
- **Detection quality across failover**
  (``test_promoted_ttd_within_two_batches``): post-promotion TTD on
  the paymentFailure shape within 2 batches of the uninterrupted run.
"""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from opentelemetry_demo_tpu.models import AnomalyDetector, DetectorConfig
from opentelemetry_demo_tpu.models.detector import DetectorState
from opentelemetry_demo_tpu.runtime import checkpoint, qualbench
from opentelemetry_demo_tpu.runtime.checkpoint import StaleEpochError
from opentelemetry_demo_tpu.runtime.daemon import DetectorDaemon
from opentelemetry_demo_tpu.runtime.faultwire import FaultWire
from opentelemetry_demo_tpu.runtime.kafka_broker import KafkaBroker
from opentelemetry_demo_tpu.runtime.kafka_orders import (
    DeferredOffsets,
    Order,
    OrdersSource,
    encode_order,
)
from opentelemetry_demo_tpu.runtime.replication import (
    ACK,
    DELTA,
    ROLE_FENCED,
    ROLE_PRIMARY,
    ROLE_STANDBY,
    EpochFence,
    ReplicationPrimary,
    ReplicationStandby,
    decode_arrays,
    decode_frame,
    encode_frame,
)
from opentelemetry_demo_tpu.runtime.tensorize import SpanTensorizer

pytestmark = pytest.mark.chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(num_services=8, hll_p=8, cms_width=512)


# --- epoch fence + frame codec ----------------------------------------


class TestEpochFence:
    def test_observe_stale_check_bump(self):
        f = EpochFence(0)
        assert not f.stale()
        f.check()  # no raise
        f.observe(2)
        assert f.stale()
        with pytest.raises(StaleEpochError):
            f.check("checkpoint")
        assert f.fenced_writes == 1
        # Promotion claims an epoch above everything observed.
        assert f.bump() == 3
        assert not f.stale()
        f.check()  # serving again

    def test_frame_round_trip(self):
        arrays = {
            "cms_bank": np.arange(12, dtype=np.int32).reshape(3, 4),
            "lat_mean": np.linspace(0, 1, 5).astype(np.float32),
        }
        blob = encode_frame(
            DELTA, 7, seq=42, base_seq=41, arrays=arrays,
            meta={"offsets": {"0": 9}, "hll_monotone": False},
        )
        frame = decode_frame(blob[4:])  # strip the length prefix
        assert frame["type"] == DELTA
        assert frame["epoch"] == 7
        assert (frame["seq"], frame["base_seq"]) == (42, 41)
        # The ARRAYS payload rides as ONE verified columnar frame
        # (runtime.frame) and stays raw until the apply step verifies
        # it — decode_arrays is that verify+decode.
        payload = decode_arrays(frame["arrays"])
        assert (payload["cms_bank"] == arrays["cms_bank"]).all()
        assert payload["lat_mean"].dtype == np.float32
        assert frame["meta"] == {"offsets": {"0": 9}, "hll_monotone": False}
        # ACK carries no payload.
        ack = decode_frame(encode_frame(ACK, 7, seq=42)[4:])
        assert ack["type"] == ACK and ack["arrays"] == b""


# --- checkpoint epoch fencing -----------------------------------------


class TestCheckpointFencing:
    def test_save_refuses_older_epoch_on_shared_path(self, tmp_path):
        det = AnomalyDetector(DetectorConfig(**SMALL))
        path = str(tmp_path / "snap")
        checkpoint.save(path, det, epoch=3, dispatch_lock=None)
        assert checkpoint.peek_epoch(path) == 3
        with pytest.raises(StaleEpochError):
            checkpoint.save(path, det, epoch=2, dispatch_lock=None)
        # Equal or newer epochs replace normally.
        checkpoint.save(path, det, epoch=3, dispatch_lock=None)
        checkpoint.save(path, det, epoch=4, dispatch_lock=None)
        _det, meta = checkpoint.load(path, DetectorConfig(**SMALL))
        assert meta["epoch"] == 4

    def test_pre_epoch_snapshot_treated_as_epoch_zero(self, tmp_path):
        det = AnomalyDetector(DetectorConfig(**SMALL))
        path = str(tmp_path / "snap")
        checkpoint.save(path, det, dispatch_lock=None)  # default epoch 0
        assert checkpoint.peek_epoch(path) == 0
        checkpoint.save(path, det, epoch=1, dispatch_lock=None)  # newer writer wins


# --- deferred-confirmation offset cap (satellite) ---------------------


class _FakeTicket:
    def __init__(self, done=False, error=None):
        self._done = done
        self._error = error


class TestDeferredOffsets:
    def test_resolve_merges_only_clean_confirmations(self):
        d = DeferredOffsets(cap=8)
        ok = _FakeTicket(done=True)
        failed = _FakeTicket(done=True, error=RuntimeError("flush died"))
        pending = _FakeTicket(done=False)
        d.add(ok, {0: 5})
        d.add(failed, {0: 9})
        d.add(pending, {1: 3})
        merged = d.resolve()
        assert merged == {0: 5}  # failed flush's offsets never merge
        assert len(d) == 1  # only the pending entry survives

    def test_cap_sheds_oldest_and_forces_barrier(self):
        d = DeferredOffsets(cap=3)
        for i in range(5):
            d.add(_FakeTicket(), {0: i})
        assert len(d) == 3
        assert d.dropped_total == 2  # oldest two shed (replay on restart)
        assert d.take_barrier() is True  # caller owes a checkpoint
        assert d.take_barrier() is False  # one barrier per episode
        # The survivors are the NEWEST entries.
        for t, _offs in d._items:
            t._done = True
        assert d.resolve() == {0: 4}


# --- convergence / anti-entropy ---------------------------------------


def _drive(detector, tz, rng, steps, t0=0.0, dt=0.05, lock=None):
    """Feed ``steps`` random batches through detector.observe.

    ``lock`` serializes observes against a concurrent replication
    snapshot_fn: observe DONATES the state buffers, so an unlocked
    snapshot could read a just-deleted array (the daemon guards the
    same race with the pipeline's dispatch lock)."""
    import contextlib

    t = t0
    for _ in range(steps):
        recs = qualbench._batch(rng, tz)
        with (lock or contextlib.nullcontext()):
            detector.observe(recs, t)
        t += dt
    return t


def _state_arrays(detector) -> dict[str, np.ndarray]:
    return {
        k: np.asarray(v) for k, v in detector.state._asdict().items()
    }


def _make_snapshot_fn(detector, offsets, lock=None):
    import contextlib

    def snapshot():
        with (lock or contextlib.nullcontext()):
            arrays = _state_arrays(detector)
            clock_t_prev = detector.clock._t_prev
        return arrays, {
            "offsets": dict(offsets),
            "service_names": [],
            "clock_t_prev": clock_t_prev,
            "config": list(detector.config._replace(sketch_impl=None)),
        }

    return snapshot


def _wait_converged(standby, target_arrays, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        arrs, _meta = standby.snapshot()
        if arrs and all(
            (arrs[k] == target_arrays[k]).all() for k in target_arrays
        ):
            return True
        time.sleep(0.05)
    return False


class TestAntiEntropy:
    def test_blackholed_standby_converges_by_merge(self):
        """Deprive a standby of N deltas (link severed via faultwire),
        keep the primary evolving, heal — the standby converges through
        ONE aggregate delta merge (hll max / cms add), with NO snapshot
        re-bootstrap, bit-identical to an unpartitioned replica."""
        config = DetectorConfig(**SMALL)
        detector = AnomalyDetector(config)
        tz = SpanTensorizer(
            num_services=qualbench.S, batch_size=qualbench.B
        )
        rng = np.random.default_rng(3)
        offsets = {0: 0}
        import threading

        lock = threading.Lock()
        fence_p = EpochFence()
        primary = ReplicationPrimary(
            _make_snapshot_fn(detector, offsets, lock), fence_p,
            interval_s=0.05,
        )
        primary.start()
        proxy = FaultWire("127.0.0.1", primary.port)
        proxy.start()
        fence_a = EpochFence()
        partitioned = ReplicationStandby(
            f"127.0.0.1:{proxy.port}", fence_a
        )
        partitioned.RECONNECT_BACKOFF_S = 0.1
        fence_b = EpochFence()
        witness = ReplicationStandby(  # the unpartitioned replica
            f"127.0.0.1:{primary.port}", fence_b
        )
        try:
            partitioned.start()
            witness.start()
            assert partitioned.wait_for_state(10.0)
            assert witness.wait_for_state(10.0)
            t = _drive(detector, tz, rng, steps=10, lock=lock)
            assert _wait_converged(partitioned, _state_arrays(detector))
            acked_seq = partitioned.applied_seq
            # Partition: sever the link and refuse reconnects — the
            # standby is deprived of every delta while the primary
            # keeps observing (including across window rotations).
            proxy.rst_connects = True
            proxy.kill_connections()
            t = _drive(detector, tz, rng, steps=25, t0=t, lock=lock)
            time.sleep(0.3)  # several missed intervals
            assert partitioned.applied_seq == acked_seq  # truly deprived
            # Heal: reconnect resumes from the retained acked base —
            # anti-entropy is the aggregate delta, not a re-bootstrap.
            proxy.clear()
            final = _state_arrays(detector)
            assert _wait_converged(partitioned, final, timeout=20.0)
            assert partitioned.snapshots_applied == 1, (
                "convergence must come from merge, not snapshot replay"
            )
            # Bit-identical to the unpartitioned replica on the sketch
            # banks; EWMA/latest block exact at quiescence (documented
            # tolerance: ≤ one interval stale during flow, equal once
            # the final delta lands — which _wait_converged asserted).
            assert _wait_converged(witness, final, timeout=20.0)
            part_arrays, part_meta = partitioned.snapshot()
            wit_arrays, _ = witness.snapshot()
            for key in ("hll_bank", "cms_bank"):
                assert (part_arrays[key] == wit_arrays[key]).all(), key
            for key in ("lat_mean", "lat_var", "cusum", "step_idx"):
                assert np.allclose(
                    part_arrays[key], wit_arrays[key]
                ), key
            assert part_meta["offsets"] == {"0": 0}
        finally:
            partitioned.stop()
            witness.stop()
            proxy.stop()
            primary.stop()


# --- fencing: all three write paths -----------------------------------


class TestFencing:
    def test_stale_primary_fenced_on_all_three_paths(self, tmp_path):
        """After a promotion (epoch bump), the stale primary's three
        durable write paths all reject: replication frames (FENCED
        reply), checkpoint saves (fence + on-disk epoch), Kafka offset
        commits (fence + the broker's epoch-tagged metadata)."""
        config = DetectorConfig(**SMALL)
        detector = AnomalyDetector(config)
        fence_old = EpochFence(0)
        primary = ReplicationPrimary(
            _make_snapshot_fn(detector, {0: 0}), fence_old,
            interval_s=0.05,
        )
        primary.start()
        fence_new = EpochFence(0)
        standby = ReplicationStandby(
            f"127.0.0.1:{primary.port}", fence_new
        )
        broker = KafkaBroker()
        broker.start()
        try:
            standby.start()
            assert standby.wait_for_state(10.0)

            # --- the promotion: the standby bumps the epoch ----------
            new_epoch = fence_new.bump()
            assert new_epoch == 1

            # Path 3 (replication frame): the stale primary's next
            # delta is answered FENCED, never applied.
            applied_before = standby.applied_seq
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not fence_old.stale():
                time.sleep(0.05)
            assert fence_old.stale(), "stale primary never learned the epoch"
            assert standby.fenced_sent >= 1
            assert standby.applied_seq == applied_before

            # Path 1 (checkpoint save): both layers refuse — the
            # process-local fence, and the on-disk epoch on a shared
            # volume even for a writer with no fence knowledge.
            path = str(tmp_path / "shared")
            checkpoint.save(path, detector, epoch=new_epoch, dispatch_lock=None)
            with pytest.raises(StaleEpochError):
                fence_old.check("checkpoint")
            with pytest.raises(StaleEpochError):
                checkpoint.save(path, detector, epoch=fence_old.epoch, dispatch_lock=None)

            # Path 2 (Kafka offset commit): the promoted side commits
            # with its epoch tag; the stale primary's commit is
            # fence-refused, and a RESURRECTED stale primary discovers
            # the epoch from the broker before its first write.
            broker.ensure_topic("orders")
            promoted_orders = OrdersSource(f"127.0.0.1:{broker.port}")
            promoted_orders.fence = fence_new
            promoted_orders.commit({0: 7}, epoch=new_epoch)
            stale_orders = OrdersSource(f"127.0.0.1:{broker.port}")
            stale_orders.fence = fence_old
            with pytest.raises(StaleEpochError):
                stale_orders.commit({0: 3}, epoch=fence_old.epoch)
            resurrected = OrdersSource(f"127.0.0.1:{broker.port}")
            assert resurrected.last_committed_epoch() == new_epoch
            promoted_orders.close()
            stale_orders.close()
            resurrected.close()
        finally:
            standby.stop()
            primary.stop()
            broker.stop()


# --- daemon integration -----------------------------------------------


def _daemon_env(monkeypatch, tmp_path, name, **extra):
    monkeypatch.setenv("ANOMALY_OTLP_PORT", "0")
    monkeypatch.setenv("ANOMALY_OTLP_GRPC_PORT", "-1")
    monkeypatch.setenv("ANOMALY_METRICS_PORT", "0")
    monkeypatch.setenv("ANOMALY_BATCH", "256")
    monkeypatch.setenv("ANOMALY_CHECKPOINT", str(tmp_path / name))
    monkeypatch.delenv("KAFKA_ADDR", raising=False)
    for knob in (
        "ANOMALY_ROLE", "ANOMALY_REPLICATION_PORT",
        "ANOMALY_REPLICATION_TARGET", "ANOMALY_REPLICATION_INTERVAL_S",
        "ANOMALY_FAILOVER_TIMEOUT_S", "ANOMALY_PRIMARY_HEALTH_ADDR",
    ):
        monkeypatch.delenv(knob, raising=False)
    for k, v in extra.items():
        monkeypatch.setenv(k, v)


def _step_until(daemon, cond, timeout_s=20.0, poll_s=0.02):
    deadline = time.monotonic() + timeout_s
    t = 0.0
    while time.monotonic() < deadline:
        daemon.step(t)
        if cond():
            return
        t += 0.25
        time.sleep(poll_s)
    raise AssertionError("condition not reached before timeout")


def _healthz(port: int) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5.0)
    conn.request("GET", "/healthz")
    return json.loads(conn.getresponse().read().decode())


class TestDaemonRoles:
    def test_standby_healthz_role_epoch_and_probe(
        self, monkeypatch, tmp_path
    ):
        """Satellite: /healthz carries role+epoch; health_probe --role
        prints them. A standby binds NO ingest ports until promotion."""
        from opentelemetry_demo_tpu.runtime.health_probe import probe_role

        _daemon_env(
            monkeypatch, tmp_path, "sb",
            ANOMALY_ROLE="standby",
            ANOMALY_REPLICATION_TARGET="127.0.0.1:1",  # nothing there
            ANOMALY_FAILOVER_TIMEOUT_S="3600",
        )
        daemon = DetectorDaemon(DetectorConfig(**SMALL))
        daemon.start()
        try:
            assert daemon.role == ROLE_STANDBY
            assert daemon.receiver is None  # no ingest before promotion
            doc = _healthz(daemon.exporter.port)
            assert doc["role"] == "standby"
            assert doc["epoch"] == 0
            assert doc["status"] == "ok"  # a healthy standby IS healthy
            assert probe_role(f"127.0.0.1:{daemon.exporter.port}") == (
                "standby", 0,
            )
            daemon.step(0.0)
            daemon._supervisor.tick()
            text_conn = http.client.HTTPConnection(
                "127.0.0.1", daemon.exporter.port
            )
            text_conn.request("GET", "/metrics")
            text = text_conn.getresponse().read().decode()
            assert 'anomaly_role{role="standby"} 1.0' in text
            assert "anomaly_epoch 0.0" in text
        finally:
            daemon.shutdown()

    def test_stale_primary_boots_fenced_from_broker_tag(
        self, monkeypatch, tmp_path
    ):
        """A resurrected primary whose successor already committed at a
        newer epoch parks FENCED at boot — no orders pumped, no
        checkpoint written."""
        broker = KafkaBroker()
        broker.start()
        try:
            broker.ensure_topic("orders")
            promoted = OrdersSource(f"127.0.0.1:{broker.port}")
            promoted.commit({0: 5}, epoch=2)
            promoted.close()
            _daemon_env(
                monkeypatch, tmp_path, "stale",
                KAFKA_ADDR=f"127.0.0.1:{broker.port}",
            )
            daemon = DetectorDaemon(DetectorConfig(**SMALL))
            try:
                assert daemon.role == ROLE_FENCED
                assert daemon._fence.observed == 2
                daemon.step(0.0)  # must not raise, must not commit
                doc_role = daemon._healthz()[1]["role"]
                assert doc_role == "fenced"
            finally:
                daemon.shutdown()  # must not write a snapshot
            assert not checkpoint.exists(str(tmp_path / "stale"))
        finally:
            broker.stop()

    def test_failover_drill_sigkill_primary(self, monkeypatch, tmp_path):
        """THE drill: SIGKILL a real primary daemon subprocess under
        live Kafka + OTLP load; the in-process standby promotes with
        offset continuity and answers OTLP ingest."""
        from opentelemetry_demo_tpu.runtime.otlp_export import (
            encode_export_request,
        )
        from opentelemetry_demo_tpu.runtime.tensorize import SpanRecord

        broker = KafkaBroker()
        broker.start()
        broker.ensure_topic("orders")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["PYTHONUNBUFFERED"] = "1"
        env.update({
            "ANOMALY_OTLP_PORT": "0",
            # gRPC leg ON (ephemeral): its grpc.health.v1 service is
            # the standby's pre-promotion double-check target below.
            "ANOMALY_OTLP_GRPC_PORT": "0",
            "ANOMALY_METRICS_PORT": "0",
            "ANOMALY_BATCH": "128",
            "ANOMALY_PUMP_INTERVAL_S": "0.05",
            "ANOMALY_CHECKPOINT": str(tmp_path / "primary"),
            "ANOMALY_CHECKPOINT_INTERVAL_S": "1",
            "ANOMALY_NUM_SERVICES": "8",
            "ANOMALY_CMS_WIDTH": "512",
            "ANOMALY_HLL_P": "8",
            "ANOMALY_INGEST_WORKERS": "0",  # serial: offsets confirm inline
            "ANOMALY_ROLE": "primary",
            "ANOMALY_REPLICATION_PORT": "0",
            "ANOMALY_REPLICATION_INTERVAL_S": "0.1",
            "KAFKA_ADDR": f"127.0.0.1:{broker.port}",
        })
        proc = subprocess.Popen(
            [sys.executable, "-m", "opentelemetry_demo_tpu.runtime.daemon"],
            cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        standby = None
        try:
            line = None
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                out = proc.stdout.readline()
                if not out:
                    if proc.poll() is not None:
                        raise RuntimeError(
                            f"primary exited rc={proc.returncode}"
                        )
                    time.sleep(0.05)
                    continue
                if "anomaly-detector:" in out:
                    line = out
                    break
            assert line, "primary never announced"
            otlp_port = int(re.search(r"otlp-http :(\d+)", line).group(1))
            grpc_port = int(re.search(r"otlp-grpc :(\d+)", line).group(1))
            repl_port = int(re.search(r"repl :(\d+)", line).group(1))
            assert repl_port > 0 and grpc_port > 0

            # Live load on both legs: orders into the broker, spans
            # over OTLP/HTTP at the primary.
            for i in range(12):
                broker.append("orders", encode_order(Order(
                    order_id=f"ord-{i}", tracking_id=f"trk-{i}",
                    shipping_cost_units=5.0, item_count=1,
                    product_ids=("EYE-PLO-25",), total_quantity=1,
                )))
            body = encode_export_request([
                SpanRecord(
                    service="payment", duration_us=900.0,
                    trace_id=os.urandom(8), is_error=False, attr="p",
                )
                for _ in range(16)
            ])
            conn = http.client.HTTPConnection(
                "127.0.0.1", otlp_port, timeout=10.0
            )
            conn.request(
                "POST", "/v1/traces", body=body,
                headers={"Content-Type": "application/x-protobuf"},
            )
            assert conn.getresponse().status == 200

            # In-process standby attached to the live primary.
            _daemon_env(
                monkeypatch, tmp_path, "standby",
                ANOMALY_ROLE="standby",
                ANOMALY_REPLICATION_TARGET=f"127.0.0.1:{repl_port}",
                ANOMALY_FAILOVER_TIMEOUT_S="2.0",
                # The pre-promotion health double-check — the
                # product's own spurious-promotion guard, and the
                # reason this drill is deterministic in-suite: the
                # primary's FIRST jitted dispatch can hold its
                # dispatch lock for many seconds under full-suite CPU
                # contention, starving the replication shipper (it
                # snapshots under that lock) past any reasonable
                # silence watchdog. A silence + SERVING health answer
                # resets the watchdog instead of split-braining;
                # after the SIGKILL below the probe fails and
                # promotion proceeds.
                ANOMALY_PRIMARY_HEALTH_ADDR=f"127.0.0.1:{grpc_port}",
                ANOMALY_INGEST_WORKERS="0",
                KAFKA_ADDR=f"127.0.0.1:{broker.port}",
            )
            standby = DetectorDaemon(DetectorConfig(**SMALL))
            standby.start()
            # Wait until the replicated mirror carries CONFIRMED
            # offsets for the pre-kill orders (JSON round-trips the
            # partition keys as strings).
            def replicated_offset() -> int:
                offs = standby.repl_standby.meta.get("offsets") or {}
                return max((int(o) for o in offs.values()), default=0)

            _step_until(
                standby, lambda: replicated_offset() >= 12,
                timeout_s=60.0,
            )
            replicated = {
                int(p): int(o)
                for p, o in standby.repl_standby.meta["offsets"].items()
            }

            # SIGKILL: the real thing, mid-load.
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
            t_kill = time.monotonic()
            _step_until(
                standby, lambda: standby.role == ROLE_PRIMARY,
                timeout_s=30.0,
            )
            ttd = time.monotonic() - t_kill
            assert ttd < 15.0
            # Offset continuity: promotion resumed exactly at the
            # replicated (confirmed) map — nothing regressed.
            assert standby._offsets == replicated
            assert standby._fence.epoch >= 1
            # Post-promotion the orders pump consumes NEW records from
            # the replicated position (at-least-once, no gap).
            for i in range(12, 15):
                broker.append("orders", encode_order(Order(
                    order_id=f"ord-{i}", tracking_id=f"trk-{i}",
                    shipping_cost_units=5.0, item_count=1,
                    product_ids=("EYE-PLO-25",), total_quantity=1,
                )))
            floor = replicated.get(0, 0)
            _step_until(
                standby,
                lambda: standby._offsets.get(0, 0) >= 15,
                timeout_s=30.0,
            )
            assert standby._offsets.get(0, 0) >= max(floor, 15)
            # ...and answers OTLP ingest on its own resolved port.
            conn = http.client.HTTPConnection(
                "127.0.0.1", standby.receiver.port, timeout=10.0
            )
            conn.request(
                "POST", "/v1/traces", body=body,
                headers={"Content-Type": "application/x-protobuf"},
            )
            assert conn.getresponse().status == 200
            # The promotion checkpoint is durable and epoch-stamped.
            assert checkpoint.peek_epoch(str(tmp_path / "standby")) >= 1
        finally:
            if standby is not None:
                standby.shutdown()
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=15)
            broker.stop()


# --- detection quality across failover --------------------------------


def test_promoted_ttd_within_two_batches(tmp_path):
    """Acceptance bar: post-promotion TTD on the paymentFailure shape
    within 2 batches of the uninterrupted run (steady-state TTD — the
    same quantity bench.py's quality leg measures)."""
    WARM, WINDOW, FAILOVER_AT = 100, 40, 50
    config = DetectorConfig(**SMALL)

    def failover_clone(det: AnomalyDetector) -> AnomalyDetector:
        """Replicate det's state to a standby over a REAL link, then
        promote the standby into a fresh detector instance."""
        fence_p = EpochFence()
        primary = ReplicationPrimary(
            _make_snapshot_fn(det, {0: 0}), fence_p, interval_s=0.02
        )
        primary.start()
        fence_s = EpochFence()
        standby = ReplicationStandby(f"127.0.0.1:{primary.port}", fence_s)
        standby.start()
        try:
            assert standby.wait_for_state(10.0)
            assert _wait_converged(standby, _state_arrays(det))
            fence_s.bump()
            arrays, meta = standby.snapshot()
        finally:
            standby.stop()
            primary.kill()  # abrupt, the SIGKILL shape
        det2 = AnomalyDetector(config)
        det2.state = DetectorState(
            **{k: jax.device_put(v) for k, v in arrays.items()}
        )
        det2.clock._t_prev = meta.get("clock_t_prev")
        return det2

    def run(with_failover: bool):
        rng = np.random.default_rng(11)
        frng = np.random.default_rng(7)
        det = AnomalyDetector(config)
        tz = SpanTensorizer(num_services=qualbench.S, batch_size=qualbench.B)
        mutate = qualbench.error_burst(frng, 5, 1.0)
        for step in range(WARM):
            det.observe(qualbench._batch(rng, tz), step * qualbench.DT_S)
            if with_failover and step == FAILOVER_AT:
                det = failover_clone(det)
        for k in range(WINDOW):
            report = det.observe(
                qualbench._batch(rng, tz, mutate=mutate, step=k),
                (WARM + k) * qualbench.DT_S,
            )
            if bool(np.asarray(report.flags)[5]):
                return k + 1
        return None

    baseline = run(with_failover=False)
    promoted = run(with_failover=True)
    assert baseline is not None, "fault must be detectable at all"
    assert promoted is not None, "fault undetectable after failover"
    assert abs(promoted - baseline) <= 2, (
        f"failover moved TTD beyond the bar: {promoted} vs {baseline}"
    )
