"""Benchmark: the flagship detector's throughput north star.

**Throughput** — sustained spans/sec through the full single-chip
detector update (HLL + CMS + EWMA heads + heavy-hitter query + window
rotation) on device-resident batches (BASELINE: "≥200,000 spans/sec
sketch updates on a single v5e-1"). Detection lag is measured from the
daemon's OTLP door by ``benchmark/run.py`` (``verdict_p95_ms``).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "spans/sec", "vs_baseline": N,
     ...}

Methodology: two state-chained regions of k1 and k2 steps, each
terminated by a real device→host scalar fetch (the chain's final
``step_idx``); the SLOPE (t2-t1)/(k2-k1) is the per-step cost — fixed
costs (fetch, loop overhead) cancel and device compute cannot be
hidden. The state is donated every step and batches live on device;
window-rotation masks cycle at the cadence a real stream at the
baseline rate would see.

It measures the chip: on any other backend it exits non-zero instead
of timing the CPU under chip names, and a failed leg fails the run.
The leading ``device`` field names what ran it.
"""

from __future__ import annotations

import json
import os
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from opentelemetry_demo_tpu.models import (
    DetectorConfig,
    detector_init,
    detector_step,
)
from opentelemetry_demo_tpu.runtime import SpanTensorizer

BASELINE_SPANS_PER_SEC = 200_000.0
# Host-ingest SLO (the r6 parallel-ingest tentpole): the r5 serial
# native path measured 2.26M spans/s on its CI host — 53× under
# the device rate it feeds. The pooled engine must clear 3× that.
R5_HOST_INGEST_SPANS_PER_SEC = 2_260_000.0
HOST_INGEST_TARGET = 3.0 * R5_HOST_INGEST_SPANS_PER_SEC


def make_batch_pool(config, batch_size, n_pool, rng):
    tz = SpanTensorizer(num_services=config.num_services, batch_size=batch_size)
    pool = []
    for _ in range(n_pool):
        tb = tz.pack_arrays(
            svc=rng.integers(0, 20, size=batch_size),
            lat_us=rng.gamma(4.0, 250.0, size=batch_size).astype(np.float32),
            trace_id=rng.integers(0, 2**63, size=batch_size, dtype=np.uint64),
            is_error=(rng.random(batch_size) < 0.02).astype(np.float32),
            attr_key=rng.zipf(1.5, size=batch_size).astype(np.uint64),
        )
        pool.append(
            tuple(
                jax.device_put(jnp.asarray(x))
                for x in (
                    tb.svc, tb.lat_us, tb.is_error,
                    tb.trace_hi, tb.trace_lo, tb.attr_hi, tb.attr_lo, tb.valid,
                )
            )
        )
    return pool


def measure_throughput(
    config: DetectorConfig,
    batch_size: int,
    rng,
    min_signal_s: float = 0.5,
    target_region_s: float = 2.0,
) -> float:
    """Slope-timed spans/sec through the full detector step.

    Times two state-chained regions of k1/k2 steps, each terminated by a
    real device→host scalar fetch, and reports (t2-t1)/(k2-k1) — fixed
    costs (fetch, loop overhead) cancel, device compute cannot be
    hidden.
    """
    step = jax.jit(partial(detector_step, config), donate_argnums=0)
    n_pool = 4
    pool = make_batch_pool(config, batch_size, n_pool, rng)
    dt_host = batch_size / BASELINE_SPANS_PER_SEC
    dt = jnp.float32(dt_host)

    # Rotation cadence as seen by a stream at the baseline rate.
    steps_per_sec = max(int(1.0 / dt_host), 1)
    masks = []
    for i in range(max(steps_per_sec * 60, 240)):
        masks.append(
            (i % steps_per_sec == 0,
             i % (steps_per_sec * 10) == 0,
             i % (steps_per_sec * 60) == 0)
        )
    uniq = {m: jnp.asarray(m) for m in set(masks)}
    mask_seq = [uniq[m] for m in masks]

    state = detector_init(config)
    # Warmup / compile, then a real fetch so timing starts synchronized.
    state, _report = step(state, *pool[0], dt, mask_seq[1])
    _ = int(np.asarray(state.step_idx))

    def region(k: int, state):
        t0 = time.perf_counter()
        for i in range(k):
            state, _report = step(
                state, *pool[i % n_pool], dt, mask_seq[i % len(mask_seq)]
            )
        _ = int(np.asarray(state.step_idx))  # fetch forces the chain
        return time.perf_counter() - t0, state

    # Calibrate k from a probe SLOPE (two probe lengths) so the fixed
    # fetch cost doesn't inflate the estimate and undersize the timed
    # regions.
    # k1 is bounded ([8, 2000]) so a probe spike can neither hang the
    # bench for hours nor shrink the regions to pure RTT jitter.
    ta, state = region(4, state)
    tb, state = region(12, state)
    per_step_est = max((tb - ta) / 8, 1e-5)
    k1 = min(max(int(target_region_s / per_step_est), 8), 2000)

    # Accept a measurement only when the inter-region signal dwarfs
    # RTT jitter; otherwise grow the regions and retry.
    per_step = 0.0
    signal = 0.0
    for _attempt in range(4):
        k2 = 3 * k1
        t1, state = region(k1, state)
        t2, state = region(k2, state)
        per_step = (t2 - t1) / (k2 - k1)
        signal = t2 - t1
        if per_step > 0 and signal >= min_signal_s:
            break
        k1 = min(k1 * 4, 20_000)
    if per_step <= 0 or signal < min_signal_s:
        raise RuntimeError(
            f"slope {per_step!r} with only {signal:.3f}s of inter-region "
            "signal after retries — timing noise exceeded the signal; "
            "refusing to report"
        )
    return batch_size / per_step


def measure_impl_matrix(rng) -> dict[str, float]:
    """impl × batch-size crossover matrix (BASELINE config #4 audit).

    The dense Pallas kernel's per-span cost is a fixed sweep of all
    sketch cell tiles per batch tile — flat in B — so it owns the
    small-batch low-latency regime; the XLA path's O(1)-per-span
    scatter-free formulation wins throughput at large B. The matrix in
    the artifact makes the auto-select crossover auditable instead of
    asserted. Looser signal floor (0.3 s) than the headline number —
    these are regime comparisons, not the record.
    """
    out: dict[str, float] = {}
    # Both impls at both sides of the reference-geometry ~24k crossover
    # (the r5 calibration table above fused.expected_rates): 16384 is
    # the dense kernel's last winning point, 65536 deep in the xla
    # path's MXU-histogram regime. Compiles dominate the cost, so the
    # sweep stays at 8 entries.
    for impl in ("pallas", "xla"):
        for batch in (2048, 16384, 65536, 524288):
            config = DetectorConfig(sketch_impl=impl)
            rate = measure_throughput(
                config, batch, rng, min_signal_s=0.3, target_region_s=0.8
            )
            out[f"{impl}@{batch}"] = round(rate, 1)
    return out


def main():
    from opentelemetry_demo_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip: no TPU found (JAX's device 0 is "
            f"{dev.platform!r}); refusing to time it under chip names"
        )
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    # 2M: the XLA path (auto-selected for large batches; CMS counting
    # via the transposed-int8 MXU histogram, cms.cms_update_hist)
    # plateaus ~123M spans/s at B=2M single-chip (r5: 105M@512k with
    # this function's tight floors — a loose-floor sweep sampled 97M
    # there — then 115M@1M,
    # 123M@2M, flat to 8M; the r4 f32 engine's 2^24 key cap that
    # blocked >4M-key batches is gone with int32 accumulation).
    batch_size = int(os.environ.get("BENCH_BATCH", 2097152))
    rng = np.random.default_rng(0)
    spans_per_sec = measure_throughput(DetectorConfig(), batch_size, rng)

    # ---- impl × batch crossover (config #4 audit) --------------------
    matrix = {}
    if os.environ.get("BENCH_MATRIX", "1") != "0":
        matrix = measure_impl_matrix(rng)

    # ---- host ingest (SURVEY §7 hard part (a)) -----------------------
    # The other half of the ≥200k/s budget: OTLP bytes → columns on the
    # HOST. Serial = the r5 path (one decode+tensorize per request, one
    # thread) kept as the BEFORE number; the headline is the parallel
    # ingest engine (runtime.ingest_pool: batched decode_many, pooled
    # buffers, coalesced tensorize, N workers) with its worker-count
    # scaling curve. None/{} when the .so can't build here.
    ingest_serial = None
    ingest_rate = None
    ingest_scaling: dict[str, float] = {}
    ingest_detail: dict = {}
    if os.environ.get("BENCH_INGEST", "1") != "0":
        from opentelemetry_demo_tpu.runtime import ingestbench

        payloads = ingestbench.make_payloads()
        ingest_serial = ingestbench.measure_native(
            repeat=3, payloads=payloads
        )
        ingest_scaling = ingestbench.measure_scaling(
            workers_list=(1, 2, 3, 4), payloads=payloads,
            detail=ingest_detail,
        )
        if ingest_scaling:
            ingest_rate = max(ingest_scaling.values())

    # ---- end-to-end ingest spine (payload → flagged report) ----------
    # The number ROADMAP item 1 is gated on: sustained spans/s from raw
    # OTLP bytes through decode pool → admission → device-put spine →
    # donated one-pass step → harvested report. The SLO below checks it
    # against min(host ingest, kernel): ≥90% means the transfer and
    # host glue are genuinely hidden behind the slower of the two
    # endpoints, not just fast in isolation.
    e2e = {}
    if os.environ.get("BENCH_SPINE", "1") != "0":
        from opentelemetry_demo_tpu.runtime import spinebench

        e2e = spinebench.measure_e2e(
            seconds=float(os.environ.get("BENCH_SPINE_SECONDS", "6.0"))
        ) or {}

    # ---- native front door + million-key soak (r19) ------------------
    # The zero-Python door measured against the in-process pool at
    # matched workers (the r19 tentpole gate), plus the scale-of-keys
    # soak: ≥1M distinct (tenant×service) keys through ingest→sketch→
    # query with RSS-per-million-keys reported. Heavy: trim with
    # BENCH_FRONTDOOR_KEYS or skip with BENCH_FRONTDOOR=0. {} on
    # failure — additive artifact fields.
    frontdoor = {}
    frontdoor_soak = {}
    churn_soak = {}
    if os.environ.get("BENCH_FRONTDOOR", "1") != "0":
        from opentelemetry_demo_tpu.runtime import frontdoorbench

        frontdoor = frontdoorbench.measure_frontdoor_vs_pool(
            seconds=float(
                os.environ.get("BENCH_FRONTDOOR_SECONDS", "4.0")
            ),
        ) or {}
        frontdoor_soak = frontdoorbench.measure_million_key_soak(
            target_keys=int(
                os.environ.get("BENCH_FRONTDOOR_KEYS", "1048576")
            ),
        ) or {}
        churn_soak = frontdoorbench.measure_churn_soak(
            waves=int(os.environ.get("BENCH_CHURN_WAVES", "8")),
        ) or {}

    # ---- self-telemetry overhead (the ISSUE 10 canary) ---------------
    # Tracer-on vs tracer-off spinebench A/B with the full production
    # wiring (sampled batch traces + phase histograms): the detector
    # watching itself must cost ≤3% of the path it watches, proven per
    # run, not asserted.
    selftrace_ab = {}
    if os.environ.get("BENCH_SELFTRACE", "1") != "0":
        from opentelemetry_demo_tpu.runtime import spinebench

        selftrace_ab = spinebench.measure_selftrace_overhead() or {}

    # ---- verdict provenance overhead (the ISSUE 18 canary) -----------
    # Provenance-on vs provenance-off spinebench A/B: the per-report
    # trajectory ring (the only provenance work on the hot path —
    # bundle assembly fires only on flags) must cost ≤3% of spine
    # throughput, same discipline as the selftrace gate above.
    #
    explain_ab = {}
    if os.environ.get("BENCH_EXPLAIN", "1") != "0":
        from opentelemetry_demo_tpu.runtime import spinebench

        explain_ab = spinebench.measure_explain_overhead() or {}

    # ---- history replay (the time-travel tentpole) -------------------
    # Record a synthetic incident into the on-disk segment log, then
    # re-feed the recorded frames through a FRESH real pipeline under
    # virtual-time injection: replay_speedup is recorded-seconds per
    # wall-second (gated >= the ANOMALY_HISTORY_REPLAY_RATE target,
    # 10x on CI), and the replayed flag verdicts must equal the
    # recording run's bit-for-bit. history_range_query_p99_ms prices
    # the read path over the just-written ladder.
    replay = {}
    if os.environ.get("BENCH_REPLAY", "1") != "0":
        from opentelemetry_demo_tpu.runtime.replaybench import (
            measure_replay,
        )

        replay = measure_replay()

    # ---- hot-standby failover (the replication tentpole) -------------
    # Real replication link, real kill: failover_ttd_s is the blind
    # window a primary host loss costs (watchdog fire → promoted), and
    # replication_lag_p99_ms bounds how stale the standby's mirror can
    # be. CPU-friendly small geometry — the protocol, not the kernels,
    # is under test.
    repl = {}
    if os.environ.get("BENCH_REPL", "1") != "0":
        from opentelemetry_demo_tpu.runtime.replbench import (
            measure_failover,
        )

        repl = measure_failover()

    # ---- sharded-fleet reshard drill (the fleet tentpole) ------------
    # Kill one of three shards under deterministic load beside an
    # UNKILLED witness fleet: shard_reshard_ttd_s is kill → a survivor
    # answering the victim's keys from its adopted replicated frame,
    # fleet_ok gates the witness-pinned bit-exactness, the blackholed-
    # shard labeled-partial answer, and the noisy-tenant quota
    # isolation. (The live SIGKILL-a-daemon leg runs under `make
    # fleetbench`; the in-proc leg here keeps the flagship line fast.)
    fleet_drill = {}
    if os.environ.get("BENCH_FLEET", "1") != "0":
        from opentelemetry_demo_tpu.runtime.replbench import (
            measure_reshard,
        )

        fleet_drill = measure_reshard()

    # ---- elastic-fleet autoscale drill (the elastic tentpole) --------
    # Two REAL daemon shards wired as an adoptive pair, autoscaler on
    # the heir: ramp OTLP load until admission saturates and a
    # scale-out is proposed, SIGKILL the victim mid-resize, and watch
    # the heir adopt its keyspace with zero operator action.
    # autoscale_tta_s is SIGKILL → adoption applied; autoscale_ok
    # gates the whole contract (real-saturation proposal, automatic
    # adoption, bit-exact witness pin, no oscillation over the quiet
    # window). Slow (two daemon boots) — gate off with
    # BENCH_AUTOSCALE=0.
    autoscale_drill = {}
    if os.environ.get("BENCH_AUTOSCALE", "1") != "0":
        from opentelemetry_demo_tpu.runtime.replbench import (
            measure_adoption,
        )

        autoscale_drill = measure_adoption()

    # ---- live query plane (the read-path tentpole) -------------------
    # Real HTTP query service hammered beside live ingest in one
    # process: query_p99_ms is the dashboard-refresh cost over live
    # sketches, query_qps the sustained read rate, ingest_ratio the
    # "reads don't degrade the write path" guard (the ingest SLOs
    # above stay gated independently).
    queryq = {}
    if os.environ.get("BENCH_QUERY", "1") != "0":
        from opentelemetry_demo_tpu.runtime.querybench import (
            measure_query,
        )

        queryq = measure_query()

    # ---- detection quality: per-fault TTD + false-positive rate ------
    # Detector math is backend-independent; a CPU subprocess steps the
    # ~1900 per-report fetches off the chip, which this process holds.
    quality = {}
    if os.environ.get("BENCH_QUALITY", "1") != "0":
        quality = measure_quality_subprocess()

    # ---- closed-loop auto-mitigation (the remediation tentpole) ------
    # Time-to-mitigate beside time-to-detect: the controller flips the
    # scenario's mitigation flag through the live store, the injector
    # (reading the same store) heals, and the controller VERIFIES the
    # recovery with its own heads — per scenario, with the rollback
    # drill (a mitigation that doesn't heal rolls back on deadline)
    # and the no-oscillation gate (zero flag writes over a long clean
    # run). Same CPU-subprocess methodology as quality.
    mitig = {}
    if os.environ.get("BENCH_MITIG", "1") != "0":
        mitig = measure_mitigation_subprocess()

    # ---- SLO verdicts (BASELINE.md:20-21) ----------------------------
    # Explicit pass/fail so a reader never reconstructs the argument.
    # e2e verdict basis (the ISSUE's gate): min(host ingest, kernel AT
    # THE MATCHED geometry/batch) — the spine bench measures its own
    # device-only reference so the ratio compares like with like; the
    # default-geometry headline kernel is the fallback basis.
    e2e_rate = e2e.get("spans_per_sec")
    e2e_kernel = e2e.get("kernel_spans_per_sec") or spans_per_sec
    # Ingest basis at the e2e run's OWN worker count (the sweep's max
    # may be a deeper pool than the e2e configured — holding the e2e
    # to a 4-worker ingest rate it never had would fail the gate for
    # the wrong reason); fall back to the sweep max.
    e2e_ingest = (
        ingest_scaling.get(str(e2e.get("workers"))) or ingest_rate
        if ingest_scaling else ingest_rate
    )
    e2e_bound = (
        min(e2e_ingest, e2e_kernel) if e2e_ingest else None
    )
    # Decode's share of pooled flush wall time at the 2-worker
    # geometry (the r15 decode-wall attribution; phase_share keys are
    # the TOP-level partition — scan/extract ride decode_split).
    decode_share_2w = (
        (ingest_detail.get("2") or {}).get("phase_share", {}).get("decode")
        if ingest_detail else None
    )
    slo = {
        "north_star_throughput_ok": bool(
            spans_per_sec >= BASELINE_SPANS_PER_SEC
        ),
        # Host-ingest verdict: the pooled engine must sustain ≥3× the
        # r5 serial rate (6.78M spans/s). Same hardware-eligibility
        # rule as decode_wall_ok: a 1-core box cannot run a worker POOL
        # against anything, so the verdict is None (unmeasurable), not
        # a fake regression.
        "host_ingest_ok": (
            bool(ingest_rate >= HOST_INGEST_TARGET)
            if ingest_rate is not None and (os.cpu_count() or 1) >= 2
            else None
        ),
        # Decode-wall verdict (r15): decode's share of pooled flush
        # wall time at the 2-worker CI geometry must sit ≤0.70 — the
        # two-pass scanner's intra-call sharding spreads extraction
        # over spare cores, so decode stops being the one serialized
        # envelope. The lever IS a second core: on a single-core
        # runner no thread can shard anything and the gate reports
        # None (unmeasurable), not a fake pass/fail.
        "decode_wall_ok": (
            bool(decode_share_2w <= 0.70)
            if decode_share_2w is not None and (os.cpu_count() or 1) >= 2
            else None
        ),
        # End-to-end spine verdict: payload→report throughput must
        # reach ≥90% of min(host ingest, kernel) — transfer + host
        # glue hidden behind the slower endpoint, proven not asserted.
        # Null-when-ineligible (decode_wall_ok's rule): the e2e spine
        # needs pool workers + pump + "device" step overlapping, which
        # one core cannot express.
        "e2e_ok": (
            bool(e2e_rate >= 0.9 * e2e_bound)
            if e2e_rate is not None and e2e_bound is not None
            and (os.cpu_count() or 1) >= 2
            else None
        ),
        # Self-telemetry verdict: the batch-lifecycle tracer + phase
        # histograms must cost ≤3% of e2e spine throughput.
        "selftrace_overhead_ok": (
            bool(selftrace_ab["ratio"] <= 1.03)
            if selftrace_ab.get("ratio") is not None else None
        ),
        # Provenance verdict: the evidence plane's per-report ring must
        # cost ≤3% of spine throughput (bundle assembly is flag-rare).
        "explain_overhead_ok": (
            bool(explain_ab["ratio"] <= 1.03)
            if explain_ab.get("ratio") is not None else None
        ),
        # Time-travel verdict: replaying a recorded segment log through
        # the real pipeline must run ≥10× wall clock with verdicts
        # bit-identical to the recording run.
        "replay_ok": replay.get("replay_ok"),
        # Auto-mitigation verdict: ≥3 scenarios with verified recovery,
        # the rollback drill restoring the exact prior flag state, and
        # ZERO flag writes over the long clean run (no oscillation).
        "mitigation_ok": mitig.get("mitigation_ok"),
        # Counterfactual pre-flight verdicts (r17; ride the mitigbench
        # subprocess when BENCH_SHADOW=1): shadow_ok = bit-identical
        # shadow replay at ≥ the rate target AND would-help released
        # within 2× the ungated TTM AND the wrong-flag refusal drill
        # holding (below). preflight_refusal_ok = the refusal drill
        # alone — a mitigation that would NOT help is refused BEFORE
        # any actuator write: zero flag-store mutations, budget token
        # refunded, flight-recorder evidence (ring event + dump file).
        "shadow_ok": mitig.get("shadow_ok"),
        "preflight_refusal_ok": mitig.get("preflight_refusal_ok"),
        # Front-door verdict (r19): OTLP/HTTP spans/s through the
        # native acceptor must meet the in-process pool at matched
        # workers — the framing provably free relative to decode. On a
        # 1-core box the bench's OWN load generator timeshares the
        # serving core, so the verdict is None by the same eligibility
        # rule as decode_wall_ok.
        "frontdoor_ok": (
            bool(
                frontdoor["frontdoor_spans_per_sec"]
                >= frontdoor["pool_spans_per_sec"]
            )
            if frontdoor.get("pool_spans_per_sec")
            and (os.cpu_count() or 1) >= 2
            else None
        ),
        # Million-key soak verdict: bounded intern count, read-back
        # identity, drift refusal at scale, zero corrupt frames —
        # computed inside the soak itself (frontdoorbench).
        "frontdoor_soak_ok": frontdoor_soak.get("soak_ok"),
        # Bounded-memory verdict (r20): RSS per million distinct keys
        # under SOAK_RSS_CEILING_MB_PER_MILLION (the old append-only
        # table's measured ~935 MB/M leak is the fail line). None when
        # RSS is unmeasurable or BENCH_FRONTDOOR_KEYS trimmed the run
        # below the normalization floor.
        "soak_rss_ok": frontdoor_soak.get("soak_rss_ok"),
        # Churn-soak verdict (r20 tentpole gate): ≥3× key budget of
        # distinct keys with churn through a keyspace-enabled pipeline
        # — evictions recycling ids under generation bumps, live-key
        # ids bit-stable, evicted keys answering from history labeled
        # source:"evicted", generation-drifted fleet merge refused,
        # zero corrupt frames, steady-state RSS slope ≈ 0.
        "churn_ok": churn_soak.get("churn_ok"),
    }

    print(
        json.dumps(
            {
                "device": device,
                "metric": "sketch_update_throughput_single_chip",
                "value": round(spans_per_sec, 1),
                "unit": "spans/sec",
                "vs_baseline": round(spans_per_sec / BASELINE_SPANS_PER_SEC, 3),
                **slo,
                "ttd_s": {
                    name: entry.get("ttd_s")
                    for name, entry in (quality.get("ttd") or {}).items()
                },
                "fp_rate": quality.get("fp_rate"),
                "paymentFailure_ttd_by_rate": quality.get(
                    "paymentFailure_ttd_by_rate"
                ),
                "detection_quality": quality or None,
                "host_ingest_spans_per_sec": (
                    round(ingest_rate, 1) if ingest_rate else None
                ),
                "host_ingest_serial_spans_per_sec": (
                    round(ingest_serial, 1) if ingest_serial else None
                ),
                "host_ingest_scaling": ingest_scaling or None,
                "host_ingest_vs_r5": (
                    round(ingest_rate / R5_HOST_INGEST_SPANS_PER_SEC, 3)
                    if ingest_rate else None
                ),
                "host_ingest_phase_share": (
                    ingest_detail.get(
                        max(
                            ingest_scaling,
                            key=lambda k: ingest_scaling[k],
                        ),
                        {},
                    ).get("phase_share")
                    if ingest_scaling else None
                ),
                "host_ingest_decode_share": decode_share_2w,
                "host_ingest_decode_split": (
                    (ingest_detail.get("2") or {}).get("decode_split")
                    if ingest_detail else None
                ),
                "e2e_spans_per_sec": (
                    round(e2e_rate, 1) if e2e_rate else None
                ),
                "e2e_vs_kernel": (
                    round(e2e_rate / e2e_kernel, 3) if e2e_rate else None
                ),
                "e2e_kernel_spans_per_sec": e2e.get("kernel_spans_per_sec"),
                "e2e_vs_host_ingest": (
                    round(e2e_rate / ingest_rate, 3)
                    if e2e_rate and ingest_rate else None
                ),
                "e2e_overlap_ratio": e2e.get("overlap_ratio"),
                "e2e_phase_share": e2e.get("phase_share"),
                "e2e_note": (
                    "payload->flagged-report through decode pool + "
                    "admission + device-put spine + donated one-pass "
                    "step; e2e_ok gates >=90% of min(host ingest, "
                    "kernel at the spine bench's own geometry/batch). "
                    "The host threads share the chip host's cores"
                ) if e2e else None,
                "frontdoor_spans_per_sec": frontdoor.get(
                    "frontdoor_spans_per_sec"
                ),
                "frontdoor_pool_spans_per_sec": frontdoor.get(
                    "pool_spans_per_sec"
                ),
                "frontdoor_vs_pool": frontdoor.get("frontdoor_vs_pool"),
                "frontdoor_soak_keys": frontdoor_soak.get("distinct_keys"),
                "frontdoor_soak_rss_per_million_keys_mb": (
                    frontdoor_soak.get("rss_per_million_keys_mb")
                ),
                "frontdoor_soak_keys_per_sec": frontdoor_soak.get(
                    "keys_per_sec"
                ),
                "frontdoor_soak_overflow_keys": frontdoor_soak.get(
                    "overflow_keys"
                ),
                "churn_soak_evictions": churn_soak.get("evictions"),
                "churn_soak_generation": churn_soak.get("generation"),
                "churn_soak_distinct_streamed": churn_soak.get(
                    "distinct_streamed"
                ),
                "churn_soak_rss_slope_mb": churn_soak.get("rss_slope_mb"),
                "selftrace_overhead_ratio": selftrace_ab.get("ratio"),
                "selftrace_spans_per_sec_on": selftrace_ab.get(
                    "spans_per_sec_on"
                ),
                "selftrace_traces_exported": selftrace_ab.get(
                    "traces_exported"
                ),
                "explain_overhead_ratio": explain_ab.get("ratio"),
                "explain_spans_per_sec_on": explain_ab.get(
                    "spans_per_sec_on"
                ),
                "query_p99_ms": queryq.get("query_p99_ms"),
                "query_p50_ms": queryq.get("query_p50_ms"),
                "query_qps": queryq.get("query_qps"),
                "query_ingest_ratio": queryq.get("ingest_ratio"),
                "explain_p99_ms": queryq.get("explain_p99_ms"),
                "replay_speedup": replay.get("replay_speedup"),
                "replay_verdicts_identical": replay.get(
                    "replay_verdicts_identical"
                ),
                "replay_batches": replay.get("replay_batches"),
                "history_range_query_p99_ms": replay.get(
                    "history_range_query_p99_ms"
                ),
                "history_range_query_p50_ms": replay.get(
                    "history_range_query_p50_ms"
                ),
                "time_to_mitigate_s": mitig.get("time_to_mitigate_s"),
                "mitigation_rollback_exercised": (
                    mitig.get("rollback_drill", {}).get("rolled_back")
                    if mitig else None
                ),
                "mitigation_no_oscillation": (
                    mitig.get("no_oscillation", {}).get("ok")
                    if mitig else None
                ),
                "mitigation_detail": mitig or None,
                "preflight_verdict_s": mitig.get("preflight_verdict_s"),
                "preflight_ttm_ratio": mitig.get("preflight_ttm_ratio"),
                "shadow_identical": mitig.get("shadow_identical"),
                "shadow_speedup": mitig.get("shadow_speedup"),
                "collector_keep_ratio": mitig.get("collector_keep_ratio"),
                "collector_storage_reduction": mitig.get(
                    "collector_storage_reduction"
                ),
                "failover_ttd_s": repl.get("failover_ttd_s"),
                "replication_lag_p99_ms": repl.get(
                    "replication_lag_p99_ms"
                ),
                "failover_converged_exact": repl.get("converged_exact"),
                "shard_reshard_ttd_s": fleet_drill.get(
                    "shard_reshard_ttd_s"
                ),
                "fleet_ok": fleet_drill.get("fleet_ok"),
                "fleet_reshard_bitexact": fleet_drill.get(
                    "reshard_bitexact"
                ),
                "fleet_partial_answer_ok": fleet_drill.get(
                    "partial_answer_ok"
                ),
                "fleet_noisy_tenant_isolated": fleet_drill.get(
                    "noisy_tenant_isolated"
                ),
                "autoscale_tta_s": autoscale_drill.get(
                    "autoscale_tta_s"
                ),
                "autoscale_ok": autoscale_drill.get("autoscale_ok"),
                "autoscale_adoption_bitexact": autoscale_drill.get(
                    "adoption_bitexact"
                ),
                "sketch_impl_matrix": matrix,
            }
        )
    )


def _measure_module_subprocess(module: str, timeout_s: float) -> dict:
    """Run a bench module in a CPU interpreter (this process holds the
    chip); its last stdout line is the result. A failed leg fails the
    run."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", module],
        cwd=here, env=env, capture_output=True, text=True,
        timeout=timeout_s,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"{module} exited {proc.returncode}:\n{proc.stderr[-2000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def measure_quality_subprocess(timeout_s: float = 900.0) -> dict:
    """Detection-quality scenarios (runtime.qualbench) on CPU: detector
    math, identical on any backend."""
    return _measure_module_subprocess(
        "opentelemetry_demo_tpu.runtime.qualbench", timeout_s
    )


def measure_mitigation_subprocess(timeout_s: float = 1500.0) -> dict:
    """Closed-loop mitigation drill (runtime.mitigbench) on CPU: the
    same stepped-report methodology as qualbench, plus the remediation
    controller acting through a live flag store. With BENCH_SHADOW=1
    (default) the subprocess folds in the counterfactual pre-flight
    leg — shadow bit-identity, both verdict directions, the collector
    keep/drop measurement — hence the wider timeout."""
    return _measure_module_subprocess(
        "opentelemetry_demo_tpu.runtime.mitigbench", timeout_s
    )


if __name__ == "__main__":
    main()
