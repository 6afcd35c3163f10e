"""Detection-lag measurement: one methodology, two entry points.

The p99 submit→harvest lag through the REAL DetectorPipeline at a paced
span rate — the second BASELINE north star ("<100 ms p99 detection lag
under the default Locust load profile"). Both ``bench.py`` (the driver
artifact) and ``scripts/bench_lag.py`` (the standalone CLI) call this,
so the reported numbers can never silently diverge.

Timing integrity: every harvest ends in a real device→host fetch (the
packed report), so the lag samples are fetch-terminated.
"""

from __future__ import annotations

import time

import numpy as np

from ..models import AnomalyDetector, DetectorConfig
from .pipeline import DetectorPipeline
from .tensorize import SpanColumns

BASELINE_LAG_MS = 100.0


def make_columns(rng, rows: int) -> SpanColumns:
    return SpanColumns(
        svc=rng.integers(0, 20, size=rows).astype(np.int32),
        lat_us=rng.gamma(4.0, 250.0, size=rows).astype(np.float32),
        is_error=(rng.random(rows) < 0.02).astype(np.float32),
        trace_key=rng.integers(0, 2**63, size=rows, dtype=np.uint64),
        attr_crc=rng.zipf(1.5, size=rows).astype(np.uint64),
    )


def measure_lag(
    rate: float = 2_000.0,
    seconds: float = 12.0,
    batch: int = 256,
    harvest_interval_s: float = 0.0,
    harvest_async: bool = False,
    rtt_probe: bool = True,
    seed: int = 0,
    config: DetectorConfig | None = None,
    adaptive: bool = False,
    max_batch_growth: int = 8,
    settle_s: float = 3.0,
) -> dict:
    """Drive the pipeline at ``rate`` spans/s; return lag statistics.

    The default rate models the north star's own config — the default
    Locust profile is 5 users with 1-10 s waits (~10²-10³ spans/s), not
    the 200k/s throughput stress config (pass ``rate=200_000`` +
    ``harvest_async=True`` for that regime).

    With ``rtt_probe`` (default), every harvest launches one timed
    1-scalar fetch CONCURRENT with its report fetch, and the result
    carries ``p99_net_ms`` = p99 of elementwise lag−RTT beside the
    gross number, plus the RTT distribution itself so the gross p99 can
    be judged against the fetch's own floor and jitter.
    """
    detector = AnomalyDetector(config or DetectorConfig())
    pipe = DetectorPipeline(
        detector,
        batch_size=batch,
        harvest_interval_s=harvest_interval_s,
        harvest_async=harvest_async,
        rtt_probe=rtt_probe,
        adaptive_batching=adaptive,
        max_batch_growth=max_batch_growth,
    )
    rng = np.random.default_rng(seed)
    # Pre-build chunks so generation cost stays off the timed path.
    chunks = [make_columns(rng, batch) for _ in range(16)]
    interval = batch / rate

    # Warmup compiles the step; scrub it from every reported stat.
    # Adaptive mode precompiles the whole width ladder here so a
    # mid-run escalation never pays a compile on the timed path.
    pipe.submit_columns(chunks[0])
    pipe.pump(time.monotonic())
    pipe.drain()
    pipe.warm_widths()

    def paced_loop(duration_s: float, i0: int = 0) -> int:
        end = time.monotonic() + duration_s
        next_at = time.monotonic()
        i = i0
        while time.monotonic() < end:
            now = time.monotonic()
            if now < next_at:
                time.sleep(min(next_at - now, interval))
                continue
            next_at += interval
            pipe.submit_columns(chunks[i % len(chunks)])
            pipe.pump(time.monotonic())
            i += 1
        return i

    # Settle phase (adaptive only): let the width controller find its
    # operating point before measurement — the same warmup-scrub policy
    # as the compile warmup above. The controller's transient (a few
    # hundred ms of skips while it jumps to target) is real but
    # one-time per stress onset; the reported numbers are the sustained
    # regime an operator lives in. ``final_batch_width`` +
    # ``settle_s`` in the output keep the transient auditable.
    i = 0
    if adaptive and settle_s > 0:
        i = paced_loop(settle_s)
        # Barrier before the stats reset: under harvest_async the
        # settle phase's last dispatches are still in flight, and the
        # harvester would otherwise attribute their lag samples and
        # controller-transient skips to the measured window.
        pipe.drain()

    pipe.stats.lag_ms.clear()
    pipe.stats.rtt_ms.clear()
    base_batches = pipe.stats.batches
    base_spans = pipe.stats.spans
    base_skipped = pipe.stats.reports_skipped

    paced_loop(seconds, i)
    pipe.close()

    batches = pipe.stats.batches - base_batches
    skipped = pipe.stats.reports_skipped - base_skipped
    out = {
        "p99_ms": round(pipe.stats.lag_p99_ms(), 3),
        "rate": rate,
        "batches": batches,
        "spans": pipe.stats.spans - base_spans,
        "reports_skipped": skipped,
        # Skip *rate* beside the raw count: a skipped-report tally is
        # only judgeable against the batch denominator it came from.
        "skip_rate": round(skipped / batches, 4) if batches else None,
        # Where the adaptive controller settled (== batch unless it
        # widened under skip pressure) and how long it was given to
        # settle before the measured window.
        "final_batch_width": pipe.batch_width,
        "settle_s": settle_s if adaptive else None,
    }
    net = pipe.stats.lag_net_samples()
    rtt = np.asarray(pipe.stats.rtt_ms, dtype=np.float64)
    rtt = rtt[~np.isnan(rtt)]  # timed-out probes append NaN sentinels
    if net.size and rtt.size:
        out.update(
            p99_net_ms=round(float(np.percentile(net, 99)), 3),
            p50_net_ms=round(float(np.percentile(net, 50)), 3),
            rtt_p50_ms=round(float(np.percentile(rtt, 50)), 3),
            rtt_p99_ms=round(float(np.percentile(rtt, 99)), 3),
            rtt_pairs=int(net.size),
        )
    return out
