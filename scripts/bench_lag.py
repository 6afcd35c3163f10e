"""Detection-lag benchmark CLI: the second north-star metric.

Thin argument front-end over the shared methodology in
``opentelemetry_demo_tpu.runtime.lagbench`` (also what ``bench.py``
embeds in the driver artifact). Prints one JSON line:

    {"metric": "detection_lag_p99", "value": N, "unit": "ms",
     "vs_baseline": <100ms-baseline ratio>, ...}

Usage: python scripts/bench_lag.py [--rate 200000] [--seconds 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from opentelemetry_demo_tpu.runtime.lagbench import (  # noqa: E402
    BASELINE_LAG_MS,
    measure_lag,
)
from opentelemetry_demo_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--rate", type=float, default=2_000.0,
                        help="spans/sec to sustain (default models the "
                        "default Locust profile; 200000 = stress config)")
    parser.add_argument("--seconds", type=float, default=8.0)
    parser.add_argument("--batch", type=int, default=256)
    parser.add_argument("--harvest-interval", type=float, default=0.0,
                        help="report readback cadence, s (0 = every batch)")
    parser.add_argument("--harvest-async", action="store_true",
                        help="fetch reports on a background thread")
    args = parser.parse_args()

    enable_compile_cache()
    stats = measure_lag(
        rate=args.rate,
        seconds=args.seconds,
        batch=args.batch,
        harvest_interval_s=args.harvest_interval,
        harvest_async=args.harvest_async,
    )
    out = {
        "metric": "detection_lag_p99",
        "value": stats["p99_ms"],
        "unit": "ms",
        "vs_baseline": round(BASELINE_LAG_MS / max(stats["p99_ms"], 1e-9), 3),
        "rate_spans_per_sec": stats["rate"],
        "batches": stats["batches"],
        "spans": stats["spans"],
        "reports_skipped": stats["reports_skipped"],
    }
    # Paired-probe fields (see lagbench): net = lag − concurrent RTT.
    for key in ("p99_net_ms", "p50_net_ms", "rtt_p50_ms", "rtt_p99_ms",
                "rtt_pairs"):
        if key in stats:
            out[key] = stats[key]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
