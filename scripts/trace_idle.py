"""Name a device trace's idle time by the detector's own spans.

Reads one JAX profiler trace (``.xplane.pb``) of the serving daemon and
splits the device's idle time in a window by what the daemon thread was
doing: the serve loop's sleep (``detector.loop_wait``), a loop pass
(``detector.step``) and each span nested in it (``detector.dispatch``,
``detector.harvest``, ``detector.self_report``, ...). The daemon thread
is the host-plane line that carries ``detector.loop_wait``. The window
is the first ``--window`` span on the host plane (the benchmark's
``bench.window``), or the whole trace.

    python scripts/trace_idle.py <trace.xplane.pb> [--window bench.window]

Prints one JSON document: the window and busy seconds, the idle seconds
under each span name of the daemon thread and under none, and every
idle gap over ``--gap-ms`` with the innermost daemon-thread span that
covers most of it and the share of it under loop_wait or step.
"""

from __future__ import annotations

import argparse
import json
from collections import Counter, defaultdict

import numpy as np

PREFIX = "detector."
LOOP_WAIT = "detector.loop_wait"
STEP = "detector.step"


def _union(iv) -> np.ndarray:
    iv = sorted((s, e) for s, e in iv if e > s)
    out: list[list[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out).reshape(-1, 2)


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two disjoint, sorted interval sets."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        total += max(hi - lo, 0.0)
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def reduce(path: str, window: str | None = "bench.window", gap_ms: float = 1.0) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    busy_iv, daemon, spans_all = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not busy_iv:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    busy_iv = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]
                spans_all += evs
                if any(n == LOOP_WAIT for _, _, n in evs):
                    daemon += [ev for ev in evs if ev[2].startswith(PREFIX)]
    wins = [(s, e) for s, e, n in spans_all if n == window]
    t0, t1 = wins[0] if wins else (min(s for s, _, _ in spans_all), max(e for _, e, _ in spans_all))
    busy = _union((max(s, t0), min(e, t1)) for s, e in busy_iv)
    edges = np.concatenate([[t0], busy.ravel(), [t1]]).reshape(-1, 2)
    idle = edges[edges[:, 1] > edges[:, 0]]
    by_name: dict[str, list] = defaultdict(list)
    for s, e, n in daemon:
        by_name[n].append((s, e))
    unions = {n: _union(iv) for n, iv in by_name.items()}
    spanned = _union([iv for ivs in by_name.values() for iv in ivs])
    outer = _union(by_name[LOOP_WAIT] + by_name[STEP])
    idle_s = float(np.sum(idle[:, 1] - idle[:, 0])) / 1e9
    under = {n: _overlap(idle, u) / 1e9 for n, u in sorted(unions.items())}
    under["(no detector span)"] = idle_s - _overlap(idle, spanned) / 1e9
    gaps = []
    for g0, g1 in idle:
        if g1 - g0 < gap_ms * 1e6:
            continue
        # (overlap, span length, name) of each daemon span in the gap:
        # the innermost span covering half of it names it, else the
        # span that covers most of it.
        cands = [(min(e, g1) - max(s, g0), e - s, n) for s, e, n in daemon
                 if min(e, g1) > max(s, g0)]
        half = [c for c in cands if c[0] >= (g1 - g0) / 2]
        name = min(half, key=lambda c: c[1])[2] if half else (max(cands)[2] if cands else None)
        gaps.append({"ms": (g1 - g0) / 1e6, "span": name,
                     "under_loop_or_step": _overlap(np.asarray([[g0, g1]]), outer) / (g1 - g0)})
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": float(np.sum(busy[:, 1] - busy[:, 0])) / 1e9 if len(busy) else 0.0,
        "idle_s": idle_s,
        "idle_s_under": under,
        "gaps_over_ms": gap_ms,
        "gaps": len(gaps),
        "gaps_least_under_loop_or_step": min((x["under_loop_or_step"] for x in gaps), default=None),
        "gaps_by_span": dict(Counter(x["span"] for x in gaps).most_common()),
        "longest_gaps": sorted(gaps, key=lambda x: -x["ms"])[:10],
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace")
    ap.add_argument("--window", default="bench.window")
    ap.add_argument("--gap-ms", type=float, default=1.0)
    args = ap.parse_args()
    print(json.dumps(reduce(args.trace, args.window, args.gap_ms), indent=1, default=str))


if __name__ == "__main__":
    main()
