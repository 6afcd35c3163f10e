"""Share of the decode workers' time spent busy over the window (%):
Δ anomaly_ingest_pool_busy_seconds_total over the window's seconds
times the configuration's ANOMALY_INGEST_WORKERS."""

BUSY = "anomaly_ingest_pool_busy_seconds_total"


def read(run):
    workers = int(run.cell.config["env"].get("ANOMALY_INGEST_WORKERS", 0))
    if workers <= 0 or (BUSY, ()) not in run.snap1[0]:
        return None  # no decode pool, or a program that does not count it
    return 100.0 * run.delta(BUSY) / (run.seconds * workers)
