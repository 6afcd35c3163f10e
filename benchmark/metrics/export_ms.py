"""Mean time to export one harvested report (registry export,
remediation observe and the verdict counters):
Δ anomaly_phase_seconds{phase="export"} sum over count."""


def read(run):
    return run.phase_mean_ms("anomaly_phase_seconds", phase="export")
