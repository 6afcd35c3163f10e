"""Mean time one accepted OTLP trace export spends in the daemon's door,
from handler entry to its 200 written, the decode-ticket wait included:
Δ anomaly_phase_seconds{phase="door"} sum over count in the window."""


def read(run):
    return run.phase_mean_ms("anomaly_phase_seconds", phase="door")
