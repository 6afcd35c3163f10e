"""Mean time from the pump taking a batch to its dispatch starting (the
spine's staging, put and ring wait):
Δ anomaly_phase_seconds{phase="ring_wait"} sum over count."""


def read(run):
    return run.phase_mean_ms("anomaly_phase_seconds", phase="ring_wait")
