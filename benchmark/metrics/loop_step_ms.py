"""Mean host time of one pass of the daemon's serve loop (the step
between two pump sleeps): Δ anomaly_phase_seconds{phase="loop_step"}
sum over count."""


def read(run):
    return run.phase_mean_ms("anomaly_phase_seconds", phase="loop_step")
