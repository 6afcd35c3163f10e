"""Mean time a dispatched batch's report waits in flight, from the
dispatch's end to the start of its fetch:
Δ anomaly_phase_seconds{phase="report_wait"} sum over count."""


def read(run):
    return run.phase_mean_ms("anomaly_phase_seconds", phase="report_wait")
