"""Mean wait of a batch's oldest row in the pipeline's pending queue,
from its enqueue until a pump takes the batch:
Δ anomaly_phase_seconds{phase="queue_wait"} sum over count."""


def read(run):
    return run.phase_mean_ms("anomaly_phase_seconds", phase="queue_wait")
