"""Median of the scheduler's ``queue_wait`` stage over the window: submit
until the scheduler drained the request (service telemetry, host
clock)."""


def read(run):
    snap = run.snapshot
    if snap is None or not snap["stages_ms"]["queue_wait"]["count"]:
        return None
    return snap["stages_ms"]["queue_wait"]["p50"]
