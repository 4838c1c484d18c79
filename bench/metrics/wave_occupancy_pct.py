"""Lanes used over lanes offered by the window's engine waves (service
telemetry counters)."""


def read(run):
    snap = run.snapshot
    if snap is None or not snap["dispatches"]:
        return None
    return 100.0 * snap["wave_occupancy"]
