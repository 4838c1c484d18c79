"""Mean of the scheduler's ``engine`` stage over the window: one dispatch's
engine waves, ending when the answers are on the host (service
telemetry, host clock)."""


def read(run):
    snap = run.snapshot
    if snap is None or not snap["stages_ms"]["engine"]["count"]:
        return None
    return snap["stages_ms"]["engine"]["mean"]
