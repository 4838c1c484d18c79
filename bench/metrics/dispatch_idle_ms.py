"""Host time of one scheduler dispatch in which the device runs none of
its waves: the mean over the window's dispatches of the live
``scheduler/dispatch`` span's duration less the time its engine waves
kept the scheduler blocked on the device (the ``dispatch`` and
``device_wait`` stages of the service's telemetry, host clock).  One
scheduler thread drives the one device, so this is the device's idle time
inside a dispatch: triage, copy-back, row assembly, caching and answering.
A program without those stages gives nothing to read."""


def read(run):
    stages = (run.snapshot or {}).get("stages_ms", {})
    dispatch, waited = stages.get("dispatch"), stages.get("device_wait")
    if not dispatch or not waited or not dispatch["count"]:
        return None
    return (dispatch["mean"] * dispatch["count"]
            - waited["mean"] * waited["count"]) / dispatch["count"]
