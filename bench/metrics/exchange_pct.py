"""Share of the traversal program's device time spent in collective
operations: the phase-2 ButterFly exchange's ``collective-permute`` rounds
and its scalar ``all-reduce`` (``pmax``), over the traversal module's
device time, both the mean over the chips (device trace).  Only a
single-source run on more than one chip has an exchange to read."""


def read(run):
    t = run.trace
    if (run.driver != "single_source" or run.chips <= 1 or t is None
            or t.collective_s <= 0 or t.module_s <= 0):
        return None
    return 100.0 * t.collective_s / t.module_s
