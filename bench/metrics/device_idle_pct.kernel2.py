"""Share of the kernel-2 window in which no operation ran on the device:
1 - (union of device-op intervals) / window, from the device trace, mean
over the chips."""


def read(run):
    if run.driver != "single_source" or run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
