"""Share of the HBM roofline that the traversal program reached: the bytes
any traversal of the reached components needs (``harness.work``) over
the traversal program's device time times the chips' HBM bandwidth
(device trace).  Bandwidth bounds it: a traversal does next to no
arithmetic."""


def read(run):
    t = run.trace
    if run.driver != "single_source" or t is None or t.module_s <= 0:
        return None
    return 100.0 * run.bytes_needed / (
        t.module_s * run.peak("hbm_bytes_per_s") * run.chips)
