"""95th percentile, over every request of the window, of the time from
when it was due (open loop) or submitted (closed loop) to its answer on
the client (host clock).  A request that failed or never came counts as
infinitely late; where those reach the 95th percentile there is no
number to report."""

import math


def read(run):
    if not run.driver.startswith("service") or not run.window.latencies:
        return None
    xs = sorted(run.window.latencies)
    rank = 0.95 * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        return None
    return (xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)) * 1e3
