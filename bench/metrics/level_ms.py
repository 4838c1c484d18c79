"""Device time of one level of the traversal program: the traversal
module's device time in the window (device trace) over the levels its
completed traversals ran.  The level loop runs until the frontier is
empty, so a traversal from a root of eccentricity e runs e + 1 levels:
read from its distances, which the window keeps for every traversal
(the kernel-2 mix compares them all).  Unlike ``gteps``, it does not move
with the depth of the roots drawn."""

import numpy as np


def read(run):
    t = run.trace
    answers = run.window.answers
    if (run.driver != "single_source" or t is None or t.module_s <= 0
            or not answers or len(answers) != run.window.completed):
        return None
    unreached = np.iinfo(np.int32).max
    levels = sum(int(np.max(dist[dist != unreached])) + 1
                 for _, dist in answers)
    return 1e3 * t.module_s / levels
