#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``: a run of a cell
as ``bench/run.py`` makes it, with the program's own ``max_levels`` cutting
every traversal one level short of the shallowest root's depth.  It has to
come out not correct.  The benchmark's own runs never run it.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>
"""

from run import prepare

if __name__ == "__main__":
    raise SystemExit(prepare(control=True))
