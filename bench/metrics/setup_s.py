"""Process start to the start of the window: graph build through the
program's ETL, root pick, placement, and compile or persistent-cache
read (host clock)."""


def read(run):
    return run.setup_s
