"""Graph500 kernel-2 rate: the undirected edges of the components that the
window's completed traversals reached, over the window, in billions per
second (host clock)."""


def read(run):
    if run.driver != "single_source" or run.window.seconds <= 0:
        return None
    return run.teps_edges / run.window.seconds / 1e9
