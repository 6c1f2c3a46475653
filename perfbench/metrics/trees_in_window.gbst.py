"""Trees finished (folded and dumped) between the window's two boundaries
(counter `gbst.trees`); None where the program counts none."""


def read(run):
    return run.counters_window.get("gbst.trees")
