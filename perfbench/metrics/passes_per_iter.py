"""Loss+gradient passes an L-BFGS iteration in the window (line-search
trials included)."""


def read(run):
    it = run.counters_window.get("lbfgs.iterations")
    return run.window.steps / it if it else None
