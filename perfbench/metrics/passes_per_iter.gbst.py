"""Loss+gradient passes an L-BFGS iteration in a window of soft trees, by the
harness's passes over the program's `lbfgs.iterations`: a tree's first
evaluation is a pass and no iteration, an iteration whose search failed is
an iteration and adds no pass."""


def read(run):
    it = run.counters_window.get("lbfgs.iterations")
    return run.window.steps / it if it else None
