"""Floor time of the window's passes' tree outputs (work count
`gbst_tree_output`) over the device seconds under the scopes `gbst.lookup`
and `gbst.mixture` together. The fold's two forward evaluations run under
the same scopes and are no passes: their seconds are in the divisor and
their work is not in the floor, which keeps the share a lower bound."""
from pb import spans, work

SCOPES = ("gbst.lookup", "gbst.mixture")


def read(run):
    by_scope = spans.scope_seconds(run)
    if by_scope is None or run.window.steps <= 0:
        return None
    seconds = sum(by_scope.get(s, 0.0) for s in SCOPES)
    if not seconds:
        return None
    floor = work.floor_seconds(work.counter("gbst_tree_output")(run.cell.sizes),
                               run.device["kind"])
    return 100.0 * floor * run.window.steps / seconds
