"""A scope's share of its roofline: the floor time of a work count for the
window's steps over the device seconds of the operations under one of the
program's scopes (by the scope map the program wrote at compile). The
configuration's `work.scopes` block says which count belongs to which scope.
"""

from __future__ import annotations

from typing import Optional

from pb import spans, work


def scope_roofline_pct(run, scope: str) -> Optional[float]:
    """None in an untraced run, where the program wrote no scope map, or
    where no operation of the trace lies under `scope`."""
    by_scope = spans.scope_seconds(run)
    if by_scope is None or run.window.steps <= 0:
        return None
    seconds = by_scope.get(scope)
    if not seconds:
        return None
    count = work.counter(run.cell.config["work"]["scopes"][scope])
    floor = work.floor_seconds(count(run.cell.sizes), run.device["kind"])
    return 100.0 * floor * run.window.steps / seconds
