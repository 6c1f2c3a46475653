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
    pd = spans.profile(run)
    scope_map = spans.program_scope_map()
    if pd is None or not scope_map or run.window.steps <= 0:
        return None
    seconds = spans.scope_self_seconds(spans.ops_with_modules(pd), scope_map).get(scope)
    if not seconds:
        return None
    count = work.counter(run.cell.config["work"]["scopes"][scope])
    floor = work.floor_seconds(count(run.cell.sizes), run.device["kind"])
    return 100.0 * floor * run.window.steps / seconds
