"""Device seconds by the program's subscopes: its second naming beside the
scopes (`ytklearn_tpu.obs.scopes.subscope`), for a part of a scope that a
reader wants apart without taking it out of the scope. The partitioned
histogram passes are `gbdt.hist.part`, and stay under `gbdt.hist`. Where
the program has no such map (a parent commit from before it existed, or a
program with no operation under a subscope), every entry point returns None
and the reader leaves its metric out.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, Optional, Sequence

from pb import spans


def program_subscope_map() -> Optional[Dict[str, Dict[str, str]]]:
    try:
        from ytklearn_tpu import obs
    except ImportError:
        return None
    fn = getattr(getattr(obs, "scopes", None), "subscope_map", None)
    return None if fn is None else fn()


_BY_SUBSCOPE: Dict[int, Dict[str, float]] = {}  # id(ProfileData) -> seconds


def subscope_seconds(run) -> Optional[Dict[str, float]]:
    """Device self seconds of the traced window by subscope ("" for the
    operations under none), by `spans.scope_self_seconds`' reduction."""
    pd = spans.profile(run)
    sub_map = program_subscope_map()
    if pd is None or not sub_map:
        return None
    if id(pd) not in _BY_SUBSCOPE:
        _BY_SUBSCOPE.clear()
        _BY_SUBSCOPE[id(pd)] = spans.scope_self_seconds(
            spans.ops_with_modules(pd), sub_map)
        print("perfbench subscopes: " + json.dumps(
            sorted(_BY_SUBSCOPE[id(pd)].items(), key=lambda kv: -kv[1])),
            file=sys.stderr)
    return _BY_SUBSCOPE[id(pd)]


def subscope_share_pct(run, names: Sequence[str]) -> Optional[float]:
    """Device self seconds under `names` over the busy seconds of the traced
    window; None where no operation of the trace is under one of them."""
    by = subscope_seconds(run)
    if by is None or run.trace.busy_s <= 0 or not any(s in by for s in names):
        return None
    return 100.0 * sum(by.get(s, 0.0) for s in names) / run.trace.busy_s
