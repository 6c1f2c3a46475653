"""How much of what the program launched a device trace kept.

The profiler keeps about 310 MB of device events of a window and drops the
rest without a word; whatever the window holds after the last kept event
then reads as idle. The program counts every launch of a compiled program
by its module name (counter `launches.<module>`: `scopes.Program` and the
GBDT round dispatch), and the trace's `XLA Modules` line holds one event a
launch it kept. Laid onto one clock (`spans.clock_offset`), the two say
whether the trace was cut: the share is under 100 exactly when the profiler
kept less than the program ran.

Modules the program does not count (an eager operation, a transfer) are left
out of both sides. A module event counts where it starts before the window's
close on the trace's clock. The window's open needs no edge: every family
opens it with the device drained, after the counters' snapshot and the
profiler's start, so the trace holds nothing launched before it; and the
trace's device clock runs about a millisecond ahead of its host clock, so
the first launch after the open can start before the open on that clock.

Where the program counts no launch (a parent commit from before the
counters) or the trace holds none of the program's spans, every entry point
returns None and the reader leaves its metric out.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Sequence

from pb import spans

LAUNCHES = "launches."
MODULE_LINE = "XLA Modules"


def launches_in_window(run) -> Dict[str, float]:
    """Module name -> launches the program counted inside the window."""
    return {k[len(LAUNCHES):]: v for k, v in run.counters_window.items()
            if k.startswith(LAUNCHES) and v > 0}


def module_starts(pd, device: int = 0) -> Dict[str, List[float]]:
    """Module name -> start seconds (the trace's clock) of every event of one
    device's module line (`jit_iteration(123)` -> `jit_iteration`)."""
    out: Dict[str, List[float]] = {}
    for plane in pd.planes:
        m = spans.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != device:
            continue
        for line in plane.lines:
            if line.name == MODULE_LINE:
                for ev in line.events:
                    out.setdefault(ev.name.split("(", 1)[0], []).append(ev.start_ns * 1e-9)
    return out


def kept(starts: Dict[str, List[float]], launches: Dict[str, float],
         close: float) -> Optional[dict]:
    """{"pct", "by_module"}: module events that start by `close` of the
    modules in `launches`, over those launches; None without a launch."""
    total = sum(launches.values())
    if total <= 0:
        return None
    by_module = {name: [sum(t <= close for t in starts.get(name, ())), n]
                 for name, n in sorted(launches.items())}
    return {"pct": 100.0 * sum(k for k, _ in by_module.values()) / total,
            "by_module": by_module}


def offset_spread(annotations: Sequence[dict], registry: Sequence[dict]) -> Optional[dict]:
    """The differences `clock_offset` takes the median of (trace start less
    registry start of each span both hold), as quartiles and extremes about
    that median, in milliseconds."""
    by_id = {s["id"]: s for s in registry}
    diffs = [a["start"] - by_id[a["id"]]["start"] for a in annotations if a["id"] in by_id]
    if len(diffs) < 2:
        return None
    mid = spans.clock_offset(annotations, registry)
    q1, _, q3 = statistics.quantiles(diffs, n=4)
    return {"matched": len(diffs), "iqr_ms": 1e3 * (q3 - q1),
            "min_ms": 1e3 * (min(diffs) - mid), "max_ms": 1e3 * (max(diffs) - mid)}


def trace_kept_pct(run) -> Optional[float]:
    """The share for a traced run; prints the `perfbench kept:` line (kept
    and launched a module, the clock offset's spread, the last device event
    against the window's end)."""
    pd = spans.profile(run)
    if pd is None:
        return None
    launches = launches_in_window(run)
    registry = spans.window_spans(run)
    if not launches or not registry:
        return None
    annotations = spans.trace_annotations(pd)
    offset = spans.clock_offset(annotations, registry)
    if offset is None:
        return None
    close = run.window.t_close + offset
    starts = module_starts(pd)
    got = kept(starts, launches, close)
    if got is None:
        return None
    from pb import xplane

    dev = run.trace.devices[0]
    print("perfbench kept: " + json.dumps({
        "pct": got["pct"], "by_module": got["by_module"],
        "xplane_mb": os.path.getsize(xplane.find_xplane(run.trace_dir)) / 1e6,
        "last_event_before_close_s": close - dev.last_ns * 1e-9,
        # negative: the device clock runs ahead of the host's by at least that
        "first_launch_after_open_ms": 1e3 * (min(
            (t for name in launches for t in starts.get(name, ())), default=close)
            - run.window.t_open - offset),
        "offset_spread": offset_spread(annotations, registry),
    }), file=sys.stderr)
    return got["pct"]
