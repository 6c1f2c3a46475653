"""Arithmetic on the program's own spans, shared by the readers that take
them (perfbench/metrics/*): from the program's registry on the host's clock
(`ytklearn_tpu.obs.spans_between`), and laid onto the device trace's clock
through the annotations the same spans leave in the trace's host planes.

A span here is a dict with `name`, `id`, `parent`, `step`, `start`, `end`
(seconds, one clock throughout a list). Where the program has no such
function, span, counter or map (a parent commit from before they existed),
every entry point returns None and the reader leaves its metric out.

Which operation of a trace belongs to which of the program's scopes is
looked up in the map the program wrote when it compiled (`obs.scopes`:
module name -> instruction name -> scope); no HLO text is read here beyond
an instruction's name in front of its ` = `.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


# -- the registry's spans, on time.perf_counter ----------------------------

def program_spans(t0: float, t1: float) -> Optional[List[dict]]:
    """The program's finished spans that overlap [t0, t1]."""
    try:
        from ytklearn_tpu import obs
    except ImportError:
        return None
    fn = getattr(obs, "spans_between", None)
    return None if fn is None else fn(t0, t1)


def window_spans(run) -> Optional[List[dict]]:
    return program_spans(run.window.t_open, run.window.t_close)


def setup_spans(run) -> Optional[List[dict]]:
    return program_spans(float("-inf"), run.window.t_open)


def clipped(span: dict, t0: float, t1: float) -> float:
    """Seconds of the span that lie inside [t0, t1]."""
    return max(0.0, min(span["end"], t1) - max(span["start"], t0))


def totals(spans: Iterable[dict], t0: float, t1: float) -> Dict[str, float]:
    """Seconds by span name, each span clipped at the interval's edges."""
    out: Dict[str, float] = {}
    for s in spans:
        d = clipped(s, t0, t1)
        if d > 0:
            out[s["name"]] = out.get(s["name"], 0.0) + d
    return out


def self_seconds(spans: Sequence[dict]) -> Dict[int, float]:
    """Span id -> its duration minus what its children cover (children of
    one span lie on one thread and do not overlap)."""
    out = {s["id"]: s["end"] - s["start"] for s in spans}
    for s in spans:
        if s["parent"] in out:
            out[s["parent"]] -= s["end"] - s["start"]
    return {k: max(v, 0.0) for k, v in out.items()}


def seconds_of(spans: Optional[Sequence[dict]], names: Sequence[str]) -> Optional[float]:
    """Whole seconds of the spans with one of `names`; None where none is there."""
    if spans is None:
        return None
    hit = [s["end"] - s["start"] for s in spans if s["name"] in names]
    return sum(hit) if hit else None


def share_inside(run, names: Sequence[str]) -> Optional[float]:
    """Percent of the window spent inside spans with one of `names` (which
    do not overlap one another); None where the program has no such span."""
    spans = window_spans(run)
    if spans is None or not any(s["name"] in names for s in spans):
        return None
    w = run.window
    inside = sum(clipped(s, w.t_open, w.t_close) for s in spans if s["name"] in names)
    return 100.0 * inside / w.length_s


def share_outside(run, names: Sequence[str]) -> Optional[float]:
    """Percent of the window NOT spent inside the spans with one of `names`."""
    inside = share_inside(run, names)
    return None if inside is None else 100.0 - inside


# -- the device trace -------------------------------------------------------

_PROFILES: Dict[str, object] = {}  # xplane path -> ProfileData, read once a run


def profile(run):
    """The traced run's ProfileData, or None in an untraced run."""
    if run.trace is None:
        return None
    from jax.profiler import ProfileData

    from pb import xplane

    path = xplane.find_xplane(run.trace_dir)
    if path not in _PROFILES:
        _PROFILES.clear()
        _PROFILES[path] = ProfileData.from_file(path)
    return _PROFILES[path]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - a stat the binding cannot decode
        return {}


def trace_annotations(pd) -> List[dict]:
    """The program's spans as the trace holds them: host-plane events with a
    span's name and the `id` the program gave it, seconds on the trace's
    clock. A span that began before the trace did is not among them."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not SPAN_NAME.match(ev.name):
                    continue
                st = _stats(ev)
                if "id" not in st:
                    continue
                step = st.get("step", st.get("step_num"))
                out.append({
                    "name": ev.name, "id": int(st["id"]), "parent": None,
                    "step": None if step is None else int(step),
                    "start": ev.start_ns * 1e-9,
                    "end": (ev.start_ns + ev.duration_ns) * 1e-9,
                })
    return out


def clock_offset(annotations: Sequence[dict], spans: Sequence[dict]) -> Optional[float]:
    """Seconds to add to a registry time to get the trace's: the median
    difference of the starts of the spans both sides hold, matched by id."""
    by_id = {s["id"]: s for s in spans}
    diffs = sorted(a["start"] - by_id[a["id"]]["start"]
                   for a in annotations if a["id"] in by_id)
    return diffs[len(diffs) // 2] if diffs else None


def shifted(spans: Sequence[dict], offset: float) -> List[dict]:
    return [{**s, "start": s["start"] + offset, "end": s["end"] + offset} for s in spans]


def path_at(spans: Sequence[dict], t: float) -> List[dict]:
    """The spans open at time t, outermost first."""
    return sorted((s for s in spans if s["start"] <= t < s["end"]),
                  key=lambda s: (s["start"], -s["end"]))


def name_gaps(gaps: Sequence[Tuple[float, float]], spans: Sequence[dict]) -> List[dict]:
    """Each idle gap (lo, hi seconds) with the span path over its middle and
    whether a span of that path carries a step."""
    out = []
    for lo, hi in gaps:
        path = path_at(spans, 0.5 * (lo + hi))
        out.append({
            "seconds": hi - lo,
            "path": [s["name"] for s in path],
            "step": next((s["step"] for s in reversed(path)
                          if s["step"] is not None), None),
        })
    return out


def window_gaps(device_gaps_ns, first_ns: float, last_ns: float,
                w_lo: float, w_hi: float) -> List[Tuple[float, float]]:
    """The device's idle intervals inside the window [w_lo, w_hi] (seconds on
    the trace's clock): the gaps between operations, and the two edges
    between the window's boundaries and the first and last operation."""
    gaps = [(lo * 1e-9, hi * 1e-9) for lo, hi in device_gaps_ns]
    gaps.append((w_lo, first_ns * 1e-9))
    gaps.append((last_ns * 1e-9, w_hi))
    out = []
    for lo, hi in gaps:
        lo, hi = max(lo, w_lo), min(hi, w_hi)
        if hi > lo:
            out.append((lo, hi))
    return sorted(out)


def idle_by_span(run) -> Optional[List[dict]]:
    """The traced window's idle gaps of the first device, each named by the
    program's spans. The registry's spans are laid onto the trace's clock by
    the annotations the trace holds of them."""
    pd = profile(run)
    if pd is None:
        return None
    w = run.window
    spans = window_spans(run)
    if not spans:
        return None
    offset = clock_offset(trace_annotations(pd), spans)
    if offset is None:
        return None
    dev = run.trace.devices[0]
    gaps = window_gaps(dev.gaps, dev.first_ns, dev.last_ns,
                       w.t_open + offset, w.t_close + offset)
    return name_gaps(gaps, shifted(spans, offset))


def idle_unnamed_pct(run) -> Optional[float]:
    """Percent of the traced window in which the device was idle under no
    span that carries a step. Prints the `perfbench spans:` line: idle
    seconds by innermost span, and the ten longest gaps with their paths."""
    named = idle_by_span(run)
    if named is None:
        return None
    by_span: Dict[str, float] = {}
    for g in named:
        key = g["path"][-1] if g["path"] else "_no_span_"
        by_span[key] = by_span.get(key, 0.0) + g["seconds"]
    longest = sorted(named, key=lambda g: -g["seconds"])[:10]
    from pb import xplane

    print("perfbench spans: " + json.dumps({
        "xplane_bytes": os.path.getsize(xplane.find_xplane(run.trace_dir)),
        "idle_s": sum(g["seconds"] for g in named),
        "idle_unnamed_s": sum(g["seconds"] for g in named if g["step"] is None),
        "idle_by_span": sorted(by_span.items(), key=lambda kv: -kv[1]),
        "longest_gaps": [[g["seconds"], ">".join(g["path"]) or "_no_span_", g["step"]]
                         for g in longest],
    }), file=sys.stderr)
    unnamed = sum(g["seconds"] for g in named if g["step"] is None)
    return 100.0 * unnamed / run.window.length_s


# -- operations by the program's scopes -------------------------------------

def program_scope_map() -> Optional[Dict[str, Dict[str, str]]]:
    try:
        from ytklearn_tpu import obs
    except ImportError:
        return None
    scopes = getattr(obs, "scopes", None)
    return None if scopes is None else scopes.scope_map()


def ops_with_modules(pd, device: int = 0) -> List[Tuple[float, float, str, str]]:
    """(start_ns, dur_ns, module, instruction) of every operation of one
    device: the instruction's name is what stands before ` = ` in the
    event's name, its module the event of the module line that holds its
    start (`jit_iteration(123)` -> `jit_iteration`)."""
    import bisect

    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) != device:
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name.split("(", 1)[0]) for ev in line.events)
            elif line.name == "XLA Ops":
                ops = [(ev.start_ns, ev.duration_ns,
                        ev.name.split(" = ", 1)[0].lstrip("%")) for ev in line.events]
        starts = [mo[0] for mo in mods]
        out = []
        for start, dur, instr in ops:
            j = bisect.bisect_right(starts, start) - 1
            module = mods[j][2] if j >= 0 and start < mods[j][1] else ""
            out.append((start, dur, module, instr))
        return out
    return []


def scope_self_seconds(ops: Sequence[Tuple[float, float, str, str]],
                       scope_map: Dict[str, Dict[str, str]]) -> Dict[str, float]:
    """Self seconds by scope ("" for operations under none), by the
    reduction `pb.xplane` charges operations with: an operation nested in
    another (a loop around its body) is charged what its children leave."""
    from pb import xplane

    events = [(start, dur, scope_map.get(module, {}).get(instr, ""), "")
              for start, dur, module, instr in ops]
    return xplane._reduce_op_line(events)[1]


_BY_SCOPE: Dict[int, Dict[str, float]] = {}  # id(ProfileData) -> seconds by scope


def scope_seconds(run) -> Optional[Dict[str, float]]:
    """Device self seconds of the traced window by the program's scopes,
    reduced once a run (a window of soft trees holds millions of operations
    and three readers ask); None in an untraced run or where the program
    wrote no scope map."""
    pd = profile(run)
    scope_map = program_scope_map()
    if pd is None or not scope_map:
        return None
    if id(pd) not in _BY_SCOPE:
        _BY_SCOPE.clear()
        _BY_SCOPE[id(pd)] = scope_self_seconds(ops_with_modules(pd), scope_map)
        print("perfbench scopes: " + json.dumps(
            sorted(_BY_SCOPE[id(pd)].items(), key=lambda kv: -kv[1])), file=sys.stderr)
    return _BY_SCOPE[id(pd)]


def scope_share_pct(run, scopes: Sequence[str]) -> Optional[float]:
    """Device self seconds of the operations under `scopes` over the busy
    seconds of the traced window; None where the program wrote no map or no
    operation of the trace is under one of them."""
    by_scope = scope_seconds(run)
    if by_scope is None or run.trace.busy_s <= 0:
        return None
    if not any(s in by_scope for s in scopes):
        return None
    return 100.0 * sum(by_scope.get(s, 0.0) for s in scopes) / run.trace.busy_s
