"""Reader of what `jax.profiler` writes on the installed JAX: the
`*.xplane.pb` under `<dir>/plugins/profile/<time>/`, through
`jax.profiler.ProfileData` and nothing else.

From a traced window it takes, per device: the seconds in which an operation
ran (the union of the op line's intervals), every operation's self time by
name, and the idle gaps, each attributed to the innermost host event that
covers its middle. Reduction only: what a kernel is called is matched by the
patterns a configuration's file gives.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
NO_HOST = "_no_host_span_"


@dataclasses.dataclass
class DeviceTrace:
    busy_s: float
    first_ns: float
    last_ns: float
    op_self_s: Dict[str, float]  # op key -> self seconds
    op_descr: Dict[str, str]  # op key -> text the patterns are matched on
    gaps: List[Tuple[float, float]]  # (start_ns, end_ns) between busy spans


@dataclasses.dataclass
class TraceSummary:
    devices: List[DeviceTrace]
    idle_gaps: List[Tuple[str, float]]  # host event name -> idle seconds
    n_events: int

    @property
    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices traced."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def op_seconds(self, patterns: Sequence[str]) -> Optional[float]:
        """Self seconds of the ops whose description matches any pattern,
        averaged over the devices; None where nothing matches."""
        rx = [re.compile(p) for p in patterns]
        total, hit = 0.0, False
        for d in self.devices:
            for key, s in d.op_self_s.items():
                if any(r.search(d.op_descr[key]) for r in rx):
                    total += s
                    hit = True
        return total / len(self.devices) if hit else None

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        acc: Dict[str, float] = {}
        for d in self.devices:
            for key, s in d.op_self_s.items():
                acc[key] = acc.get(key, 0.0) + s / len(self.devices)
        return sorted(acc.items(), key=lambda kv: -kv[1])[:k]


def find_xplane(trace_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 - a stat the binding cannot decode
        return {}


HLO_RE = re.compile(r"^%(\S+) = \(?([a-z0-9]+\[[0-9,]*\]).*? ([a-z][a-z0-9\-]*)\(")


def op_key(name: str) -> str:
    """A short name that tells operations apart as far as the trace allows.
    On this JAX an event of the op line is named by its whole HLO text,
    `%fusion.60 = f32[8,262144]{...} fusion(...), kind=kCustom, calls=...`:
    keep the op's name, result shape, opcode and fusion kind."""
    m = HLO_RE.match(name)
    if not m:
        return re.sub(r"[^A-Za-z0-9.\-]+", "_", name)[:96]
    kind = re.search(r"kind=(k[A-Za-z]+)", name)
    target = re.search(r'custom_call_target="([^"]+)"', name)
    parts = [m.group(1), re.sub(r"[^A-Za-z0-9]+", "_", m.group(2)).strip("_"),
             m.group(3), kind.group(1) if kind else "",
             target.group(1) if target else ""]
    return "_".join(re.sub(r"[^A-Za-z0-9.\-]+", "_", p) for p in parts if p)


def _reduce_op_line(events) -> Tuple[float, Dict[str, float], Dict[str, str],
                                     List[Tuple[float, float]], float, float]:
    """events: (start_ns, dur_ns, key, descr), any order. Nested events (a
    while loop around its body) are charged their self time only."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    self_s: Dict[str, float] = {}
    descr: Dict[str, str] = {}
    stack: List[List] = []  # [end_ns, key, child_ns, dur_ns]
    busy_ns, gaps = 0.0, []
    cur_lo = cur_hi = None

    def pop():
        end, key, child, dur = stack.pop()
        self_s[key] = self_s.get(key, 0.0) + max(dur - child, 0.0) * 1e-9
        if stack:
            stack[-1][2] += dur

    for start, dur, key, d in events:
        end = start + dur
        while stack and stack[-1][0] <= start:
            pop()
        stack.append([end, key, 0.0, dur])
        descr.setdefault(key, d)
        if cur_lo is None:
            cur_lo, cur_hi = start, end
        elif start > cur_hi:
            busy_ns += cur_hi - cur_lo
            gaps.append((cur_hi, start))
            cur_lo, cur_hi = start, end
        else:
            cur_hi = max(cur_hi, end)
    while stack:
        pop()
    if cur_lo is None:
        return 0.0, {}, {}, [], 0.0, 0.0
    busy_ns += cur_hi - cur_lo
    return busy_ns * 1e-9, self_s, descr, gaps, events[0][0], cur_hi


def _host_events(pd) -> List[Tuple[float, float, str]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    out.sort()
    return out


def _attribute_gaps(gaps, host, top: int = 200) -> List[Tuple[str, float]]:
    """Idle seconds by the innermost host event that covers a gap's middle;
    the `top` longest gaps are looked up, the rest go to NO_HOST."""
    import bisect

    starts = [h[0] for h in host]
    acc: Dict[str, float] = {}
    ranked = sorted(gaps, key=lambda g: g[0] - g[1])
    for i, (lo, hi) in enumerate(ranked):
        name = NO_HOST
        if i < top and host:
            mid = 0.5 * (lo + hi)
            j = bisect.bisect_right(starts, mid)
            best = None
            for s, e, n in host[max(0, j - 4000):j]:
                if e >= mid and (best is None or e - s < best[0]):
                    best = (e - s, n)
            if best is not None:
                name = best[1]
        acc[name] = acc.get(name, 0.0) + (hi - lo) * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])


def summarize(path: str, chips: int = 1) -> TraceSummary:
    """Reduce one xplane file. `chips`: how many device planes must be there."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, n_events = [], 0
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs = []
        named: Dict[str, Tuple[str, str]] = {}  # event name -> (key, text)
        for line in plane.lines:
            if line.name != OP_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if name not in named:
                    # an operation's key and text are its first event's: a
                    # window holds the same few hundred operations millions
                    # of times, and reading an event's stats is the slow part
                    st = _stats(ev)
                    text = " ".join(
                        [name] + [v for v in st.values() if isinstance(v, str)]
                    )
                    named[name] = (op_key(name), text[:4000])
                evs.append((ev.start_ns, ev.duration_ns) + named[name])
        n_events += len(evs)
        busy, self_s, descr, gaps, first, last = _reduce_op_line(evs)
        devices.append(DeviceTrace(busy, first, last, self_s, descr, gaps))
    if len(devices) < chips:
        raise RuntimeError(
            f"trace {path} has {len(devices)} TPU device planes, need {chips}"
        )
    devices = devices[:chips]
    idle = _attribute_gaps(devices[0].gaps, _host_events(pd)) if devices else []
    return TraceSummary(devices, idle, n_events)


def describe(path: str, max_events: int = 6) -> str:
    """What a trace holds, for a look by hand: planes, lines, first events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name}: {len(evs)} events")
            for ev in evs[:max_events]:
                out.append(
                    f"    {ev.name} start={ev.start_ns} dur={ev.duration_ns} "
                    f"stats={ {k: str(v)[:300] for k, v in _stats(ev).items()} }"
                )
    return "\n".join(out)
