"""Tracing + metrics core — one process-wide registry for every layer.

The reference scattered its run evidence across ad-hoc plumbing
(`trainer.time_stats`, per-tree device fetches, stderr progress prints);
this module replaces all of that with three primitives every layer shares:

  spans     nested wall-clock intervals (`with span("tree.grow", tree=t):`)
            with optional device-settled timing (`settle=` blocks on a jax
            value before the end timestamp is taken). A span records its
            own id, its parent's id and the `step` (boosting round, L-BFGS
            iteration) it belongs to, and is a `jax.profiler`
            TraceAnnotation carrying both, so that it lies in a device
            trace on the device trace's clock.
  counters  monotonically accumulated floats (`inc("ingest.rows", n)`)
  gauges    last-write-wins floats (`gauge("gbdt.partition", 1)`)

Everything lands in one `Registry`; the exporters (obs/export.py) turn it
into a JSONL event stream and a Chrome-trace/Perfetto JSON file.

Disabled-path contract (the < 1% tier-1 overhead budget): with obs off,
`span()` is one module-global attribute load plus a cached no-op context
manager, and `inc()`/`gauge()`/`event()` are one attribute load + return.
No locks, no allocation beyond the kwargs dict at the call site. Tests
pin this (tests/test_obs.py::test_disabled_path_is_noop).

Env knobs (read once at import; `configure()` overrides at runtime):
  YTK_TRACE=path        enable + write a Chrome-trace JSON at process exit
  YTK_TRACE_JSONL=path  enable + write the JSONL event stream at exit
  YTK_OBS=1             enable collection without any export
  YTK_OBS=0             force-disable (wins over the path knobs)
"""

from __future__ import annotations

import collections
import itertools
import math
import os
import threading
import time
from typing import Dict, List, Optional

from ..config import knobs

# process-level clock origin: span timestamps are seconds since import on
# the monotonic clock (Chrome trace wants relative µs; JSONL carries the
# wall origin in its meta line so events can be re-anchored)
_T0 = time.perf_counter()
WALL_T0 = time.time()


def _now() -> float:
    return time.perf_counter() - _T0


# span ids: one process-wide sequence (next() on a C iterator is atomic
# under the interpreter lock)
_span_ids = itertools.count(1)

# jax.profiler's annotation classes, looked up once when collection is
# first enabled (`_load_annotations`), never inside a span. With no
# profiler session live an annotation is a flag test in C++.
_TraceAnnotation = None
_StepTraceAnnotation = None


def _load_annotations() -> None:
    global _TraceAnnotation, _StepTraceAnnotation
    if _TraceAnnotation is not None:
        return
    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    _TraceAnnotation, _StepTraceAnnotation = TraceAnnotation, StepTraceAnnotation


class Registry:
    """Process-wide store for counters, gauges, and finished span events.

    Span *stacks* are thread-local (nesting is a per-thread property);
    counters/gauges/events are shared under one lock — contention is nil
    because the hot paths touch the registry a handful of times per
    tree/iteration, never per row.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        self.events: List[dict] = []
        # flight-recorder ring (obs/recorder.py): a bounded deque the
        # recorder installs so the last N events survive for a postmortem
        # dump even though `events` may be huge. None when not installed.
        self.ring = None
        # metrics history plane: per-metric bounded (wall_ts, value) rings
        # fed by sample_history() (the heartbeat sampler thread) so every
        # counter/gauge has a recent time series, not just a point-in-time
        # value. None until enable_history(); bounded per metric by
        # YTK_OBS_HISTORY_N. /metrics?history=1 exports it.
        self.history = None
        self._history_n = 0
        self._tls = threading.local()

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def inc(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = float(value)

    def add_event(self, ev: dict) -> None:
        with self._lock:
            self.events.append(ev)
            if self.ring is not None:
                self.ring.append(ev)

    def snapshot(self) -> dict:
        """Point-in-time copy of counters + gauges (the bench/report
        surface; events are export-only)."""
        with self._lock:
            return {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
            }

    def reset(self) -> None:
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.events.clear()
            if self.ring is not None:
                self.ring.clear()
            if self.history is not None:
                self.history.clear()

    # -- metrics history plane -------------------------------------------

    def enable_history(self, n: int) -> None:
        """Arm per-metric time-series rings of length `n` (idempotent at
        the same capacity; re-arming at a new capacity starts fresh)."""
        with self._lock:
            if self.history is None or self._history_n != n:
                self.history = {}
                self._history_n = max(1, int(n))

    def disable_history(self) -> None:
        with self._lock:
            self.history = None
            self._history_n = 0

    def sample_history(self, now: Optional[float] = None) -> None:
        """Append one (wall_ts, value) sample per live counter/gauge. One
        lock hold, dict-scan cost — called at the history interval (1 s
        default), never per request/row."""
        if self.history is None:
            return
        if now is None:
            now = time.time()
        ts = round(now, 3)
        with self._lock:
            hist = self.history
            if hist is None:  # disabled between check and lock
                return
            n = self._history_n
            for name, value in self.counters.items():
                ring = hist.get(name)
                if ring is None:
                    ring = hist[name] = collections.deque(maxlen=n)
                ring.append((ts, value))
            for name, value in self.gauges.items():
                ring = hist.get(name)
                if ring is None:
                    ring = hist[name] = collections.deque(maxlen=n)
                ring.append((ts, value))

    def history_snapshot(self) -> Optional[dict]:
        """{"series": {name: [[wall_ts, value], ...]}} or None when the
        history plane is off."""
        with self._lock:
            if self.history is None:
                return None
            return {
                "ring_n": self._history_n,
                "series": {
                    name: [[t, v] for t, v in ring]
                    for name, ring in sorted(self.history.items())
                },
            }


REGISTRY = Registry()

#: process identity attached to every obs event + flight dump (serve fleet:
#: a replica worker stamps its replica_id here at startup, so a fleet
#: postmortem names the sick replica instead of "some pid"). Empty = solo
#: process, nothing is attached. Written once at process start, read-only
#: after — no lock needed.
IDENTITY: Dict[str, object] = {}


def set_identity(**kw) -> None:
    """Stamp process identity (e.g. replica_id=3) onto every subsequent
    obs event and flight dump. Values must be JSON-serializable."""
    IDENTITY.update({k: v for k, v in kw.items() if v is not None})


class _State:
    __slots__ = ("enabled", "trace_path", "jsonl_path")

    def __init__(self):
        self.enabled = False
        self.trace_path: Optional[str] = None
        self.jsonl_path: Optional[str] = None


_state = _State()
_UNSET = object()


def enabled() -> bool:
    return _state.enabled


class _NoopSpan:
    """Cached do-nothing context manager — the whole disabled span path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **kw):
        return self

    dur = 0.0


NOOP_SPAN = _NoopSpan()


class Span:
    """An open span; records one complete ("X") event on exit.

    `settle` (array, pytree, or zero-arg callable returning one) is
    block_until_ready'd before the end timestamp — opt-in device-settled
    timing for spans that enqueue async device work.

    `step` is the boosting round or L-BFGS iteration the span belongs to;
    a span given none inherits its parent's. The thread-local stack holds
    the open spans themselves, so a child reads its parent's id and step
    from the top of it. `dur` is the recorded duration, once closed."""

    __slots__ = ("name", "args", "t0", "dur", "id", "parent", "step",
                 "_settle", "_is_step", "_ann")

    def __init__(self, name: str, args: dict, settle=None, step=None,
                 is_step: bool = False):
        self.name = name
        self.args = args
        self.id = next(_span_ids)
        self.parent = None
        self.step = step
        self._settle = settle
        self._is_step = is_step

    def add(self, **kw) -> "Span":
        self.args.update(kw)
        return self

    def __enter__(self) -> "Span":
        stack = REGISTRY._stack()
        if stack:
            top = stack[-1]
            self.parent = top.id
            if self.step is None:
                self.step = top.step
        stack.append(self)
        if self._is_step:
            self._ann = _StepTraceAnnotation(self.name, step_num=self.step, id=self.id)
        elif self.step is not None:
            self._ann = _TraceAnnotation(self.name, id=self.id, step=self.step)
        else:
            self._ann = _TraceAnnotation(self.name, id=self.id)
        self._ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._settle is not None:
            try:
                import jax

                target = self._settle() if callable(self._settle) else self._settle
                jax.block_until_ready(target)
            # ytklint: allow(broad-except) reason=settle targets may be deleted/donated by exit time; timing must never kill the run
            except Exception:
                pass
        self.dur = _now() - self.t0
        self._ann.__exit__(exc_type, exc, tb)
        stack = REGISTRY._stack()
        if stack:
            stack.pop()
        ev = {
            "name": self.name,
            "ph": "X",
            "ts": self.t0,
            "dur": self.dur,
            "tid": threading.get_ident(),
            "depth": len(stack),
            "id": self.id,
            "parent": self.parent,
        }
        if self.step is not None:
            ev["step"] = self.step
        if self.args:
            ev["args"] = self.args
        if exc_type is not None:
            ev.setdefault("args", {})["error"] = exc_type.__name__
        REGISTRY.add_event(ev)
        return False


def span(name: str, settle=None, step=None, **args):
    """`with span("tree.grow", tree=t): ...` — no-op when obs is disabled.

    `settle`: a jax value (or a callable producing one) to block on before
    the end timestamp (device-settled duration). `step`: the round or
    iteration this span belongs to; children inherit it."""
    if not _state.enabled:
        return NOOP_SPAN
    return Span(name, args, settle, step)


def step_span(name: str, step, settle=None, **args):
    """A span that IS a step of the training loop (`gbdt.round`,
    `lbfgs.iteration`): as `span(..., step=step)`, and in a device trace a
    `StepTraceAnnotation` with `step_num`, which the profiler's own tools
    read as the step boundary."""
    if not _state.enabled:
        return NOOP_SPAN
    return Span(name, args, settle, step, is_step=True)


def root_span(name: str, **args):
    """`span(name)`, unless the calling thread already stands inside a span
    of that name: an entry point (the CLI, around the data load) and the
    layer under it (a trainer, which the benchmark calls directly) both ask
    for the run's root, and one is recorded."""
    if not _state.enabled or any(s.name == name for s in REGISTRY._stack()):
        return NOOP_SPAN
    return Span(name, args)


def current_span() -> Optional[Span]:
    """The innermost open span of the calling thread, if any."""
    stack = REGISTRY._stack()
    return stack[-1] if stack else None


def spans_between(t0: float, t1: float) -> List[dict]:
    """The finished spans that overlap [t0, t1], both given on
    `time.perf_counter`: each as {name, id, parent, step, start, end, tid,
    args}, start and end on that same clock and not clipped. What a reader
    of a measured interval needs, without `REGISTRY.events` or the clock
    origin."""
    lo, hi = t0 - _T0, t1 - _T0
    with REGISTRY._lock:
        evs = [
            ev for ev in REGISTRY.events
            if ev["ph"] == "X" and "id" in ev
            and ev["ts"] <= hi and ev["ts"] + ev["dur"] >= lo
        ]
    return [
        {
            "name": ev["name"],
            "id": ev["id"],
            "parent": ev["parent"],
            "step": ev.get("step"),
            "start": ev["ts"] + _T0,
            "end": ev["ts"] + ev["dur"] + _T0,
            "tid": ev["tid"],
            "args": dict(ev.get("args") or {}),
        }
        for ev in evs
    ]


def inc(name: str, value: float = 1.0) -> None:
    if not _state.enabled:
        return
    REGISTRY.inc(name, value)


def gauge(name: str, value: float) -> None:
    if not _state.enabled:
        return
    REGISTRY.gauge(name, value)


def event(name: str, **args) -> None:
    """Instant event (Chrome-trace "i" phase) — a point-in-time marker."""
    if not _state.enabled:
        return
    ev = {
        "name": name,
        "ph": "i",
        "ts": _now(),
        "tid": threading.get_ident(),
        "depth": len(REGISTRY._stack()),
    }
    if IDENTITY:
        args = {**IDENTITY, **args} if args else dict(IDENTITY)
    if args:
        ev["args"] = args
    REGISTRY.add_event(ev)


def snapshot() -> dict:
    return REGISTRY.snapshot()


def reset() -> None:
    REGISTRY.reset()


# ---------------------------------------------------------------------------
# Collective-call recording (parallel/collectives.py hooks)
# ---------------------------------------------------------------------------


def _leaf_bytes(x) -> int:
    """Static byte size of an array-like (works on jax tracers: shape and
    dtype are trace-time facts) or a pytree of them."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            return int(math.prod(shape)) * int(dtype.itemsize)
        # ytklint: allow(broad-except) reason=abstract/extended dtypes without itemsize count as 0 bytes in the census
        except Exception:
            return 0
    if isinstance(x, dict):
        return sum(_leaf_bytes(v) for v in x.values())
    if isinstance(x, (tuple, list)):
        return sum(_leaf_bytes(v) for v in x)
    return 0


def record_collective(verb: str, x, axis_name: str) -> None:
    """Count a collective verb + its operand bytes and drop a zero-duration
    span into the trace.

    Called from the collectives module at *trace time* (inside jit
    tracing), so counts are per-compilation, not per-execution — a static
    census of the program's collective surface. That is exactly what you
    want when debugging a hung multi-host collective ("which verbs, what
    sizes, staged from where"); per-step collective wall time lives in the
    XLA profile (YTK_PROFILE_DIR)."""
    if not _state.enabled:
        return
    nbytes = _leaf_bytes(x)
    REGISTRY.inc(f"collectives.{verb}.calls", 1.0)
    REGISTRY.inc(f"collectives.{verb}.bytes", float(nbytes))
    REGISTRY.add_event(
        {
            "name": f"collectives.{verb}",
            "ph": "X",
            "ts": _now(),
            "dur": 0.0,
            "tid": threading.get_ident(),
            "depth": len(REGISTRY._stack()),
            "args": {"axis": axis_name, "bytes": nbytes, "traced": True},
        }
    )


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

_atexit_registered = False


def _ensure_atexit() -> None:
    global _atexit_registered
    if _atexit_registered:
        return
    import atexit

    atexit.register(flush)
    _atexit_registered = True


def flush() -> None:
    """Write the configured exports now (also runs at process exit)."""
    from .export import export_chrome_trace, export_jsonl

    if _state.trace_path:
        export_chrome_trace(_state.trace_path, REGISTRY)
    if _state.jsonl_path:
        export_jsonl(_state.jsonl_path, REGISTRY)


def configure(
    enabled: Optional[bool] = None,
    trace_path=_UNSET,
    jsonl_path=_UNSET,
) -> None:
    """Runtime configuration (the CLI's --trace-out lands here).

    Setting a non-empty export path implies enabled=True unless `enabled`
    is explicitly passed as False in the same call."""
    if trace_path is not _UNSET:
        _state.trace_path = trace_path or None
        if trace_path and enabled is None:
            enabled = True
    if jsonl_path is not _UNSET:
        _state.jsonl_path = jsonl_path or None
        if jsonl_path and enabled is None:
            enabled = True
    if enabled is not None:
        if enabled:
            _load_annotations()
        _state.enabled = bool(enabled)
    if _state.trace_path or _state.jsonl_path:
        _ensure_atexit()


def _configure_from_env() -> None:
    flag = knobs.get_raw("YTK_OBS")
    if flag == "0":  # force-off wins over everything
        return
    trace = knobs.get_str("YTK_TRACE")
    jsonl = knobs.get_str("YTK_TRACE_JSONL")
    if trace or jsonl or flag == "1":
        configure(enabled=True, trace_path=trace, jsonl_path=jsonl)


_configure_from_env()
