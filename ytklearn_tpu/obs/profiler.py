"""ytkprof — device-time, compile-cost, and memory-watermark profiling.

The r7 span substrate answers *what ran and for how long on the host*;
this plane answers the three questions it could not:

  where does **device** time go?   phase accounting + an opt-in
      `jax.profiler.trace` capture per phase, parsed into device-time
      buckets per named span and a top-k kernel table. On CPU/interpreter
      (no hardware) the plane degrades to settled wall-time: phases still
      decompose the run, the kernel table comes from the CPU trace's HLO
      events when a capture exists and is empty otherwise.

  why did a steady-state **recompile** fire?   a compile ledger records
      every XLA backend compile (program label, abstract arg signature,
      compile ms). Instrumented call sites label the compile via
      `LEDGER.program(...)`; the r8 RetraceSentinel asks the ledger for
      entries since it armed, so `health.retrace` names the culprit
      program and the argument/dim that changed instead of reporting a
      bare counter delta.

  what allocated the memory?   a background watermark sampler feeds
      device bytes-in-use + host RSS into bounded history rings (the r17
      ring idiom) and attributes peak watermarks to the enclosing
      profiler phase; the phase peaks ride flight dumps so an OOM
      postmortem names the allocating phase.

Disabled-path contract (mirrors obs core): with `YTK_PROF` unset/`0`,
`phase()` is one module-global attribute load plus a cached no-op
context manager and `LEDGER.program()` returns the same cached no-op —
zero new per-call work (tests/test_profiler.py pins this).

Knobs: YTK_PROF (`1` = on, a path = on + capture dir), YTK_PROF_TOPK,
YTK_PROF_MEM_S, YTK_PROF_LEDGER_N. The CLI's `--profile [DIR]` lands on
`configure_profiler()`.
"""

from __future__ import annotations

import collections
import logging
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..config import knobs
from . import core
from .recorder import thread_guard

log = logging.getLogger("ytklearn_tpu.obs.profiler")

_UNSET = object()

SCHEMA = "ytkprof"

# signature strings are capped so a pathological pytree cannot bloat
# events, ledger entries, or flight dumps
_SIG_MAX_LEAVES = 256
_DIFF_MAX_LINES = 16


class _ProfState:
    __slots__ = ("on", "capture_dir", "topk", "mem_interval")

    def __init__(self):
        self.on = False
        self.capture_dir: Optional[str] = None
        self.topk = 10
        self.mem_interval = 0.5


_state = _ProfState()


def enabled() -> bool:
    return _state.on


def capture_dir() -> Optional[str]:
    return _state.capture_dir


# ---------------------------------------------------------------------------
# Phase accounting
# ---------------------------------------------------------------------------


class _NoopPhase:
    """Cached do-nothing context manager — the whole disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_PHASE = _NoopPhase()

#: per-process phase stack shared across threads *for reading* by the mem
#: sampler (which must attribute a sample to "the phase the trainer is in
#: right now"); writes happen under _acc_lock. Entries are phase names.
_phase_stack: List[str] = []

_acc_lock = threading.Lock()
#: name -> {"wall_s": float, "count": int, "depth": int(min seen)}
_phases: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
#: (phase_name, capture_subdir) for every completed jax.profiler capture
_captures: List[Tuple[str, str]] = []
#: only one jax.profiler.trace may be live per process
_capture_active = threading.Lock()


def current_phase() -> Optional[str]:
    """Innermost open profiler phase (None outside any phase). Lock-free
    read of the shared stack — worst case the sampler sees a phase one
    tick stale, which is fine for watermark attribution."""
    st = _phase_stack
    return st[-1] if st else None


class _Phase:
    __slots__ = ("name", "_span", "_capture", "_cap_dir", "_t0")

    def __init__(self, name: str, settle, capture: bool, args: dict):
        self.name = name
        self._span = core.span(name, settle=settle, **args)
        self._capture = capture
        self._cap_dir = None

    def __enter__(self) -> "_Phase":
        with _acc_lock:
            _phase_stack.append(self.name)
        # capture must open *before* the span: TraceAnnotations only
        # record when the profiler is live at annotation start, and the
        # phase's own annotation is the top-level bucket in the capture
        if self._capture and _state.capture_dir:
            self._start_capture()
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def _start_capture(self) -> None:
        # one live capture per process: a second concurrent request (or a
        # YTK_PROFILE_DIR trace already running) skips and counts instead
        # of raising out of the phase body
        if not _capture_active.acquire(blocking=False):
            core.inc("prof.capture.skipped")
            return
        sub = os.path.join(
            _state.capture_dir,
            "%s_%d" % (self.name.replace("/", "_"), os.getpid()),
        )
        try:
            import jax.profiler

            os.makedirs(sub, exist_ok=True)
            jax.profiler.start_trace(sub)
            self._cap_dir = sub
        except Exception as e:  # capture is best-effort decoration
            log.debug("prof capture start failed for %s: %s", self.name, e)
            core.inc("prof.capture.failed")
            _capture_active.release()

    def __exit__(self, exc_type, exc, tb) -> bool:
        # Span.__exit__ runs the settle (block_until_ready) before its end
        # timestamp; exiting the span *before* taking our own end time
        # means the accountant records the settled duration too
        self._span.__exit__(exc_type, exc, tb)
        if self._cap_dir is not None:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
                with _acc_lock:
                    _captures.append((self.name, self._cap_dir))
            except Exception as e:  # backend may tear down mid-phase
                log.debug("prof capture stop failed: %s", e)
                core.inc("prof.capture.failed")
            finally:
                _capture_active.release()
        dt = time.perf_counter() - self._t0
        with _acc_lock:
            if _phase_stack:
                _phase_stack.pop()
            depth = len(_phase_stack)
            rec = _phases.get(self.name)
            if rec is None:
                _phases[self.name] = {"wall_s": dt, "count": 1, "depth": depth}
            else:
                rec["wall_s"] += dt
                rec["count"] += 1
                if depth < rec["depth"]:
                    rec["depth"] = depth
        return False


def phase(name: str, settle=None, capture: bool = False, **args):
    """`with profiler.phase("gbdt.train", capture=True): ...`

    Opens an obs span (which carries the TraceAnnotation when armed),
    pushes the phase for watermark attribution, optionally wraps the body
    in a `jax.profiler.trace` capture, and records settled wall time into
    the phase accountant.

    With the plane off this *is* `core.span(...)` — call sites that used
    to open a bare span can move to phase() without changing behavior,
    and with obs off too the whole call degrades to the same cached
    NOOP_SPAN the r7 contract pins."""
    if not _state.on:
        return core.span(name, settle=settle, **args)
    return _Phase(name, settle, capture, args)


def phases_snapshot() -> Dict[str, dict]:
    """{name: {wall_s, count, depth}} in first-seen order."""
    with _acc_lock:
        return {k: dict(v) for k, v in _phases.items()}


def coverage(wall_s: float) -> float:
    """Fraction of `wall_s` decomposed by top-level (depth-0) phases."""
    if wall_s <= 0:
        return 0.0
    with _acc_lock:
        top = sum(v["wall_s"] for v in _phases.values() if v["depth"] == 0)
    return min(1.0, top / wall_s)


# ---------------------------------------------------------------------------
# Abstract signatures (the retrace culprit vocabulary)
# ---------------------------------------------------------------------------


def _leaf_abstract(x) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            name = getattr(dtype, "name", None) or str(dtype)
            return "%s[%s]" % (name, ",".join(str(int(d)) for d in shape))
        # ytklint: allow(broad-except) reason=extended dtypes/symbolic dims fall back to repr below
        except Exception:
            pass
    return type(x).__name__


def abstract_signature(*trees) -> List[List[str]]:
    """Flatten pytrees into `[path, "f32[4,8]"]` pairs — a hashable-ish,
    JSON-friendly abstract signature of a jit call's arguments. Capped at
    _SIG_MAX_LEAVES leaves (a trailing marker records the overflow)."""
    try:
        from jax.tree_util import keystr, tree_flatten_with_path
    # ytklint: allow(broad-except-swallow) reason=jax absent or too old: signatures degrade to positional type names
    except Exception:
        return [["args[%d]" % i, _leaf_abstract(t)] for i, t in enumerate(trees)]
    out: List[List[str]] = []
    for i, tree in enumerate(trees):
        leaves, _ = tree_flatten_with_path(tree)
        for path, leaf in leaves:
            if len(out) >= _SIG_MAX_LEAVES:
                return out + [["...", "+more leaves"]]
            out.append(["args[%d]%s" % (i, keystr(path)), _leaf_abstract(leaf)])
    return out


def signature_diff(old, new) -> List[str]:
    """Human-readable lines naming what changed between two signatures
    (`args[0][1]: f32[4,8] -> f32[5,8]`; added/removed leaves included)."""
    if old is None or new is None:
        return []
    o = {p: a for p, a in old}
    n = {p: a for p, a in new}
    lines: List[str] = []
    for p, a in new:
        if p not in o:
            lines.append("%s: added %s" % (p, a))
        elif o[p] != a:
            lines.append("%s: %s -> %s" % (p, o[p], a))
        if len(lines) >= _DIFF_MAX_LINES:
            lines.append("...")
            return lines
    for p, a in old:
        if p not in n:
            lines.append("%s: removed %s" % (p, a))
            if len(lines) >= _DIFF_MAX_LINES:
                lines.append("...")
                return lines
    return lines


# ---------------------------------------------------------------------------
# Compile ledger
# ---------------------------------------------------------------------------


class CompileLedger:
    """Every XLA backend compile, named. `jax.monitoring` fires compile
    durations synchronously on the compiling thread but carries no
    program identity, so instrumented call sites push a label (and a lazy
    signature thunk) onto a thread-local stack via `program()`; the
    listener attributes the compile to the innermost label, computes the
    signature diff against that program's previous compile, and appends a
    bounded ledger entry. Unlabelled compiles land as `<unlabeled>` —
    still counted, still timed, just anonymous."""

    def __init__(self, maxlen: int = 512):
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.entries: "collections.deque[dict]" = collections.deque(maxlen=maxlen)
        self._last_sig: Dict[str, Any] = {}
        self._by_program: Dict[str, dict] = {}
        self.seq = 0

    # -- labelling ----------------------------------------------------------

    class _ProgramCtx:
        __slots__ = ("_ledger", "_frame")

        def __init__(self, ledger, frame):
            self._ledger = ledger
            self._frame = frame

        def __enter__(self):
            st = getattr(self._ledger._tls, "labels", None)
            if st is None:
                st = self._ledger._tls.labels = []
            st.append(self._frame)
            return self

        def __exit__(self, *exc):
            st = getattr(self._ledger._tls, "labels", None)
            if st:
                st.pop()
            return False

    def program(self, name: str, sig=None, sig_fn=None):
        """`with LEDGER.program("gbdt.round", sig_fn=lambda: ...):` — any
        backend compile inside the body is attributed to `name`. `sig_fn`
        is only called if a compile actually lands (keep it cheap anyway:
        it runs on the compiling thread). Cached no-op when off."""
        if not _state.on:
            return NOOP_PHASE
        return CompileLedger._ProgramCtx(self, (name, sig, sig_fn))

    def _current_label(self):
        st = getattr(self._tls, "labels", None)
        return st[-1] if st else None

    # -- the monitoring listener entry point --------------------------------

    def on_compile(self, duration_s: float) -> None:
        if not _state.on:
            return
        frame = self._current_label()
        if frame is None:
            name, sig = "<unlabeled>", None
        else:
            name, sig, sig_fn = frame
            if sig is None and sig_fn is not None:
                try:
                    sig = sig_fn()
                # ytklint: allow(broad-except) reason=a signature thunk over donated/deleted args must not kill the compile path
                except Exception:
                    sig = None
        ms = duration_s * 1000.0
        with self._lock:
            self.seq += 1
            prev = self._last_sig.get(name)
            changed = signature_diff(prev, sig) if sig is not None else []
            if sig is not None:
                self._last_sig[name] = sig
            entry = {
                "seq": self.seq,
                "ts": round(time.time(), 3),
                "program": name,
                "ms": round(ms, 3),
            }
            if sig is not None:
                entry["sig"] = sig
            if changed:
                entry["changed"] = changed
            self.entries.append(entry)
            agg = self._by_program.setdefault(name, {"compiles": 0, "ms": 0.0})
            agg["compiles"] += 1
            agg["ms"] += ms
        core.inc("compile.ledger.compiles")
        core.inc("compile.ledger.ms", ms)
        if changed:
            core.event("compile.ledger.retrace", program=name, ms=round(ms, 1),
                       changed=changed)

    # -- queries ------------------------------------------------------------

    def mark(self) -> int:
        """Current sequence number — pair with entries_since() to ask
        "what compiled after this point" (the RetraceSentinel handshake)."""
        with self._lock:
            return self.seq

    def entries_since(self, seq: int, limit: int = 8) -> List[dict]:
        with self._lock:
            out = [dict(e) for e in self.entries if e["seq"] > seq]
        return out[-limit:]

    def snapshot(self, limit: int = 32) -> dict:
        with self._lock:
            tail = [dict(e) for e in list(self.entries)[-limit:]]
            return {
                "compiles": sum(v["compiles"] for v in self._by_program.values()),
                "total_ms": round(
                    sum(v["ms"] for v in self._by_program.values()), 3
                ),
                "by_program": {
                    k: {"compiles": v["compiles"], "ms": round(v["ms"], 3)}
                    for k, v in sorted(self._by_program.items())
                },
                "entries": tail,
            }

    def reset(self) -> None:
        with self._lock:
            self.entries.clear()
            self._last_sig.clear()
            self._by_program.clear()
            self.seq = 0


LEDGER = CompileLedger(maxlen=knobs.get_int("YTK_PROF_LEDGER_N") or 512)

_ledger_listener_installed = False


def _install_ledger_listener() -> None:
    """Route jax.monitoring backend-compile durations into LEDGER
    (idempotent; one enabled() check per event when the plane is off)."""
    global _ledger_listener_installed
    if _ledger_listener_installed:
        return
    try:
        import jax.monitoring as monitoring

        def _on_duration(event: str, duration: float, **kw) -> None:
            if _state.on and event.endswith("backend_compile_duration"):
                LEDGER.on_compile(duration)

        monitoring.register_event_duration_secs_listener(_on_duration)
        _ledger_listener_installed = True
    except Exception as e:  # noqa: BLE001 — the ledger is evidence, never the run
        log.debug("compile ledger unavailable: %s", e)
        _ledger_listener_installed = True  # don't retry every call


# ---------------------------------------------------------------------------
# Memory watermark sampler
# ---------------------------------------------------------------------------


def _device_mem_stats() -> Tuple[Optional[float], Optional[float]]:
    """(bytes_in_use, peak_bytes_in_use) from the first jax device, or
    (None, None) on backends without memory_stats (CPU returns None)."""
    try:
        import jax

        dev = jax.devices()[0]
        stats = dev.memory_stats()
        if not stats:
            return None, None
        return (
            float(stats.get("bytes_in_use", 0)),
            float(stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))),
        )
    # ytklint: allow(broad-except) reason=memory_stats is backend-optional; the sampler degrades to host RSS only
    except Exception:
        return None, None


def _host_rss_bytes() -> Optional[float]:
    """Current RSS from /proc (linux); falls back to ru_maxrss (a peak,
    but monotone — still a usable watermark signal)."""
    try:
        # ytklint: allow(unseamed-io) reason=/proc pseudo-file sampler; local kernel read, no durability or retry semantics apply
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) * 1024.0
    # ytklint: allow(broad-except) reason=/proc is linux-only; resource fallback below
    except Exception:
        pass
    try:
        import resource
        import sys

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return float(rss) * (1.0 if sys.platform == "darwin" else 1024.0)
    # ytklint: allow(broad-except) reason=no resource module = no host watermark; device side still samples
    except Exception:
        return None


class MemWatermarkSampler:
    """Background thread sampling device bytes-in-use + host RSS into
    bounded (wall_ts, value) rings, attributing running peaks to the
    enclosing profiler phase. Mirrors the heartbeat sampler lifecycle
    (daemon thread, Event stop, joined in stop())."""

    SERIES = ("mem.device_bytes_in_use", "mem.device_peak_bytes",
              "mem.host_rss_bytes")

    def __init__(self):
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop: Optional[threading.Event] = None
        self._ring_n = 0
        self.rings: Dict[str, "collections.deque"] = {}
        #: phase -> {"device_peak_bytes": x, "host_rss_peak_bytes": y}
        self.phase_peaks: Dict[str, dict] = {}

    def sample_once(self, now: Optional[float] = None) -> None:
        """One tick (also the deterministic unit tests' entry point):
        read stats *outside* the lock, then append + attribute under it."""
        in_use, peak = _device_mem_stats()
        rss = _host_rss_bytes()
        ph = current_phase() or "<none>"
        ts = round(now if now is not None else time.time(), 3)
        with self._lock:
            if self._ring_n <= 0:
                return
            for name, val in (
                ("mem.device_bytes_in_use", in_use),
                ("mem.device_peak_bytes", peak),
                ("mem.host_rss_bytes", rss),
            ):
                if val is None:
                    continue
                ring = self.rings.get(name)
                if ring is None:
                    ring = self.rings[name] = collections.deque(
                        maxlen=self._ring_n
                    )
                ring.append((ts, val))
            pk = self.phase_peaks.setdefault(ph, {})
            if peak is not None or in_use is not None:
                dv = peak if peak is not None else in_use
                if dv > pk.get("device_peak_bytes", -1.0):
                    pk["device_peak_bytes"] = dv
            if rss is not None and rss > pk.get("host_rss_peak_bytes", -1.0):
                pk["host_rss_peak_bytes"] = rss
        if in_use is not None:
            core.gauge("mem.sampled.device_bytes_in_use", in_use)
        if rss is not None:
            core.gauge("mem.sampled.host_rss_bytes", rss)

    @thread_guard
    def _run(self, stop: threading.Event, interval: float) -> None:
        while not stop.is_set():
            self.sample_once()
            stop.wait(interval)

    def start(self, interval: Optional[float] = None,
              ring_n: Optional[int] = None) -> bool:
        if interval is None:
            interval = _state.mem_interval
        if ring_n is None:
            ring_n = knobs.get_int("YTK_OBS_HISTORY_N") or 256
        if interval <= 0 or ring_n <= 0:
            return False
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return False
            if self._ring_n != ring_n:
                self.rings = {}
                self._ring_n = int(ring_n)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._run,
                args=(self._stop, float(interval)),
                name="ytk-prof-mem",
                daemon=True,
            )
            self._thread.start()
        return True

    def stop(self) -> None:
        with self._lock:
            t, ev = self._thread, self._stop
            self._thread = None
            self._stop = None
        if ev is not None:
            ev.set()
        if t is not None:
            t.join(timeout=2.0)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "ring_n": self._ring_n,
                "series": {
                    name: [[t, v] for t, v in ring]
                    for name, ring in sorted(self.rings.items())
                },
                "phase_peaks": {k: dict(v) for k, v in self.phase_peaks.items()},
            }

    def reset(self, ring_n: Optional[int] = None) -> None:
        with self._lock:
            self.rings = {}
            self.phase_peaks = {}
            if ring_n is not None:
                self._ring_n = int(ring_n)


MEM = MemWatermarkSampler()


# ---------------------------------------------------------------------------
# Trace-capture parser (the *.xplane.pb that jax.profiler writes)
# ---------------------------------------------------------------------------

#: obs span names are lowercase dotted identifiers ("gbdt.train",
#: "serve.score"); in a capture a span is a TraceAnnotation that carries the
#: span's `id` — anything else on a host thread is interpreter or
#: jax-runtime noise
_ANN_NAME = re.compile(r"^[a-z][a-z0-9_.\-]*$")


def _event_stats(ev) -> dict:
    try:
        return dict(ev.stats)
    # ytklint: allow(broad-except) reason=a stat the binding cannot decode; the event still counts by name and time
    except Exception:
        return {}


def _self_times(ops: List[Tuple[float, float, str]]) -> List[Tuple[float, str, float]]:
    """(start, dur, name) device ops -> (midpoint, name, self ns) each: a
    `while` around its body's ops is charged what they leave over."""
    out: List[Tuple[float, str, float]] = []
    stack: List[list] = []  # [start, end, name, child_ns]

    def pop():
        start, end, name, child = stack.pop()
        out.append(((start + end) / 2.0, name, max(end - start - child, 0.0)))
        if stack:
            stack[-1][3] += end - start

    for start, dur, name in sorted(ops, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= start:
            pop()
        stack.append([start, start + dur, name, 0.0])
    while stack:
        pop()
    return out


def parse_xplane(path: str) -> Optional[dict]:
    """Bucket one captured `*.xplane.pb` into per-annotation device time
    and a kernel aggregate, through `jax.profiler.ProfileData`.

    Layout facts (from the captures this parser was written against):
      * obs spans are the host-plane (`/host:...`) events with a lowercase
        dotted name and an `id` stat (obs/core.py makes every span a
        TraceAnnotation carrying its id);
      * device work is the `XLA Ops` line of a `/device:...` plane (TPU: an
        event is named by its whole HLO text, `%fusion.3 = f32[...] ...`;
        the instruction name is kept), or, in a CPU capture, the host-plane
        events that carry an `hlo_op` stat.

    Returns {"annotations": {name: ms}, "span_device_ms": {name: ms},
    "kernels": {name: {"ms", "count"}}} or None if unreadable."""
    try:
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
    except Exception as e:  # partial/corrupt captures are skipped, not fatal
        log.debug("xplane parse failed for %s: %s", path, e)
        return None
    ann: List[Tuple[float, float, str]] = []  # (lo, hi, name)
    ops: List[Tuple[float, float, str]] = []  # (start, dur, name)
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                if device:
                    name = ev.name.split(" = ", 1)[0].lstrip("%")
                    ops.append((ev.start_ns, ev.duration_ns, name))
                    continue
                st = _event_stats(ev)
                if "hlo_op" in st:
                    ops.append((ev.start_ns, ev.duration_ns, str(st["hlo_op"])))
                elif "id" in st and _ANN_NAME.match(ev.name):
                    ann.append((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
    annotations: Dict[str, float] = {}
    for lo, hi, name in ann:
        annotations[name] = annotations.get(name, 0.0) + (hi - lo) / 1e6
    # innermost-containing-annotation attribution: smallest annotation
    # interval that contains the kernel's midpoint
    ann.sort(key=lambda iv: iv[1] - iv[0])
    span_device: Dict[str, float] = {}
    kernels: Dict[str, dict] = {}
    for mid, kname, self_ns in _self_times(ops):
        k = kernels.setdefault(kname, {"ms": 0.0, "count": 0})
        k["ms"] += self_ns / 1e6
        k["count"] += 1
        for lo, hi, name in ann:
            if lo <= mid < hi:
                span_device[name] = span_device.get(name, 0.0) + self_ns / 1e6
                break
    return {
        "annotations": {k: round(v, 3) for k, v in annotations.items()},
        "span_device_ms": {k: round(v, 3) for k, v in span_device.items()},
        "kernels": {
            k: {"ms": round(v["ms"], 3), "count": v["count"]}
            for k, v in kernels.items()
        },
    }


def parse_capture_dir(root: str) -> Optional[dict]:
    """Find + parse the newest `*.xplane.pb` under a capture dir (jax
    nests them below plugins/profile/<run>/)."""
    newest, newest_m = None, -1.0
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".xplane.pb"):
                p = os.path.join(dirpath, fn)
                m = os.path.getmtime(p)
                if m > newest_m:
                    newest, newest_m = p, m
    return parse_xplane(newest) if newest else None


def parse_captures(topk: Optional[int] = None) -> dict:
    """Merge every completed phase capture into span device-time buckets
    and one top-k kernel table."""
    if topk is None:
        topk = _state.topk
    with _acc_lock:
        captures = list(_captures)
    span_device: Dict[str, float] = {}
    kernels: Dict[str, dict] = {}
    parsed = 0
    for _phase_name, cap_dir in captures:
        res = parse_capture_dir(cap_dir)
        if res is None:
            continue
        parsed += 1
        for k, v in res["span_device_ms"].items():
            span_device[k] = round(span_device.get(k, 0.0) + v, 3)
        for k, v in res["kernels"].items():
            agg = kernels.setdefault(k, {"ms": 0.0, "count": 0})
            agg["ms"] = round(agg["ms"] + v["ms"], 3)
            agg["count"] += v["count"]
    top = sorted(kernels.items(), key=lambda kv: -kv[1]["ms"])[: max(0, topk)]
    total_ms = sum(v["ms"] for v in kernels.values())
    return {
        "captures": len(captures),
        "parsed": parsed,
        "span_device_ms": span_device,
        "device_total_ms": round(total_ms, 3),
        "top_kernels": [
            {
                "name": k,
                "ms": v["ms"],
                "count": v["count"],
                "share": round(v["ms"] / total_ms, 4) if total_ms else 0.0,
            }
            for k, v in top
        ],
    }


# ---------------------------------------------------------------------------
# Report / flight-dump surface
# ---------------------------------------------------------------------------


def report(wall_s: Optional[float] = None, topk: Optional[int] = None) -> dict:
    """The `ytkprof` schema: everything the plane knows, JSON-ready."""
    rep = {
        "schema": SCHEMA,
        "schema_version": 1,
        "enabled": _state.on,
        "phases": phases_snapshot(),
        "compile": LEDGER.snapshot(),
        "mem": MEM.snapshot(),
        "kernels": parse_captures(topk=topk),
    }
    if wall_s is not None:
        rep["wall_s"] = round(wall_s, 4)
        rep["phase_coverage"] = round(coverage(wall_s), 4)
    return rep


def format_report(rep: dict) -> str:
    """Render a ytkprof report for terminals (the profile_* CLIs and
    prof_drill share this — one timing presentation, one plane)."""
    lines: List[str] = []
    phases = rep.get("phases") or {}
    if phases:
        lines.append("phase                          wall_s   calls")
        for name, p in phases.items():
            pad = "  " * p.get("depth", 0)
            lines.append(
                "%-30s %7.3f  %6d" % (pad + name, p["wall_s"], p["count"])
            )
    if rep.get("wall_s") is not None:
        lines.append(
            "wall %.3fs  coverage %.1f%%"
            % (rep["wall_s"], 100.0 * rep.get("phase_coverage", 0.0))
        )
    comp = rep.get("compile") or {}
    if comp.get("compiles"):
        lines.append(
            "compiles %d  total %.1f ms"
            % (comp["compiles"], comp.get("total_ms", 0.0))
        )
        for name, v in (comp.get("by_program") or {}).items():
            lines.append(
                "  %-28s %3d compile(s)  %8.1f ms"
                % (name, v["compiles"], v["ms"])
            )
    kern = rep.get("kernels") or {}
    if kern.get("top_kernels"):
        lines.append(
            "top kernels (device total %.1f ms over %d capture(s)):"
            % (kern.get("device_total_ms", 0.0), kern.get("parsed", 0))
        )
        for k in kern["top_kernels"]:
            lines.append(
                "  %-40s %8.2f ms  x%-5d %5.1f%%"
                % (k["name"][:40], k["ms"], k["count"], 100.0 * k["share"])
            )
    peaks = (rep.get("mem") or {}).get("phase_peaks") or {}
    if peaks:
        lines.append("memory peaks by phase:")
        for ph, v in peaks.items():
            bits = []
            if "device_peak_bytes" in v:
                bits.append("device %.1f MiB" % (v["device_peak_bytes"] / 2**20))
            if "host_rss_peak_bytes" in v:
                bits.append("rss %.1f MiB" % (v["host_rss_peak_bytes"] / 2**20))
            lines.append("  %-28s %s" % (ph, "  ".join(bits)))
    return "\n".join(lines)


def flight_block() -> Optional[dict]:
    """Compact prof block for flight dumps (phase wall table, ledger
    tail, phase-attributed memory peaks) — None when the plane is off so
    dumps stay byte-identical for non-profiled runs."""
    if not _state.on:
        return None
    mem = MEM.snapshot()
    return {
        "phases": phases_snapshot(),
        "compile": LEDGER.snapshot(limit=16),
        "mem_phase_peaks": mem.get("phase_peaks", {}),
    }


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


def _activate() -> None:
    """Arm everything the plane rides on: obs collection (spans, which
    are jax TraceAnnotations, so captures carry span names), the health
    compile counters, the ledger listener, and the watermark sampler."""
    from . import health

    core.configure(enabled=True)
    health.install_trace_counters()
    _install_ledger_listener()
    MEM.start()


def configure_profiler(
    on: Optional[bool] = None,
    capture_dir=_UNSET,
    topk: Optional[int] = None,
    mem_interval: Optional[float] = None,
) -> None:
    """Runtime configuration (the CLI's --profile lands here). Setting a
    capture dir implies on=True unless `on=False` is passed explicitly."""
    if capture_dir is not _UNSET:
        _state.capture_dir = capture_dir or None
        if capture_dir and on is None:
            on = True
    if topk is not None:
        _state.topk = int(topk)
    if mem_interval is not None:
        _state.mem_interval = float(mem_interval)
    if on is not None:
        was = _state.on
        _state.on = bool(on)
        if _state.on and not was:
            _activate()
        elif was and not _state.on:
            MEM.stop()


def reset_profiler() -> None:
    """Clear accumulated state (tests; the sampler thread keeps running
    if armed — stop it via configure_profiler(on=False))."""
    with _acc_lock:
        _phases.clear()
        del _captures[:]
        del _phase_stack[:]
    LEDGER.reset()
    MEM.reset()


def _configure_from_env() -> None:
    raw = knobs.get_raw("YTK_PROF")
    topk = knobs.get_int("YTK_PROF_TOPK")
    mem_s = knobs.get_float("YTK_PROF_MEM_S")
    if topk is not None:
        _state.topk = topk
    if mem_s is not None:
        _state.mem_interval = mem_s
    if raw is None or raw == "" or raw == "0":
        return
    if raw == "1":
        configure_profiler(on=True)
    else:
        configure_profiler(on=True, capture_dir=raw)


_configure_from_env()
