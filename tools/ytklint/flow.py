"""ytkflow: whole-repo interprocedural analysis for ytklint.

The r10 rules and the r15 ytkrace pass see one module at a time with at
most one level of same-module call propagation — an IO call or a lock
acquisition two hops away through ``serve/fleet/`` is invisible. This
pass resolves imports across ``ytklearn_tpu/``, ``scripts/`` and
``bench.py`` into one symbol table and a bounded call graph (direct
calls, ``self.``-method calls, functions passed by name — the same
resolution idioms rules.py/concurrency.py already use), then runs four
whole-repo rules on it:

``unseamed-io``
    raw IO primitives (open, os.replace/rename/remove, urllib, socket,
    subprocess, shutil) outside the blessed seam files — r13's "every
    IO site is chaos-drillable and retried" claim, statically checked.

``metric-name-drift``
    census of every obs name literal at producer sites (inc / gauge /
    event / span names) checked against consumer references in the
    health sentinels, the bench/regress gates, obs_report.py and
    bench.py. A consumer watching a name nobody emits is a finding;
    the producer side is pinned by the generated name-map section in
    docs/observability.md (``python -m tools.ytklint names regen|check``
    — the knob-table doc-sync pattern applied to metrics).

``deep-blocking-under-lock`` / ``deep-host-sync-in-jit``
    N-level cross-module deepening of blocking-call-under-lock and
    host-sync-in-jit, with the call chain printed in the finding (the
    r14 respawn-bug shape, caught through module boundaries). Chains
    the 1-level rules already report are not duplicated.

``silent-thread-death``
    a resolved thread entry point whose body can raise with no
    enclosing except that logs, records an event, or re-raises — a
    worker thread that can die without a flight-ring trace. The fix is
    ``@thread_guard`` (ytklearn_tpu/obs/recorder.py), which the rule
    recognizes.

The graph is attached to every FileContext as ``ctx.flow`` by a
GRAPH_BUILDERS hook (core.py), so per-file rules, suppressions, and the
stale-suppression audit work unchanged. Fixtures plant cross-module
chains with ``core.lint_sources({path: source, ...})``.
"""

from __future__ import annotations

import ast
import fnmatch
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from . import concurrency
from .core import DEFAULT_PATHS, _REPO_ROOT, rule
from .rules import _dotted, _tail_name, _traced_scopes

#: call-chain search depth bound — deep enough for any real chain in
#: this tree (front -> worker -> retry is 3), shallow enough to stay
#: linear on pathological graphs
MAX_DEPTH = 8

#: the blessed IO seams: fs.* (atomic replace / read seam), the retry
#: wrapper itself, the flight-recorder dump path (must work while the
#: process is dying — cannot depend on the seams it reports on), and
#: the native toolchain build (compiler subprocesses, gated separately)
BLESSED_IO_FILES = frozenset({
    "ytklearn_tpu/io/fs.py",
    "ytklearn_tpu/io/native.py",
    "ytklearn_tpu/resilience/retry.py",
    "ytklearn_tpu/obs/recorder.py",
})

#: files whose metric-name references are the consumer side of the
#: census (sentinels, gates, reports, the benchmark's readers), as
#: repo-relative paths or globs; the benchmark's files are read for the
#: census and the name map, not linted
CONSUMER_FILES = (
    "ytklearn_tpu/obs/health.py",
    "scripts/obs_report.py",
    "scripts/check_bench_regress.py",
    "bench.py",
    "perfbench/metrics/*.py",
    "perfbench/families/*.py",
    "perfbench/pb/*.py",
)


def is_consumer(path: str) -> bool:
    return any(fnmatch.fnmatchcase(path, pat) for pat in CONSUMER_FILES)


DOC_BEGIN = "<!-- metric-name-map:begin -->"
DOC_END = "<!-- metric-name-map:end -->"

_HOST_SYNC_ZERO_ARG_TAILS = {"item", "tolist"}
_HOST_SYNC_NAMES = {"device_get", "block_until_ready"}

_IO_OS_TAILS = {"replace", "rename", "renames", "remove", "unlink"}
_IO_SUBPROCESS_NAMES = {"Popen", "check_call", "check_output"}
_IO_MODULE_PREFIXES = ("urllib.", "socket.", "subprocess.", "shutil.")
_IO_FROM_MODULES = {"os", "socket", "shutil", "subprocess",
                    "urllib.request", "urllib.error"}
#: dotted names under the IO module prefixes that do no IO at all:
#: urllib.parse is pure string manipulation, gethostname/getfqdn are
#: local lookups — flagging them would train people to ignore the rule
_IO_EXEMPT_PREFIXES = ("urllib.parse.",)
_IO_EXEMPT_DOTTED = {"socket.gethostname", "socket.getfqdn"}


def _module_of(path: str) -> str:
    p = path[:-3] if path.endswith(".py") else path
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


def _import_binds(tree: ast.AST, mod: str, is_pkg: bool) -> Dict[str, tuple]:
    """name -> ("module", dotted) | ("from", base module, symbol).
    Walks the whole tree: this repo lazy-imports inside functions."""
    binds: Dict[str, tuple] = {}
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            for a in n.names:
                if a.asname:
                    binds[a.asname] = ("module", a.name)
                else:
                    root = a.name.split(".")[0]
                    binds[root] = ("module", root)
        elif isinstance(n, ast.ImportFrom):
            if n.level:
                parts = mod.split(".")
                if not is_pkg:
                    parts = parts[:-1]
                drop = n.level - 1
                if drop:
                    parts = parts[: len(parts) - drop]
                base = ".".join(parts)
                if n.module:
                    base = f"{base}.{n.module}" if base else n.module
            else:
                base = n.module or ""
            for a in n.names:
                if a.name == "*":
                    continue
                binds[a.asname or a.name] = ("from", base, a.name)
    return binds


class _FlowFunc:
    """One function in the whole-repo graph, wrapping its per-module
    concurrency facts (lock regions, Thread ctors)."""

    __slots__ = ("path", "module", "conc", "traced",
                 "call_sites", "blocking_direct", "host_sync_direct",
                 "io_direct", "thread_spawns")

    def __init__(self, path: str, module: str, conc_fn) -> None:
        self.path = path
        self.module = module
        self.conc = conc_fn
        self.traced = False
        # (line, resolved target keys, dotted callee, held locks)
        self.call_sites: List[Tuple[int, tuple, str, frozenset]] = []
        self.blocking_direct: List[Tuple[int, str]] = []
        self.host_sync_direct: List[Tuple[int, str]] = []
        self.io_direct: List[Tuple[int, str]] = []
        # (ctor line, resolved entry keys, dotted target)
        self.thread_spawns: List[Tuple[int, tuple, str]] = []

    @property
    def qual(self) -> str:
        return self.conc.qual

    @property
    def label(self) -> str:
        return f"{self.module}.{self.conc.qual}"


def _io_primitive(call: ast.Call, tail: Optional[str], dotted: str,
                  binds: Dict[str, tuple]) -> Optional[str]:
    """Description when `call` is a raw IO primitive, else None."""
    f = call.func
    if isinstance(f, ast.Name):
        if f.id == "open":
            return "open()"
        b = binds.get(f.id)
        if b and b[0] == "from" and b[1] in _IO_FROM_MODULES:
            return f"{b[1]}.{b[2]}()"
        if tail in _IO_SUBPROCESS_NAMES:
            return f"subprocess.{tail}()"
        return None
    if not dotted:
        return None
    if (dotted in _IO_EXEMPT_DOTTED
            or any(dotted.startswith(p) for p in _IO_EXEMPT_PREFIXES)):
        return None
    root = dotted.split(".")[0]
    if dotted.startswith("os.") and tail in _IO_OS_TAILS:
        return f"{dotted}()"
    if any(dotted.startswith(p) for p in _IO_MODULE_PREFIXES):
        return f"{dotted}()"
    b = binds.get(root)
    if b and b[0] == "from" and b[1] == "urllib" :
        return f"urllib.{b[2]}.{'.'.join(dotted.split('.')[1:])}()"
    return None


def _host_sync_primitive(call: ast.Call, tail: Optional[str],
                         dotted: str) -> Optional[str]:
    if tail in _HOST_SYNC_NAMES:
        return f"{dotted or tail}()"
    if (tail in _HOST_SYNC_ZERO_ARG_TAILS and not call.args
            and not call.keywords and isinstance(call.func, ast.Attribute)):
        return f".{tail}()"
    return None


class FlowGraph:
    """Whole-repo symbol table + bounded call graph over one set of
    parsed FileContexts. Rule findings are computed lazily per rule so
    the per-rule wall-time in the json artifact stays honest."""

    def __init__(self, ctxs: Sequence) -> None:
        self.paths: Dict[str, object] = {}
        self.modules: Dict[str, str] = {}       # dotted module -> path
        self.funcs: Dict[tuple, _FlowFunc] = {}  # (path, qual) -> func
        self.by_simple: Dict[str, Dict[str, List[tuple]]] = {}
        self.module_io: Dict[str, List[Tuple[int, str]]] = {}
        self.callers: Dict[tuple, List[tuple]] = {}
        self._binds: Dict[str, Dict[str, tuple]] = {}
        self._rule_cache: Dict[str, Dict[str, List[Tuple[int, str]]]] = {}
        for ctx in ctxs:
            self._register(ctx)
        for ctx in ctxs:
            self._link(ctx)
        self.census = MetricCensus(ctxs)

    # -- construction ------------------------------------------------------

    def _register(self, ctx) -> None:
        path = ctx.path
        mod = _module_of(path)
        self.paths[path] = ctx
        self.modules[mod] = path
        self._binds[path] = _import_binds(
            ctx.tree, mod, path.endswith("__init__.py"))
        conc = concurrency._analysis(ctx)
        simple = self.by_simple.setdefault(path, {})
        traced_ids = {id(fn) for fn, _static in _traced_scopes(ctx)}
        for cfn in conc.funcs:
            key = (path, cfn.qual)
            ffn = _FlowFunc(path, mod, cfn)
            ffn.traced = id(cfn.node) in traced_ids
            self.funcs[key] = ffn
            simple.setdefault(cfn.name, []).append(key)

    def _lookup(self, mod: str, name: str, _depth: int = 0) -> Optional[tuple]:
        """Module-level symbol in `mod`, chasing re-exports (the obs
        package re-exports core's producers) a few levels."""
        path = self.modules.get(mod)
        if path is None:
            return None
        key = (path, name)
        if key in self.funcs:
            return key
        if _depth >= 3:
            return None
        b = self._binds.get(path, {}).get(name)
        if b and b[0] == "from":
            return self._lookup(b[1], b[2], _depth + 1)
        return None

    def _resolve_ref(self, path: str, encl, expr: ast.expr
                     ) -> Tuple[tuple, str]:
        """Resolve a callable reference (a call's func, or a function
        passed by name) -> (target keys, dotted name). Bounded
        overapproximation: simple-name matches within the module, exact
        symbol matches across modules."""
        binds = self._binds.get(path, {})
        dotted = _dotted(expr)
        targets: List[tuple] = []
        if isinstance(expr, ast.Name):
            local = self.by_simple.get(path, {}).get(expr.id)
            if local:
                targets = list(local)
            else:
                b = binds.get(expr.id)
                if b and b[0] == "from":
                    hit = self._lookup(b[1], b[2])
                    if hit:
                        targets = [hit]
                    else:
                        dotted = f"{b[1]}.{b[2]}"
        elif isinstance(expr, ast.Attribute) and dotted:
            parts = dotted.split(".")
            if parts[0] == "self" and len(parts) == 2:
                cls = encl.conc.cls if encl is not None else None
                if cls is not None:
                    for key in self.by_simple.get(path, {}).get(parts[1], []):
                        g = self.funcs[key]
                        if g.conc.cls is not None and g.conc.cls.name == cls.name:
                            targets.append(key)
            else:
                b = binds.get(parts[0])
                full = None
                if b is not None:
                    if b[0] == "module":
                        full = ".".join([b[1]] + parts[1:])
                    else:
                        full = ".".join([b[1], b[2]] + parts[1:])
                if full:
                    dotted = full
                    fparts = full.split(".")
                    for cut in range(len(fparts) - 1, 0, -1):
                        m = ".".join(fparts[:cut])
                        if m not in self.modules:
                            continue
                        rest = fparts[cut:]
                        if len(rest) == 1:
                            hit = self._lookup(m, rest[0])
                            if hit:
                                targets = [hit]
                        elif len(rest) == 2:
                            key = (self.modules[m], ".".join(rest))
                            if key in self.funcs:
                                targets = [key]
                        break
        return tuple(targets), dotted

    def _link(self, ctx) -> None:
        path = ctx.path
        binds = self._binds[path]
        for key, ffn in list(self.funcs.items()):
            if key[0] != path:
                continue
            for n in concurrency._child_statements(ffn.conc.node):
                if not isinstance(n, ast.Call):
                    continue
                tail = _tail_name(n.func)
                dotted = _dotted(n.func)
                io = _io_primitive(n, tail, dotted, binds)
                if io:
                    ffn.io_direct.append((n.lineno, io))
                hs = _host_sync_primitive(n, tail, dotted)
                if hs:
                    ffn.host_sync_direct.append((n.lineno, hs))
                if tail == "Thread":
                    target = next(
                        (kw.value for kw in n.keywords if kw.arg == "target"),
                        None)
                    if target is not None:
                        tkeys, tdot = self._resolve_ref(path, ffn, target)
                        ffn.thread_spawns.append((n.lineno, tkeys, tdot))
                    continue
                targets, rdot = self._resolve_ref(path, ffn, n.func)
                held = ffn.conc.held_at(n.lineno)
                if targets:
                    ffn.call_sites.append((n.lineno, targets, rdot, held))
                    for t in targets:
                        self.callers.setdefault(t, []).append(key)
            ffn.blocking_direct = concurrency._direct_blocking_anywhere(
                ffn.conc)
            # module-level IO (import-time reads, top-level helpers)
        mod_io: List[Tuple[int, str]] = []
        for n in concurrency._child_statements(ctx.tree):
            if isinstance(n, ast.Call):
                io = _io_primitive(n, _tail_name(n.func), _dotted(n.func),
                                   binds)
                if io:
                    mod_io.append((n.lineno, io))
        if mod_io:
            self.module_io[path] = mod_io

    # -- chain search ------------------------------------------------------

    def _shortest_chain(self, roots: Sequence[tuple],
                        terminal) -> Optional[Tuple[List[tuple], int, str]]:
        """BFS over the call graph from `roots` to the nearest function
        where `terminal(func)` yields (line, desc); -> (path keys,
        line, desc)."""
        frontier: List[Tuple[tuple, Tuple[tuple, ...]]] = [
            (r, (r,)) for r in roots if r in self.funcs
        ]
        seen: Set[tuple] = {r for r, _chain in frontier}
        depth = 0
        while frontier and depth < MAX_DEPTH:
            depth += 1
            nxt: List[Tuple[tuple, Tuple[tuple, ...]]] = []
            for key, chain in frontier:
                fn = self.funcs[key]
                hits = terminal(fn)
                if hits:
                    line, desc = hits[0]
                    return list(chain), line, desc
                for _line, targets, _dotted_name, _held in fn.call_sites:
                    for t in targets:
                        if t not in seen and t in self.funcs:
                            seen.add(t)
                            nxt.append((t, chain + (t,)))
            frontier = nxt
        return None

    def _inbound(self, key: tuple) -> Optional[_FlowFunc]:
        """A caller of `key` from another module, if any (BFS up)."""
        seen = {key}
        frontier = [key]
        depth = 0
        while frontier and depth < MAX_DEPTH:
            depth += 1
            nxt = []
            for k in frontier:
                for c in self.callers.get(k, []):
                    if c in seen:
                        continue
                    seen.add(c)
                    if c[0] != key[0]:
                        return self.funcs[c]
                    nxt.append(c)
            frontier = nxt
        return None

    def _fmt_chain(self, start: _FlowFunc, chain: List[tuple]) -> str:
        hops = [start.label] + [self.funcs[k].label for k in chain]
        return " -> ".join(hops)

    # -- per-rule findings (computed lazily, cached per rule) --------------

    def rule_findings(self, name: str, path: str) -> List[Tuple[int, str]]:
        if name not in self._rule_cache:
            compute = {
                "unseamed-io": self._compute_unseamed_io,
                "metric-name-drift": self._compute_metric_drift,
                "deep-blocking-under-lock": self._compute_deep_blocking,
                "deep-host-sync-in-jit": self._compute_deep_host_sync,
                "silent-thread-death": self._compute_thread_death,
            }[name]
            per_path: Dict[str, List[Tuple[int, str]]] = {}
            for p, line, msg in compute():
                per_path.setdefault(p, []).append((line, msg))
            self._rule_cache[name] = per_path
        return self._rule_cache[name].get(path, [])

    def _compute_unseamed_io(self):
        out = []
        for path, lines in self.module_io.items():
            if not _unseamed_io_applies(path):
                continue
            for line, desc in lines:
                out.append((path, line,
                            f"raw {desc} at module level outside the IO "
                            "seams — route through fs.* / retry_call so "
                            "chaos drills and retries cover it"))
        for key, fn in self.funcs.items():
            if not _unseamed_io_applies(fn.path):
                continue
            for line, desc in fn.io_direct:
                caller = self._inbound(key)
                via = (f" (reached from {caller.label} in {caller.path})"
                       if caller is not None else "")
                out.append((fn.path, line,
                            f"raw {desc} in `{fn.qual}` outside the IO "
                            f"seams{via} — route through fs.* / retry_call "
                            "so chaos drills and retries cover it, or "
                            "suppress with the reason it is exempt"))
        return out

    def _compute_metric_drift(self):
        return self.census.orphan_findings()

    def _compute_deep_blocking(self):
        out = []
        for key, fn in self.funcs.items():
            direct_lines = {ln for ln, _d, _h in fn.conc.blocking}
            direct_lines.update(ln for ln, _r, _h in fn.conc.maybe_joins)
            for line, targets, dotted, held in fn.call_sites:
                if not held or line in direct_lines:
                    continue
                got = self._shortest_chain(
                    targets, lambda g: g.blocking_direct)
                if got is None:
                    continue
                chain, bline, desc = got
                # 1-level same-module chains are blocking-call-under-lock's
                # jurisdiction — only report what the r15 pass cannot see
                if len(chain) == 1 and chain[0][0] == key[0]:
                    continue
                term = self.funcs[chain[-1]]
                out.append((fn.path, line, (
                    f"holding {sorted(held)} across call chain "
                    f"`{self._fmt_chain(fn, chain)}`, which blocks on "
                    f"{desc} ({term.path}:{bline}) — every sibling thread "
                    "needing this lock stalls behind the chain (deep "
                    "propagation of blocking-call-under-lock)")))
        return out

    def _compute_deep_host_sync(self):
        out = []
        for key, fn in self.funcs.items():
            if not fn.traced:
                continue
            for line, targets, dotted, _held in fn.call_sites:
                live = [t for t in targets
                        if t in self.funcs and not self.funcs[t].traced]
                got = self._shortest_chain(
                    live, lambda g: [] if g.traced else g.host_sync_direct)
                if got is None:
                    continue
                chain, sline, desc = got
                term = self.funcs[chain[-1]]
                out.append((fn.path, line, (
                    f"traced `{fn.qual}` reaches host sync {desc} "
                    f"({term.path}:{sline}) through call chain "
                    f"`{self._fmt_chain(fn, chain)}` — forces a device "
                    "round-trip inside jit (deep propagation of "
                    "host-sync-in-jit)")))
        return out

    def _compute_thread_death(self):
        out = []
        for key, fn in self.funcs.items():
            for line, targets, dotted in fn.thread_spawns:
                for t in targets:
                    entry = self.funcs.get(t)
                    if entry is None or _entry_is_guarded(entry.conc.node):
                        continue
                    out.append((fn.path, line, (
                        f"thread target `{entry.label}` ({entry.path}:"
                        f"{entry.conc.node.lineno}) can raise with no "
                        "enclosing except that logs, records an event, or "
                        "re-raises — the worker dies with no flight-ring "
                        "trace; decorate the entry with @thread_guard "
                        "(ytklearn_tpu/obs/recorder.py)")))
                    break
        return out


def _unseamed_io_applies(path: str) -> bool:
    return path.startswith("ytklearn_tpu/") and path not in BLESSED_IO_FILES


_GUARD_DECORATORS = {"thread_guard"}
_BENIGN_CALL_TAILS = {"wait", "is_set", "sleep", "monotonic",
                      "perf_counter", "time", "locked"}
_HANDLER_LOG_TAILS = {"exception", "error", "critical", "warning",
                      "event", "obs_event", "add_event", "record"}


def _entry_is_guarded(node) -> bool:
    """True when a thread entry function cannot die silently: every
    risky statement sits under a broad except that logs / records an
    event / re-raises, or the entry carries @thread_guard."""
    for dec in node.decorator_list:
        if _tail_name(dec) in _GUARD_DECORATORS:
            return True
        if isinstance(dec, ast.Call) and _tail_name(dec.func) in _GUARD_DECORATORS:
            return True

    def handler_ok(h: ast.ExceptHandler) -> bool:
        broad = h.type is None or _tail_name(h.type) in (
            "Exception", "BaseException")
        if not broad:
            return False
        for b in ast.walk(h):
            if isinstance(b, ast.Raise):
                return True
            if isinstance(b, ast.Call) and _tail_name(b.func) in _HANDLER_LOG_TAILS:
                return True
        return False

    # parent links inside this entry only (nested defs excluded: they
    # run on whatever thread calls them, not necessarily this one)
    parent: Dict[int, ast.AST] = {}
    stack: List[ast.AST] = list(node.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                          ast.ClassDef)):
            continue
        for c in ast.iter_child_nodes(n):
            parent[id(c)] = n
            stack.append(c)

    def covered(n: ast.AST) -> bool:
        cur = parent.get(id(n))
        prev = n
        while cur is not None:
            # only the try BODY is covered by the handlers — a risky
            # call inside a handler, else: or finally: still escapes
            if (isinstance(cur, ast.Try)
                    and any(prev is s for s in cur.body)
                    and any(handler_ok(h) for h in cur.handlers)):
                return True
            prev, cur = cur, parent.get(id(cur))
        return False

    def risky(n: ast.AST) -> bool:
        if isinstance(n, ast.Raise):
            # a raise inside an except handler is the log-then-reraise
            # pattern the rule doc blesses, not a silent death
            cur = parent.get(id(n))
            while cur is not None:
                if isinstance(cur, ast.ExceptHandler):
                    return False
                cur = parent.get(id(cur))
            return True
        if isinstance(n, ast.Call):
            tail = _tail_name(n.func)
            return (tail not in _BENIGN_CALL_TAILS
                    and tail not in _HANDLER_LOG_TAILS)
        return False

    stack = list(node.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                          ast.ClassDef)):
            continue
        if risky(n) and not covered(n):
            return False
        stack.extend(ast.iter_child_nodes(n))
    return True


# ---------------------------------------------------------------------------
# Metric-name census
# ---------------------------------------------------------------------------

#: producer wrapper spellings at call sites (obs/core.py API plus the
#: `from ..obs import inc as obs_inc` aliases this repo standardizes on)
_PRODUCER_KINDS = {
    "inc": "counter", "obs_inc": "counter",
    "gauge": "gauge", "obs_gauge": "gauge",
    "event": "event", "obs_event": "event",
    "span": "span", "obs_span": "span", "phase": "span",
    "step_span": "span", "obs_step_span": "span",
    "root_span": "span", "obs_root_span": "span",
    "hop": "span", "hop_at": "span", "batch_hop": "span",
    # `obs.scopes`: names on the device, read back through the scope maps
    "scope": "scope", "subscope": "scope",
}

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\.?$")
#: consumer literals that look dotted but are not metric names
_NON_METRIC_LAST_SEGMENTS = {"py", "md", "json", "sh", "txt", "yaml", "csv",
                             "jsonl", "log"}
_NON_METRIC_PREFIXES = ("ytklearn_tpu.", "scripts.", "tools.", "tests.",
                        "jax.", "numpy.", "np.", "os.", "sys.", "time.",
                        "threading.", "subprocess.")
_PATHISH_CALL_TAILS = {"join", "exists", "open", "dirname", "abspath",
                       "isfile", "isdir", "Path", "remove", "unlink",
                       "set_path"}  # a config key (`model.data_path`)


def _producer_name(arg: ast.expr) -> Tuple[Optional[str], bool]:
    """(name-or-prefix, is_dynamic) from a producer's first argument."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value, False
    if isinstance(arg, ast.JoinedStr):
        head = ""
        for v in arg.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                head += v.value
            else:
                break
        return (head, True) if head else (None, False)
    return None, False


class MetricCensus:
    """Producers (exact names + dynamic f-string prefixes) across the
    linted tree, consumers in CONSUMER_FILES, checked both ways: orphan
    consumer references are lint findings; the producer inventory is
    pinned by the generated docs/observability.md name-map section."""

    def __init__(self, ctxs: Sequence, consumers_only: Sequence = ()) -> None:
        # name -> {"kinds": set, "files": set}
        self.exact: Dict[str, dict] = {}
        self.prefixes: Dict[str, dict] = {}
        # consumer path -> [(line, literal)]
        self.consumer_refs: Dict[str, List[Tuple[int, str]]] = {}
        for ctx in ctxs:
            self._scan_producers(ctx)
            if is_consumer(ctx.path):
                self._scan_consumer(ctx)
        for ctx in consumers_only:
            self._scan_consumer(ctx)

    def _scan_producers(self, ctx) -> None:
        for n in ast.walk(ctx.tree):
            if not isinstance(n, ast.Call) or not n.args:
                continue
            kind = _PRODUCER_KINDS.get(_tail_name(n.func) or "")
            if kind is None:
                continue
            name, dynamic = _producer_name(n.args[0])
            if not name or "." not in name:
                continue
            table = self.prefixes if dynamic else self.exact
            row = table.setdefault(name, {"kinds": set(), "files": set()})
            row["kinds"].add(kind)
            row["files"].add(ctx.path)

    def _scan_consumer(self, ctx) -> None:
        # dotted literals that are not metric references: logger names,
        # and filename components fed to path calls (os.path.join(d,
        # "higgs.train") is a dataset file, not a counter)
        skip_ids: Set[int] = set()
        skip_names: Set[str] = set()
        for n in ast.walk(ctx.tree):
            if not isinstance(n, ast.Call):
                continue
            tail = _tail_name(n.func)
            if tail == "getLogger" or tail in _PATHISH_CALL_TAILS:
                for a in n.args:
                    skip_ids.add(id(a))
                    if isinstance(a, ast.Name):
                        skip_names.add(a.id)
        # and the literals a loop binds to such an argument:
        # `for key, name in (("model.data_path", "gbdt.model"), ...)`
        for n in ast.walk(ctx.tree):
            if not (isinstance(n, ast.For) and isinstance(n.target, ast.Tuple)
                    and isinstance(n.iter, (ast.Tuple, ast.List))):
                continue
            bound = [t.id if isinstance(t, ast.Name) else None for t in n.target.elts]
            for row in n.iter.elts:
                if isinstance(row, (ast.Tuple, ast.List)):
                    for name, v in zip(bound, row.elts):
                        if name in skip_names:
                            skip_ids.add(id(v))
        refs: List[Tuple[int, str]] = []
        for n in ast.walk(ctx.tree):
            if not (isinstance(n, ast.Constant) and isinstance(n.value, str)):
                continue
            if id(n) in skip_ids:
                continue
            s = n.value
            if not _NAME_RE.match(s):
                continue
            if s.rstrip(".").rsplit(".", 1)[-1] in _NON_METRIC_LAST_SEGMENTS:
                continue
            if s.startswith(_NON_METRIC_PREFIXES):
                continue
            refs.append((n.lineno, s))
        if refs:
            self.consumer_refs[ctx.path] = refs

    def _satisfied(self, lit: str) -> bool:
        base = lit.rstrip(".")
        if base in self.exact:
            return True
        # plain startswith, not segment-wise: consumers legitimately
        # filter families like "continual.ftrl" that producers extend
        # with underscores ("continual.ftrl_steps")
        for p in self.exact:
            if p.startswith(base):
                return True  # consumer uses `lit` as a family prefix
        for h in self.prefixes:
            if lit.startswith(h) or h.startswith(base):
                return True
        return False

    def orphan_findings(self) -> List[Tuple[str, int, str]]:
        out = []
        for path, refs in self.consumer_refs.items():
            for line, lit in refs:
                if self._satisfied(lit):
                    continue
                out.append((path, line, (
                    f"consumer references metric name {lit!r} that no "
                    "producer site emits (census over inc/gauge/event/span "
                    "literals) — the sentinel/gate/report is watching a "
                    "name that can never fire; fix the name or suppress "
                    "with the reason it is external")))
        return out

    # -- doc name map ------------------------------------------------------

    def _consumers_of(self, name: str, dynamic: bool) -> List[str]:
        hits = []
        probe = name.rstrip(".")
        for path, refs in self.consumer_refs.items():
            for _line, lit in refs:
                base = lit.rstrip(".")
                ok = (
                    base == probe
                    or probe.startswith(base + ".")
                    or (dynamic and base.startswith(name))
                    or (not dynamic and base.startswith(probe + "."))
                )
                if ok:
                    hits.append(path)
                    break
        return sorted(hits)

    def table_markdown(self) -> str:
        rows = []
        for name, row in self.exact.items():
            rows.append((name, False, row))
        for name, row in self.prefixes.items():
            rows.append((name, True, row))
        rows.sort(key=lambda r: r[0])
        out = [
            "| name | kind | produced in | consumed by |",
            "|---|---|---|---|",
        ]
        for name, dynamic, row in rows:
            shown = f"`{name}*`" if dynamic else f"`{name}`"
            kinds = "/".join(sorted(row["kinds"]))
            prod = ", ".join(sorted(row["files"]))
            cons = ", ".join(self._consumers_of(name, dynamic)) or "—"
            out.append(f"| {shown} | {kinds} | {prod} | {cons} |")
        out.append("")
        out.append(f"{len(rows)} names. Generated by "
                   "`python -m tools.ytklint names regen` — do not edit "
                   "between the markers; CI checks both ways.")
        return "\n".join(out)


def census_for_repo() -> MetricCensus:
    """Producers and consumers over the linted tree, and the consumers of
    the consumer files outside it (the benchmark's readers)."""
    from .core import contexts_for_paths

    ctxs = contexts_for_paths(DEFAULT_PATHS)
    linted = {c.path for c in ctxs}
    outside = sorted({str(f) for pat in CONSUMER_FILES for f in _REPO_ROOT.glob(pat)
                      if f.relative_to(_REPO_ROOT).as_posix() not in linted})
    return MetricCensus(ctxs, consumers_only=contexts_for_paths(outside))


def check_doc_sync(doc_path: pathlib.Path,
                   census: Optional[MetricCensus] = None) -> List[str]:
    """Both ways: every censused name has a doc row, every doc row is a
    censused name — enforced as `generated block == regenerated block`
    (the knob-table pattern)."""
    census = census or census_for_repo()
    if not doc_path.exists():
        return [f"{doc_path}: missing"]
    text = doc_path.read_text(encoding="utf-8")
    if DOC_BEGIN not in text or DOC_END not in text:
        return [f"{doc_path}: missing {DOC_BEGIN} / {DOC_END} markers"]
    block = text.split(DOC_BEGIN, 1)[1].split(DOC_END, 1)[0].strip()
    want = census.table_markdown().strip()
    if block != want:
        return [
            f"{doc_path}: metric name-map section is stale — a producer "
            "or consumer changed; run `python -m tools.ytklint names "
            "regen` and commit the result"
        ]
    return []


def regen_doc(doc_path: pathlib.Path,
              census: Optional[MetricCensus] = None) -> None:
    census = census or census_for_repo()
    text = doc_path.read_text(encoding="utf-8")
    if DOC_BEGIN not in text or DOC_END not in text:
        raise SystemExit(
            f"{doc_path}: missing {DOC_BEGIN} / {DOC_END} markers")
    head, rest = text.split(DOC_BEGIN, 1)
    _stale, tail = rest.split(DOC_END, 1)
    new = (f"{head}{DOC_BEGIN}\n{census.table_markdown()}\n{DOC_END}{tail}")
    doc_path.write_text(new, encoding="utf-8")


def names_main(argv: Sequence[str]) -> int:
    """`python -m tools.ytklint names {table|check|regen} [doc]`."""
    import sys

    cmd = argv[0] if argv else "check"
    doc = (pathlib.Path(argv[1]) if len(argv) > 1
           else _REPO_ROOT / "docs" / "observability.md")
    if cmd == "table":
        print(census_for_repo().table_markdown())
        return 0
    if cmd == "regen":
        regen_doc(doc)
        print(f"ytklint names: regenerated metric name map in {doc}",
              file=sys.stderr)
        return 0
    if cmd == "check":
        problems = check_doc_sync(doc)
        for p in problems:
            print(p, file=sys.stderr)
        if not problems:
            print(f"ytklint names: {doc} metric name map in sync",
                  file=sys.stderr)
        return 1 if problems else 0
    print(f"ytklint names: unknown subcommand {cmd!r} "
          "(expected table | check | regen)", file=sys.stderr)
    return 2


# ---------------------------------------------------------------------------
# Rule registration + graph builder hook
# ---------------------------------------------------------------------------


def _attach(ctxs) -> None:
    graph = FlowGraph(ctxs)
    for ctx in ctxs:
        ctx.flow = graph


def _flow_findings(ctx, name: str) -> Iterable[Tuple[int, str]]:
    if ctx.flow is None:
        _attach([ctx])
    return ctx.flow.rule_findings(name, ctx.path)


@rule(
    "unseamed-io",
    "raw IO primitive (open/os.replace/urllib/socket/subprocess/shutil) "
    "reachable outside the blessed seams (fs.*, retry, recorder dump, "
    "native build) — not chaos-drillable, not retried",
    applies=_unseamed_io_applies,
    needs_graph=True,
)
def unseamed_io(ctx) -> Iterable[Tuple[int, str]]:
    return _flow_findings(ctx, "unseamed-io")


@rule(
    "metric-name-drift",
    "sentinel/gate/report references an obs metric name no producer "
    "site emits (whole-repo census of inc/gauge/event/span literals)",
    applies=lambda path: path in CONSUMER_FILES,
    needs_graph=True,
)
def metric_name_drift(ctx) -> Iterable[Tuple[int, str]]:
    return _flow_findings(ctx, "metric-name-drift")


@rule(
    "deep-blocking-under-lock",
    "lock held across a cross-module / multi-hop call chain that ends "
    "in a blocking primitive (N-level deepening of "
    "blocking-call-under-lock, chain printed in the finding)",
    needs_graph=True,
)
def deep_blocking_under_lock(ctx) -> Iterable[Tuple[int, str]]:
    return _flow_findings(ctx, "deep-blocking-under-lock")


@rule(
    "deep-host-sync-in-jit",
    "jit/pjit-traced function reaches .item()/.tolist()/device_get/"
    "block_until_ready through a resolved call chain (N-level deepening "
    "of host-sync-in-jit)",
    needs_graph=True,
)
def deep_host_sync_in_jit(ctx) -> Iterable[Tuple[int, str]]:
    return _flow_findings(ctx, "deep-host-sync-in-jit")


@rule(
    "silent-thread-death",
    "Thread target resolved to an entry whose body can raise with no "
    "enclosing except that logs, records an event, or re-raises — the "
    "worker dies without a flight-ring trace (@thread_guard fixes it)",
    needs_graph=True,
)
def silent_thread_death(ctx) -> Iterable[Tuple[int, str]]:
    return _flow_findings(ctx, "silent-thread-death")


# runs whenever tools.ytklint is imported: every lint entry point gets
# the whole-repo graph attached before rules fire
from .core import GRAPH_BUILDERS  # noqa: E402

GRAPH_BUILDERS.append(_attach)
