"""The ytklint rule set (catalog + rationale: docs/static_analysis.md).

Two JAX-semantic rules (host-sync-in-jit, retrace-hazard) share a traced-
scope analysis: a function is *traced* when it is jit-decorated
(`@jax.jit`, `@partial(jax.jit, ...)`) or passed by name to
`jax.jit` / `shard_map` / `pallas_call`, and
everything lexically inside it (nested defs included) runs under the
tracer. Parameters declared static (static_argnames/static_argnums) are
concrete Python values and are excluded from the traced-value heuristics.
"""

from __future__ import annotations

import ast
import functools
import pathlib
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import rule

# ---------------------------------------------------------------------------
# Traced-scope analysis (shared by host-sync-in-jit and retrace-hazard)
# ---------------------------------------------------------------------------

_JIT_NAMES = {"jit", "pjit"}
_WRAPPER_CALLS = {"jit", "pjit", "shard_map",
                  "pallas_call"}


def _tail_name(node: ast.expr) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _dotted(node: ast.expr) -> str:
    """Best-effort dotted name of an expression ("jax.numpy.sum")."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_expr(node: ast.expr) -> bool:
    """Does this expression evaluate to a jit-like transform?"""
    if _tail_name(node) in _JIT_NAMES:
        return True
    if isinstance(node, ast.Call):
        fname = _tail_name(node.func)
        if fname == "partial" and node.args and _is_jit_expr(node.args[0]):
            return True
        if fname in _JIT_NAMES:  # @jax.jit(static_argnames=...) factory form
            return True
    return False


def _static_param_names(fn: ast.FunctionDef, call: Optional[ast.Call]) -> Set[str]:
    """Resolve static_argnames/static_argnums from a jit call/decorator."""
    if call is None:
        return set()
    names: Set[str] = set()
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.add(n.value)
        elif kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    if 0 <= n.value < len(params):
                        names.add(params[n.value])
    return names


def _jit_call_of(dec: ast.expr) -> Optional[ast.Call]:
    """The Call node carrying static-arg kwargs, if the decorator has one."""
    if isinstance(dec, ast.Call):
        return dec
    return None


class _TracedScopes:
    """All traced FunctionDefs of a module + their static param names."""

    def __init__(self, tree: ast.AST):
        self.scopes: List[Tuple[ast.FunctionDef, Set[str]]] = []
        defs: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
                for dec in node.decorator_list:
                    if _is_jit_expr(dec):
                        self.scopes.append(
                            (node, _static_param_names(node, _jit_call_of(dec)))
                        )
                        break
        # functions passed by name: jax.jit(f), shard_map(f, mesh, ...)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            if _tail_name(node.func) not in _WRAPPER_CALLS:
                continue
            target = node.args[0]
            if isinstance(target, ast.Name) and target.id in defs:
                for fn in defs[target.id]:
                    if not any(fn is s for s, _ in self.scopes):
                        self.scopes.append(
                            (fn, _static_param_names(fn, node))
                        )

    def __iter__(self):
        return iter(self.scopes)


def _traced_scopes(ctx) -> "_TracedScopes":
    """Per-file traced-scope map, cached on the FileContext — the jit
    rules and the flow pass share one walk per file."""
    got = getattr(ctx, "_traced_scopes", None)
    if got is None:
        got = ctx._traced_scopes = _TracedScopes(ctx.tree)
    return got


def _traced_value_names(fn: ast.FunctionDef, static: Set[str]) -> Set[str]:
    """Names that plausibly hold traced values inside `fn`: its own and
    nested functions' parameters, minus declared-static ones."""
    names: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            a = node.args
            for p in a.posonlyargs + a.args + a.kwonlyargs:
                names.add(p.arg)
            if a.vararg:
                names.add(a.vararg.arg)
    return names - static


def _references(node: ast.AST, names: Set[str]) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id in names for n in ast.walk(node)
    )


# ---------------------------------------------------------------------------
# Rule 1: host-sync-in-jit
# ---------------------------------------------------------------------------


@rule(
    "host-sync-in-jit",
    "host synchronization (.item()/float()/np.asarray/traced branch) "
    "inside a jit/shard_map-traced function",
)
def host_sync_in_jit(ctx) -> Iterable[Tuple[int, str]]:
    for fn, static in _traced_scopes(ctx):
        traced = _traced_value_names(fn, static)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                tail = _tail_name(node.func)
                if isinstance(node.func, ast.Attribute) and tail in (
                    "item", "tolist"
                ) and not node.args:
                    yield (node.lineno,
                           f".{tail}() inside traced function "
                           f"`{fn.name}` forces a device->host sync")
                elif isinstance(node.func, ast.Name) and tail in (
                    "float", "int", "bool"
                ) and len(node.args) == 1 and _references(node.args[0], traced):
                    yield (node.lineno,
                           f"{tail}() on a traced value inside `{fn.name}` "
                           "concretizes it on host (sync or trace error); "
                           "keep the math in jnp")
                elif (
                    isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in ("np", "numpy", "onp")
                    and tail in ("asarray", "array")
                    and node.args
                    and _references(node.args[0], traced)
                ):
                    yield (node.lineno,
                           f"np.{tail}() on a traced value inside "
                           f"`{fn.name}` pulls it to host; use jnp")
                elif tail in ("device_get", "block_until_ready"):
                    yield (node.lineno,
                           f"{tail}() inside traced function `{fn.name}` "
                           "is a host sync (and a no-op on tracers)")
            elif isinstance(node, (ast.If, ast.While)):
                test = node.test
                jnp_rooted = any(
                    isinstance(n, ast.Name) and n.id == "jnp"
                    for n in ast.walk(test)
                )
                traced_compare = any(
                    isinstance(n, ast.Compare) and _references(n, traced)
                    for n in ast.walk(test)
                )
                if jnp_rooted or traced_compare:
                    kw = "if" if isinstance(node, ast.If) else "while"
                    yield (node.lineno,
                           f"python `{kw}` on a traced comparison inside "
                           f"`{fn.name}` — use jnp.where/lax.cond "
                           "(host sync at best, trace error at worst)")


# ---------------------------------------------------------------------------
# Rule 2: retrace-hazard
# ---------------------------------------------------------------------------

_TIME_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
               "time.time_ns", "datetime.now", "datetime.utcnow"}


@rule(
    "retrace-hazard",
    "trace-time nondeterminism (time/random/env reads, unsorted dict "
    "iteration, unhashable static args) inside a traced function",
)
def retrace_hazard(ctx) -> Iterable[Tuple[int, str]]:
    for fn, _static in _traced_scopes(ctx):
        # unhashable defaults become unhashable static args / weak closures
        for default in fn.args.defaults + [
            d for d in fn.args.kw_defaults if d is not None
        ]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                yield (default.lineno,
                       f"mutable default on traced function `{fn.name}` — "
                       "unhashable as a static arg and retrace bait as a "
                       "closure; use a tuple or None")
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if dotted in _TIME_CALLS:
                    yield (node.lineno,
                           f"{dotted}() inside traced `{fn.name}` is baked "
                           "in at trace time — every call traces a "
                           "different constant (retrace bait)")
                elif dotted.startswith("random.") or (
                    ".random." in dotted and not dotted.startswith("jax.")
                ):
                    yield (node.lineno,
                           f"host RNG `{dotted}` inside traced `{fn.name}` "
                           "— use jax.random with an explicit key")
                elif "environ" in dotted or dotted == "os.getenv" or (
                    dotted.split(".")[-1] in (
                        "get_raw", "get_str", "get_int", "get_float",
                        "get_bool",
                    ) and "knobs" in dotted
                ):
                    yield (node.lineno,
                           f"environment read inside traced `{fn.name}` is "
                           "frozen at trace time and invisible to the "
                           "compiled program — read it outside and pass "
                           "the value in")
            elif isinstance(node, ast.For) and isinstance(node.iter, ast.Call):
                it = node.iter
                if (
                    isinstance(it.func, ast.Attribute)
                    and it.func.attr in ("items", "keys", "values")
                    and not it.args
                ):
                    yield (node.lineno,
                           f"dict iteration order inside traced `{fn.name}` "
                           "depends on insertion order — wrap in sorted() "
                           "so every process traces the same program")


# ---------------------------------------------------------------------------
# Rule 3: undeclared-knob
# ---------------------------------------------------------------------------

_KNOBS_PY = "ytklearn_tpu/config/knobs.py"
_ACCESSORS = {"get_raw", "get_str", "get_int", "get_float", "get_bool"}


@functools.lru_cache(maxsize=1)
def _declared_knobs() -> Optional[frozenset]:
    """YTK_* names declared in the registry, parsed from its AST (cheap —
    no ytklearn_tpu import). Anchored to this repo checkout, so the lint
    works from any cwd; None when the registry is missing entirely."""
    path = pathlib.Path(__file__).resolve().parents[2] / _KNOBS_PY
    if not path.is_file():
        return None
    tree = ast.parse(path.read_text(encoding="utf-8"), str(path))
    names = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Call)
            and _tail_name(node.func) == "_knob"
            and node.args
            and isinstance(node.args[0], ast.Constant)
        ):
            names.add(node.args[0].value)
    return frozenset(names)


def _ytk_key(node: ast.expr) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str) and \
            node.value.startswith("YTK_"):
        return node.value
    return None


@rule(
    "undeclared-knob",
    "YTK_* environ read outside the central registry "
    "(ytklearn_tpu/config/knobs.py), or a knob accessor naming an "
    "undeclared knob",
    applies=lambda p: not p.endswith(_KNOBS_PY),
)
def undeclared_knob(ctx) -> Iterable[Tuple[int, str]]:
    declared = _declared_knobs()
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
            if "environ" in _dotted(node.value):
                key = _ytk_key(node.slice)
                if key:
                    yield (node.lineno,
                           f"os.environ[{key!r}] — read knobs through "
                           "ytklearn_tpu.config.knobs (typed accessor + "
                           "doc-synced registry)")
        elif isinstance(node, ast.Call) and node.args:
            dotted = _dotted(node.func)
            tail = dotted.split(".")[-1]
            key = _ytk_key(node.args[0])
            if key is None:
                continue
            if "environ" in dotted and tail in ("get", "setdefault", "pop"):
                yield (node.lineno,
                       f"os.environ.{tail}({key!r}) — read knobs through "
                       "ytklearn_tpu.config.knobs")
            elif dotted == "os.getenv":
                yield (node.lineno,
                       f"os.getenv({key!r}) — read knobs through "
                       "ytklearn_tpu.config.knobs")
            elif tail in _ACCESSORS and "knobs" in dotted:
                if declared is not None and key not in declared:
                    yield (node.lineno,
                           f"knob {key} is not declared in "
                           f"{_KNOBS_PY} — declare name/type/default/doc "
                           "there (and regen the running-guide table)")


# ---------------------------------------------------------------------------
# Rule 4: broad-except-swallow
# ---------------------------------------------------------------------------

_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = [_tail_name(t)] if not isinstance(t, ast.Tuple) else [
        _tail_name(el) for el in t.elts
    ]
    return any(n in ("Exception", "BaseException") for n in names)


@rule(
    "broad-except-swallow",
    "`except Exception` (or bare except) that neither re-raises, logs, "
    "nor uses the caught exception",
)
def broad_except_swallow(ctx) -> Iterable[Tuple[int, str]]:
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.ExceptHandler) and _is_broad(node)):
            continue
        reraises = any(
            isinstance(n, ast.Raise) for b in node.body for n in ast.walk(b)
        )
        logs = any(
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr in _LOG_METHODS
            for b in node.body
            for n in ast.walk(b)
        )
        uses_exc = node.name is not None and any(
            isinstance(n, ast.Name) and n.id == node.name
            for b in node.body
            for n in ast.walk(b)
        )
        if not (reraises or logs or uses_exc):
            what = "bare except" if node.type is None else "except Exception"
            yield (node.lineno,
                   f"{what} swallows the failure — narrow the type, log "
                   "it, re-raise, or annotate why ignoring is safe")


# ---------------------------------------------------------------------------
# Rule 5: bare-print (absorbs scripts/check_no_print.sh)
# ---------------------------------------------------------------------------


def _bare_print_applies(path: str) -> bool:
    return (
        path.startswith("ytklearn_tpu/")
        and not path.endswith("ytklearn_tpu/cli.py")
    )


@rule(
    "bare-print",
    "bare print() in library code — progress output goes through logging "
    "or obs.heartbeat (allowlist: cli.py, whose stdout IS its contract)",
    applies=_bare_print_applies,
)
def bare_print(ctx) -> Iterable[Tuple[int, str]]:
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "print"
        ):
            yield (node.lineno,
                   "bare print() — use logging or ytklearn_tpu.obs."
                   "heartbeat so the output is structured and exportable")


# ---------------------------------------------------------------------------
# Rule 6: sleep-in-except (ad-hoc retry loops)
# ---------------------------------------------------------------------------

_RETRY_PY = "ytklearn_tpu/resilience/retry.py"


@rule(
    "sleep-in-except",
    "time.sleep inside an except handler — an ad-hoc retry/backoff loop "
    "that bypasses ytklearn_tpu.resilience.retry (no typed transient "
    "classification, no capped backoff, no io.retry.* evidence)",
    applies=lambda p: not p.endswith(_RETRY_PY),
)
def sleep_in_except(ctx) -> Iterable[Tuple[int, str]]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        for stmt in node.body:
            for n in ast.walk(stmt):
                if not isinstance(n, ast.Call):
                    continue
                dotted = _dotted(n.func)
                if dotted == "time.sleep" or (
                    isinstance(n.func, ast.Name) and n.func.id == "sleep"
                ):
                    yield (n.lineno,
                           "sleep inside an except handler is an ad-hoc "
                           "retry loop — route through resilience.retry."
                           "retry_call (typed classification, capped "
                           "deterministic backoff, io.retry.* counters)")


# ---------------------------------------------------------------------------
# serve-lock-discipline (r10) graduated into the repo-wide concurrency
# pass: tools/ytklint/concurrency.py's `unguarded-shared-write` subsumes
# it (guarded-state map over every package, module globals, Thread
# escapes). core.RULE_ALIASES keeps the old name valid in allow()
# comments and --select — the check_no_print.sh delegating precedent.
# ---------------------------------------------------------------------------
