"""Names of the program's own on the device.

A device trace names an operation by what XLA made of it (`%fusion.60`, an
HLO text), which changes whenever the program or the compiler does. Three
things here let a reader say which of the program's operations is which
without reading XLA's text:

  scope(name)        `jax.named_scope` under a registered name
                     (`gbdt.hist`, `fm.gather_v`): the name lands in the
                     `op_name` metadata of every instruction traced under
                     it, and of their transposes under autodiff.
  Program(fn)        a jitted step compiled ahead of time once per
                     argument signature, under `fn`'s own fixed name (the
                     trace's module line reads `jit_<name>`), so that the
                     compiled HLO is at hand when it is made. Every call
                     adds 1 to the counter `launches.jit_<name>`: what the
                     program asked of the device, against which a device
                     trace's module line says how much of it was kept.
  compile_lowered()  compiles a `jax.stages.Lowered` and reads, once per
                     compile, the compiled HLO's `metadata={op_name=...}`
                     into a map module name -> instruction name -> scope,
                     kept for `scope_map()` and dropped into the event
                     stream (one `scope_map` event a module, so the JSONL
                     export has it). The trace's `%fusion.60` of module
                     `jit_iteration` is then `fm.gather_v` whatever XLA
                     numbers it next week.

The installed profiler does not carry `op_name` to the op-line events (an
event is named by its HLO text and has three timing stats), which is why
the map is taken at compile time.

  subscope(name)     a second naming beside the scopes, for a part of a
                     scope that a reader wants apart WITHOUT taking it out
                     of the scope (`gbdt.hist.part`: the partitioned
                     histogram passes, which stay under `gbdt.hist`). It has
                     a map of its own (`subscope_map()`, one `subscope_map`
                     event a module that has any); the scope map does not
                     know it.

JAX's persistent compile cache keys a program by its operations with the
debug information stripped, `op_name` included: a program that differs from
a cached one only in its scopes is served the cached executable, whose
metadata names the OTHER program's scopes (seen on this JAX: a second
process that renamed a scope read the first one's name back). So
`compile_lowered` makes the scopes part of the program: which scope every
operation lies under, in program order, is hashed into a module attribute
(`mhlo.frontend_attributes {ytk_scopes}`), which the cache key does cover.
A program under no scope is left as it was; an operation's subscope is
hashed with its scope, and a program with no subscope hashes as before.
"""

from __future__ import annotations

import hashlib
import re
import threading
from typing import Dict, Optional

import jax

from . import core

_lock = threading.Lock()
_SCOPES: set = set()
_SUBSCOPES: set = set()
_MAPS: Dict[str, Dict[str, str]] = {}
_SUBMAPS: Dict[str, Dict[str, str]] = {}

_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTR_RE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)\s*=\s.*\bop_name="([^"]*)"'
)


def scope(name: str):
    """`with scope("gbdt.hist"): ...` inside a traced function."""
    _SCOPES.add(name)
    return jax.named_scope(name)


def subscope(name: str):
    """`with subscope("gbdt.hist.part"): ...`: see the module docstring."""
    _SUBSCOPES.add(name)
    return jax.named_scope(name)


def innermost_scope(op_name: str, names=None) -> Optional[str]:
    """The registered scope (or the name of `names`) that lies deepest in
    an `op_name` path
    (`jit(iteration)/while/body/transpose(jvp(fm.gather_v))/scatter-add`
    -> `fm.gather_v`), or None."""
    best, at = None, -1
    for name in _SCOPES if names is None else names:
        for m in re.finditer(r"(?<![\w.])" + re.escape(name) + r"(?![\w.])", op_name):
            if m.start() > at:
                best, at = name, m.start()
    return best


def parse_hlo(text: str, names=None):
    """(module name, {instruction name: scope}) of one compiled HLO text;
    with `names`, by those names instead of the registered scopes."""
    module, (ops,) = _parse_hlo(text, (_SCOPES if names is None else names,))
    return module, ops


def _parse_hlo(text: str, name_sets):
    """One pass over a compiled HLO text (the round program's is megabytes):
    (module name, one {instruction name: name} map a set of names)."""
    module, maps = None, [{} for _ in name_sets]
    for line in text.splitlines():
        if module is None:
            m = _MODULE_RE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR_RE.match(line)
        if m:
            for names, ops in zip(name_sets, maps):
                sc = innermost_scope(m.group(2), names) if names else None
                if sc is not None:
                    ops[m.group(1)] = sc
    return module, maps


_LOC_NAME = re.compile(r'^loc\("([^"]*)"')


def _scope_digest(module) -> Optional[str]:
    """Hash of the scope of every operation of an MLIR module, in program
    order (the name stack is the operation's location name); None where no
    operation lies under a registered scope."""
    seen: Dict[str, str] = {}
    marks = []

    def walk(op):
        m = _LOC_NAME.match(str(op.location))
        name = m.group(1) if m else ""
        if name not in seen:
            sub = innermost_scope(name, _SUBSCOPES) if _SUBSCOPES else None
            seen[name] = (innermost_scope(name) or "-") + (
                "" if sub is None else "|" + sub)
        marks.append(seen[name])
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner.operation)

    walk(module.operation)
    if all(m == "-" for m in marks):
        return None
    return hashlib.sha1("\n".join(marks).encode()).hexdigest()[:16]


def compile_lowered(lowered):
    """`lowered.compile()`, the program's scopes made part of what the
    compile cache keys it by (see the module docstring), and its scope map
    recorded."""
    if _SCOPES:
        from jax._src.lib.mlir import ir

        module = lowered.compiler_ir("stablehlo")
        digest = _scope_digest(module)
        if digest is not None:
            with module.context:
                attrs = module.operation.attributes
                front = {}
                if "mhlo.frontend_attributes" in attrs:
                    front = {a.name: a.attr for a in ir.DictAttr(attrs["mhlo.frontend_attributes"])}
                front["ytk_scopes"] = ir.StringAttr.get(digest)
                attrs["mhlo.frontend_attributes"] = ir.DictAttr.get(front)
    compiled = lowered.compile()
    if core.enabled() and _SCOPES:
        name, (ops, sub) = _parse_hlo(
            compiled.as_text(), (_SCOPES, _SUBSCOPES))
        if name is not None:
            with _lock:
                _MAPS[name] = ops
            core.event("scope_map", module=name, ops=ops)
            if sub:
                with _lock:
                    _SUBMAPS[name] = sub
                core.event("subscope_map", module=name, ops=sub)
    return compiled


def scope_map() -> Dict[str, Dict[str, str]]:
    """module name -> instruction name -> scope, of every program compiled
    so far with obs on."""
    with _lock:
        return {m: dict(ops) for m, ops in _MAPS.items()}


def subscope_map() -> Dict[str, Dict[str, str]]:
    """module name -> instruction name -> subscope, of the programs compiled
    so far with obs on that have an operation under one."""
    with _lock:
        return {m: dict(ops) for m, ops in _SUBMAPS.items()}


def _signature(args) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(
        (x.aval, x.sharding) if isinstance(x, jax.Array)
        else jax.api_util.shaped_abstractify(x)
        for x in leaves
    )


class Program:
    """`Program(fn)(*args)` runs `jax.jit(fn)` compiled ahead of time: one
    compile per argument signature (shapes, dtypes, shardings), the compiled
    object kept, its scope map recorded when it is made. The function's
    `__name__` is the program's name on the device, and a call counts one
    launch of it (`launches.jit_<name>`)."""

    def __init__(self, fn, **jit_kwargs):
        self.jit = jax.jit(fn, **jit_kwargs)
        self.launches = f"launches.jit_{fn.__name__}"
        self._compiled: Dict[tuple, object] = {}

    def compile(self, *args):
        """The compiled program for these arguments' signature, made now if
        it is not there: lowering runs nothing, so a loop can have the
        programs of a later step made before its first."""
        key = _signature(args)
        compiled = self._compiled.get(key)
        if compiled is None:
            compiled = self._compiled[key] = compile_lowered(self.jit.lower(*args))
        return compiled

    def __call__(self, *args):
        core.inc(self.launches)
        return self.compile(*args)(*args)
