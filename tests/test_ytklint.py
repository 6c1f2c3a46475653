"""ytklint self-tests: per-rule fixtures + the repo-wide clean gate.

Each rule gets (at least) one failing snippet, one passing snippet, and a
suppression check — the fixture contract from ISSUE 5. The repo-wide test
is the actual gate: ytklint must run clean over ytklearn_tpu/, scripts/
and bench.py, and the knob registry must match the running-guide table in
both directions.
"""

import textwrap

import pytest

from tools.ytklint import (
    RULES,
    RULE_ALIASES,
    lint_paths,
    lint_paths_report,
    lint_source,
    lint_source_report,
    lint_sources,
    report_json,
)
from ytklearn_tpu.config import knobs


def run(src, path="ytklearn_tpu/x.py", select=None):
    return lint_source(textwrap.dedent(src), path, select)


def rules_hit(src, path="ytklearn_tpu/x.py"):
    return {f.rule for f in run(src, path)}


def test_rule_catalog_is_the_issue_catalog():
    assert set(RULES) == {
        "host-sync-in-jit",
        "retrace-hazard",
        "undeclared-knob",
        "broad-except-swallow",
        "bare-print",
        "sleep-in-except",
        # the r15 concurrency pass (tools/ytklint/concurrency.py)
        "unguarded-shared-write",
        "lock-order-inversion",
        "blocking-call-under-lock",
        "thread-lifecycle",
        # the ytkflow interprocedural pass (tools/ytklint/flow.py)
        "unseamed-io",
        "metric-name-drift",
        "deep-blocking-under-lock",
        "deep-host-sync-in-jit",
        "silent-thread-death",
    }
    for r in RULES.values():
        assert r.doc  # every rule documents itself for --list-rules
    # the flow rules run in the post-graph phase, the rest per-file
    assert {r.name for r in RULES.values() if r.needs_graph} == {
        "unseamed-io", "metric-name-drift", "deep-blocking-under-lock",
        "deep-host-sync-in-jit", "silent-thread-death",
    }
    # serve-lock-discipline graduated into unguarded-shared-write; the
    # alias keeps old suppressions/--select invocations valid
    assert RULE_ALIASES["serve-lock-discipline"] == "unguarded-shared-write"
    # the deep rules grew out of the 1-level pass; short spellings stay
    assert RULE_ALIASES["cross-module-blocking"] == "deep-blocking-under-lock"
    assert RULE_ALIASES["cross-module-host-sync"] == "deep-host-sync-in-jit"


# ---------------------------------------------------------------------------
# host-sync-in-jit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "body",
    [
        "return x.item()",
        "return x.tolist()",
        "return float(x) * 2",
        "return np.asarray(x).sum()",
        "return jax.device_get(x)",
        "if x > 0:\n            return x\n        return -x",
    ],
)
def test_host_sync_in_jit_fails(body):
    src = f"""\
    import jax
    import numpy as np

    @jax.jit
    def f(x):
        {body}
    """
    assert "host-sync-in-jit" in rules_hit(src)


def test_host_sync_catches_functions_passed_to_jit_and_shard_map():
    src = """\
    import jax

    def f(x):
        return x.item()

    g = jax.jit(f)

    def k(x):
        return float(x)

    out = shard_map(k, mesh, in_specs=None, out_specs=None)
    """
    found = run(src)
    assert {f.rule for f in found} == {"host-sync-in-jit"}
    assert len(found) == 2


def test_host_sync_passes():
    src = """\
    import jax
    from functools import partial

    @partial(jax.jit, static_argnames=("n",))
    def f(x, n):
        return x * float(n)  # static arg: a real python value

    def host_side(x):
        return x.item()  # not traced — host code may sync freely
    """
    assert run(src) == []


def test_host_sync_suppression():
    src = """\
    import jax

    @jax.jit
    def f(x):
        # ytklint: allow(host-sync-in-jit) reason=fixture demonstrating suppression
        return x.item()
    """
    assert run(src) == []
    # same-line form
    src2 = """\
    import jax

    @jax.jit
    def f(x):
        return x.item()  # ytklint: allow(host-sync-in-jit) reason=demo
    """
    assert run(src2) == []


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "body",
    [
        "return x * time.time()",
        "return x * random.random()",
        "return x * np.random.rand()",
        "s = 0\n        for k, v in d.items():\n            s = s + v\n        return x + s",
        "return x * knobs.get_float('YTK_HEALTH_INGEST_TOL')",
        "return x * float(os.environ.get('N', 1))",
    ],
)
def test_retrace_hazard_fails(body):
    src = f"""\
    import jax, time, random, os
    import numpy as np
    from ytklearn_tpu.config import knobs

    d = {{}}

    @jax.jit
    def f(x):
        {body}
    """
    assert "retrace-hazard" in rules_hit(src)


def test_retrace_hazard_mutable_default_fails():
    src = """\
    import jax

    @jax.jit
    def f(x, opts=[]):
        return x
    """
    assert "retrace-hazard" in rules_hit(src)


def test_retrace_hazard_passes():
    src = """\
    import jax, time

    @jax.jit
    def f(x, key, d):
        s = x
        for k, v in sorted(d.items()):  # deterministic trace order
            s = s + v
        return s + jax.random.uniform(key)  # device RNG is fine

    def host(x):
        return time.time(), x  # untraced host timing is fine
    """
    assert run(src) == []


# ---------------------------------------------------------------------------
# undeclared-knob
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "line",
    [
        "v = os.environ.get('YTK_FOO')",
        "v = os.environ['YTK_FOO']",
        "v = os.getenv('YTK_FOO')",
        "v = knobs.get_str('YTK_NOT_A_REAL_KNOB')",
    ],
)
def test_undeclared_knob_fails(line):
    src = f"""\
    import os
    from ytklearn_tpu.config import knobs

    {line}
    """
    assert "undeclared-knob" in rules_hit(src)


def test_undeclared_knob_passes():
    src = """\
    import os
    from ytklearn_tpu.config import knobs

    a = knobs.get_bool("YTK_HEALTH")  # declared accessor read
    b = os.environ.get("JAX_PLATFORMS")  # non-YTK envs are out of scope
    os.environ["YTK_HEALTH"] = "0"  # writes (test setup) are allowed
    """
    assert run(src) == []
    # the registry module itself is the one sanctioned reader
    raw = 'import os\nv = os.environ.get("YTK_HEALTH")\n'
    assert lint_source(raw, "ytklearn_tpu/config/knobs.py") == []


# ---------------------------------------------------------------------------
# broad-except-swallow
# ---------------------------------------------------------------------------


def test_broad_except_fails():
    src = """\
    try:
        work()
    except Exception:
        pass
    """
    assert "broad-except-swallow" in rules_hit(src)
    src_bare = """\
    try:
        work()
    except:
        result = None
    """
    assert "broad-except-swallow" in rules_hit(src_bare)


@pytest.mark.parametrize(
    "handler",
    [
        "except ValueError:\n    pass",  # narrow type
        "except Exception:\n    log.warning('failed')",  # logs
        "except Exception:\n    raise RuntimeError('wrapped')",  # re-raises
        "except Exception as e:\n    results.append(e)",  # propagates it
    ],
)
def test_broad_except_passes(handler):
    src = f"try:\n    work()\n{handler}\n"
    assert run(src) == []


def test_broad_except_suppression_uses_issue_alias():
    src = """\
    try:
        work()
    # ytklint: allow(broad-except) reason=best-effort cleanup must not mask the original error
    except Exception:
        pass
    """
    assert run(src) == []


# ---------------------------------------------------------------------------
# bare-print
# ---------------------------------------------------------------------------


def test_bare_print_fails_in_library():
    assert "bare-print" in rules_hit("print('hi')\n")


def test_bare_print_allowlists_cli_and_ignores_scripts():
    assert lint_source("print('{}')\n", "ytklearn_tpu/cli.py") == []
    assert lint_source("print('report')\n", "scripts/report.py") == []


def test_bare_print_suppression():
    src = "print('x')  # ytklint: allow(bare-print) reason=fixture\n"
    assert run(src) == []


# ---------------------------------------------------------------------------
# unguarded-shared-write (subsumes serve-lock-discipline)
# ---------------------------------------------------------------------------

_LOCKED_CLASS = """\
import threading

class Q:
    def __init__(self):
        self._lock = threading.Lock()
        self.depth = 0  # __init__ publishes before threads exist

    def push(self):
        with self._lock:
            self.depth += 1

    def reset(self):
        {reset_body}
"""


def test_sleep_in_except_fails():
    src = """
    import time

    def fetch(path):
        for _ in range(3):
            try:
                # ytklint: allow(unseamed-io) reason=fixture
                return open(path).read()
            except OSError:
                time.sleep(1.0)
    """
    assert rules_hit(src) == {"sleep-in-except"}
    # bare `from time import sleep` spelling is the same ad-hoc loop
    src2 = """
    from time import sleep

    def fetch(path):
        try:
            # ytklint: allow(unseamed-io) reason=fixture
            return open(path).read()
        except OSError:
            sleep(0.5)
    """
    assert rules_hit(src2) == {"sleep-in-except"}


def test_sleep_in_except_passes():
    # sleeping OUTSIDE a handler (polling) is not a retry loop
    src = """
    import time

    def poll(path):
        while not ready(path):
            time.sleep(1.0)
    """
    assert run(src, select=["sleep-in-except"]) == []
    # the sanctioned implementation is exempt by path
    src2 = """
    import time

    def retry_call(fn):
        try:
            return fn()
        except OSError:
            time.sleep(0.1)
    """
    assert run(src2, path="ytklearn_tpu/resilience/retry.py",
               select=["sleep-in-except"]) == []


def test_sleep_in_except_suppression():
    src = """
    import time

    def fetch(path):
        try:
            return open(path).read()
        except OSError:
            # ytklint: allow(sleep-in-except) reason=test fixture exercising the raw loop
            time.sleep(1.0)
    """
    assert run(src, select=["sleep-in-except"]) == []


def test_unguarded_shared_write_fails():
    src = _LOCKED_CLASS.format(reset_body="self.depth = 0  # no lock!")
    found = lint_source(src, "ytklearn_tpu/serve/q.py")
    assert {f.rule for f in found} == {"unguarded-shared-write"}


def test_unguarded_shared_write_passes_under_lock():
    src = _LOCKED_CLASS.format(
        reset_body="with self._lock:\n            self.depth = 0"
    )
    assert lint_source(src, "ytklearn_tpu/serve/q.py") == []


def test_unguarded_shared_write_is_repo_wide_now():
    """The r10 rule stopped at serve/; the concurrency pass covers every
    package (the retrain-lock heartbeat and obs recorder live outside
    serve/ and are just as threaded)."""
    src = _LOCKED_CLASS.format(reset_body="self.depth = 0")
    found = lint_source(src, "ytklearn_tpu/gbdt/q.py")
    assert {f.rule for f in found} == {"unguarded-shared-write"}


def test_unguarded_shared_write_r14_inflight_rmw_plant():
    """The acceptance plant: the exact r14 `_inflight` bug — a lockless
    dict read-modify-write in one method while every other mutation of
    the same attr holds the lock (the lost update skewed least-queued
    balancing forever)."""
    src = """\
    import threading

    class Front:
        def __init__(self):
            self._inflight_lock = threading.Lock()
            self._inflight = {}

        def _post(self, rid, rows):
            with self._inflight_lock:
                self._inflight[rid] = self._inflight.get(rid, 0) + len(rows)

        def _done(self, rid, rows):
            self._inflight[rid] = self._inflight.get(rid, 0) - len(rows)
    """
    found = run(src)
    assert [f.rule for f in found] == ["unguarded-shared-write"]
    assert "_inflight" in found[0].message and "_done" in found[0].message


def test_unguarded_shared_write_module_global():
    """Module-global state counts too: a `global` rebind (or a write to a
    module-level singleton's attr) guarded in one function and lockless
    in another."""
    src = """\
    import threading

    _lock = threading.Lock()
    _cache = None

    def warm():
        global _cache
        with _lock:
            _cache = build()

    def poke():
        global _cache
        _cache = None
    """
    assert rules_hit(src) == {"unguarded-shared-write"}


def test_unguarded_shared_write_thread_escape_iteration():
    """The Thread(target=) escape: a dict mutated on a thread path while
    another method iterates it with no common lock (the r15 _respawns
    finding in the fleet front)."""
    src = """\
    import threading

    class Fleet:
        def __init__(self):
            self.slots = {}
            self._t = None

        def start(self):
            # ytklint: allow(silent-thread-death) reason=fixture
            self._t = threading.Thread(target=self._monitor, daemon=True)
            self._t.start()

        def _monitor(self):
            self.slots[0] = object()

        def stop(self):
            for s in list(self.slots.values()):
                use(s)
    """
    found = run(src)
    assert [f.rule for f in found] == ["unguarded-shared-write"]
    assert "thread path" in found[0].message and "stop" in found[0].message


def test_unguarded_shared_write_common_lock_passes():
    src = """\
    import threading

    class Fleet:
        def __init__(self):
            self.slots = {}
            self._lock = threading.Lock()
            self._t = None

        def start(self):
            # ytklint: allow(silent-thread-death) reason=fixture
            self._t = threading.Thread(target=self._monitor, daemon=True)
            self._t.start()

        def _monitor(self):
            with self._lock:
                self.slots[0] = object()

        def stop(self):
            with self._lock:
                snap = list(self.slots.values())
            for s in snap:
                use(s)
    """
    assert run(src) == []


def test_unguarded_shared_write_suppression_accepts_legacy_alias():
    """Existing allow(serve-lock-discipline) comments keep suppressing
    the successor rule (the check_no_print.sh wrapper precedent)."""
    src = _LOCKED_CLASS.format(
        reset_body="self.depth = 0  # ytklint: allow(serve-lock-discipline) reason=single-writer reset before worker start"
    )
    assert lint_source(src, "ytklearn_tpu/serve/q.py") == []


# ---------------------------------------------------------------------------
# lock-order-inversion
# ---------------------------------------------------------------------------

_TWO_LOCKS = """\
import threading

class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def one(self):
        with self._a:
            with self._b:
                return 1

    def two(self):
        {two_body}
"""


def test_lock_order_inversion_plant_is_flagged():
    """The acceptance plant: A->B in one method, B->A in another."""
    src = _TWO_LOCKS.format(
        two_body="with self._b:\n            with self._a:\n                return 2"
    )
    found = run(src)
    assert {f.rule for f in found} == {"lock-order-inversion"}
    # both acquisition sites are named (fix either to break the cycle)
    assert len(found) == 2


def test_lock_order_consistent_nesting_passes():
    src = _TWO_LOCKS.format(
        two_body="with self._a:\n            with self._b:\n                return 2"
    )
    assert run(src) == []


def test_lock_order_inversion_through_a_call():
    """One-level call propagation: holding A and calling a method that
    takes B is an A->B edge even without lexical nesting."""
    src = """\
    import threading

    class Pair:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def under_b(self):
            with self._b:
                return 1

        def one(self):
            with self._a:
                return self.under_b()

        def two(self):
            with self._b:
                with self._a:
                    return 2
    """
    assert "lock-order-inversion" in rules_hit(src)


def test_lock_order_inversion_suppression():
    src = _TWO_LOCKS.format(
        two_body=(
            "with self._b:\n"
            "            # ytklint: allow(lock-order-inversion) reason=fixture demonstrating suppression\n"
            "            with self._a:\n"
            "                return 2"
        )
    )
    found = run(src)
    # the suppressed side is silenced; the partner edge still reports
    assert [f.rule for f in found] == ["lock-order-inversion"]


# ---------------------------------------------------------------------------
# blocking-call-under-lock
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "body",
    [
        "self.procs[rid].wait(timeout=10.0)",
        "time.sleep(1.0)",
        "self.worker.join(5.0)",
        "urlopen('http://127.0.0.1:1/readyz')",
        "subprocess.run(['cc'], check=True)",
        "chaos_point('serve.load')",
        "retry_call(fn, site='io.read')",
    ],
)
def test_blocking_call_under_lock_fails(body):
    src = f"""\
    import subprocess, threading, time
    from urllib.request import urlopen

    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.procs = {{}}
            self.worker = None

        def heal(self, rid, fn):
            with self._lock:
                {body}
    """
    assert "blocking-call-under-lock" in rules_hit(src)


def test_blocking_join_with_variable_timeout_is_still_a_join():
    """Review fix: `self.t.join(self.timeout)` — one variable positional
    arg — must not be misread as str.join(iterable) when the receiver is
    a known thread binding (the exact r14 respawn-bug shape)."""
    src = """\
    import threading

    class M:
        def __init__(self):
            self._lock = threading.Lock()
            self.timeout = 15.0
            self.t = threading.Thread(target=work, daemon=True)

        def stop(self):
            with self._lock:
                self.t.join(self.timeout)
    """
    assert "blocking-call-under-lock" in rules_hit(src)
    # ...while a genuine str.join under a lock stays clean
    src2 = """\
    import threading

    _lock = threading.Lock()

    def render(parts):
        with _lock:
            return ",".join(parts) + "|".join(sorted(parts))
    """
    assert run(src2) == []


def test_blocking_call_outside_lock_passes():
    src = """\
    import threading, time

    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self.state = {}

        def heal(self, rid, proc):
            with self._lock:
                self.state[rid] = "dead"
            proc.wait(timeout=10.0)  # blocking AFTER the lock released
            time.sleep(0.1)
    """
    assert run(src) == []


def test_condition_wait_on_held_lock_is_not_blocking():
    """Condition.wait on the HELD lock releases it — the batcher linger
    idiom must stay clean."""
    src = """\
    import threading

    class B:
        def __init__(self):
            self._lock = threading.Lock()
            self._not_empty = threading.Condition(self._lock)
            self._queue = []

        def take(self):
            with self._not_empty:
                while not self._queue:
                    self._not_empty.wait(timeout=0.05)
                return self._queue.pop()
    """
    assert run(src) == []


def test_blocking_call_one_level_propagation():
    """The r14 respawn-bug shape: the blocking work hides one call away
    (monitor held a conceptual lock across a spawn that compiled jax for
    tens of seconds)."""
    src = """\
    import subprocess, threading

    _lock = threading.Lock()

    def _build():
        # ytklint: allow(unseamed-io) reason=fixture
        subprocess.run(["cc", "native.c"], check=True)

    def load():
        with _lock:
            _build()
    """
    found = run(src)
    assert [f.rule for f in found] == ["blocking-call-under-lock"]
    assert "_build" in found[0].message


def test_blocking_call_under_lock_suppression():
    src = """\
    import subprocess, threading

    _lock = threading.Lock()

    def load():
        with _lock:
            # ytklint: allow(blocking-call-under-lock, unseamed-io) reason=fixture: build serialization is the point
            subprocess.run(["cc"], check=True)
    """
    assert run(src) == []


# ---------------------------------------------------------------------------
# thread-lifecycle
# ---------------------------------------------------------------------------


def test_thread_lifecycle_unjoined_nondaemon_fails():
    src = """\
    import threading

    def fire():
        threading.Thread(target=work).start()
    """
    assert rules_hit(src) == {"thread-lifecycle"}


def test_thread_lifecycle_joined_or_daemon_passes():
    src = """\
    import threading

    class App:
        def __init__(self):
            self._worker = threading.Thread(target=work)

        def start(self):
            self._worker.start()
            threading.Thread(target=poll, daemon=True).start()

        def stop(self):
            self._worker.join(timeout=10.0)
    """
    assert run(src) == []


def test_thread_lifecycle_list_sweep_join_passes():
    """The chaos_drill idiom: a comprehension of threads joined by a
    `for t in threads: t.join()` sweep."""
    src = """\
    import threading

    def drill():
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
    """
    assert run(src) == []


def test_thread_lifecycle_untimed_event_wait_in_loop_fails():
    src = """\
    import threading

    class App:
        def __init__(self):
            self._stop = threading.Event()

        def loop(self):
            while True:
                self._stop.wait()
    """
    assert "thread-lifecycle" in rules_hit(src)


def test_thread_lifecycle_timed_event_wait_passes():
    src = """\
    import threading

    class App:
        def __init__(self):
            self._stop = threading.Event()

        def loop(self):
            while not self._stop.wait(0.25):
                tick()
    """
    assert run(src) == []


def test_thread_lifecycle_suppression():
    src = """\
    import threading

    def fire():
        # ytklint: allow(thread-lifecycle) reason=fixture: fire-and-forget by design
        threading.Thread(target=work).start()
    """
    assert run(src) == []


# ---------------------------------------------------------------------------
# suppression hygiene
# ---------------------------------------------------------------------------


def test_suppression_without_reason_is_itself_a_finding():
    src = "print('x')  # ytklint: allow(bare-print)\n"
    found = run(src)
    assert {f.rule for f in found} == {"bare-print", "bad-suppression"}


def test_suppression_with_unknown_rule_is_flagged():
    src = "x = 1  # ytklint: allow(no-such-rule) reason=typo\n"
    assert {f.rule for f in run(src)} == {"bad-suppression"}


def test_suppression_only_covers_named_rule():
    src = """\
    import jax, time

    @jax.jit
    def f(x):
        return x.item() * time.time()  # ytklint: allow(host-sync-in-jit) reason=fixture
    """
    assert {f.rule for f in run(src)} == {"retrace-hazard"}


def test_unused_suppression_is_flagged():
    """The stale-suppression audit: a suppression whose rule no longer
    fires on the covered line is itself a finding, so the inventory
    cannot drift as code moves (this exact audit retired a dead
    broad-except allow in gbdt/trainer.py)."""
    src = """\
    import logging
    log = logging.getLogger(__name__)
    try:
        work()
    # ytklint: allow(broad-except) reason=stale — the handler logs now
    except Exception:
        log.warning("failed")
    """
    found = run(src)
    assert [f.rule for f in found] == ["unused-suppression"]
    assert "allow(broad-except-swallow)" in found[0].message


def test_unused_suppression_respects_select_scope():
    """A --select run only audits the rules it actually ran: a
    suppression for an unselected rule is not reported (check_no_print's
    `--select bare-print` must not flag unrelated suppressions)."""
    src = """\
    x = 1  # ytklint: allow(retrace-hazard) reason=not audited under this select
    print("x")
    """
    found = run(src, select=["bare-print"])
    assert [f.rule for f in found] == ["bare-print"]
    # ...but a full run audits it
    assert "unused-suppression" in {f.rule for f in run(src)}


def test_live_suppression_is_not_flagged_unused():
    src = "print('x')  # ytklint: allow(bare-print) reason=fixture\n"
    assert run(src) == []


# ---------------------------------------------------------------------------
# machine-readable output (--format json)
# ---------------------------------------------------------------------------


def test_json_report_carries_findings_and_suppression_inventory():
    import json

    src = textwrap.dedent("""\
    print("loud")
    print("quiet")  # ytklint: allow(bare-print) reason=demo inventory entry
    """)
    rep = lint_source_report(src, "ytklearn_tpu/x.py")
    doc = report_json(
        {"findings": rep.findings, "suppressed": rep.suppressed, "files": 1}
    )
    doc = json.loads(json.dumps(doc))  # must be JSON-serializable as-is
    assert doc["schema"] == "ytklint"
    assert set(doc["rules"]) == set(RULES)
    assert [f["rule"] for f in doc["findings"]] == ["bare-print"]
    assert doc["findings"][0]["line"] == 1
    assert doc["findings"][0]["suppressed"] is False
    (sup,) = doc["suppressed"]
    assert sup["rule"] == "bare-print" and sup["line"] == 2
    assert sup["reason"] == "demo inventory entry"


def test_json_cli_shape(tmp_path):
    import json
    import pathlib
    import subprocess
    import sys as _sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    out = subprocess.run(
        [_sys.executable, "-m", "tools.ytklint", "--format", "json",
         "ytklearn_tpu/config"],
        cwd=repo, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["schema"] == "ytklint" and doc["files"] >= 3
    assert doc["findings"] == []


# ---------------------------------------------------------------------------
# the gate: the repo itself is clean, and the knob docs are in sync
# ---------------------------------------------------------------------------


def test_repo_is_ytklint_clean(monkeypatch):
    import pathlib

    monkeypatch.chdir(pathlib.Path(__file__).resolve().parent.parent)
    found = lint_paths(["ytklearn_tpu", "scripts", "bench.py"])
    assert found == [], "\n".join(str(f) for f in found)


def test_knob_doc_sync_both_ways(tmp_path, monkeypatch):
    import pathlib

    monkeypatch.chdir(pathlib.Path(__file__).resolve().parent.parent)
    assert knobs.check_doc_sync("docs/running_guide.md") == []
    # a missing declared knob AND an undocumented extra both fail
    table = knobs.table_markdown()
    tampered = table.replace("| `YTK_HEALTH` |", "| `YTK_IMAGINARY` |")
    doc = tmp_path / "guide.md"
    doc.write_text(f"# guide\n\n{tampered}\n")
    problems = knobs.check_doc_sync(str(doc))
    assert any("YTK_HEALTH" in p for p in problems)  # declared, not documented
    assert any("YTK_IMAGINARY" in p for p in problems)  # documented, undeclared


def test_knob_accessors(monkeypatch):
    with pytest.raises(KeyError):
        knobs.get_str("YTK_NOT_DECLARED_ANYWHERE")
    assert knobs.get_int("YTK_FLIGHT_N") == 4096
    assert knobs.get_bool("YTK_HEALTH") is True
    monkeypatch.setenv("YTK_HEALTH", "off")
    assert knobs.get_bool("YTK_HEALTH") is False
    # an empty export means "cleared", not "off": default-on knobs stay on
    monkeypatch.setenv("YTK_HEALTH", "")
    assert knobs.get_bool("YTK_HEALTH") is True
    assert knobs.get_float("YTK_SERVE_WATCH_S") == 5.0
    assert knobs.get_raw("YTK_OBS") is None


def test_lint_paths_relativizes_absolute_repo_paths(tmp_path):
    # path-scoped rules must fire when the caller passes absolute paths —
    # a violating file reached via /abs/path/to/repo/ytklearn_tpu/... must
    # still hit the library-scoped bare-print rule
    import pathlib

    repo = pathlib.Path(__file__).resolve().parent.parent
    target = repo / "ytklearn_tpu" / "_ytklint_abs_path_fixture.py"
    target.write_text("print('x')\n")
    try:
        found = lint_paths([str(target)])
    finally:
        target.unlink()
    assert [f.rule for f in found] == ["bare-print"]
    assert found[0].path == "ytklearn_tpu/_ytklint_abs_path_fixture.py"
    # ...while a file OUTSIDE the repo keeps its own path and stays out of
    # the library-scoped rule
    outside = tmp_path / "bare.py"
    outside.write_text("print('x')\n")
    assert lint_paths([str(outside)]) == []


def test_lint_paths_refuses_zero_file_runs(tmp_path):
    with pytest.raises(FileNotFoundError):
        lint_paths(["no_such_dir_anywhere"])
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError):
        lint_paths([str(empty)])


# ---------------------------------------------------------------------------
# the ytkflow interprocedural pass (tools/ytklint/flow.py)
# ---------------------------------------------------------------------------


def runs(sources, select=None):
    return lint_sources(
        {p: textwrap.dedent(s) for p, s in sources.items()}, select
    )


# -- unseamed-io -------------------------------------------------------------


@pytest.mark.parametrize(
    "body",
    [
        "with open(p) as f:\n            return f.read()",
        "os.replace(p, p + '.new')",
        "shutil.rmtree(p)",
        "subprocess.check_call(['ls', p])",
    ],
)
def test_unseamed_io_fails(body):
    src = f"""\
    import os
    import shutil
    import subprocess

    def f(p):
        {body}
    """
    assert "unseamed-io" in rules_hit(src)


def test_unseamed_io_module_level_read_is_flagged():
    src = """\
    import os

    CONF = open("defaults.hocon").read()
    """
    found = run(src)
    assert [f.rule for f in found] == ["unseamed-io"]
    assert "module level" in found[0].message


def test_unseamed_io_blessed_seams_and_exempt_calls_pass():
    # the fs seam file itself may do raw IO — it IS the seam
    seam = """\
    import os

    def commit(tmp, path):
        os.replace(tmp, path)
    """
    assert runs({"ytklearn_tpu/io/fs.py": seam}) == []
    # urllib.parse is string manipulation; gethostname is a local lookup
    clean = """\
    import socket
    import urllib.parse

    def f(url):
        q = urllib.parse.urlsplit(url).query
        return socket.gethostname(), urllib.parse.parse_qs(q)
    """
    assert run(clean) == []
    # scripts/ and tools/ are outside the seam contract
    raw = """\
    def f(p):
        return open(p).read()
    """
    assert runs({"scripts/adhoc.py": raw}) == []


def test_unseamed_io_reports_cross_module_reach():
    # the finding on the callee names a caller from another module, so
    # the reader sees how production code reaches the raw primitive
    found = runs({
        "ytklearn_tpu/aaa.py": """\
            from ytklearn_tpu.bbb import dump

            def save(doc, p):
                dump(doc, p)
            """,
        "ytklearn_tpu/bbb.py": """\
            def dump(doc, p):
                with open(p, "w") as f:
                    f.write(doc)
            """,
    })
    hits = [f for f in found if f.rule == "unseamed-io"]
    assert len(hits) == 1
    assert hits[0].path == "ytklearn_tpu/bbb.py"
    assert "reached from ytklearn_tpu.aaa.save" in hits[0].message


def test_unseamed_io_suppression():
    src = """\
    def f():
        # ytklint: allow(unseamed-io) reason=/proc read, fixture
        with open("/proc/self/status") as fh:
            return fh.read()
    """
    assert run(src) == []


# -- metric-name-drift -------------------------------------------------------


def test_metric_name_drift_orphan_consumer_fails():
    # a sentinel watching a name nobody emits is exactly the bug this
    # rule exists for — the consumer file is the finding site
    found = runs({
        "ytklearn_tpu/obs/health.py": """\
            def check(snap):
                return snap["counters"].get("nobody.emits_this", 0.0)
            """,
    })
    hits = [f for f in found if f.rule == "metric-name-drift"]
    assert len(hits) == 1
    assert "nobody.emits_this" in hits[0].message


def test_metric_name_drift_satisfied_by_producer_and_prefix():
    found = runs({
        "ytklearn_tpu/prod.py": """\
            from ytklearn_tpu.obs import inc, gauge

            def work(model):
                inc("serve.requests")
                gauge(f"serve.model.{model}.latency", 1.0)
            """,
        "scripts/obs_report.py": """\
            def render(snap):
                c = snap["counters"]
                return c.get("serve.requests"), c.get("serve.model.a.latency")
            """,
    })
    assert [f for f in found if f.rule == "metric-name-drift"] == []


def test_metric_name_drift_ignores_non_metric_literals():
    src = """\
    import logging

    log = logging.getLogger("ytklearn_tpu.serve.front")

    def render(paths):
        import os.path
        return os.path.join("bench_out", "higgs.train")
    """
    assert runs({"bench.py": src}) == []


def test_metric_name_drift_suppression():
    found = runs({
        "scripts/obs_report.py": """\
            def render(mb):
                c = mb.get("counters") or {}
                # ytklint: allow(metric-name-drift) reason=suffix keys, fixture
                return c.get("cache.hit", 0.0), c.get("cache.miss", 0.0)
            """,
    })
    assert [f for f in found if f.rule == "metric-name-drift"] == []


# -- deep-blocking-under-lock ------------------------------------------------


# the r14 respawn-bug shape, planted through a module boundary: the
# monitor holds its lock across a call into worker.py, and the callee
# blocks on proc.wait() — invisible to the 1-level per-module pass
_FRONT_SRC = """\
    import threading

    from ytklearn_tpu.workerx import drain_replica

    class Front:
        def __init__(self):
            self._lock = threading.Lock()
            self.replicas = {}

        def restart(self, rid):
            with self._lock:
                h = self.replicas.pop(rid)
                drain_replica(h)
    """

_WORKER_SRC = """\
    import subprocess

    def drain_replica(h):
        h.proc.terminate()
        # ytklint: allow(unseamed-io) reason=fixture
        subprocess.run(["kill", str(h.pid)], check=True)
    """


def test_deep_blocking_under_lock_cross_module_plant():
    found = runs({
        "ytklearn_tpu/frontx.py": _FRONT_SRC,
        "ytklearn_tpu/workerx.py": _WORKER_SRC,
    })
    hits = [f for f in found if f.rule == "deep-blocking-under-lock"]
    assert len(hits) == 1
    assert hits[0].path == "ytklearn_tpu/frontx.py"
    # the finding prints the resolved chain and the terminal primitive
    assert ("ytklearn_tpu.frontx.Front.restart -> "
            "ytklearn_tpu.workerx.drain_replica") in hits[0].message
    assert "ytklearn_tpu/workerx.py" in hits[0].message


def test_deep_blocking_outside_lock_passes():
    src = _FRONT_SRC.replace(
        "with self._lock:\n                h = self.replicas.pop(rid)\n"
        "                drain_replica(h)",
        "h = self.replicas.pop(rid)\n            drain_replica(h)")
    found = runs({
        "ytklearn_tpu/frontx.py": src,
        "ytklearn_tpu/workerx.py": _WORKER_SRC,
    })
    assert [f for f in found if f.rule == "deep-blocking-under-lock"] == []


def test_deep_blocking_same_module_one_hop_is_not_duplicated():
    # a 1-level same-module chain is blocking-call-under-lock's finding;
    # the deep rule must not double-report it
    src = """\
    import subprocess, threading

    _lock = threading.Lock()

    def stop(h):
        # ytklint: allow(unseamed-io) reason=fixture
        subprocess.run(["kill", str(h.pid)], check=True)

    def restart(h):
        with _lock:
            stop(h)
    """
    found = run(src)
    assert "blocking-call-under-lock" in {f.rule for f in found}
    assert "deep-blocking-under-lock" not in {f.rule for f in found}


def test_deep_blocking_suppression_accepts_issue_alias():
    src = _FRONT_SRC.replace(
        "drain_replica(h)",
        "# ytklint: allow(cross-module-blocking) reason=fixture\n"
        "                drain_replica(h)")
    found = runs({
        "ytklearn_tpu/frontx.py": src,
        "ytklearn_tpu/workerx.py": _WORKER_SRC,
    })
    assert [f for f in found if f.rule == "deep-blocking-under-lock"] == []


# -- deep-host-sync-in-jit ---------------------------------------------------


def test_deep_host_sync_cross_module_plant():
    found = runs({
        "ytklearn_tpu/jitted.py": """\
            import jax

            from ytklearn_tpu.helperx import to_scalar

            @jax.jit
            def step(x):
                return to_scalar(x)
            """,
        "ytklearn_tpu/helperx.py": """\
            def to_scalar(x):
                return x.item()
            """,
    })
    hits = [f for f in found if f.rule == "deep-host-sync-in-jit"]
    assert len(hits) == 1
    assert hits[0].path == "ytklearn_tpu/jitted.py"
    assert ("ytklearn_tpu.helperx.to_scalar" in hits[0].message
            and ".item()" in hits[0].message)


def test_deep_host_sync_clean_helper_passes():
    found = runs({
        "ytklearn_tpu/jitted.py": """\
            import jax

            from ytklearn_tpu.helperx import double

            @jax.jit
            def step(x):
                return double(x)
            """,
        "ytklearn_tpu/helperx.py": """\
            def double(x):
                return x * 2
            """,
    })
    assert [f for f in found if f.rule == "deep-host-sync-in-jit"] == []


# -- silent-thread-death -----------------------------------------------------


def test_silent_thread_death_fails():
    src = """\
    import threading

    def worker(q):
        while True:
            item = q.get()
            item.process()

    def start(q):
        t = threading.Thread(target=worker, args=(q,), daemon=True)
        t.start()
        return t
    """
    found = run(src)
    hits = [f for f in found if f.rule == "silent-thread-death"]
    assert len(hits) == 1
    assert "worker" in hits[0].message and "thread_guard" in hits[0].message


def test_silent_thread_death_guarded_entries_pass():
    # decorator form
    src = """\
    import threading

    from ytklearn_tpu.obs.recorder import thread_guard

    @thread_guard
    def worker(q):
        while True:
            q.get().process()

    def start(q):
        t = threading.Thread(target=worker, args=(q,), daemon=True)
        t.start()
    """
    assert run(src) == []
    # handler form: a broad except that logs covers the loop body
    src2 = """\
    import threading
    import logging

    log = logging.getLogger(__name__)

    def worker(q):
        try:
            while True:
                q.get().process()
        except Exception:
            log.exception("worker died")

    def start(q):
        t = threading.Thread(target=worker, args=(q,), daemon=True)
        t.start()
    """
    assert run(src2) == []


def test_silent_thread_death_risky_call_inside_handler_still_fails():
    # the except body itself can raise — only the try BODY is covered
    src = """\
    import threading
    import logging

    log = logging.getLogger(__name__)

    def worker(q):
        try:
            while True:
                q.get().process()
        except Exception:
            q.rollback()

    def start(q):
        t = threading.Thread(target=worker, args=(q,), daemon=True)
        t.start()
    """
    found = run(src)
    assert "silent-thread-death" in {f.rule for f in found}


def test_silent_thread_death_suppression():
    src = """\
    import threading

    def worker(q):
        q.get().process()

    def start(q):
        # ytklint: allow(silent-thread-death) reason=fixture
        t = threading.Thread(target=worker, args=(q,), daemon=True)
        t.start()
    """
    assert run(src) == []


# -- stale-suppression audit covers the flow rules ---------------------------


def test_unused_flow_suppression_is_flagged():
    # a suppression for a graph rule that no longer fires is inventory
    # drift, same as the per-file rules (and aliases resolve first)
    src = """\
    def f(p):
        # ytklint: allow(unseamed-io) reason=stale, nothing raw below
        return p.upper()
    """
    found = run(src)
    assert [f.rule for f in found] == ["unused-suppression"]
    src2 = """\
    def f(h):
        # ytklint: allow(cross-module-blocking) reason=stale alias form
        return h.name
    """
    found2 = run(src2)
    assert [f.rule for f in found2] == ["unused-suppression"]
    assert "deep-blocking-under-lock" in found2[0].message


# -- timing artifact + deflake budget ----------------------------------------


def test_timing_block_in_report_and_json():
    report = lint_paths_report(["bench.py"])
    t = report["timing"]
    assert t["parse_seconds"] >= 0.0
    assert t["graph_seconds"] >= 0.0
    assert t["total_seconds"] >= t["parse_seconds"]
    assert set(t["rule_seconds"]) <= set(RULES)
    # the deflake verdict: full runs carry the baseline comparison
    assert t["budget_ratio"] == 1.5
    assert isinstance(t["within_budget"], bool)
    doc = report_json(report)
    assert doc["schema_version"] == 2
    assert doc["timing"] == t
    # a selected run cannot claim a budget verdict (the baseline rules
    # did not all run)
    sel = lint_paths_report(["bench.py"], ["bare-print"])
    assert "within_budget" not in sel["timing"]


# -- metric name map doc sync ------------------------------------------------


def test_metric_doc_sync_both_ways(tmp_path, monkeypatch):
    import pathlib

    from tools.ytklint import flow

    monkeypatch.chdir(pathlib.Path(__file__).resolve().parent.parent)
    census = flow.census_for_repo()
    # the checked-in doc is in sync (the CI gate)
    assert flow.check_doc_sync(
        pathlib.Path("docs/observability.md"), census) == []
    # a drifted copy fails loudly, and regen repairs it
    doc = tmp_path / "obs.md"
    doc.write_text(
        f"# obs\n\n{flow.DOC_BEGIN}\nstale\n{flow.DOC_END}\n",
        encoding="utf-8")
    problems = flow.check_doc_sync(doc, census)
    assert problems and "stale" in problems[0]
    flow.regen_doc(doc, census)
    assert flow.check_doc_sync(doc, census) == []
    # missing markers are their own failure, not a silent pass
    bare = tmp_path / "bare.md"
    bare.write_text("# no markers\n", encoding="utf-8")
    assert any("markers" in p for p in flow.check_doc_sync(bare, census))


def test_the_census_reads_the_benchmarks_readers(monkeypatch):
    """The benchmark reads the program's names from files that are not
    linted: the census takes their references, and every one of them names
    something the program makes (a config key or a file name in a path
    call is no metric)."""
    import pathlib

    from tools.ytklint import flow

    monkeypatch.chdir(pathlib.Path(__file__).resolve().parent.parent)
    census = flow.census_for_repo()
    bench = {p: refs for p, refs in census.consumer_refs.items()
             if p.startswith("perfbench/")}
    assert {"perfbench/pb/trials.py", "perfbench/metrics/hist_scope_share.py",
            "perfbench/families/gbst.py"} <= set(bench)
    assert [(p, line, lit) for p, refs in bench.items() for line, lit in refs
            if not census._satisfied(lit)] == []
    # scopes are produced names too: the scope shares' readers consume them
    assert "scope" in census.exact["gbdt.route"]["kinds"]
    assert census._consumers_of("gbdt.route", False) == [
        "perfbench/metrics/route_scope_share.py"]
    assert flow.is_consumer("perfbench/metrics/x.py")
    assert not flow.is_consumer("perfbench/tools/x.py")


# -- --changed-only ----------------------------------------------------------


def test_changed_files_lists_repo_paths_and_rejects_bad_refs():
    from tools.ytklint.core import changed_files

    got = changed_files("HEAD")
    assert isinstance(got, set)
    assert all(isinstance(p, str) and not p.startswith("/") for p in got)
    with pytest.raises(RuntimeError):
        changed_files("no-such-ref-anywhere")


def test_changed_only_filters_findings_but_keeps_graph(capsys, tmp_path):
    # a finding in an UNchanged file is filtered out; the whole-repo
    # graph was still built (the summary line says so)
    from tools.ytklint.core import main

    rc = main(["--changed-only", "--base", "HEAD", "bench.py"])
    err = capsys.readouterr().err
    assert "whole-repo graph still built" in err
    assert rc in (0, 1)
