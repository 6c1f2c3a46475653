"""Run-health layer tests (ISSUE 3 acceptance): flight-recorder ring +
dump round-trip (Perfetto-valid), NaN/divergence/ingest/tree sentinels,
the strict-mode HealthError escalation carrying a flight dump whose ring
holds the failing span, the disabled-path no-op contract extended to
health.py/recorder.py, heartbeat derived rates, snapshot thread-safety
under concurrent inc(), memory/compile telemetry, and the
obs_report/check_bench_regress scripts."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ytklearn_tpu import obs
from ytklearn_tpu.obs import HealthError, health, recorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from test_obs import _validate_chrome_trace  # noqa: E402


@pytest.fixture
def obs_on():
    obs.reset()
    obs.configure(enabled=True)
    yield obs
    obs.configure(enabled=False)
    obs.reset()


@pytest.fixture
def health_env(tmp_path):
    """Health on (non-strict), recorder pointed at tmp; full teardown."""
    health.configure_health(on=True, strict=False)
    recorder.uninstall()
    recorder._state.dir = str(tmp_path)
    yield tmp_path
    recorder.uninstall()
    recorder._state.dir = None
    health.configure_health(on=True, strict=None, ingest_tol=0.01)


# ---------------------------------------------------------------------------
# disabled-path contract (the tier-1 overhead budget, extended to the new
# modules: one attribute load + return, no registry traffic, no escalation)
# ---------------------------------------------------------------------------


def test_health_disabled_is_noop(health_env):
    obs.configure(enabled=False)
    obs.reset()
    health.configure_health(on=False, strict=True)  # strict must NOT win
    assert health.check_loss("x", float("nan")) is True
    assert health.check_ingest("x", errors=500, rows=500) is True
    assert health.check_tree("x", 1, [float("nan")]) is True
    g = health.ProgressGuard("x", window=1)
    assert g.update(1.0) is True and g.update(1.0) is True
    s = health.RetraceSentinel("x")
    s.arm()
    assert s.baseline is None and s.check() is True
    assert obs.snapshot() == {"counters": {}, "gauges": {}}
    assert obs.REGISTRY.events == []


def test_recorder_auto_install_noop_when_obs_off():
    obs.configure(enabled=False)
    recorder.uninstall()
    recorder.auto_install()
    assert not recorder.installed()
    assert obs.REGISTRY.ring is None


def test_record_memory_noop_when_obs_off():
    obs.configure(enabled=False)
    obs.reset()
    health.record_memory("unit")
    assert obs.snapshot()["gauges"] == {}


# ---------------------------------------------------------------------------
# sentinels
# ---------------------------------------------------------------------------


def test_check_loss_nan_fires_counter_and_event(obs_on, health_env):
    assert health.check_loss("unit.site", float("inf"), it=3) is False
    snap = obs.snapshot()
    assert snap["counters"]["health.nan"] == 1.0
    assert snap["counters"]["health.nan.unit.site"] == 1.0
    evs = [e for e in obs.REGISTRY.events if e["name"] == "health.nan"]
    assert evs and evs[0]["args"]["site"] == "unit.site"
    assert evs[0]["args"]["it"] == 3
    assert health.check_loss("unit.site", 0.25) is True
    assert obs.snapshot()["counters"]["health.nan"] == 1.0  # healthy: no inc


def test_progress_guard_divergence(obs_on, health_env):
    g = health.ProgressGuard("unit.guard", window=3)
    assert g.update(10.0) is True  # improvement
    assert g.update(9.0) is True
    for _ in range(2):
        assert g.update(9.0) is True  # stalling, under window
    assert g.update(9.0) is False  # window hit -> fires
    snap = obs.snapshot()
    assert snap["counters"]["health.divergence"] == 1.0
    assert snap["counters"]["health.divergence.unit.guard"] == 1.0
    assert g.update(9.0) is True  # re-armed, counts from zero again


def test_ingest_error_rate_sentinel(obs_on, health_env):
    # under the min-lines floor: never fires
    assert health.check_ingest("unit.ingest", errors=10, rows=20) is True
    # 5% > the 1% default over enough lines: fires
    assert health.check_ingest("unit.ingest", errors=10, rows=190) is False
    assert obs.snapshot()["counters"]["health.ingest_errors"] == 1.0
    # within tolerance: clean
    assert health.check_ingest("unit.ingest", errors=1, rows=990) is True


def test_ingest_sentinel_fires_through_reader(obs_on, health_env):
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.io.reader import DataIngest

    lines = []
    for i in range(150):
        lines.append(f"1###{i % 2}###f0:1.0,f1:{i}.0")
    lines += ["garbage line"] * 12  # ~7.4% error rate, under the abs cap
    DataIngest(CommonParams()).parse_rows(lines, max_error_tol=100, is_train=True)
    snap = obs.snapshot()
    assert snap["counters"]["health.ingest_errors.ingest.parse"] == 1.0
    assert snap["counters"]["ingest.error_lines"] == 12.0


def test_ingest_sentinel_rate_ignores_y_sampling(obs_on, health_env):
    """The rate denominator counts parse-valid lines BEFORE y_sampling
    drops: keeping 5% of the majority class must not turn a 0.5% error
    rate into a fired alarm."""
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.io.reader import DataIngest

    p = CommonParams()
    p.data.y_sampling = [("0", 0.05)]  # drop ~95% of label-0 rows
    lines = [f"1###0###f0:{i}.0" for i in range(400)]
    lines.insert(100, "garbage")
    lines.insert(300, "garbage")  # 2/402 = 0.5% < the 1% tolerance
    rows = DataIngest(p).parse_rows(lines, max_error_tol=100, is_train=True)
    assert len(rows) < 100  # subsampling really dropped most rows
    assert "health.ingest_errors" not in obs.snapshot()["counters"]


def test_check_tree_empty_and_nan_gain(obs_on, health_env):
    assert health.check_tree("unit.tree", 1, [0.0], tree=4) is False
    assert health.check_tree("unit.tree", 5, [1.0, float("nan")], tree=5) is False
    assert health.check_tree("unit.tree", 5, [1.0, 2.0], tree=6) is True
    snap = obs.snapshot()
    assert snap["counters"]["health.empty_tree"] == 1.0
    assert snap["counters"]["health.nan.unit.tree"] == 1.0


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_is_bounded(obs_on, health_env):
    recorder.install(ring_n=8)
    for i in range(30):
        obs.event("tick", i=i)
    assert len(obs.REGISTRY.ring) == 8
    # the ring keeps the newest events; the full list keeps everything
    assert obs.REGISTRY.ring[-1]["args"]["i"] == 29
    assert obs.REGISTRY.ring[0]["args"]["i"] == 22
    assert len(obs.REGISTRY.events) == 30


def test_flight_dump_roundtrip_and_perfetto_valid(obs_on, health_env):
    recorder.install(ring_n=64)
    recorder.set_config_fingerprint({"model": "linear", "l2": 0.1})
    with obs.span("phase.x", k=1):
        pass
    obs.inc("rows", 5)
    obs.gauge("speed", 2.5)
    path = recorder.dump(reason="unit-test")
    assert path and os.path.exists(path)
    # the dump IS a chrome trace: the shared validator must accept it
    events = _validate_chrome_trace(path)
    assert any(e["name"] == "phase.x" and e["ph"] == "X" for e in events)
    # ...with the flight block carrying ring + snapshot + runtime
    fl = recorder.load_flight(path)
    assert fl["reason"] == "unit-test"
    assert fl["schema_version"] >= 1
    assert fl["snapshot"]["counters"]["rows"] == 5.0
    assert fl["snapshot"]["gauges"]["speed"] == 2.5
    assert any(e["name"] == "phase.x" for e in fl["ring"])
    assert fl["ring_capacity"] == 64
    assert fl["config_fingerprint"]["sha1"]
    assert fl["runtime"]["pid"] == os.getpid()
    assert recorder.last_dump_path() == path


def test_flight_dump_excepthook(obs_on, health_env):
    recorder.install(ring_n=16)
    obs.event("before-crash")
    try:
        sys.excepthook(ValueError, ValueError("boom"), None)
    finally:
        pass
    path = recorder.last_dump_path()
    assert path and os.path.exists(path)
    fl = recorder.load_flight(path)
    assert fl["reason"] == "excepthook"
    assert "boom" in fl["exception"]


# ---------------------------------------------------------------------------
# the acceptance run: injected NaN loss in L-BFGS
# ---------------------------------------------------------------------------


def _nan_lbfgs(max_iter=3):
    import jax.numpy as jnp

    from ytklearn_tpu.optimize import LBFGSConfig, minimize_lbfgs

    def bad_loss(w, x):  # non-finite from the first evaluation on
        return jnp.sum(w * x) * jnp.float32("nan")

    return minimize_lbfgs(
        bad_loss,
        np.ones(4, np.float32),
        LBFGSConfig(max_iter=max_iter),
        batch=(np.ones(4, np.float32),),
    )


def test_lbfgs_nan_sentinel_nonstrict(obs_on, health_env):
    res = _nan_lbfgs()
    assert res.status == "nan_loss"
    assert res.n_iter == 1  # detected at the first sync, not after max_iter
    snap = obs.snapshot()
    assert snap["counters"]["health.nan"] == 1.0
    assert snap["counters"]["health.nan.lbfgs.loss"] == 1.0
    evs = [e for e in obs.REGISTRY.events if e["name"] == "health.nan"]
    assert evs and evs[0]["args"]["site"] == "lbfgs.loss"


def test_lbfgs_nan_strict_raises_with_flight_dump(obs_on, health_env):
    health.configure_health(strict=True)
    with pytest.raises(HealthError) as ei:
        _nan_lbfgs()
    err = ei.value
    # the message names the dump; the file exists and parses
    assert err.dump_path and err.dump_path in str(err)
    assert os.path.exists(err.dump_path)
    events = _validate_chrome_trace(err.dump_path)
    fl = recorder.load_flight(err.dump_path)
    # the ring holds the failing iteration's span (check runs after the
    # span closes, so the evidence precedes the escalation)
    ring_names = [e["name"] for e in fl["ring"]]
    assert "lbfgs.iteration" in ring_names
    assert any(e["name"] == "lbfgs.iteration" for e in events)
    assert fl["reason"] == "health.nan:lbfgs.loss"
    assert fl["snapshot"]["counters"]["health.nan"] == 1.0


def test_lbfgs_nan_with_obs_disabled_no_registry_traffic(health_env):
    """Detection still works with obs off (the run dies loudly, not with
    garbage), while the obs registry sees zero traffic — the no-overhead
    contract for the disabled collection path."""
    obs.configure(enabled=False)
    obs.reset()
    res = _nan_lbfgs()
    assert res.status == "nan_loss"
    assert obs.snapshot() == {"counters": {}, "gauges": {}}
    assert obs.REGISTRY.events == []


def test_lbfgs_health_off_keeps_legacy_behavior(obs_on, health_env):
    """YTK_HEALTH=0: exactly the pre-r8 control flow — the NaN surfaces
    as the line search failing to find a step (-3), never as nan_loss."""
    health.configure_health(on=False)
    res = _nan_lbfgs(max_iter=3)
    assert res.status == "line_search_failed(-3)"
    assert "health.nan" not in obs.snapshot()["counters"]


# ---------------------------------------------------------------------------
# telemetry: memory gauges + compile counters + retrace sentinel
# ---------------------------------------------------------------------------


def test_record_memory_gauges(obs_on, health_env):
    health.record_memory("unit")
    g = obs.snapshot()["gauges"]
    # host RSS is always available; device stats only on TPU/GPU backends
    assert g["mem.unit.host_rss_peak_bytes"] > 0
    assert g["mem.host_rss_peak_bytes"] == g["mem.unit.host_rss_peak_bytes"]


def test_compile_counters_and_retrace_sentinel(obs_on, health_env):
    import jax
    import jax.numpy as jnp

    health.install_trace_counters()
    # a fresh jit + a fresh shape forces a real XLA compile
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.arange(7, dtype=jnp.float32)).block_until_ready()
    c = obs.snapshot()["counters"]
    assert c.get("compile.traces.backend_compile", 0) >= 1
    assert c.get("compile.traces.backend_compile_secs", 0) > 0

    sentinel = health.RetraceSentinel("unit.loop")
    sentinel.arm()
    assert sentinel.check() is True  # no compiles since arm
    f(jnp.arange(11, dtype=jnp.float32)).block_until_ready()  # retrace!
    assert sentinel.check(round=5) is False
    c = obs.snapshot()["counters"]
    assert c["compile.retraces.unexpected"] >= 1.0
    assert c["health.retrace"] == 1.0
    assert sentinel.check() is True  # re-baselined


# ---------------------------------------------------------------------------
# satellites: heartbeat rates + snapshot thread-safety
# ---------------------------------------------------------------------------


def test_heartbeat_derived_rates(obs_on):
    hb = obs.heartbeat("rates", every_s=1000.0)
    assert hb.beat(rows=100) is True  # first beat: totals only, no rate
    first = [e for e in obs.REGISTRY.events if e["ph"] == "i"][-1]
    assert "rows_per_s" not in first.get("args", {})
    hb._prev_t -= 2.0  # pretend the last beat was 2 s ago
    hb._last = 0.0
    assert hb.beat(rows=300) is True
    ev = [e for e in obs.REGISTRY.events if e["ph"] == "i"][-1]
    # 200 rows over ~2 s
    assert ev["args"]["rows_per_s"] == pytest.approx(100.0, rel=0.1)
    assert "rows=300" in ev["args"]["msg"]
    assert "rows_per_s=" in ev["args"]["msg"]


def test_heartbeat_rate_skips_non_monotone(obs_on):
    hb = obs.heartbeat("rates2", every_s=0.0)
    hb.beat(rows=100)
    hb._prev_t -= 1.0
    hb.beat(rows=50)  # counter went down: re-baseline, no negative rate
    ev = [e for e in obs.REGISTRY.events if e["ph"] == "i"][-1]
    assert "rows_per_s" not in ev["args"]


def test_snapshot_and_exporters_threadsafe(obs_on, tmp_path):
    """Concurrent inc() from ingest-style threads vs snapshot()/exporters:
    no exception, no lost increments (copy-under-lock is pinned here)."""
    N_THREADS, N_INC = 4, 4000
    stop = threading.Event()
    errors = []

    def hammer():
        try:
            for i in range(N_INC):
                obs.inc("ts.counter")
                if i % 500 == 0:
                    obs.event("ts.event", i=i)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                obs.snapshot()
                obs.chrome_trace_events()
                obs.export_jsonl(str(tmp_path / "ts.jsonl"))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(N_THREADS)]
    r = threading.Thread(target=reader)
    r.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    r.join()
    assert not errors
    assert obs.snapshot()["counters"]["ts.counter"] == N_THREADS * N_INC


# ---------------------------------------------------------------------------
# scripts: obs_report + check_bench_regress
# ---------------------------------------------------------------------------


def _run_script(name, *args, cwd=None):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", name), *args],
        capture_output=True,
        text=True,
        cwd=cwd or REPO,
    )


def test_obs_report_on_flight_dump(obs_on, health_env):
    recorder.install(ring_n=32)
    with obs.span("gbdt.round", round=1):
        pass
    obs.inc("health.nan")
    obs.inc("gbdt.efb.downgrade")
    obs.gauge("mem.unit.host_rss_peak_bytes", 1 << 30)
    path = recorder.dump(reason="report-test")
    r = _run_script("obs_report.py", path)
    assert r.returncode == 0, r.stderr
    out = r.stdout
    assert "run-health report" in out and "(flight)" in out
    assert "health.nan" in out
    assert "gbdt.efb.downgrade" in out
    assert "1.0 GiB" in out
    assert "gbdt.round" in out


def test_obs_report_on_jsonl_and_bench(obs_on, tmp_path):
    with obs.span("train.round"):
        pass
    obs.inc("lbfgs.iterations", 7)
    p = str(tmp_path / "ev.jsonl")
    obs.export_jsonl(p)
    r = _run_script("obs_report.py", p)
    assert r.returncode == 0, r.stderr
    assert "(jsonl)" in r.stdout and "train.round" in r.stdout
    _bench_artifact(tmp_path, 5, 1.0)
    r = _run_script("obs_report.py", str(tmp_path / "BENCH_r05.json"))
    assert r.returncode == 0, r.stderr
    assert "(bench)" in r.stdout and "trees_per_sec" in r.stdout


def _bench_artifact(tmp_path, rnd, value, downgrades=0, health_events=0):
    rec = {
        "n": rnd,
        "cmd": "python bench.py",
        "rc": 0,
        "parsed": {
            "schema_version": 3,
            "metric": "gbdt_trees_per_sec",
            "value": value,
            "unit": "trees/s",
            "downgrades": downgrades,
            "health_events": health_events,
            "obs": {"counters": {}, "gauges": {}},
        },
    }
    (tmp_path / f"BENCH_r{rnd:02d}.json").write_text(json.dumps(rec))


def test_check_bench_regress_skips_fresh_clone(tmp_path):
    r = _run_script("check_bench_regress.py", "--dir", str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SKIP" in r.stdout
    _bench_artifact(tmp_path, 1, 1.0)
    r = _run_script("check_bench_regress.py", "--dir", str(tmp_path))
    assert r.returncode == 0 and "SKIP" in r.stdout


def test_check_bench_regress_pass_and_fail(tmp_path):
    _bench_artifact(tmp_path, 1, 1.0)
    _bench_artifact(tmp_path, 2, 0.95)  # within the 15% band
    r = _run_script("check_bench_regress.py", "--dir", str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout

    _bench_artifact(tmp_path, 3, 0.5)  # throughput cliff
    r = _run_script("check_bench_regress.py", "--dir", str(tmp_path))
    assert r.returncode == 1
    assert "throughput regressed" in r.stderr

    _bench_artifact(tmp_path, 4, 1.0, downgrades=2)  # fast but downgraded
    r = _run_script("check_bench_regress.py", "--dir", str(tmp_path))
    assert r.returncode == 1
    assert "downgrades increased" in r.stderr

    _bench_artifact(tmp_path, 5, 1.0, downgrades=2, health_events=3)
    r = _run_script("check_bench_regress.py", "--dir", str(tmp_path))
    assert r.returncode == 1
    assert "health sentinel hits increased" in r.stderr

    _bench_artifact(tmp_path, 6, 1.05, downgrades=2, health_events=3)
    r = _run_script("check_bench_regress.py", "--dir", str(tmp_path))
    assert r.returncode == 0, r.stdout + r.stderr  # steady state again


def test_check_bench_regress_on_real_repo_artifacts():
    """The gate must pass on whatever artifacts are checked in (today too
    few BENCH records to compare: it skips cleanly)."""
    r = _run_script("check_bench_regress.py")
    assert r.returncode == 0, r.stdout + r.stderr


def test_read_bench_record_unwraps_driver_shape(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from ablate_engine import read_bench_record

    wrapped = {
        "n": 9,
        "cmd": "python bench.py",
        "rc": 0,
        "parsed": {
            "schema_version": 3,
            "metric": "m",
            "value": 2.5,
            "health_events": 4,
        },
    }
    p = tmp_path / "BENCH_r09.json"
    p.write_text(json.dumps(wrapped))
    rec = read_bench_record(str(p))
    assert rec["trees_per_sec"] == 2.5
    assert rec["metric"] == "m"
    assert rec["health_events"] == 4
    # a failed round (parsed: null) normalizes to empty, not a crash
    p2 = tmp_path / "BENCH_r10.json"
    p2.write_text(json.dumps({"n": 10, "cmd": "c", "rc": 1, "parsed": None}))
    rec2 = read_bench_record(str(p2))
    assert rec2["trees_per_sec"] is None and rec2["health_events"] == 0


def test_crash_flags_and_dump_path_are_lockless(tmp_path, monkeypatch):
    """Regression (r15 concurrency pass): the crash-path state
    (`_state.abnormal`, `_state.last_dump_path`, the dump itself) is
    deliberately lockless — a signal handler or excepthook that took
    `_install_lock` would deadlock the moment the interrupted thread
    held it. uninstall() used to reset those flags INSIDE the install
    lock, which made them look lock-guarded when the lock never
    protected them (ytklint `unguarded-shared-write`). Pin: a dump fired
    while another thread holds `_install_lock` completes immediately."""
    monkeypatch.setenv("YTK_FLIGHT_DIR", str(tmp_path))
    obs.configure(enabled=True)
    try:
        acquired = threading.Event()
        release = threading.Event()

        def holder():
            with recorder._install_lock:
                acquired.set()
                release.wait(timeout=30.0)

        t = threading.Thread(target=holder, daemon=True)
        t.start()
        assert acquired.wait(timeout=10.0)
        done = []

        def dumper():
            done.append(recorder.dump("lockless-pin"))

        d = threading.Thread(target=dumper, daemon=True)
        try:
            d.start()
            d.join(timeout=5.0)
            assert done and done[0], (
                "dump() blocked on _install_lock — the crash path must "
                "never take it"
            )
            assert os.path.exists(done[0])
            assert recorder.last_dump_path() == done[0]
        finally:
            release.set()
            t.join(timeout=10.0)
        # uninstall resets the flags without needing the lock either
        recorder.uninstall()
        assert recorder.last_dump_path() is None
        assert not recorder._state.abnormal
    finally:
        obs.configure(enabled=False)
        obs.reset()
