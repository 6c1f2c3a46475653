"""ytkprof plane tests (ISSUE 20 acceptance): the disabled path stays the
r7 cached no-op (zero new per-call work with YTK_PROF unset), the compile
ledger names the retrace culprit on a planted shape change, the memory
watermark rings stay bounded and attribute peaks to the enclosing phase,
the capture parser buckets device time under named annotations, flight
dumps carry the prof block, and obs_report renders the checked-in PROF
artifact."""

import json
import os
import subprocess
import sys
import time

import pytest

from ytklearn_tpu import obs
from ytklearn_tpu.obs import core as obs_core, health, profiler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture
def prof_on():
    """Armed profiler (which arms obs + annotations underneath) with the
    background sampler disabled (mem_interval=0) so every tick in a test
    is an explicit, deterministic sample_once() call."""
    obs.reset()
    profiler.reset_profiler()
    profiler.configure_profiler(on=True, mem_interval=0.0)
    yield profiler
    profiler.configure_profiler(on=False, capture_dir=None)
    profiler.reset_profiler()
    obs_core.configure(enabled=False)
    obs.reset()


# ---------------------------------------------------------------------------
# disabled-path contract
# ---------------------------------------------------------------------------


def test_disabled_path_is_cached_noop():
    """The acceptance pin: with YTK_PROF unset and obs off, phase() is
    the SAME cached no-op span the r7 contract guarantees, and
    LEDGER.program() is one cached no-op context — no allocation, no
    registry writes, no accounting."""
    obs.configure(enabled=False)
    obs.reset()
    profiler.reset_profiler()
    assert not profiler.enabled()
    p1 = profiler.phase("a", x=1)
    p2 = profiler.phase("b", settle=object())
    assert p1 is p2 is obs.NOOP_SPAN
    boom = lambda: 1 / 0  # noqa: E731 — must never be called when off
    c1 = profiler.LEDGER.program("x", sig_fn=boom)
    c2 = profiler.LEDGER.program("y")
    assert c1 is c2 is profiler.NOOP_PHASE
    with profiler.phase("c"), profiler.LEDGER.program("z", sig_fn=boom):
        pass
    assert profiler.phases_snapshot() == {}
    assert profiler.LEDGER.snapshot()["compiles"] == 0
    assert obs.snapshot() == {"counters": {}, "gauges": {}}


def test_phase_delegates_to_span_when_only_obs_on():
    """Call sites that moved from obs_span() to phase() must keep their
    spans when obs is on but the profiler is not."""
    obs.reset()
    obs.configure(enabled=True)
    try:
        with profiler.phase("only.obs"):
            time.sleep(0.002)
        evs = [e for e in obs.REGISTRY.events if e["name"] == "only.obs"]
        assert len(evs) == 1 and evs[0]["dur"] > 0
        assert profiler.phases_snapshot() == {}  # accountant stayed off
    finally:
        obs.configure(enabled=False)
        obs.reset()


# ---------------------------------------------------------------------------
# phase accounting
# ---------------------------------------------------------------------------


def test_phase_accounting_depth_and_coverage(prof_on):
    with profiler.phase("outer"):
        time.sleep(0.02)
        with profiler.phase("inner"):
            time.sleep(0.01)
    with profiler.phase("outer"):
        pass
    snap = profiler.phases_snapshot()
    assert snap["outer"]["depth"] == 0 and snap["outer"]["count"] == 2
    assert snap["inner"]["depth"] == 1
    assert snap["outer"]["wall_s"] >= snap["inner"]["wall_s"] > 0
    # coverage counts depth-0 phases only — nested time is not double-counted
    assert profiler.coverage(snap["outer"]["wall_s"]) == pytest.approx(
        1.0
    )


# ---------------------------------------------------------------------------
# abstract signatures + the compile ledger
# ---------------------------------------------------------------------------


def test_abstract_signature_and_diff():
    import numpy as np

    a = np.zeros((4, 8), np.float32)
    b = np.zeros((5, 8), np.float32)
    sig_a = profiler.abstract_signature(a, {"w": a})
    assert ["args[0]", "float32[4,8]"] in sig_a
    assert any(p.startswith("args[1]") and "'w'" in p for p, _ in sig_a)
    diff = profiler.signature_diff(
        profiler.abstract_signature(a), profiler.abstract_signature(b)
    )
    assert diff == ["args[0]: float32[4,8] -> float32[5,8]"]
    assert profiler.signature_diff(None, sig_a) == []


def test_planted_shape_change_names_culprit(prof_on):
    """The tentpole retrace story: warm a jit program, arm the sentinel,
    recompile it with a changed leading dim — health.retrace must carry
    the signature diff AND the ledger culprit naming the program."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2.0).sum())
    x1 = jnp.ones((4, 8), jnp.float32)
    with profiler.LEDGER.program(
        "toy.step", sig_fn=lambda: profiler.abstract_signature(x1)
    ):
        f(x1).block_until_ready()
    led = profiler.LEDGER.snapshot()
    assert led["compiles"] >= 1 and "toy.step" in led["by_program"]
    assert led["total_ms"] > 0

    sent = health.RetraceSentinel("toy")
    sent.arm(sig=profiler.abstract_signature(x1))
    assert sent.check(sig=profiler.abstract_signature(x1))  # steady state

    x2 = jnp.ones((5, 8), jnp.float32)
    with profiler.LEDGER.program(
        "toy.step", sig_fn=lambda: profiler.abstract_signature(x2)
    ):
        f(x2).block_until_ready()
    assert not sent.check(sig=profiler.abstract_signature(x2), round=7)

    evs = [e for e in obs.REGISTRY.events if e["name"] == "health.retrace"]
    assert len(evs) == 1
    args = evs[0]["args"]
    assert "args[0]: float32[4,8] -> float32[5,8]" in args["changed"]
    culprits = args["culprits"]
    assert any(c["program"] == "toy.step" for c in culprits)
    hit = next(c for c in culprits if c["program"] == "toy.step")
    assert hit["ms"] > 0
    assert "args[0]: float32[4,8] -> float32[5,8]" in hit.get("changed", [])
    # the ledger's own retrace event fired too, naming the same program
    assert any(
        e["name"] == "compile.ledger.retrace"
        and e["args"]["program"] == "toy.step"
        for e in obs.REGISTRY.events
    )


def test_ledger_ring_is_bounded(prof_on):
    for i in range(40):
        profiler.LEDGER.on_compile(0.001)
    assert len(profiler.LEDGER.entries) == 40
    profiler.LEDGER.reset()
    old_entries = profiler.LEDGER.entries
    try:
        profiler.LEDGER.entries = type(old_entries)(maxlen=8)
        for i in range(40):
            profiler.LEDGER.on_compile(0.001)
        assert len(profiler.LEDGER.entries) == 8
        # seq keeps counting across eviction — entries_since stays correct
        assert profiler.LEDGER.entries[-1]["seq"] == 40
        assert profiler.LEDGER.entries_since(35) == list(
            profiler.LEDGER.entries
        )[-5:]
    finally:
        profiler.LEDGER.reset()
        profiler.LEDGER.entries = old_entries


# ---------------------------------------------------------------------------
# memory watermark rings
# ---------------------------------------------------------------------------


def test_mem_ring_bound_eviction_and_phase_attribution(prof_on):
    profiler.MEM.reset(ring_n=4)
    for i in range(10):
        profiler.MEM.sample_once(now=float(i))
    snap = profiler.MEM.snapshot()
    series = snap["series"]["mem.host_rss_bytes"]  # CPU run: RSS always
    assert len(series) == 4  # bounded: 6 oldest ticks evicted
    assert [t for t, _ in series] == [6.0, 7.0, 8.0, 9.0]
    assert all(v > 0 for _, v in series)
    assert "<none>" in snap["phase_peaks"]  # outside any phase

    with profiler.phase("mem.probe"):
        profiler.MEM.sample_once(now=42.0)
    peaks = profiler.MEM.snapshot()["phase_peaks"]
    assert peaks["mem.probe"]["host_rss_peak_bytes"] > 0
    # gauges mirror the latest tick for /metrics scrapes
    assert obs.snapshot()["gauges"]["mem.sampled.host_rss_bytes"] > 0


# ---------------------------------------------------------------------------
# capture parser
# ---------------------------------------------------------------------------


#: a small capture recorded by jax.profiler on the CPU backend (python
#: tracer off): obs spans gbdt.train > gbdt.round x3 around a jitted
#: matmul+sum, then one jitted tanh+sum outside every span
CAPTURE = os.path.join(REPO, "tests", "data", "capture_cpu.xplane.pb")


def test_parse_xplane_buckets_device_time():
    res = profiler.parse_xplane(CAPTURE)
    # spans are the annotations that carry an id; runtime scopes
    # (PjitFunction(step), PjRtCpuExecutable::Execute) are not
    assert set(res["annotations"]) == {"gbdt.train", "gbdt.round"}
    assert res["annotations"]["gbdt.train"] >= res["annotations"]["gbdt.round"] > 0
    # three steps of the matmul, all inside gbdt.round (innermost wins:
    # nothing is left for gbdt.train); the tanh ran outside every span
    assert res["kernels"]["dot_general.1"]["count"] == 3
    assert res["kernels"]["wrapped_tanh"]["count"] == 1
    assert set(res["span_device_ms"]) == {"gbdt.round"}
    total = sum(v["ms"] for v in res["kernels"].values())
    outside = total - res["span_device_ms"]["gbdt.round"]
    assert res["kernels"]["wrapped_tanh"]["ms"] <= outside < 0.1 * total
    assert profiler.parse_xplane(os.path.join(REPO, "README.md")) is None


def test_self_time_charges_a_loop_what_its_body_leaves():
    ops = [(0.0, 100.0, "while"), (10.0, 30.0, "fusion.1"),
           (50.0, 40.0, "fusion.2"), (150.0, 50.0, "fusion.1")]
    got = {}
    for _mid, name, self_ns in profiler._self_times(ops):
        got[name] = got.get(name, 0.0) + self_ns
    assert got == {"while": 30.0, "fusion.1": 80.0, "fusion.2": 40.0}


def test_parse_capture_dir_and_topk(prof_on, tmp_path):
    import shutil

    sub = os.path.join(str(tmp_path), "plugins", "profile", "run1")
    os.makedirs(sub)
    shutil.copy(CAPTURE, os.path.join(sub, "host.xplane.pb"))
    assert profiler.parse_capture_dir(str(tmp_path)) is not None
    assert profiler.parse_capture_dir(os.path.join(REPO, "docs")) is None
    # register it as a completed capture and merge through parse_captures
    profiler._captures.append(("gbdt.train", str(tmp_path)))
    merged = profiler.parse_captures(topk=2)
    assert merged["parsed"] == 1
    assert len(merged["top_kernels"]) == 2
    assert merged["top_kernels"][0]["name"] == "dot_general.1"
    assert merged["top_kernels"][0]["share"] > 0.5
    assert merged["span_device_ms"]["gbdt.round"] > 0


# ---------------------------------------------------------------------------
# report / flight / rendered artifact
# ---------------------------------------------------------------------------


def test_flight_dump_carries_prof_block(prof_on, tmp_path):
    from ytklearn_tpu.obs import recorder

    with profiler.phase("probe.phase"):
        profiler.MEM.sample_once(now=1.0)
    profiler.LEDGER.on_compile(0.002)
    recorder.install(flight_dir=str(tmp_path))
    try:
        path = recorder.dump(reason="test_profiler")
    finally:
        recorder.uninstall()
    with open(path) as f:
        doc = json.load(f)
    prof = doc["flight"]["prof"]
    assert "probe.phase" in prof["phases"]
    assert prof["compile"]["compiles"] == 1
    assert prof["mem_phase_peaks"]["probe.phase"]["host_rss_peak_bytes"] > 0


def test_flight_dump_prof_block_absent_when_off(tmp_path):
    from ytklearn_tpu.obs import recorder

    obs.reset()
    obs.configure(enabled=True)
    try:
        recorder.install(flight_dir=str(tmp_path))
        try:
            path = recorder.dump(reason="test_profiler_off")
        finally:
            recorder.uninstall()
        with open(path) as f:
            doc = json.load(f)
        assert "prof" not in doc["flight"]
    finally:
        obs.configure(enabled=False)
        obs.reset()


def test_report_schema_and_format(prof_on):
    with profiler.phase("fmt.phase"):
        pass
    rep = profiler.report(wall_s=1.0)
    assert rep["schema"] == "ytkprof" and rep["enabled"]
    assert "fmt.phase" in rep["phases"]
    assert 0.0 <= rep["phase_coverage"] <= 1.0
    text = profiler.format_report(rep)
    assert "fmt.phase" in text and "coverage" in text
    json.dumps(rep)  # JSON-ready end to end


def test_obs_report_renders_checked_in_prof_artifact():
    """The checked-in PROF drill artifact must render through obs_report
    (the satellite acceptance: phases, kernel table, compile ledger)."""
    path = os.path.join(REPO, "PROF_r20.json")
    assert os.path.exists(path), "PROF_r20.json artifact missing"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         path],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert "profiling drill" in r.stdout
    assert "profiled phases" in r.stdout
    assert "compile ledger" in r.stdout
    assert "gbdt.train" in r.stdout
    with open(path) as f:
        rec = json.load(f)
    assert rec["schema"] == "ytkprof_drill"
    assert rec["phase_coverage"] >= 0.9  # the headline acceptance number
    assert rec["retraces"] == 0
    assert rec["prof"]["kernels"]["top_kernels"]
