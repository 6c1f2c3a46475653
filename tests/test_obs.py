"""Obs subsystem tests (ISSUE 2 acceptance): disabled-path no-op contract,
span nesting + timing monotonicity, counter aggregation under the 8-device
CPU mesh, JSONL schema round-trip, Chrome-trace validity over real GBDT +
linear runs (>= 1 span per integrated layer: ingest, train loop, engine,
collectives), and bench-roofline identity between the obs snapshot and the
legacy trainer.time_stats path."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from ytklearn_tpu import obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture
def obs_on():
    """Enabled obs with an isolated registry; restores disabled default."""
    obs.reset()
    obs.configure(enabled=True)
    yield obs
    obs.configure(enabled=False)
    obs.reset()


# ---------------------------------------------------------------------------
# core contracts
# ---------------------------------------------------------------------------


def test_disabled_path_is_noop():
    """The < 1% tier-1 overhead budget: with obs off, span() returns ONE
    cached no-op context manager and counters/gauges/events never touch
    the registry."""
    obs.configure(enabled=False)
    obs.reset()
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is s2 is obs.NOOP_SPAN  # no allocation, no state
    with obs.span("c", settle=object()):
        obs.inc("nope", 5)
        obs.gauge("nah", 1.0)
        obs.event("never")
    assert obs.snapshot() == {"counters": {}, "gauges": {}}
    assert obs.REGISTRY.events == []


def test_span_nesting_and_monotonicity(obs_on):
    with obs.span("outer", tree=1):
        time.sleep(0.002)
        with obs.span("inner"):
            time.sleep(0.002)
        with obs.span("inner2"):
            pass
    evs = {e["name"]: e for e in obs.REGISTRY.events if e["ph"] == "X"}
    assert set(evs) == {"outer", "inner", "inner2"}
    outer, inner, inner2 = evs["outer"], evs["inner"], evs["inner2"]
    # nesting depth: children at 1, root at 0
    assert outer["depth"] == 0 and inner["depth"] == 1 and inner2["depth"] == 1
    # timing monotonicity + containment
    assert inner["dur"] >= 0.002 and outer["dur"] > inner["dur"]
    assert inner["ts"] >= outer["ts"]
    assert inner2["ts"] >= inner["ts"] + inner["dur"]
    assert inner2["ts"] + inner2["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    assert outer["args"] == {"tree": 1}
    # completion-ordered event list: inner finishes before outer
    names = [e["name"] for e in obs.REGISTRY.events]
    assert names.index("inner") < names.index("outer")


def test_counters_gauges_events(obs_on):
    obs.inc("c.x", 2)
    obs.inc("c.x", 3)
    obs.gauge("g.y", 1.5)
    obs.gauge("g.y", 2.5)  # last write wins
    obs.event("marker", k="v")
    snap = obs.snapshot()
    assert snap["counters"]["c.x"] == 5.0
    assert snap["gauges"]["g.y"] == 2.5
    inst = [e for e in obs.REGISTRY.events if e["ph"] == "i"]
    assert inst and inst[0]["name"] == "marker" and inst[0]["args"] == {"k": "v"}


def test_heartbeat_rate_limit(obs_on):
    hb = obs.heartbeat("t", every_s=100.0)
    assert hb.beat("first", rows=1) is True  # first beat always fires
    assert hb.beat("suppressed") is False
    assert hb.beat("forced", force=True) is True
    assert obs.snapshot()["counters"]["heartbeat.t"] == 2.0


def test_jsonl_schema_roundtrip(obs_on, tmp_path):
    with obs.span("phase.a", k=1):
        pass
    obs.inc("rows", 7)
    obs.gauge("speed", 3.25)
    obs.event("mark")
    path = str(tmp_path / "events.jsonl")
    obs.export_jsonl(path)
    back = obs.load_jsonl(path)
    assert back["meta"]["schema_version"] >= 1
    assert "wall_t0" in back["meta"]
    assert back["counters"] == {"rows": 7.0}
    assert back["gauges"] == {"speed": 3.25}
    spans = [e for e in back["events"] if e["ph"] == "X"]
    assert len(spans) == 1 and spans[0]["name"] == "phase.a"
    for field in ("ts", "dur", "tid", "depth"):
        assert field in spans[0]
    assert spans[0]["args"] == {"k": 1}
    insts = [e for e in back["events"] if e["ph"] == "i"]
    assert len(insts) == 1 and insts[0]["name"] == "mark"


# ---------------------------------------------------------------------------
# integrated runs
# ---------------------------------------------------------------------------


def _gbdt_data(n=2000, F=6, seed=0):
    """Identical shapes/params to tests/test_gbdt.py::make_binary so the
    in-process jit cache compiled there is reused — these tests add run
    time, not compile time, to tier-1."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = ((X[:, 0] > 0.3) | ((X[:, 1] > 0) & (X[:, 2] < 0.5))).astype(np.float32)
    flip = rng.rand(n) < 0.05
    y = np.where(flip, 1 - y, y).astype(np.float32)
    from ytklearn_tpu.gbdt.data import GBDTData

    return GBDTData(
        X=X, y=y, weight=np.ones(n, np.float32), n_real=n,
        feature_names=[str(i) for i in range(F)],
    )


def _gbdt_params(tmp_path):
    from ytklearn_tpu.config.params import ApproximateSpec, GBDTParams

    p = GBDTParams(
        round_num=3,
        max_depth=3,
        max_leaf_cnt=16,
        learning_rate=0.3,
        l2=1.0,
        min_child_hessian_sum=1e-6,
        eval_metric=["auc"],
        approximate=[ApproximateSpec(type="sample_by_quantile", max_cnt=32)],
    )
    p.model.data_path = str(tmp_path / "model")
    p.model.dump_freq = 0
    return p


def _run_mesh_gbdt(tmp_path, mesh8):
    from ytklearn_tpu.gbdt.trainer import GBDTTrainer

    trainer = GBDTTrainer(_gbdt_params(tmp_path), mesh=mesh8, engine="device")
    res = trainer.train(_gbdt_data())
    return trainer, res


@pytest.fixture(scope="module")
def integrated(tmp_path_factory, mesh8):
    """ONE obs-enabled GBDT-on-mesh + linear run shared by the integrated
    assertions below (device-engine compiles are the expensive part of
    this file; every test reads the same captured registry state)."""
    tmp = tmp_path_factory.mktemp("obs_run")
    obs.reset()
    obs.configure(enabled=True)
    try:
        trainer, res = _run_mesh_gbdt(tmp, mesh8)
        lin_res = _run_linear(tmp)
        trace_path = str(tmp / "trace.json")
        obs.export_chrome_trace(trace_path)
        snap = obs.snapshot()
        events = list(obs.REGISTRY.events)
    finally:
        obs.configure(enabled=False)
        obs.reset()
    return {
        "trainer": trainer,
        "res": res,
        "lin_res": lin_res,
        "snap": snap,
        "events": events,
        "trace_path": trace_path,
    }


def _write_linear_data(tmp_path, n=48):
    rng = np.random.RandomState(3)
    path = tmp_path / "lin.train.ytklearn"
    with open(path, "w") as f:
        for _ in range(n):
            x = rng.randn(3)
            y = int(x[0] + 0.5 * x[1] > 0)
            feats = ",".join(f"f{j}:{x[j]:.4f}" for j in range(3))
            f.write(f"1###{y}###{feats}\n")
    return str(path)


def _run_linear(tmp_path):
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.train import HoagTrainer

    p = CommonParams()
    p.data.train_paths = [_write_linear_data(tmp_path)]
    p.model.data_path = str(tmp_path / "lr.model")
    p.line_search.lbfgs_max_iter = 4
    return HoagTrainer(p, "linear").train()


def test_mesh8_counter_aggregation(integrated):
    """Counters from a row-sharded device-engine run: per-tree wave-log
    accumulation must agree with the trainer's time_stats totals, and the
    traced collective surface (psum_scatter feature-slice combine) must be
    counted with operand bytes."""
    trainer, res = integrated["trainer"], integrated["res"]
    assert len(res.model.trees) == 3
    snap = integrated["snap"]
    c = snap["counters"]
    ts = trainer.time_stats

    assert c["gbdt.trees"] == 3.0
    assert c["gbdt.rounds"] == 3.0
    # per-tree accumulation == whole-run wave-log totals (one registry,
    # no parallel bookkeeping)
    assert c["gbdt.hist_rows_scanned"] == pytest.approx(ts["hist_rows_scanned"])
    assert c["gbdt.hist_rows_needed"] == pytest.approx(ts["hist_rows_needed"])
    assert c["gbdt.waves"] == pytest.approx(ts["hist_passes"])
    # traced collectives: the engine's histogram combine is a psum_scatter
    assert c["collectives.psum_scatter.calls"] >= 1
    assert c["collectives.psum_scatter.bytes"] > 0
    # gbdt.stat.* gauges mirror every scalar time_stat
    g = snap["gauges"]
    for k, v in ts.items():
        if isinstance(v, (bool, int, float)):
            assert g[f"gbdt.stat.{k}"] == pytest.approx(float(v))


def _validate_chrome_trace(path):
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events, "empty trace"
    open_be = {}
    for ev in events:
        assert "name" in ev and "ph" in ev and "pid" in ev
        if ev["ph"] in ("X", "B", "E", "i", "C"):
            assert "ts" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        key = (ev["pid"], ev.get("tid"), ev["name"])
        if ev["ph"] == "B":
            open_be[key] = open_be.get(key, 0) + 1
        elif ev["ph"] == "E":
            open_be[key] = open_be.get(key, 0) - 1
            assert open_be[key] >= 0, f"E without B: {key}"
    assert all(v == 0 for v in open_be.values()), f"unmatched B/E: {open_be}"
    return events


def test_trace_covers_all_layers(integrated):
    """The acceptance run: a GBDT + a linear training with tracing on must
    produce a Chrome-trace file that parses, has matched B/E (we only emit
    complete X events) and >= 1 span per integrated layer."""
    assert integrated["lin_res"].n_iter >= 1
    events = _validate_chrome_trace(integrated["trace_path"])
    span_names = {e["name"] for e in events if e["ph"] == "X"}
    layers = {
        "ingest": ("ingest.",),
        "train_loop": ("train.", "lbfgs."),
        "engine": ("gbdt.",),
        "collectives": ("collectives.",),
    }
    for layer, prefixes in layers.items():
        assert any(
            n.startswith(p) for n in span_names for p in prefixes
        ), f"no span for layer {layer}; got {sorted(span_names)}"
    # counter samples ride along for Perfetto
    assert any(e["ph"] == "C" for e in events)


def test_roofline_obs_identity(integrated):
    """bench roofline derived from the obs registry snapshot must be
    value-identical to the legacy time_stats-derived fields."""
    import bench

    trainer = integrated["trainer"]
    legacy_stats = {
        k: v for k, v in trainer.time_stats.items()
        if isinstance(v, (bool, int, float))
    }
    from_obs = bench.gbdt_stats_from_obs(trainer, snapshot=integrated["snap"])
    assert from_obs  # came from gbdt.stat.* gauges, not the fallback
    kind = "TPU v5 lite"  # peaks are looked up by device_kind
    assert bench.roofline_fields(from_obs, 3, kind) == bench.roofline_fields(
        legacy_stats, 3, kind
    )


def test_gbdt_stats_obs_fallback():
    """With obs disabled (empty registry), gbdt_stats_from_obs falls back
    to the trainer's time_stats so bench still reports."""
    import bench

    obs.configure(enabled=False)
    obs.reset()

    class _Trainer:
        time_stats = {
            "hist_rows_scanned": 5.0, "train": 1.5, "partition": True,
            "wave_log_ignored": "str",
        }

    stats = bench.gbdt_stats_from_obs(_Trainer())
    assert stats == {
        "hist_rows_scanned": 5.0, "train": 1.5, "partition": True,
    }


# ---------------------------------------------------------------------------
# satellites: bench schema tolerance + the no-print guard
# ---------------------------------------------------------------------------


def test_read_bench_record_tolerates_both_shapes(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from ablate_engine import read_bench_record

    old = {  # v1: the BENCH_r01..r05 flat shape
        "metric": "gbdt_trees_per_sec", "value": 1.2, "unit": "trees/s",
        "auc": 0.94, "logloss": 0.31, "trees": 40, "mxu_pct_peak": 12.0,
    }
    new = dict(old)
    new.update(
        schema_version=2,
        downgrades=1,
        obs={"counters": {"gbdt.downgrade.total": 1.0}, "gauges": {}},
    )
    po, pn = tmp_path / "old.json", tmp_path / "new.json"
    po.write_text(json.dumps(old))
    pn.write_text(json.dumps(new))
    ro, rn = read_bench_record(str(po)), read_bench_record(str(pn))
    assert ro["schema_version"] == 1 and rn["schema_version"] == 2
    for r in (ro, rn):
        assert r["trees_per_sec"] == 1.2
        assert r["auc"] == 0.94
        assert r["mxu_pct_peak"] == 12.0
    assert ro["downgrades"] == 0 and ro["obs"] == {}
    assert rn["downgrades"] == 1
    assert rn["obs"]["counters"]["gbdt.downgrade.total"] == 1.0


def test_check_no_print_passes():
    r = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "check_no_print.sh")],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_real_higgs_loader_has_ingest_spans(obs_on, tmp_path):
    """bench's real-Higgs branch goes through GBDTIngest — ingest spans and
    row counters must appear (the YTK_TRACE acceptance path for bench)."""
    import bench

    rng = np.random.RandomState(0)
    for name, rows in (("higgs.train", 40), ("higgs.test", 10)):
        with open(tmp_path / name, "w") as f:
            for _ in range(rows):
                y = int(rng.rand() > 0.5)
                feats = ",".join(
                    f"{j}:{v:.4f}" for j, v in enumerate(rng.randn(28))
                )
                f.write(f"1###{y}###{feats}\n")
    os.environ["YTK_HIGGS_DIR"] = str(tmp_path)
    try:
        train, test, source = bench.resolve_gbdt_data(64, 16)
    finally:
        del os.environ["YTK_HIGGS_DIR"]
    assert source == "higgs" and train.n_real == 40
    snap = obs.snapshot()
    assert snap["counters"]["ingest.rows"] == 50.0
    names = {e["name"] for e in obs.REGISTRY.events}
    assert "ingest.parse" in names


# ---------------------------------------------------------------------------
# thread_guard: a worker thread must not die silently
# ---------------------------------------------------------------------------


def test_thread_guard_logs_records_and_reraises(obs_on):
    from ytklearn_tpu.obs.recorder import thread_guard

    @thread_guard
    def entry(x):
        raise ValueError(f"boom {x}")

    assert entry.__name__ == "entry"  # functools.wraps
    with pytest.raises(ValueError, match="boom 7"):
        entry(7)
    died = [e for e in obs.REGISTRY.events if e["name"] == "thread.died"]
    assert len(died) == 1
    assert died[0]["args"]["error"] == "ValueError"
    assert "entry" in died[0]["args"]["entry"]


def test_thread_guard_passthrough_on_success(obs_on):
    from ytklearn_tpu.obs.recorder import thread_guard

    @thread_guard
    def entry(a, b=1):
        return a + b

    assert entry(2, b=3) == 5
    assert [e for e in obs.REGISTRY.events if e["name"] == "thread.died"] == []


def test_exports_commit_atomically(obs_on, tmp_path):
    # the exporters now write through the fs seam: tmp-file + atomic
    # replace, no stray tmp artifacts left next to the export
    obs.inc("rows", 1)
    for name, fn in (("t.json", obs.export_chrome_trace),
                     ("e.jsonl", obs.export_jsonl)):
        out = tmp_path / name
        fn(str(out))
        assert out.exists()
        stray = [p.name for p in tmp_path.iterdir() if p.name != name]
        assert stray == [], stray
        out.unlink()
