"""The two training loops' spans (ISSUE 29): one span record with id, parent
and step on one clock; counters true on the path a stopped run takes; the
program's own names on the device (scopes, programs, the instruction ->
scope map). All on the CPU; nothing here describes a TPU topology."""

import math
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ytklearn_tpu import obs
from ytklearn_tpu.obs import profiler, scopes


@pytest.fixture
def obs_on():
    obs.configure(enabled=False)
    obs.reset()
    obs.configure(enabled=True)
    yield obs
    obs.configure(enabled=False, jsonl_path=None)
    obs.reset()


def _spans():
    return [e for e in obs.REGISTRY.events if e["ph"] == "X"]


# ---------------------------------------------------------------------------
# one span record: id, parent, step
# ---------------------------------------------------------------------------


def test_span_ids_parents_and_inherited_steps(obs_on):
    with obs.span("train.run") as root:
        with obs.span("gbdt.train") as cont:
            with obs.step_span("gbdt.round", 7, round=7) as rnd:
                with profiler.phase("inner.phase") as inner:  # == core.span
                    pass
            with obs.span("gbdt.sync", step=9) as sync:
                with obs.span("child"):
                    pass
    by = {e["name"]: e for e in _spans()}
    assert len({e["id"] for e in by.values()}) == 6
    assert by["train.run"]["parent"] is None and "step" not in by["train.run"]
    assert by["gbdt.train"]["parent"] == root.id and "step" not in by["gbdt.train"]
    assert by["gbdt.round"]["parent"] == cont.id and by["gbdt.round"]["step"] == 7
    assert by["inner.phase"]["parent"] == rnd.id and by["inner.phase"]["step"] == 7
    assert inner.id == by["inner.phase"]["id"]
    assert by["child"]["parent"] == sync.id and by["child"]["step"] == 9
    # the depth the older readers use is still there
    assert [by[n]["depth"] for n in ("train.run", "gbdt.train", "gbdt.round")] == [0, 1, 2]
    assert rnd.dur == by["gbdt.round"]["dur"] > 0


def test_span_stacks_are_per_thread(obs_on):
    seen = {}

    def worker():
        with obs.span("worker.root") as w:
            with obs.span("worker.child", step=3) as c:
                seen["child_parent"] = c.parent
            seen["root"] = w.id

    with obs.span("main.root", step=1) as main:
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with obs.span("main.child") as mc:
            pass
    by = {e["name"]: e for e in _spans()}
    # the worker's spans hang under the worker's root, not under main's
    assert by["worker.root"]["parent"] is None and "step" not in by["worker.root"]
    assert seen["child_parent"] == seen["root"]
    assert by["main.child"]["parent"] == main.id and mc.step == 1
    assert by["worker.root"]["tid"] != by["main.root"]["tid"]


def test_profiler_phase_spans_carry_ids_when_ytkprof_is_on(obs_on):
    profiler.reset_profiler()
    profiler.configure_profiler(on=True, mem_interval=0.0)
    try:
        with obs.span("train.run") as root:
            with profiler.phase("gbdt.prepare") as ph:
                with obs.span("leaf", step=2):
                    pass
    finally:
        profiler.configure_profiler(on=False, capture_dir=None)
        profiler.reset_profiler()
    by = {e["name"]: e for e in _spans()}
    assert by["gbdt.prepare"]["parent"] == root.id
    assert by["leaf"]["parent"] == by["gbdt.prepare"]["id"] and by["leaf"]["step"] == 2
    assert not isinstance(ph, obs.Span)  # the phase wraps the span


def test_spans_between_is_on_perf_counter_and_exports_carry_the_fields(obs_on, tmp_path):
    t0 = time.perf_counter()
    with obs.span("before"):
        pass
    t1 = time.perf_counter()
    time.sleep(0.002)
    with obs.span("outer", step=4) as outer:
        with obs.span("inner"):
            time.sleep(0.002)
    t2 = time.perf_counter()
    got = obs.spans_between(t1, t2)
    assert sorted(s["name"] for s in got) == ["inner", "outer"]
    inner = next(s for s in got if s["name"] == "inner")
    assert inner["parent"] == outer.id and inner["step"] == 4
    assert t1 <= inner["start"] <= inner["end"] <= t2
    assert [s["name"] for s in obs.spans_between(t0, t1)] == ["before"]
    # JSONL and Chrome trace carry id / parent / step
    path = obs.export_jsonl(str(tmp_path / "t.jsonl"))
    recs = {e["name"]: e for e in obs.load_jsonl(path)["events"]}
    assert recs["inner"]["parent"] == recs["outer"]["id"] and recs["inner"]["step"] == 4
    chrome = {e["name"]: e for e in obs.chrome_trace_events() if e["ph"] == "X"}
    assert chrome["inner"]["args"]["parent"] == outer.id
    assert chrome["outer"]["args"] == {"id": outer.id, "step": 4}


def test_no_switch_is_left_for_the_annotations():
    import inspect

    from ytklearn_tpu.config import knobs

    assert "jax_annotations" not in inspect.signature(obs.configure).parameters
    with pytest.raises(KeyError):
        knobs.get_raw("YTK_OBS_" + "JAX")  # the knob that went


# ---------------------------------------------------------------------------
# compile events name the span that compiled
# ---------------------------------------------------------------------------


def test_every_compile_drops_an_event_naming_the_open_span(obs_on):
    obs.health.install_trace_counters()

    def fresh_program_for_this_test(x):
        return jnp.tanh(x) * 3.0 + 0.125

    with obs.span("gbdt.sync", step=5) as sp:
        jax.jit(fresh_program_for_this_test)(jnp.ones((3, 5))).block_until_ready()
    evs = [e for e in obs.REGISTRY.events if e["name"] == "compile"
           and "fresh_program_for_this_test" in str(e["args"].get("program"))]
    assert len(evs) == 1
    a = evs[0]["args"]
    assert a["span"] == "gbdt.sync" and a["span_id"] == sp.id
    assert a["secs"] > 0 and a["cache_hit"] is False
    assert obs.snapshot()["counters"]["compile.traces.backend_compile"] >= 1


# ---------------------------------------------------------------------------
# lbfgs.passes
# ---------------------------------------------------------------------------


def test_lbfgs_passes_counts_every_line_search_trial(obs_on):
    from ytklearn_tpu.optimize.lbfgs import LBFGSConfig, minimize_lbfgs

    rng = np.random.RandomState(3)
    X = jnp.asarray(rng.randn(200, 6) * np.array([30.0, 1, 1, 1, 1, 0.01]))
    y = jnp.asarray((rng.rand(200) < 0.5).astype(np.float64))

    def loss(w, X, y):
        z = X @ w
        return jnp.sum(jnp.logaddexp(0.0, z) - y * z)

    statuses = []
    res = minimize_lbfgs(
        loss, jnp.zeros(6), LBFGSConfig(m=4, max_iter=12, eps=1e-9), batch=(X, y),
        l2_vec=jnp.full((6,), 1e-3), g_weight=200.0,
        callback=lambda it, st: statuses.append(int(st.ls_status)) if it else None,
    )
    assert res.n_iter == len(statuses) >= 3
    assert max(statuses) > 1, statuses  # the badly scaled start forces retries
    c = obs.snapshot()["counters"]
    assert c["lbfgs.passes"] == sum(abs(s) for s in statuses) + 1
    assert c["lbfgs.iterations"] == len(statuses)
    its = [e for e in _spans() if e["name"] == "lbfgs.iteration"]
    assert [e["step"] for e in its] == list(range(1, len(statuses) + 1))
    assert [e["args"]["passes"] for e in its] == [abs(s) for s in statuses]
    # a search that succeeds reports its trials as its status
    assert [e["args"]["trials"] for e in its] == statuses
    assert [e["args"]["status"] for e in its] == statuses
    first = [e for e in _spans() if e["name"] == "lbfgs.first_eval"]
    assert len(first) == 1 and "step" not in first[0]


# ---------------------------------------------------------------------------
# the convex callback's spans
# ---------------------------------------------------------------------------


def _write_rows(path, n, seed):
    r = np.random.RandomState(seed)
    w = np.random.RandomState(7).randn(8)
    with open(path, "w") as f:
        for _ in range(n):
            x = r.randn(8)
            s = x @ w + 1.5 * x[0] * x[1] - abs(x[2])
            y = int(r.rand() < 1.0 / (1.0 + math.exp(-s)))
            f.write("1###%d###%s\n" % (
                y, ",".join(f"c{i}:{x[i]:.5f}" for i in range(8))))


def test_convex_callback_spans_and_root(obs_on, tmp_path):
    from ytklearn_tpu.config import hocon
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.train import HoagTrainer

    _write_rows(tmp_path / "lin.train", 300, 1)
    _write_rows(tmp_path / "lin.test", 120, 2)
    conf = tmp_path / "lin.conf"
    conf.write_text(
        f'data {{ train {{ data_path = "{tmp_path / "lin.train"}" }} '
        f'test {{ data_path = "{tmp_path / "lin.test"}" }} }}\n'
        f'model {{ data_path = "{tmp_path / "lin.model"}" dump_freq = 2 }}\n'
        'loss { loss_function = "sigmoid", evaluate_metric = ["auc"] }\n'
        "optimization { line_search { lbfgs { convergence { max_iter = 4 } } } }\n"
    )
    res = HoagTrainer(CommonParams.from_config(hocon.load(str(conf))), "linear").train()
    assert res.n_iter >= 2
    spans = _spans()
    by_id = {e["id"]: e for e in spans}
    roots = [e for e in spans if e["parent"] is None]
    assert [e["name"] for e in roots] == ["train.run"]
    cbs = [e for e in spans if e["name"] == "train.callback"]
    assert [e["step"] for e in cbs] == list(range(0, res.n_iter + 1))
    # an iteration's callback runs inside the host's part of that step
    hosts = {e["id"]: e for e in spans if e["name"] == "lbfgs.host"}
    assert [e["step"] for e in hosts.values()] == list(range(1, res.n_iter + 1))
    assert all(e["parent"] in hosts and hosts[e["parent"]]["step"] == e["step"]
               for e in cbs if e["step"] > 0)
    for name in ("train.test_loss", "train.evaluate", "train.dump"):
        kids = [e for e in spans if e["name"] == name]
        assert kids, name
        in_cb = [e for e in kids if by_id[e["parent"]]["name"] == "train.callback"]
        assert in_cb and all(e["step"] == by_id[e["parent"]]["step"] for e in in_cb)
    # every iteration's test loss; metrics at iterations 0, 1 and every fifth
    assert len([e for e in spans if e["name"] == "train.test_loss"]) == len(cbs)
    # the final dump and evaluation hang under the root's containers, stepless
    tail = [e for e in spans if e["name"] == "train.dump"
            and by_id[e["parent"]]["name"] != "train.callback"]
    assert len(tail) == 1 and "step" not in tail[0]


# ---------------------------------------------------------------------------
# a GBDT run stopped by SIGTERM (the set-up of
# test_resilience.py::test_gbdt_sigterm_resume_bit_identical)
# ---------------------------------------------------------------------------


def test_gbdt_stopped_by_sigterm_leaves_counters_and_whole_spans(
        obs_on, tmp_path, monkeypatch, capsys):
    from ytklearn_tpu.cli import train_main
    from ytklearn_tpu.resilience import reset_chaos

    _write_rows(tmp_path / "g.train", 350, 3)
    conf = tmp_path / "pre.conf"
    conf.write_text(
        f'data {{ train {{ data_path = "{tmp_path / "g.train"}" }} '
        "max_feature_dim = 8 }\n"
        f'model {{ data_path = "{tmp_path / "pre"}" dump_freq = 2 }}\n'
        'loss { loss_function = "sigmoid" }\n'
        "optimization { round_num = 5, max_depth = 3, learning_rate = 0.3 }\n"
    )
    jsonl = str(tmp_path / "run.jsonl")
    obs.configure(jsonl_path=jsonl)
    reset_chaos()
    monkeypatch.setenv("YTK_CHAOS", "gbdt.sync:sigterm:1:0")
    try:
        rc = train_main(["gbdt", str(conf), "--devices", "1"])
    finally:
        monkeypatch.delenv("YTK_CHAOS")
        reset_chaos()
    capsys.readouterr()
    assert rc == 143 and (tmp_path / "pre").exists()
    obs.flush()
    doc = obs.load_jsonl(jsonl)
    c, g = doc["counters"], doc["gauges"]
    trees = c["gbdt.trees"]
    # the stop path published the wave log and the time stats, once
    assert 0 < trees == c["gbdt.rounds"] < 5
    assert c["gbdt.hist_rows_scanned"] >= c["gbdt.hist_rows_needed"] > 0
    assert c["gbdt.waves"] >= trees
    assert g["gbdt.stat.preprocess"] > 0 and g["gbdt.stat.train"] > 0
    assert g["gbdt.stat.hist_rows_scanned"] == c["gbdt.hist_rows_scanned"]
    assert len([e for e in doc["events"] if e["name"] == "gbdt.tree"]) == trees
    # every span has an id; every span of the training thread but the root a
    # parent that exists; every round and sync a step
    spans = [e for e in doc["events"] if e["ph"] == "X" and "id" in e]
    ids = {e["id"] for e in spans}
    assert len(ids) == len(spans)
    root = next(e for e in spans if e["name"] == "train.run")
    assert root["parent"] is None and root["args"]["error"] == "Preempted"
    for e in spans:
        if e["tid"] == root["tid"] and e is not root:
            assert e["parent"] in ids, e
    rounds = [e for e in spans if e["name"] == "gbdt.round"]
    syncs = [e for e in spans if e["name"] == "gbdt.sync"]
    assert [e["step"] for e in rounds] == list(range(int(trees)))
    assert syncs and all("step" in e and "rounds" in e["args"] for e in syncs)
    assert sum(e["dur"] for e in syncs) == pytest.approx(c["gbdt.sync_wait_s"], rel=1e-9)
    names = {e["name"] for e in spans}
    assert {"gbdt.preprocess", "gbdt.prepare", "gbdt.compile", "gbdt.train"} <= names
    # the stop path itself is a span of the round it stopped before
    stop = [e for e in spans if e["name"] == "gbdt.preempt"]
    assert len(stop) == 1 and stop[0]["step"] == trees
    assert stop[0]["args"]["error"] == "Preempted"
    order = [next(e for e in spans if e["name"] == n)
             for n in ("gbdt.preprocess", "gbdt.prepare", "gbdt.compile")]
    assert order[0]["ts"] + order[0]["dur"] <= order[1]["ts"]
    assert order[1]["ts"] + order[1]["dur"] <= order[2]["ts"]
    # the round program's scope map went into the stream at its compile
    maps = [e for e in doc["events"] if e["name"] == "scope_map"]
    assert any(e["args"]["module"] == "jit_round_step"
               and {"gbdt.hist", "gbdt.split"} <= set(e["args"]["ops"].values())
               for e in maps)


# ---------------------------------------------------------------------------
# names of the program's own on the device
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("latent,lookup_scope,width", [(4, "fm.gather_v", 5), (0, "fm.gather_w", 1)])
def test_scope_map_of_a_tiny_fm_pass_names_gather_and_scatter(obs_on, latent, lookup_scope, width):
    """One lookup a slot: with a latent part the one gather and, through
    autodiff, the one scatter-add lie under `fm.gather_v` and nothing under
    `fm.gather_w`; without one the single first-order gather keeps
    `fm.gather_w`. The gauge says which without a trace."""
    from ytklearn_tpu.config.params import CommonParams
    from ytklearn_tpu.models.fm import FMModel
    from ytklearn_tpu.optimize.blocked import make_value_and_grad

    p = CommonParams.from_config({
        "k": [1, latent], "model": {"data_path": "unused", "need_bias": True},
        "data": {"train": {"data_path": "unused"}}})
    m = FMModel(p, 64)
    assert obs.REGISTRY.gauges["fm.stat.gather_width"] == width
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, 64, size=(32, 5)), jnp.int32)
    val = jnp.asarray(rng.rand(32, 5), jnp.float32)
    y = jnp.asarray((rng.rand(32) < 0.5), jnp.float32)
    wt = jnp.ones((32,), jnp.float32)
    vg = make_value_and_grad(m.pure_loss, 16, m.batch_row_mask, None, "data", 4)

    def fm_pass(w, idx, val, y, wt):
        return vg(w, idx, val, y, wt)

    prog = scopes.Program(fm_pass)
    w = jnp.asarray(m.init_weights())
    loss, grad = prog(w, idx, val, y, wt)
    assert np.isfinite(float(loss)) and grad.shape == w.shape
    ops = scopes.scope_map()["jit_fm_pass"]
    # the CPU compiler leaves gather and scatter as instructions of their own
    # (the TPU's wraps each in a custom fusion): count them among every
    # instruction of the compiled pass, scoped or not
    text = next(iter(prog._compiled.values())).as_text()
    lookups = {op: set(re.findall(rf"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ {op}\(", text, re.M))
               for op in ("gather", "scatter")}
    assert len(lookups["gather"]) == 1 and len(lookups["scatter"]) == 1, lookups
    for names in lookups.values():
        assert [ops.get(n) for n in names] == [lookup_scope], (names, ops)
    other = ({"fm.gather_w", "fm.gather_v"} - {lookup_scope}).pop()
    assert other not in ops.values()
    # once per compile: the second call compiles nothing and writes no map
    n_maps = len([e for e in obs.REGISTRY.events if e["name"] == "scope_map"])
    prog(w, idx, val, y, wt)
    assert len([e for e in obs.REGISTRY.events if e["name"] == "scope_map"]) == n_maps == 1
    assert len(prog._compiled) == 1
    prog(w, idx[:16], val[:16], y[:16], wt[:16])  # another signature
    assert len(prog._compiled) == 2


def test_innermost_scope_reads_through_autodiff_wrappers():
    with jax.named_scope("x"):
        pass
    scopes.scope("fm.gather_v"), scopes.scope("gbdt.hist"), scopes.scope("gbdt.hist_extra")
    f = scopes.innermost_scope
    assert f("jit(iteration)/while/body/transpose(jvp(fm.gather_v))/scatter-add") == "fm.gather_v"
    assert f("jit(round_step)/gbdt.hist/jit(_hist_pallas)/pallas_call") == "gbdt.hist"
    assert f("jit(round_step)/gbdt.hist_extra/reshape") == "gbdt.hist_extra"
    assert f("jit(round_step)/fm.gather_v/gbdt.hist/add") == "gbdt.hist"
    assert f("jit(round_step)/while/body/add") is None
    text = (
        'HloModule jit_step, entry_computation_layout={()->f32[]}\n'
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(step)/gbdt.hist/mul" source_file="a.py"}\n'
        '  ROOT %add.1 = f32[8]{0} add(%a, %b), metadata={op_name="jit(step)/add"}\n'
        '  sort.2 = (s32[4]{0}) sort(%x), metadata={op_name="jit(step)/transpose(jvp(fm.gather_v))/scatter-add"}\n'
    )
    assert scopes.parse_hlo(text) == (
        "jit_step", {"fusion.3": "gbdt.hist", "sort.2": "fm.gather_v"})


def test_scopes_are_part_of_what_the_compile_cache_keys_a_program_by():
    """The persistent cache strips debug information, `op_name` with it: two
    programs that differ only in a scope would share one executable, and one
    would read the other's scope names. `compile_lowered` hashes every
    operation's scope into a module attribute, which the key covers."""

    def lower(name):
        def f(w, idx):
            if name:
                with scopes.scope(name):
                    g = w[idx]
            else:
                g = w[idx]
            return jnp.sum(g * g)

        lowered = jax.jit(f).lower(jnp.ones((16, 4)), jnp.arange(4))
        scopes.compile_lowered(lowered)
        return lowered.compiler_ir("stablehlo").operation.attributes

    def digest(attrs):
        return str(attrs["mhlo.frontend_attributes"]) if "mhlo.frontend_attributes" in attrs else None

    a, b, a2, plain = (digest(lower(n)) for n in ("fm.gather_v", "fm.gather_w", "fm.gather_v", ""))
    assert a and b and "ytk_scopes" in a and a != b
    assert a == a2          # the same program: the same key
    assert plain is None    # a program under no scope is left as it was


def test_programs_run_under_their_own_names(obs_on):
    from ytklearn_tpu.gbdt.trainer import sync_slice
    from ytklearn_tpu.optimize import lbfgs as L

    def loss(w, X):
        return jnp.sum((X @ w) ** 2)

    first_eval, iteration = L._build_programs(loss, L.LBFGSConfig(m=3), has_l1=False, n_batch=1)
    assert isinstance(first_eval, scopes.Program) and isinstance(iteration, scopes.Program)
    w, X = jnp.ones(4), jnp.ones((5, 4))
    reg = L.Reg(jnp.zeros(4), jnp.zeros(4), jnp.asarray(1.0))
    lowered = first_eval.jit.lower(w, reg, (X,))
    assert "jit_first_eval" in lowered.as_text()[:400]
    assert iteration.jit.__name__ == "iteration"
    assert sync_slice.__name__ == "sync_slice"
    assert float(sync_slice(jnp.arange(5.0), jnp.asarray(3))) == 3.0
