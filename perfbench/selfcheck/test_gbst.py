"""The `gbmlr_higgs.train` cell's self-checks on the CPU, as `test_ffm.py`
keeps them for the cell before it: the program as configured comes out
`correct` at a tiny size against the committed limits (60 iterations a tree,
as the configuration states); the window opens and closes on tree boundaries,
holds `window_trees` trees whatever `--seconds` says, and the result line
says what it held; a fit that ends in a failed line search is counted and
named, in the window's last tree too; each planted fault
(the skipped fold among them) and the bfloat16 control put in the program's
place come out not correct; the window's passes are the program's own count;
the stop through the preemption guard leaves no thread or handler behind;
the two work counts against values computed by hand at the cell's sizes.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/selfcheck -q -p no:cacheprovider
"""

import json
import os
import signal
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

import run as harness  # noqa: E402
from pb import manifest, work  # noqa: E402

NAME = "gbmlr_higgs.train"
SIZES = {"train_rows": 8192, "test_rows": 1024}


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True)
def files_of_its_own(tmp_path, monkeypatch):
    """The dumped trees are read back: a test's model directory is its own
    (tests of this file may run side by side), and so are its flight dumps."""
    monkeypatch.setenv("YTK_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path / "work"))


def drive(fault=None, after=None, seed=2147483659, seconds=0.2, window_trees=1):
    cell = tiny.tiny_cell(NAME, SIZES, traffic={"window_trees": window_trees})
    cell.config["compare"]["reference_block_rows"] = 2048
    family = manifest.load_module("families", cell.config["family"])
    mend = family.plant(fault) if fault else None
    try:
        return harness.drive(cell, seed, seconds, False, tiny.CPU_DEVICE, after=after)
    finally:
        if mend is not None:
            mend()


def test_the_program_as_configured_is_correct():
    from ytklearn_tpu.resilience import PreemptionGuard

    got = {}

    def after(run, state):
        got.update(window=run.window, counters=dict(run.counters_window),
                   gauges=dict(run.gauges), facts=dict(run.facts),
                   trees=manifest.load_module("metrics", "trees_in_window.gbst").read(run),
                   boundary=manifest.load_module("metrics", "tree_boundary_share.gbst").read(run))

    threads = threading.active_count()
    res = drive(after=after, seconds=1.0)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["compared"]) == {
        "loss_gap", "grad_gap", "dw_gap", "handback_gap", "fold_loss_gap",
        "fold_test_loss_gap", "next_tree_loss_gap"}
    assert set(res["metrics"]) == {"examples_per_s.gbst", "peak_hbm_gib", "setup_s"}
    # the window's passes are the program's own count between its boundaries
    assert got["window"].steps == got["counters"]["lbfgs.passes"] > 0
    # tree 0 ran its six iterations; the job ended at a later tree's boundary
    assert got["facts"]["iterations_a_tree"][0] == 60 and got["facts"]["trees_started"] >= 2
    assert got["gauges"]["gbst.stat.k"] == 16 and got["gauges"]["gbst.stat.stride"] == 31
    assert got["gauges"]["blocked.stat.chunks_per_pass"] >= 1
    if got["trees"]:  # a boundary fell into the window: the spans say how long
        assert 0 < got["boundary"] < 100
    # stopped through the trainer's guard: no thread, no handler of it is left
    assert threading.active_count() == threads
    handler = signal.getsignal(signal.SIGTERM)
    assert not isinstance(getattr(handler, "__self__", None), PreemptionGuard)


COUNTS = {"passes", "iterations", "trees", "failed_searches", "trials_per_iteration"}


def test_the_window_holds_whole_trees_and_the_result_says_what_it_held():
    got = {}

    def after(run, state):
        got.update(window=run.window, facts=dict(run.facts), rec=state["rec"],
                   counters=dict(run.counters_window), boundaries=list(run.boundaries))

    res = drive(after=after, seconds=0.5, window_trees=2)
    rec, w, held = got["rec"], got["window"], res["window"]
    assert set(held) == COUNTS and res["correct"]
    # opened at tree 1's boundary (warm_trees), closed at tree 3's
    first, last = got["facts"]["trees_held"]
    assert (first, last) == (1, 3) and held["trees"] == last - first
    assert held["trees"] == got["counters"]["gbst.trees"]  # the program's own count
    # both boundaries are first evaluations: the passes between them are the
    # whole fits of trees [first, last) and the next tree's first evaluation
    fits = rec.fits[first:last]
    trials = sum(abs(t) for f in fits for t in f["trials"])
    assert w.steps == held["passes"] == trials + held["trees"]
    assert held["iterations"] == sum(f["iters"] for f in fits)
    assert held["trials_per_iteration"] == pytest.approx(trials / held["iterations"])
    assert held["failed_searches"] == 0 == res["failed"]
    assert res["attempted"] == sum(f["iters"] for f in rec.fits)
    # the last boundary the family noted inside the window is the close itself
    assert got["boundaries"][-1][1] == w.steps_close
    # closed by the trees it holds, not by the 0.5 s it was asked for
    assert not w.exhausted and w.closed_by == "trees" and w.length_s > 0
    assert w.overshoot_s is None


@pytest.fixture(scope="module")
def runs_by_seconds():
    """seconds -> (result line, what the run saw), each run made once."""
    return {}


def held_at(runs: dict, seconds: float):
    if seconds not in runs:
        got = {}

        def after(run, state):
            got.update(facts=dict(run.facts), closed_by=run.window.closed_by)

        runs[seconds] = (drive(after=after, seconds=seconds, window_trees=2), got)
    return runs[seconds]


@pytest.mark.parametrize("seconds,other", [(0.0, 1e9), (1e9, 0.0)])
def test_the_window_holds_its_trees_whatever_the_clock(runs_by_seconds, seconds, other):
    """A window of no seconds and one of 1e9 seconds hold the same trees 1-2
    and the job the same fits, searches and failures."""
    res, got = held_at(runs_by_seconds, seconds)
    res_other, got_other = held_at(runs_by_seconds, other)
    assert res["correct"] and got["closed_by"] == "trees"
    assert got["facts"]["trees_held"] == [1, 3] == got_other["facts"]["trees_held"]
    assert res["window"]["trees"] == 2
    for key in ("window", "failed", "attempted"):
        assert res[key] == res_other[key], key


def test_a_fit_that_ends_in_a_failed_search_is_counted_and_named(monkeypatch):
    """A planted `LBFGSResult`: tree 1's fit comes back as the program hands
    back a search that halved its step down to `min_step`."""
    import dataclasses

    import ytklearn_tpu.boost as boost_mod

    real, n = boost_mod.minimize_lbfgs, {"fits": 0}

    def minimize(*a, **kw):
        res, n["fits"] = real(*a, **kw), n["fits"] + 1
        if n["fits"] == 2:
            return dataclasses.replace(res, status="line_search_failed(-1)")
        return res

    monkeypatch.setattr(boost_mod, "minimize_lbfgs", minimize)
    got = {}
    res = drive(after=lambda run, state: got.update(run.facts), seconds=0.2)
    assert res["failed"] == 1 and got["failed_trees"] == [1]
    assert res["window"]["failed_searches"] == 1  # tree 1 lies in the window
    assert res["attempted"] == res["failed"] + sum(got["iterations_a_tree"])
    assert res["correct"]  # a failed search is counted, not judged


def test_a_failed_search_in_the_last_tree_of_the_window_counts_once(monkeypatch):
    """Trees 1 and 2 are the window; tree 2's fit comes back failed. Tree 3
    is stopped after its first evaluation and no later tree is begun."""
    import dataclasses

    import ytklearn_tpu.boost as boost_mod

    real, n = boost_mod.minimize_lbfgs, {"fits": 0}

    def minimize(*a, **kw):
        res, n["fits"] = real(*a, **kw), n["fits"] + 1
        if n["fits"] == 3:  # tree 2
            return dataclasses.replace(res, status="line_search_failed(-1)")
        return res

    monkeypatch.setattr(boost_mod, "minimize_lbfgs", minimize)
    got = {}
    res = drive(after=lambda run, state: got.update(run.facts), seconds=0.0, window_trees=2)
    assert res["failed"] == 1 and got["failed_trees"] == [2]
    assert res["window"]["failed_searches"] == 1 and res["window"]["trees"] == 2
    assert got["trees_held"] == [1, 3]
    assert got["trees_started"] == 4 and got["iterations_a_tree"][3] == 0
    assert res["attempted"] == res["failed"] + sum(got["iterations_a_tree"])
    assert res["correct"]


def test_a_window_needs_tree_0_in_set_up():
    cell = tiny.tiny_cell(NAME, SIZES, traffic={"warm_trees": 0})
    with pytest.raises(SystemExit, match="warm_trees"):
        harness.drive(cell, 2147483659, 0.2, False, tiny.CPU_DEVICE)


@pytest.mark.parametrize("trace,key", [(False, "window_trees"), (True, "trace_trees")])
def test_a_window_holds_a_tree_at_least(trace, key):
    cell = tiny.tiny_cell(NAME, SIZES, traffic={key: 0})
    with pytest.raises(SystemExit, match="under one tree"):
        harness.drive(cell, 2147483659, 0.2, trace, tiny.CPU_DEVICE)


@pytest.mark.parametrize(
    "fault", manifest.load_module("families", "gbst").FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault):
    res = drive(fault=fault)
    assert not res["correct"], (fault, res["compared"])
    failing = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    if fault == "fold_skipped":
        assert failing & {"fold_loss_gap", "next_tree_loss_gap"}, res["compared"]
    if fault == "altered_answer":
        assert "handback_gap" in failing, res["compared"]


def test_control_bfloat16_reference_is_not_correct():
    got = {}
    cell = tiny.tiny_cell(NAME, {})
    family = manifest.load_module("families", "gbst")

    def after(run, state):
        got.update(family.control_checks(run, state, cell.config["control"]))

    res = drive(after=after)
    assert res["correct"]
    assert got and not harness.verdict(got), got


def test_every_seed_fits_the_same_train_rows_and_reorders_the_test_rows():
    import numpy as np

    family = manifest.load_module("families", "gbst")
    cell = tiny.tiny_cell(NAME, SIZES)
    data_seed = int(cell.traffic["data_seed"])
    (tr_a, te_a), (tr_b, te_b), (tr_c, te_c) = (
        family.make_rows(seed, data_seed, cell.sizes)
        for seed in (2147483659, 2147483659, 2**31 + 12345))
    for a, b, c in zip(tr_a, tr_b, tr_c):  # idx, val, y, weight: the order too
        assert np.array_equal(a, b) and np.array_equal(a, c)
    for a, b in zip(te_a, te_b):  # the same seed gives the same inputs
        assert np.array_equal(a, b)
    val_a, val_c = np.asarray(te_a[1]), np.asarray(te_c[1])
    assert not np.array_equal(val_a, val_c)  # another seed: another order
    key = lambda v: v[np.lexsort(v.T[::-1])]  # noqa: E731
    assert np.array_equal(key(val_a), key(val_c))  # of the same rows
    other = family.make_rows(2147483659, data_seed + 1, cell.sizes)[0]
    assert not np.array_equal(np.asarray(other[1]), np.asarray(tr_a[1]))


def sizes():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "gbmlr_higgs.json")) as f:
        return json.load(f)["sizes"]


def test_gbst_floors_at_higgs():
    s = sizes()
    assert (s["train_rows"], s["row_width"], s["k"], s["stride"], s["dim"]) == (
        10_500_000, 29, 16, 31, 899)
    assert s["stride"] == 2 * s["k"] - 1 and s["dim"] == s["row_width"] * s["stride"]
    out = work.counter("gbst_tree_output")(s)
    whole = work.counter("gbst_pass")(s)
    # every slot's value once, the 29 x 31 table read and written; no ids,
    # no table row a slot
    assert out["bytes"] == 10_500_000 * 29 * 4 + 2 * 899 * 4 == 1_218_007_192
    assert out["flops"] == 3 * 10_500_000 * 29 * 31 * 2 == 56_637_000_000
    assert 1e3 * work.floor_seconds(out, "TPU v5 lite") == pytest.approx(1.4872, abs=1e-3)
    # the pass: z, y and the weight of every row besides
    assert whole["bytes"] == out["bytes"] + 10_500_000 * 12 == 1_344_007_192
    assert whole["flops"] == out["flops"]
    assert 1e3 * work.floor_seconds(whole, "TPU v5 lite") == pytest.approx(1.6410, abs=1e-3)
    # HBM-bound, and the part never exceeds the whole
    assert whole["flops"] / 197e12 < 0.2 * whole["bytes"] / 819e9
    assert out["bytes"] < whole["bytes"]
