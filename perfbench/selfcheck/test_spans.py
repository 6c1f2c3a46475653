"""The span readers' arithmetic (pb/spans.py): on a synthetic span list, and
on a small trace recorded on the chip that holds the program's spans as
annotations and a scope map written at compile (tools/record_span_trace.py;
selfcheck/data/spans.facts.json is what that run read from it there)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from pb import spans, xplane  # noqa: E402

DATA = os.path.join(BENCH, "selfcheck", "data")


def sp(name, id, parent, start, end, step=None):
    return {"name": name, "id": id, "parent": parent, "step": step,
            "start": float(start), "end": float(end)}


SPANS = [
    sp("train.run", 1, None, 0, 100),
    sp("gbdt.train", 2, 1, 10, 90),
    sp("gbdt.round", 3, 2, 12, 14, step=4),
    sp("gbdt.sync", 4, 2, 14, 30, step=4),
    sp("gbdt.round", 5, 2, 31, 33, step=5),
    sp("gbdt.sync", 6, 2, 40, 95, step=9),  # runs past the window's end
]


def test_totals_clip_at_the_windows_edges():
    got = spans.totals(SPANS, 13, 50)
    assert got == {"train.run": 37.0, "gbdt.train": 37.0, "gbdt.round": 3.0,
                   "gbdt.sync": 26.0}  # 1 + 2 of rounds; 16 + 10 of syncs
    assert spans.clipped(SPANS[5], 13, 50) == 10.0
    assert spans.clipped(SPANS[2], 20, 50) == 0.0
    assert spans.totals(SPANS, 200, 300) == {}


def test_self_time_is_duration_minus_children():
    got = spans.self_seconds(SPANS)
    assert got[1] == 20.0  # 100 - 80 of gbdt.train
    assert got[2] == 80.0 - (2 + 16 + 2 + 55)
    assert got[3] == 2.0 and got[6] == 55.0
    # a span whose parent is not in the list is charged to nobody
    assert spans.self_seconds(SPANS[2:])[3] == 2.0


def test_seconds_of_returns_none_where_the_program_has_no_such_span():
    assert spans.seconds_of(SPANS, ("gbdt.sync",)) == 16.0 + 55.0
    assert spans.seconds_of(SPANS, ("gbdt.prepare",)) is None
    assert spans.seconds_of(None, ("gbdt.sync",)) is None


def test_gaps_are_named_by_the_path_over_their_middle():
    gaps = [(12.5, 13.5), (30.2, 30.8), (50.0, 60.0), (96.0, 98.0), (200.0, 201.0)]
    got = spans.name_gaps(gaps, SPANS)
    assert [g["path"] for g in got] == [
        ["train.run", "gbdt.train", "gbdt.round"],
        ["train.run", "gbdt.train"],            # between a sync and a round
        ["train.run", "gbdt.train", "gbdt.sync"],
        ["train.run"],
        [],
    ]
    assert [g["step"] for g in got] == [4, None, 9, None, None]
    assert sum(g["seconds"] for g in got if g["step"] is None) == pytest.approx(3.6)


def test_window_gaps_add_the_two_edges_and_clip():
    dev_gaps = [(20e9, 21e9), (40e9, 40.5e9), (70e9, 90e9)]
    got = spans.window_gaps(dev_gaps, first_ns=12e9, last_ns=80e9, w_lo=10.0, w_hi=75.0)
    assert got == [(10.0, 12.0), (20.0, 21.0), (40.0, 40.5), (70.0, 75.0)]
    # a window that closes after the last operation ends in an edge gap
    got = spans.window_gaps(dev_gaps[:2], 12e9, 80e9, 10.0, 85.0)
    assert got[-1] == (80.0, 85.0)


def test_clock_offset_matches_spans_by_id():
    ann = [sp("gbdt.round", 5, None, 1031.0, 1033.0, 5),
           sp("gbdt.sync", 6, None, 1040.5, 1095.0, 9),
           sp("gbdt.round", 77, None, 5.0, 6.0, 1)]   # not in the registry
    assert spans.clock_offset(ann, SPANS) in (1000.0, 1000.5)
    assert spans.clock_offset(ann[2:], SPANS) is None
    moved = spans.shifted(SPANS[:1], 1000.0)
    assert (moved[0]["start"], moved[0]["end"]) == (1000.0, 1100.0)


def test_scope_seconds_are_self_times_looked_up_by_module_and_instruction():
    scope_map = {"jit_iteration": {"fusion.60": "fm.gather_v", "sort.2": "fm.gather_v",
                                   "fusion.54": "fm.gather_w"},
                 "jit_eval_loss": {"fusion.7": "fm.gather_v"}}
    ops = [
        (0.0, 100.0, "jit_iteration", "while"),        # parent of the next three
        (10.0, 30.0, "jit_iteration", "fusion.60"),
        (40.0, 20.0, "jit_iteration", "sort.2"),
        (60.0, 10.0, "jit_iteration", "fusion.59"),    # under no scope
        (200.0, 50.0, "jit_eval_loss", "fusion.7"),
        (300.0, 50.0, "jit_eval_loss", "fusion.60"),   # same name, other module
        (400.0, 5.0, "", "fusion.54"),                 # outside every module
    ]
    got = spans.scope_self_seconds(ops, scope_map)
    assert got["fm.gather_v"] == pytest.approx((30 + 20 + 50) * 1e-9)
    assert "fm.gather_w" not in got
    assert got[""] == pytest.approx((40 + 10 + 50 + 5) * 1e-9)  # the loop's own 40
    assert sum(got.values()) == pytest.approx(205e-9)


def test_readers_return_none_without_the_programs_functions(monkeypatch):
    """A parent commit from before the spans: nothing raises."""
    from ytklearn_tpu import obs

    monkeypatch.delattr(obs, "spans_between")
    monkeypatch.delattr(obs, "scopes")
    assert spans.program_spans(0.0, 1.0) is None
    assert spans.program_scope_map() is None

    class Run:
        trace = None

        class window:
            t_open, t_close, length_s = 0.0, 1.0, 1.0

    assert spans.share_inside(Run, ("gbdt.sync",)) is None
    assert spans.seconds_of(spans.setup_spans(Run), ("gbdt.prepare",)) is None
    assert spans.idle_unnamed_pct(Run) is None
    assert spans.scope_share_pct(Run, ("gbdt.hist",)) is None


# -- the small trace recorded on the chip -----------------------------------

@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "spans.facts.json")) as f:
        facts = json.load(f)
    path = os.path.join(DATA, "spans.xplane.pb")
    return facts, ProfileData.from_file(path), xplane.summarize(path, chips=1)


def test_the_recorded_trace_holds_the_programs_spans_with_ids_and_steps(recorded):
    facts, pd, _ = recorded
    ann = spans.trace_annotations(pd)
    assert len(ann) == facts["n_annotations"]
    names = [a["name"] for a in ann]
    assert names.count("small.step") == 3 and names.count("small.sleep") == 3
    assert sorted(a["step"] for a in ann if a["name"] == "small.step") == [0, 1, 2]
    assert all(a["step"] is None for a in ann if a["name"] == "small.run")
    by_id = {s["id"]: s for s in facts["registry_spans"]}
    assert {a["id"] for a in ann} <= set(by_id)
    assert all(by_id[a["id"]]["name"] == a["name"] for a in ann)
    # both clocks tick alike: every matched span agrees on the offset
    off = spans.clock_offset(ann, facts["registry_spans"])
    assert off == pytest.approx(facts["offset"], abs=1e-9)
    for a in ann:
        assert a["start"] - by_id[a["id"]]["start"] == pytest.approx(off, abs=2e-3)


def test_the_recorded_traces_idle_time_is_named_by_the_programs_spans(recorded):
    facts, pd, summ = recorded
    dev = summ.devices[0]
    off = facts["offset"]
    gaps = spans.window_gaps(dev.gaps, dev.first_ns, dev.last_ns,
                             facts["t_open"] + off, facts["t_close"] + off)
    named = spans.name_gaps(gaps, spans.shifted(facts["registry_spans"], off))
    idle = sum(g["seconds"] for g in named)
    assert idle == pytest.approx(facts["idle_s"], rel=1e-9)
    # the window less the busy time, but for the skew between the trace's
    # host and device planes: the first operation starts about a millisecond
    # BEFORE the host span that dispatched it (PERF.md section 7)
    assert idle == pytest.approx(facts["t_close"] - facts["t_open"] - summ.busy_s, abs=2e-3)
    unnamed = sum(g["seconds"] for g in named if g["step"] is None)
    assert unnamed == pytest.approx(facts["idle_unnamed_s"], rel=1e-9)
    # three 20 ms sleeps under `small.sleep`, which carries a step: the long
    # gaps are named by it and the unnamed rest is small beside them
    longest = sorted(named, key=lambda g: -g["seconds"])[:3]
    assert all(g["path"][-1] == "small.sleep" and g["step"] is not None for g in longest)
    assert unnamed < 0.5 * idle


def test_the_recorded_traces_operations_fall_under_the_programs_scopes(recorded):
    facts, pd, summ = recorded
    ops = spans.ops_with_modules(pd)
    assert len(ops) == summ.n_events
    assert {o[2] for o in ops} == set(facts["modules"]) == {"jit_small_step", "jit_tick"}
    by_scope = spans.scope_self_seconds(ops, facts["scope_map"])
    assert sum(by_scope.values()) == pytest.approx(summ.busy_s, rel=1e-6)
    for name, s in facts["scope_seconds"].items():
        assert by_scope[name] == pytest.approx(s, rel=1e-9)
    assert by_scope["fm.gather_v"] > 0 and by_scope["gbdt.hist"] > 0
    # the map names a gather and, through autodiff, a scatter under the scope
    under = [k for k, v in facts["scope_map"]["jit_small_step"].items()
             if v == "fm.gather_v"]
    assert under
    # on the installed profiler no scope reaches an event's own text
    assert facts["scope_in_event_text"] == []
