"""The trace reader: the interval arithmetic on synthetic events, and the
whole reduction on a small trace recorded on the chip
(tools/record_small_trace.py; selfcheck/data/small.facts.json is what that
run read from it there)."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from pb import xplane  # noqa: E402

DATA = os.path.join(BENCH, "selfcheck", "data")


def test_busy_is_a_union_and_nested_ops_are_charged_self_time():
    ev = [
        (0.0, 100.0, "while", "%while.1 = ..."),      # parent of the next two
        (10.0, 30.0, "fusion.1", "%fusion.1 = ..."),
        (50.0, 40.0, "fusion.2", "%fusion.2 = ..."),
        (150.0, 50.0, "fusion.1", "%fusion.1 = ..."),  # after a gap of 50
        (200.0, 25.0, "copy", "%copy = ..."),          # abuts: no gap
    ]
    busy, self_s, descr, gaps, first, last = xplane._reduce_op_line(ev)
    assert busy == pytest.approx(175e-9)
    assert gaps == [(100.0, 150.0)]
    assert self_s["while"] == pytest.approx(30e-9)  # 100 - 30 - 40
    assert self_s["fusion.1"] == pytest.approx(80e-9)
    assert sum(self_s.values()) == pytest.approx(busy)
    assert (first, last) == (0.0, 225.0)


def test_idle_gaps_go_to_the_innermost_host_event():
    host = sorted([(0.0, 1000.0, "train"), (90.0, 160.0, "callback"),
                   (400.0, 500.0, "np.asarray")])
    gaps = [(100.0, 150.0), (420.0, 480.0), (2000.0, 2010.0)]
    got = dict(xplane._attribute_gaps(gaps, host))
    assert got == {"callback": pytest.approx(50e-9), "np.asarray": pytest.approx(60e-9),
                   xplane.NO_HOST: pytest.approx(10e-9)}


def test_op_key_keeps_name_shape_opcode_and_kind():
    name = ("%fusion.60 = f32[8,262144]{0,1:T(8,128)} fusion(s32[2621440]{0:T(1024)S(1)} "
            "%gte.1305), kind=kCustom, calls=%fused_computation.12.clone")
    assert xplane.op_key(name) == "fusion.60_f32_8_262144_fusion_kCustom"
    tup = ("%sort.2 = (s32[2621440]{0:T(1024)S(1)}, s32[2621440]{0:T(1024)S(1)}) "
           "sort(s32[2621440]{0:T(1024)S(1)} %copy-done.2), dimensions={0}")
    assert xplane.op_key(tup) == "sort.2_s32_2621440_sort"


def test_a_small_trace_recorded_on_the_chip():
    path = os.path.join(DATA, "small.xplane.pb")
    with open(os.path.join(DATA, "small.facts.json")) as f:
        facts = json.load(f)
    s = xplane.summarize(path, chips=1)
    assert s.n_events == facts["n_events"] > 0
    assert s.busy_s == pytest.approx(facts["busy_s"], rel=1e-9)
    assert 0 < s.busy_s < facts["window_s"]
    assert sum(s.devices[0].op_self_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    top = dict(s.top_ops(8))
    assert top.keys() == dict(facts["top_ops"]).keys()
    # three steps with a 20 ms host sleep after each: the idle time is there
    # and the reader names the host's sleep for it
    idle = dict(s.idle_gaps)
    assert sum(idle.values()) > 0.03
    assert any("sleep" in k for k in idle), idle
    assert s.op_seconds(["no such kernel"]) is None
    with pytest.raises(RuntimeError):
        xplane.summarize(path, chips=4)
