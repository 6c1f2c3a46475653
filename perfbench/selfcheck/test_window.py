"""Window arithmetic on synthetic step timestamps."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from pb.window import Window, close_on_boundaries  # noqa: E402


def boundaries(step_s, n, t0=100.0, stall_after=None, stall_s=0.0):
    out, t = [(t0, 0)], t0
    for i in range(1, n + 1):
        t += step_s + (stall_s if stall_after == i else 0.0)
        out.append((t, i))
    return out


def test_whole_steps_and_ends_on_a_boundary_at_or_after_seconds():
    b = boundaries(7.1, 20)
    w = close_on_boundaries(b, open_index=1, seconds=30.0)
    assert w.steps == 5  # 4 steps are 28.4 s: too short; the fifth closes it
    assert w.length_s == pytest.approx(35.5)
    assert w.length_s >= 30.0 and w.overshoot_s == pytest.approx(5.5)
    assert (w.t_close, w.steps_close) in [(t, float(s)) for t, s in b]
    assert w.rate(1000) == pytest.approx(5 * 1000 / 35.5)
    assert not w.exhausted


def test_rate_is_the_same_wherever_the_edge_falls():
    # a fixed-length window would hold 6 or 7 steps of 7.1 s in 45 s
    rates = {round(close_on_boundaries(boundaries(7.1, 20, t0=t0), 1, 45.0).rate(), 9)
             for t0 in (0.0, 1.3, 3.3, 6.9)}
    assert len(rates) == 1


def test_a_stall_inside_the_window_lowers_the_rate():
    clean = close_on_boundaries(boundaries(1.0, 60), 2, 10.0)
    stalled = close_on_boundaries(boundaries(1.0, 60, stall_after=5, stall_s=4.0), 2, 10.0)
    assert clean.rate() == pytest.approx(1.0)
    assert stalled.rate() < 0.75 and stalled.length_s >= 10.0


def test_training_that_ends_first_is_the_whole_phase():
    w = close_on_boundaries(boundaries(1.0, 5), 1, 30.0)
    assert w.exhausted and w.steps == 4 and w.length_s == pytest.approx(4.0)


def test_a_window_of_work_closes_on_work_whatever_its_length():
    w = Window(30.0, work=12, unit="trees")
    w.open(5.0, 1, units_done=1)
    assert not w.due(1e9, units_done=12) and w.due(5.001, units_done=13)
    with pytest.raises(RuntimeError, match="11 trees, before 12"):
        w.close(1e9, 380, units_done=12)  # a long window, a tree short
    w.close(5.5, 432, units_done=13)  # far short of 30 s
    assert w.closed_by == "trees" and w.steps == 431 and w.length_s == pytest.approx(0.5)
    assert w.overshoot_s is None and not w.exhausted
    # a window of the clock still refuses to close early, whatever work is done
    t = Window(30.0)
    t.open(0.0, 0)
    assert not t.due(29.9, units_done=100)
    with pytest.raises(RuntimeError, match="before 30.0s"):
        t.close(29.9, 10, units_done=100)
    t.close(30.0, 10)
    assert t.closed_by == "seconds" and t.overshoot_s == 0.0


def test_a_window_cannot_close_early_or_twice():
    w = Window(10.0)
    w.open(0.0, 3)
    assert not w.due(9.99) and w.due(10.0)
    with pytest.raises(RuntimeError):
        w.close(5.0, 4)
    w.close(12.0, 9)
    with pytest.raises(RuntimeError):
        w.close(13.0, 10)
    with pytest.raises(RuntimeError):
        Window(1.0).rate()


def tree_boundaries(trees, t0=50.0):
    """(time, passes finished) at every tree boundary of a job of soft trees:
    `trees` is a list of (seconds, passes) a tree, its fold and the next
    tree's first evaluation included; boundary i is tree i's first
    evaluation done."""
    out, t, passes = [(t0, 1)], t0, 1
    for seconds, n in trees:
        t, passes = t + seconds, passes + n
        out.append((t, passes))
    return out


@pytest.mark.parametrize("warm_trees,seconds,held", [
    (1, 30.0, 9),   # 8 trees are 27.6 s: the ninth closes it
    (1, 27.6, 8),   # a boundary at exactly --seconds closes
    (2, 30.0, 9),   # opened one tree later, closed one tree later
    (1, 3.0, 1),    # a window shorter than a tree holds one whole tree
])
def test_a_window_of_soft_trees_opens_and_closes_on_tree_boundaries(warm_trees, seconds, held):
    # a tree: 60 iterations, 70 trials, the next tree's first evaluation
    b = tree_boundaries([(3.45, 71)] * 20)
    w = close_on_boundaries(b, open_index=warm_trees, seconds=seconds)
    assert (w.t_open, w.steps_open) == b[warm_trees]
    assert (w.t_close, w.steps_close) in b and not w.exhausted
    assert b.index((w.t_close, w.steps_close)) - warm_trees == held
    assert w.steps == 71 * held and w.length_s == pytest.approx(3.45 * held)
    # a whole number of trees: as many fits as boundaries, whatever --seconds
    assert w.rate() == pytest.approx(71 / 3.45)


def test_trees_of_unlike_length_still_close_on_a_boundary():
    # a tree with a failed search takes 1.43 s more and adds no pass
    trees = [(3.4, 70), (3.6, 76), (4.83, 70), (3.3, 66), (3.5, 72)] * 4
    b = tree_boundaries(trees)
    w = close_on_boundaries(b, open_index=1, seconds=10.0)
    assert (w.t_close, w.steps_close) == b[4]  # 3.6 + 4.83 is 8.43 s: tree 3 closes it
    assert w.steps == 76 + 70 + 66 and w.length_s == pytest.approx(3.6 + 4.83 + 3.3)
    assert w.overshoot_s == pytest.approx(1.73)
