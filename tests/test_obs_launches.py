"""What the program counts of the work it asks of the device: every trial of
every line search, failed searches included (`lbfgs.passes`, the
`lbfgs.iteration` span's `trials` and `status`), and every launch of a
compiled program by its module name (`launches.<module>`), which the
benchmark lays beside a device trace's module line to tell a cut trace from
an idle device. All on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ytklearn_tpu import obs
from ytklearn_tpu.obs import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN_DATA = os.path.join(ROOT, "perfbench", "selfcheck", "data")


@pytest.fixture
def obs_on():
    obs.configure(enabled=False)
    obs.reset()
    obs.configure(enabled=True)
    yield obs
    obs.configure(enabled=False)
    obs.reset()


def _spans(name):
    return [e for e in obs.REGISTRY.events if e["ph"] == "X" and e["name"] == name]


@jax.custom_vjp
def _uphill(w):
    """sum(w^2) whose gradient points the wrong way: every search direction
    climbs, so no step ever decreases the loss enough."""
    return jnp.sum(w * w)


def _uphill_fwd(w):
    return _uphill(w), w


def _uphill_bwd(w, ct):
    return (-2.0 * w * ct,)


_uphill.defvjp(_uphill_fwd, _uphill_bwd)


def _uphill_loss(w):
    return _uphill(w)


def test_a_failed_line_search_counts_every_trial(obs_on):
    from ytklearn_tpu.optimize.lbfgs import LBFGSConfig, minimize_lbfgs

    cfg = LBFGSConfig(m=3, max_iter=5, eps=1e-12, mode="sufficient_decrease",
                      min_step=1e-4)
    seen = []
    res = minimize_lbfgs(_uphill_loss, jnp.ones(6), cfg,
                         callback=lambda it, st: seen.append(it) and False)
    assert res.status == "line_search_failed(-1)" and res.n_iter == 1
    # the step halves from 1/|g| = 0.204 until it lies under min_step: the
    # 12th trial tries 0.204 / 2^11 = 9.97e-5
    trials = int(res.state.ls_trials)
    assert trials == 12 and int(res.state.ls_status) == -1
    (it,) = _spans("lbfgs.iteration")
    assert it["args"] == {"it": 1, "passes": trials, "trials": trials, "status": -1}
    c = obs.snapshot()["counters"]
    assert c["lbfgs.passes"] == 1 + trials  # the first evaluation and every trial
    assert c["lbfgs.iterations"] == 1 and c["lbfgs.ls_failures"] == 1
    assert "lbfgs.ls_retries" not in c
    assert c["launches.jit_first_eval"] == c["lbfgs.runs"] == 1
    assert c["launches.jit_iteration"] == c["lbfgs.iterations"]
    assert seen == [0]  # a failed search leaves before the callback


def test_a_search_that_succeeds_hands_back_its_status_as_its_trials(obs_on):
    from ytklearn_tpu.optimize.lbfgs import LBFGSConfig, minimize_lbfgs

    rng = np.random.RandomState(5)
    X = jnp.asarray(rng.randn(64, 4))
    y = jnp.asarray(rng.randn(64))

    def loss(w, X, y):
        return jnp.sum((X @ w - y) ** 2)

    pairs = []
    res = minimize_lbfgs(
        loss, jnp.zeros(4), LBFGSConfig(m=3, max_iter=6, eps=1e-9), batch=(X, y),
        callback=lambda it, st: pairs.append((int(st.ls_status), int(st.ls_trials)))
        and False)
    assert res.n_iter == len(pairs) - 1 >= 2
    assert pairs[0] == (1, 0)  # the first evaluation: no search yet
    assert all(s == t > 0 for s, t in pairs[1:]), pairs
    its = _spans("lbfgs.iteration")
    assert [(e["args"]["status"], e["args"]["trials"]) for e in its] == pairs[1:]
    c = obs.snapshot()["counters"]
    assert c["lbfgs.passes"] == 1 + sum(t for _, t in pairs[1:])
    assert c["launches.jit_iteration"] == len(its) == c["lbfgs.iterations"]


def test_launches_count_every_call_through_a_program(obs_on):
    def launch_counted_step(x):
        return x * 2.0 + 1.0

    prog = scopes.Program(launch_counted_step)
    assert prog.launches == "launches.jit_launch_counted_step"
    x = jnp.ones((4,))
    for _ in range(3):
        prog(x)
    prog(jnp.ones((2, 3)))  # another signature: another compile, one launch
    assert len(prog._compiled) == 2
    prog.compile(x)  # a compile alone launches nothing
    lowered = prog.jit.lower(x).as_text()
    assert "jit_launch_counted_step" in lowered[:400]  # the module the trace names
    assert obs.snapshot()["counters"]["launches.jit_launch_counted_step"] == 4


def test_gbdt_counts_a_launch_a_round_and_a_sync_slice(obs_on, tmp_path, capsys):
    from ytklearn_tpu.cli import train_main

    r = np.random.RandomState(4)
    with open(tmp_path / "g.train", "w") as f:
        for _ in range(300):
            x = r.randn(6)
            f.write("1###%d###%s\n" % (int(x[0] * x[1] > 0),
                                       ",".join(f"c{i}:{x[i]:.5f}" for i in range(6))))
    conf = tmp_path / "g.conf"
    conf.write_text(
        f'data {{ train {{ data_path = "{tmp_path / "g.train"}" }} '
        "max_feature_dim = 6 }\n"
        f'model {{ data_path = "{tmp_path / "g.model"}" }}\n'
        'loss { loss_function = "sigmoid" }\n'
        "optimization { round_num = 4, max_depth = 3, learning_rate = 0.3 }\n"
    )
    assert train_main(["gbdt", str(conf), "--devices", "1"]) == 0
    capsys.readouterr()
    c = obs.snapshot()["counters"]
    assert c["launches.jit_round_step"] == c["gbdt.rounds"] == 4
    # a sync every round at 4 rounds; no test rows: one slice a sync
    syncs = [e for e in _spans("gbdt.sync") if e["args"].get("rounds", 0) > 0]
    assert c["launches.jit_sync_slice"] == len(syncs) == 4


def test_obs_off_counts_nothing():
    from ytklearn_tpu.optimize.lbfgs import LBFGSConfig, minimize_lbfgs

    obs.configure(enabled=False)
    obs.reset()

    def launch_uncounted_step(x):
        return x + 1.0

    scopes.Program(launch_uncounted_step)(jnp.ones((3,)))
    res = minimize_lbfgs(_uphill_loss, jnp.ones(6),
                         LBFGSConfig(m=3, max_iter=3, mode="sufficient_decrease",
                                     min_step=1e-4))
    assert int(res.state.ls_trials) == 12  # handed back whatever obs says
    assert obs.snapshot() == {"counters": {}, "gauges": {}}
    assert obs.REGISTRY.events == []
    assert obs.span("lbfgs.iteration") is obs.NOOP_SPAN


# ---------------------------------------------------------------------------
# the benchmark's reading of the two side by side (perfbench/pb/kept.py), on
# the small trace recorded on the chip (perfbench/tools/record_span_trace.py:
# three launches of `jit_small_step` and three of `jit_tick` in its window)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with open(os.path.join(SPAN_DATA, "spans.facts.json")) as f:
        facts = json.load(f)
    return facts, ProfileData.from_file(os.path.join(SPAN_DATA, "spans.xplane.pb"))


@pytest.fixture
def kept(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(ROOT, "perfbench"))
    from pb import kept

    return kept


@pytest.mark.parametrize("launches,pct", [
    ({"jit_small_step": 3, "jit_tick": 3}, 100.0),
    ({"jit_small_step": 3}, 100.0),              # only modules the program counts
    ({"jit_small_step": 4, "jit_tick": 4}, 75.0),  # launched more than the trace kept
    ({"jit_small_step": 3, "jit_never_ran": 2}, 60.0),
])
def test_trace_kept_pct_on_a_recorded_trace(recorded, kept, launches, pct):
    facts, pd = recorded
    off = facts["offset"]
    got = kept.kept(kept.module_starts(pd), launches, facts["t_close"] + off)
    assert got["pct"] == pytest.approx(pct)
    assert got["by_module"]["jit_small_step"][0] == 3


def test_trace_kept_pct_counts_only_the_window(recorded, kept):
    facts, pd = recorded
    starts = kept.module_starts(pd)
    assert sorted(starts) == ["jit_small_step", "jit_tick"]
    first_tick = min(starts["jit_tick"])
    # the first step's module starts on the trace's device clock before the
    # host span that dispatched it: the open is no edge
    first_step = min(starts["jit_small_step"])
    assert first_step < facts["t_open"] + facts["offset"] + 2e-3
    # a window that closes before the first tick holds one step, no tick
    got = kept.kept(starts, {"jit_small_step": 3, "jit_tick": 3}, first_tick - 1e-6)
    assert got["by_module"] == {"jit_small_step": [1, 3], "jit_tick": [0, 3]}
    assert kept.kept(starts, {}, 1.0) is None


def test_the_clock_offsets_spread_and_the_readers_without_counters(recorded, kept):
    from pb import spans

    facts, pd = recorded
    spread = kept.offset_spread(spans.trace_annotations(pd), facts["registry_spans"])
    assert spread["matched"] == facts["n_annotations"]
    assert 0.0 <= spread["iqr_ms"] < 1e-2 and spread["min_ms"] <= 0.0 <= spread["max_ms"]

    class Run:  # an untraced run, or a program that counts no launch
        trace = None
        counters_window = {"lbfgs.iterations": 4.0}

    assert kept.trace_kept_pct(Run) is None
    assert kept.launches_in_window(Run) == {}


def test_ls_trials_per_iteration_reads_the_programs_counters(obs_on, kept):
    from pb import trials

    from ytklearn_tpu.optimize.lbfgs import LBFGSConfig, minimize_lbfgs

    cfg = LBFGSConfig(m=3, max_iter=5, eps=1e-12, mode="sufficient_decrease",
                      min_step=1e-4)
    minimize_lbfgs(_uphill_loss, jnp.ones(6), cfg)  # one iteration, 12 trials
    minimize_lbfgs(_uphill_loss, jnp.ones(4), cfg)  # 1/|g| = 0.25: 13 trials

    class Run:
        counters_window = obs.snapshot()["counters"]

    assert trials.trials_per_iteration(Run) == pytest.approx(12.5)
    Run.counters_window = {}
    assert trials.trials_per_iteration(Run) is None
