"""The L-BFGS history as m separate vectors, newest first: an iteration
adds its own (s, y) pair at the head and hands the older vectors on as they
are (the same device buffers), so a step allocates one pair and never a
second copy of the history; at the ffm_criteo shape a rewritten (m, dim)
history was 2.6 GB of outputs a step (PERF.md, PR 31)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ytklearn_tpu import obs
from ytklearn_tpu.optimize import lbfgs as L


def _logreg(w, X, y):
    s = X @ w
    return jnp.sum(jnp.log1p(jnp.exp(-jnp.abs(s))) + jnp.maximum(s, 0.0) - s * y)


@pytest.fixture()
def problem():
    rng = np.random.RandomState(3)
    X = rng.randn(200, 6)
    y = (X @ rng.randn(6) + 0.2 * rng.randn(200) > 0).astype(np.float64)
    return jnp.asarray(X), jnp.asarray(y)


def _start(problem, m):
    cfg = L.LBFGSConfig(m=m)
    first_eval, iteration = L._build_programs(_logreg, cfg, has_l1=False, n_batch=2)
    dim = problem[0].shape[1]
    reg = L.Reg(jnp.zeros(dim), jnp.full((dim,), 1e-3), jnp.asarray(1.0))
    w0 = jnp.zeros(dim)
    pure, loss, g, _, gnorm = first_eval(w0, reg, problem)
    none = jnp.zeros(dim)
    state = L.LBFGSState(
        w=w0, g=g, loss=loss, pure_loss=pure, step=1.0 / gnorm,
        S=(none,) * m, Y=(none,) * m, ys=jnp.ones(m),
        hist_len=jnp.asarray(0, jnp.int32), ls_status=jnp.asarray(1, jnp.int32))
    return iteration, state, reg


def test_an_iteration_adds_one_pair_and_hands_the_rest_on(problem):
    m = 3
    iteration, state, reg = _start(problem, m)
    states = [state]
    for _ in range(m + 2):
        new, _, _ = iteration(states[-1], reg, problem)
        old = states[-1]
        assert int(new.ls_status) > 0
        assert isinstance(new.S, tuple) and len(new.S) == len(new.Y) == m
        np.testing.assert_allclose(new.S[0], new.w - old.w, rtol=0, atol=1e-15)
        np.testing.assert_allclose(new.Y[0], new.g - old.g, rtol=0, atol=1e-15)
        for i in range(1, m):  # the same buffers, one place older
            assert new.S[i] is old.S[i - 1] and new.Y[i] is old.Y[i - 1]
        np.testing.assert_allclose(new.ys[1:], old.ys[:-1])
        assert float(new.ys[0]) == pytest.approx(float(jnp.vdot(new.Y[0], new.S[0])))
        assert int(new.hist_len) == min(int(old.hist_len) + 1, m)
        states.append(new)
    assert float(states[-1].loss) < float(states[1].loss) < float(states[0].loss)


def test_a_failed_line_search_leaves_the_history(problem):
    iteration, state, reg = _start(problem, 3)
    state, _, _ = iteration(state, reg, problem)
    # a step that cannot shrink enough within one trial: the search fails
    cfg = L.LBFGSConfig(m=3, ls_max_iter=1)
    _, strict = L._build_programs(_logreg, cfg, has_l1=False, n_batch=2)
    bad, _, _ = strict(state._replace(step=jnp.asarray(1e6)), reg, problem)
    assert int(bad.ls_status) < 0
    assert all(a is b for a, b in zip(bad.S, state.S))
    assert all(a is b for a, b in zip(bad.Y, state.Y))
    assert int(bad.hist_len) == int(state.hist_len)
    np.testing.assert_array_equal(bad.w, state.w)


def test_two_loop_matches_the_dense_recursion(problem):
    """-H^-1 g over the newest-first pairs against the textbook recursion
    in numpy, with a history that is not yet full."""
    rng = np.random.RandomState(5)
    m, dim, have = 4, 6, 3
    S = [rng.randn(dim) for _ in range(have)]
    Y = [s * (0.5 + rng.rand(dim)) for s in S]  # y·s > 0
    ys = [float(y @ s) for s, y in zip(S, Y)]
    g = rng.randn(dim)
    q, alphas = g.copy(), []
    for s, y, r in zip(S, Y, ys):  # newest first
        a = (s @ q) / r
        q -= a * y
        alphas.append(a)
    q *= ys[0] / (Y[0] @ Y[0])
    for s, y, r, a in reversed(list(zip(S, Y, ys, alphas))):
        q += (a - (y @ q) / r) * s
    pad = [np.zeros(dim)] * (m - have)
    got = L._two_loop_core(
        jnp.asarray(g), tuple(jnp.asarray(v) for v in S + pad),
        tuple(jnp.asarray(v) for v in Y + pad), jnp.asarray(ys + [1.0] * (m - have)),
        jnp.asarray(have), m)
    np.testing.assert_allclose(got, -q, rtol=1e-12, atol=1e-12)


def test_state_bytes_gauge_from_shapes(problem):
    obs.configure(enabled=True)
    try:
        cfg = L.LBFGSConfig(m=5, max_iter=2)
        res = L.minimize_lbfgs(_logreg, jnp.zeros(6), cfg, batch=problem,
                               l2_vec=jnp.full((6,), 1e-3))
        assert res.n_iter == 2
        assert obs.snapshot()["gauges"]["lbfgs.stat.state_bytes"] == (2 * 5 + 4) * 6 * 8
    finally:
        obs.configure(enabled=False)
        obs.reset()
