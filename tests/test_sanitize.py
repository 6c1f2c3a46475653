"""Runtime sanitizer pins: one marked hot-path test per subsystem.

These tests run in two modes. In a plain tier-1 run they are ordinary
correctness tests. Under ``pytest --ytk-sanitize`` the conftest fixture
wraps each ``@pytest.mark.hotpath`` body in ``jax.transfer_guard
("disallow")`` + ``jax_debug_nans`` — the runtime twin of the ytklint
``host-sync-in-jit`` rule: any *implicit* host<->device transfer inside
the steady-state path (a hidden ``np.asarray`` on a device value, a
``float()`` sync, unstaged numpy feeding a jit call) fails the test with
the real tracer instead of burning a TPU run.

Staging discipline (docs/static_analysis.md): module-scoped fixtures
build models, compile kernels, and place inputs on device — that is load
time, where transfers are legitimate and the guard is not yet active.
The guarded test bodies then touch the device only through jit calls on
staged arrays and explicit ``jax.device_get`` fetches.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serve_models import build_gbdt, request_rows

pytestmark = []  # marks are per-test: hotpath(<subsystem>)


# ---------------------------------------------------------------------------
# gbdt: histogram + split kernels (the per-wave round-program hot path)
# ---------------------------------------------------------------------------

_B = 16  # histogram bins


@pytest.fixture(scope="module")
def gbdt_wave():
    """Staged inputs + warmed jit programs for one histogram/split wave."""
    from ytklearn_tpu.gbdt.engine import split_kernel
    from ytklearn_tpu.gbdt.hist import hist_wave

    rng = np.random.RandomState(3)
    n, F = 512, 5
    bins_np = rng.randint(0, _B, size=(F, n)).astype(np.int32)
    pos_np = rng.randint(0, 2, size=(n,)).astype(np.int32)  # nodes {0,1}
    g_np = rng.randn(n).astype(np.float32)
    h_np = np.abs(rng.randn(n)).astype(np.float32) + 0.1

    hist_fn = jax.jit(
        lambda bins_t, pos, g, h, ids: hist_wave(
            bins_t, pos, g, h, ids, B=_B, precision="f32", kernels="dense"
        )
    )
    cfg = (0.0, 1.0, 1e-3, 0.0)  # (l1, l2, min_child_hessian, max_abs)
    args = (
        jnp.asarray(bins_np),
        jnp.asarray(pos_np),
        jnp.asarray(g_np),
        jnp.asarray(h_np),
        jnp.asarray(np.array([0, 1], np.int32)),
    )
    feat_mask = jnp.asarray(np.ones(F, bool))
    # warm both programs at the exact shapes the guarded body replays
    hist = hist_fn(*args)
    split = split_kernel(hist, feat_mask, cfg)
    want = {
        "hist": jax.device_get(hist),
        "chg": jax.device_get(split[0]),
        "g_sum": float(g_np.sum()),
        "h_sum": float(h_np.sum()),
    }
    return hist_fn, split_kernel, args, feat_mask, cfg, want


@pytest.mark.hotpath("gbdt")
def test_gbdt_wave_hotpath_is_transfer_clean(gbdt_wave):
    hist_fn, split_kernel, args, feat_mask, cfg, want = gbdt_wave
    hist = hist_fn(*args)
    split = split_kernel(hist, feat_mask, cfg)
    hist_np, chg_np = jax.device_get((hist, split[0]))
    np.testing.assert_array_equal(hist_np, want["hist"])
    np.testing.assert_array_equal(chg_np, want["chg"])
    # per-node histograms partition the full gradient mass: feature 0's
    # bin sums over both nodes must reproduce the staged totals
    np.testing.assert_allclose(
        hist_np[:, 0, :, 0].sum(), want["g_sum"], rtol=1e-5
    )
    np.testing.assert_allclose(
        hist_np[:, 0, :, 1].sum(), want["h_sum"], rtol=1e-5
    )


# ---------------------------------------------------------------------------
# gbdt: GOSS + EFB growth program (r11 sampling/bundling hot path)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def goss_efb_grow():
    """A warmed whole-tree growth program with GOSS sampling on and EFB
    range tables active — the r11 hot path: top_k selection, remainder
    draw, row compaction, range-corrected split enumeration, range-aware
    routing, aux-routed full matrix."""
    from ytklearn_tpu.gbdt.engine import GrowSpec, make_grow_tree

    rng = np.random.RandomState(9)
    n, F, B = 512, 4, 16
    bins_np = rng.randint(0, B, size=(F, n)).astype(np.int32)
    # column 3 plays a two-member bundle: slots [1,7] and [8,15]
    rlo = np.zeros((F, B), np.int32)
    rhi = np.full((F, B), B - 1, np.int32)
    rlo[3, 1:8], rhi[3, 1:8] = 1, 7
    rlo[3, 8:], rhi[3, 8:] = 8, B - 1
    g_np = rng.randn(n).astype(np.float32)
    h_np = np.abs(rng.randn(n)).astype(np.float32) + 0.1
    spec = GrowSpec(
        F=F, B=B, max_nodes=15, wave=2, policy="loss", max_depth=8,
        max_leaves=8, lr=0.3, l1=0.0, l2=1.0, min_h=1e-3, max_abs=0.0,
        min_split_loss=0.0, min_split_samples=0.0, kernels="dense",
        goss_a=0.5, goss_b=0.25,
    )
    grow = jax.jit(make_grow_tree(spec, ranges=(rlo, rhi)))
    args = (
        jnp.asarray(bins_np), jnp.asarray(np.ones(n, bool)),
        jnp.asarray(g_np), jnp.asarray(h_np),
        jnp.asarray(np.ones(F, bool)),
    )
    key = jax.random.PRNGKey(5)
    tr, _pos, aux_pos, wlog = grow(*args, key=key)  # warm at exact avals
    want = {
        "leaf": jax.device_get(tr.leaf),
        "pos_train": jax.device_get(aux_pos[0]),
        "sampled": float(jax.device_get(wlog)[0, 4]),
    }
    return grow, args, key, want


@pytest.mark.hotpath("gbdt")
def test_goss_efb_grow_hotpath_is_transfer_clean(goss_efb_grow):
    grow, args, key, want = goss_efb_grow
    tr, _pos, aux_pos, wlog = grow(*args, key=key)
    leaf, pos_train, wlog_np = jax.device_get((tr.leaf, aux_pos[0], wlog))
    np.testing.assert_array_equal(leaf, want["leaf"])
    np.testing.assert_array_equal(pos_train, want["pos_train"])
    # the sampled-row count is the GOSS contract: top half + 1/4 remainder
    assert wlog_np[0, 4] == want["sampled"] == 256 + 64


# ---------------------------------------------------------------------------
# convex train: the jitted L-BFGS first_eval/iteration programs
# ---------------------------------------------------------------------------


def _logreg_loss(w, X, y):
    z = X @ w
    return jnp.sum(jnp.logaddexp(0.0, z) - y * z)


@pytest.fixture(scope="module")
def lbfgs_programs():
    """Compiled first_eval/iteration + a staged initial state, mirroring
    minimize_lbfgs's own init (which is load-time host code)."""
    from ytklearn_tpu.optimize import lbfgs as L

    rng = np.random.RandomState(7)
    n, dim = 256, 12
    X_np = rng.randn(n, dim)
    w_true = rng.randn(dim)
    y_np = (X_np @ w_true + 0.3 * rng.randn(n) > 0).astype(np.float64)

    cfg = L.LBFGSConfig(m=5, max_iter=10)
    first_eval, iteration = L._build_programs(
        _logreg_loss, cfg, has_l1=False, n_batch=2
    )
    batch = (jnp.asarray(X_np), jnp.asarray(y_np))
    dtype = batch[0].dtype
    w0 = jnp.asarray(np.zeros(dim))
    reg = L.Reg(
        l1_vec=jnp.asarray(np.zeros(dim)),
        l2_vec=jnp.asarray(np.full(dim, 1e-3)),
        g_weight=jnp.asarray(np.float64(1.0)),
    )
    pure, loss, g, wnorm, gnorm = first_eval(w0, reg, batch)
    state0 = L.LBFGSState(
        w=w0,
        g=g,
        loss=loss,
        pure_loss=pure,
        step=jnp.asarray(np.float64(1.0 / max(float(gnorm), 1e-300))),
        S=(jnp.asarray(np.zeros(dim)),) * cfg.m,
        Y=(jnp.asarray(np.zeros(dim)),) * cfg.m,
        ys=jnp.asarray(np.ones(cfg.m)),
        hist_len=jnp.asarray(np.int32(0)),
        ls_status=jnp.asarray(np.int32(1)),
    )
    iteration(state0, reg, batch)  # warm the exact avals the test replays
    loss0 = float(jax.device_get(state0.loss))
    return iteration, state0, reg, batch, loss0


@pytest.mark.hotpath("convex")
def test_lbfgs_iteration_hotpath_is_transfer_clean(lbfgs_programs):
    iteration, state, reg, batch, loss0 = lbfgs_programs
    losses = [loss0]
    for _ in range(3):
        state, _wnorm, _gnorm = iteration(state, reg, batch)
        # the per-iteration sync point, made EXPLICIT (minimize_lbfgs's
        # own float(state.loss) would be an implicit D2H under the guard)
        loss_val, ls = jax.device_get((state.loss, state.ls_status))
        assert np.isfinite(loss_val)
        assert int(ls) >= 0, "line search failed in sanitize run"
        losses.append(float(loss_val))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# serve: CompiledScorer steady-state scoring
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def warm_scorer(tmp_path_factory):
    """A warmed GBDT scorer (bit-identity family) + rows + expected scores.
    Construction compiles the whole ladder — load time, outside the guard."""
    from ytklearn_tpu.serve import CompiledScorer

    pred, names = build_gbdt(tmp_path_factory.mktemp("sanitize_gbdt"))
    rows = request_rows(13, np.random.RandomState(21), names)
    scorer = CompiledScorer(pred, ladder=(1, 4, 16))
    want = np.asarray(pred.batch_scores(rows))
    return scorer, rows, want


@pytest.mark.hotpath("serve")
def test_serve_score_hotpath_is_transfer_clean(warm_scorer):
    scorer, rows, want = warm_scorer
    got = scorer.score_batch(rows)
    np.testing.assert_array_equal(got, want)  # gbdt serve contract: bit-identical
    preds = scorer.predict_batch(rows)
    assert np.isfinite(preds).all()


# ---------------------------------------------------------------------------
# meta: the guard must actually bite, or the tests above prove nothing
# ---------------------------------------------------------------------------


@pytest.mark.hotpath("meta")
def test_sanitizer_guard_refuses_implicit_transfers(request):
    if not request.config.getoption("--ytk-sanitize"):
        pytest.skip("guard inactive without --ytk-sanitize")
    f = jax.jit(lambda x: x + 1)
    jax.device_get(f(jnp.asarray(np.ones(3))))  # explicit staging: fine
    with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
        f(np.ones(3))  # raw numpy into jit = implicit H2D
