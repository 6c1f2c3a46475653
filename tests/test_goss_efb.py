"""GOSS sampling + EFB bundling correctness (ISSUE 6, r11).

Contracts pinned here:

  GOSS off-switch      a=1.0, b=0.0 is bit-identical to the unsampled
                       engine (trees, scores, dumps).
  GOSS full-keep       a chosen so k_a == n runs the whole sampling
                       machinery (top_k + compaction + aux-routed train
                       matrix) and still reproduces the unsampled trees
                       exactly — the compaction is order-preserving.
  GOSS counts          the kept-row count is exactly ceil(a*n_real) +
                       ceil(b*(n_real - ceil(a*n_real))) and shows up in
                       the root sample_cnt, the wave log's sampled-rows
                       column, and the gbdt.goss.* obs counters.
  GOSS mesh8           per-shard top-|g| selection + histogram
                       aggregation equals a single-device run fed the
                       manually-computed union of per-shard top sets —
                       the "same global split decisions the math
                       predicts" pin (int8: exact i32 sums).
  EFB no-op            a dense dataset bundles nothing and the trainer
                       output is byte-identical with EFB on or off.
  EFB lossless         with conflict budget 0, bundled training chooses
                       the same splits as unbundled training (int8 sums
                       are exact; gains may differ in the last float ULP
                       from the reordered range correction, so structure
                       is exact and values are compared tightly).
  EFB mesh8            the bundled engine under shard_map (sliced range
                       tables, feature-axis padding) equals one device.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ytklearn_tpu.config.params import ApproximateSpec, GBDTParams, ModelParams
from ytklearn_tpu.gbdt.binning import (
    BundlePlan,
    build_bundle_plan,
    bundle_bin_matrix_t,
    plan_bundles,
)
from ytklearn_tpu.gbdt.data import GBDTData, column_stats
from ytklearn_tpu.gbdt.engine import GrowSpec, make_grow_tree
from ytklearn_tpu.gbdt.trainer import GBDTTrainer


# ---------------------------------------------------------------------------
# fixtures / helpers
# ---------------------------------------------------------------------------


def _dense_data(n=1200, F=6, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    logit = X[:, 0] * X[:, 1] + np.sin(2 * X[:, 2]) + 0.5 * (X[:, 3] > 0)
    y = (logit + 0.3 * rng.randn(n) > 0).astype(np.float32)
    return GBDTData(
        X=X, y=y, weight=np.ones(n, np.float32), n_real=n,
        feature_names=[str(i) for i in range(F)],
    )


def _sparse_data(n=1600, F_dense=3, F_excl=5, seed=5):
    """F_dense gaussian cols + F_excl mutually-exclusive nonneg sparse
    cols (exactly one nonzero per row), with signal on both blocks."""
    rng = np.random.RandomState(seed)
    Xd = rng.randn(n, F_dense).astype(np.float32)
    grp = rng.randint(0, F_excl, n)
    Xs = np.zeros((n, F_excl), np.float32)
    Xs[np.arange(n), grp] = rng.rand(n).astype(np.float32) + 0.25
    X = np.concatenate([Xd, Xs], axis=1)
    logit = (
        X[:, 0] * X[:, 1]
        + 1.5 * X[:, F_dense]
        - 1.2 * X[:, F_dense + 2]
        + 0.8 * X[:, F_dense + 3]
    )
    y = (logit + 0.3 * rng.randn(n) > 0).astype(np.float32)
    F = F_dense + F_excl
    return GBDTData(
        X=X, y=y, weight=np.ones(n, np.float32), n_real=n,
        feature_names=[f"f{i}" for i in range(F)],
    )


def _params(tmp_path, **over):
    kw = dict(
        round_num=3,
        max_depth=20,
        max_leaf_cnt=12,
        tree_grow_policy="loss",
        learning_rate=0.3,
        min_child_hessian_sum=1.0,
        loss_function="sigmoid",
        eval_metric=["auc"],
        approximate=[ApproximateSpec(max_cnt=32)],
        model=ModelParams(data_path=str(tmp_path / "m.model"), dump_freq=0),
    )
    kw.update(over)
    return GBDTParams(**kw)


def _spec(F, B, **over):
    kw = dict(
        F=F, B=B, max_nodes=15, wave=2, policy="loss", max_depth=10,
        max_leaves=8, lr=0.3, l1=0.0, l2=1.0, min_h=1.0, max_abs=0.0,
        min_split_loss=0.0, min_split_samples=0.0, kernels="dense",
    )
    kw.update(over)
    return GrowSpec(**kw)


def _tree_fields(tr):
    return {k: np.asarray(getattr(tr, k)) for k in (
        "feat", "slot", "slot_r", "left", "right", "leaf", "cnt", "n_nodes"
    )}


# ---------------------------------------------------------------------------
# GOSS
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_goss_off_switch_bit_identical(tmp_path, monkeypatch):
    """a=1.0, b=0.0 (here via the YTK_GOSS_* knobs) must be bit-identical
    to a run that never heard of GOSS: same dumps, same losses."""
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    monkeypatch.delenv("YTK_GOSS_A", raising=False)
    monkeypatch.delenv("YTK_GOSS_B", raising=False)
    res_off = GBDTTrainer(
        _params(tmp_path / "a"), engine="device", wave=4
    ).train(train=_dense_data())
    monkeypatch.setenv("YTK_GOSS_A", "1.0")
    monkeypatch.setenv("YTK_GOSS_B", "0.0")
    res_one = GBDTTrainer(
        _params(tmp_path / "b"), engine="device", wave=4
    ).train(train=_dense_data())
    assert res_one.model.dumps() == res_off.model.dumps()
    assert res_one.train_loss == res_off.train_loss


def test_goss_full_keep_runs_machinery_bit_identical():
    """k_a == n exercises the whole GOSS path — top_k selection, order-
    preserving compaction, the aux-routed full matrix — and must still
    reproduce the unsampled program exactly (trees AND the train-row
    leaf assignment read back from aux_pos[0])."""
    rng = np.random.RandomState(3)
    n, F, B = 512, 4, 16
    bins_np = rng.randint(0, B, size=(F, n)).astype(np.int32)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32) + 0.1
    args = (
        jnp.asarray(bins_np), jnp.ones((n,), bool),
        jnp.asarray(g), jnp.asarray(h), jnp.ones((F,), bool),
    )
    grow_ref = make_grow_tree(_spec(F, B))
    tr_ref, pos_ref, _, wlog_ref = jax.jit(lambda *a: grow_ref(*a))(*args)
    # ceil(0.999 * 512) = 512: every row kept, via the sampling path
    grow_goss = make_grow_tree(_spec(F, B, goss_a=0.999, goss_b=0.0))
    tr_g, _pos_fit, aux_pos, wlog_g = jax.jit(
        lambda *a: grow_goss(*a, key=jax.random.PRNGKey(0))
    )(*args)
    ref, got = _tree_fields(tr_ref), _tree_fields(tr_g)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)
    np.testing.assert_array_equal(
        np.asarray(pos_ref), np.asarray(aux_pos[0])
    )
    assert float(np.asarray(wlog_g)[0, 4]) == n


def test_goss_sample_counts_and_obs(tmp_path):
    """Kept rows = ceil(a*n_real) + ceil(b*(n_real - top)): visible in the
    root sample count, the wave-log sampled-rows column, the time_stats,
    and the gbdt.goss.* counters."""
    from ytklearn_tpu import obs

    obs.configure(enabled=True)
    obs.reset()
    n = 1200
    a, b = 0.3, 0.2
    k_a = int(np.ceil(a * n))
    k_b = int(np.ceil(b * (n - k_a)))
    tr = GBDTTrainer(
        _params(tmp_path), engine="device", wave=4, goss=(a, b)
    )
    res = tr.train(train=_dense_data(n=n))
    for t in res.model.trees:
        assert t.sample_cnt[0] == k_a + k_b
    wl = tr.wave_log
    used = wl[..., 3] > 0
    assert np.all(wl[:, 0, 4][used.any(-1)] == k_a + k_b)
    # the fit matrix the waves scan is the compacted width, not n
    assert wl[0, 0, 0] <= np.ceil((k_a + k_b) / 128) * 128
    assert tr.time_stats["goss"] is True
    assert tr.time_stats["goss_rows_per_tree"] == k_a + k_b
    snap = obs.snapshot()["counters"]
    assert snap["gbdt.goss.trees"] == len(res.model.trees)
    assert snap["gbdt.goss.rows_sampled"] == (k_a + k_b) * len(res.model.trees)
    # sampling still learns the signal
    assert res.train_metrics["auc"] > 0.8


@pytest.mark.slow
def test_goss_b_amplification_changes_stats(tmp_path):
    """b > 0 amplifies the sampled remainder by 1/b: the root hessian sum
    must exceed the top-only run's (amplified rows count extra mass) and
    approximate the full-data hessian in expectation."""
    n = 1200
    data = _dense_data(n=n)
    t_top = GBDTTrainer(
        _params(tmp_path, round_num=1), engine="device", wave=4,
        goss=(0.3, 0.0),
    )
    t_amp = GBDTTrainer(
        _params(tmp_path, round_num=1), engine="device", wave=4,
        goss=(0.3, 0.5),
    )
    r_top = t_top.train(train=data)
    r_amp = t_amp.train(train=data)
    h_top = r_top.model.trees[0].hess_sum[0]
    h_amp = r_amp.model.trees[0].hess_sum[0]
    assert h_amp > h_top
    # full-data root hessian for this loss/config, from an unsampled run
    t_full = GBDTTrainer(
        _params(tmp_path, round_num=1), engine="device", wave=4
    )
    h_full = t_full.train(train=data).model.trees[0].hess_sum[0]
    assert h_amp == pytest.approx(h_full, rel=0.25)


@pytest.mark.slow
def test_goss_mesh8_matches_manual_union(mesh8):
    """Per-shard GOSS (a=0.5, b=0) under shard_map must equal a single-
    device run fed the hand-computed union of per-shard top-|g| halves
    with the same gradients — per-shard selection + amplified-gradient
    histogram aggregation reproduces the predicted global split
    decisions exactly (int8 sums are order-independent i32)."""
    rng = np.random.RandomState(11)
    n, F, B = 2048, 8, 16
    n_loc = n // 8
    bins_np = rng.randint(0, B, size=(F, n)).astype(np.int32)
    g = rng.randn(n).astype(np.float32)
    h = np.ones((n,), np.float32)  # keep hmax shard-invariant: scales match
    # manual reference mask: per contiguous shard, top ceil(n_loc/2) by |g|
    keep = np.zeros((n,), bool)
    k = int(np.ceil(0.5 * n_loc))
    for s in range(8):
        sl = np.arange(s * n_loc, (s + 1) * n_loc)
        top = np.argsort(-np.abs(g[sl]), kind="stable")[:k]
        keep[sl[top]] = True

    spec_goss = _spec(F, B, precision="int8", goss_a=0.5, goss_b=0.0)
    grow8 = make_grow_tree(spec_goss, mesh=mesh8)
    args = (
        jnp.asarray(bins_np), jnp.ones((n,), bool),
        jnp.asarray(g), jnp.asarray(h), jnp.ones((F,), bool),
    )
    tr8, _p, aux_pos, _w = jax.jit(
        lambda *a: grow8(*a, key=jax.random.PRNGKey(0))
    )(*args)

    grow1 = make_grow_tree(_spec(F, B, precision="int8"))
    tr1, pos1, _a, _w1 = jax.jit(lambda *a: grow1(*a))(
        jnp.asarray(bins_np), jnp.asarray(keep),
        jnp.asarray(g), jnp.asarray(h), jnp.ones((F,), bool),
    )
    ref, got = _tree_fields(tr1), _tree_fields(tr8)
    for key in ref:
        np.testing.assert_array_equal(ref[key], got[key], err_msg=key)
    np.testing.assert_array_equal(np.asarray(pos1), np.asarray(aux_pos[0]))


# ---------------------------------------------------------------------------
# EFB
# ---------------------------------------------------------------------------


def test_efb_plan_greedy_budget_and_width():
    # 4 candidates: 0/1/2 mutually exclusive, 3 conflicts with everyone
    cand = np.asarray([10, 11, 12, 13])
    conflicts = np.asarray([
        [50, 0, 0, 9],
        [0, 50, 0, 9],
        [0, 0, 50, 9],
        [9, 9, 9, 50],
    ], np.int64)
    counts = np.zeros((20,), np.int64)
    counts[[10, 11, 12, 13]] = 8  # 7 nonzero bins each
    plan = plan_bundles(cand, conflicts, counts, F=20, max_conflict=0,
                        max_width=32)
    assert plan is not None
    assert plan.bundles == [[10, 11, 12]]  # 13 conflicts: stays out
    assert plan.bundle_width(0) == 1 + 3 * 7
    assert plan.n_cols == 20 - 3 + 1
    # width cap 16 only fits two 7-wide members per bundle
    plan_w = plan_bundles(cand, conflicts, counts, F=20, max_conflict=0,
                          max_width=16)
    assert all(len(m) == 2 for m in plan_w.bundles[:1])
    # a budget of 30 lets feature 13 join (9+9+9 = 27 conflicts)
    plan_c = plan_bundles(cand, conflicts, counts, F=20, max_conflict=30,
                          max_width=64)
    assert plan_c.bundles == [[10, 11, 12, 13]]
    # nothing bundles -> None
    dense_conf = np.full((4, 4), 9, np.int64)
    assert plan_bundles(cand, dense_conf, counts, 20, 0, 64) is None


def test_efb_unbundle_split_mapping():
    plan = BundlePlan(
        n_features=5,
        col_fid=np.asarray([0, 2], np.int32),  # cols 0,1 plain
        bundles=[[1, 3, 4]],
        member_lo=[[1, 4, 9]],
        member_hi=[[3, 8, 12]],
    )
    assert plan.n_cols == 3
    # plain column passes through
    assert plan.unbundle_split(1, 2, 3) == (2, 2, 3)
    # boundary inside member 3's range [4, 8]: orig bins shift by lo-1
    assert plan.unbundle_split(2, 5, 6) == (3, 2, 3)
    # slot_l below the member range = the member's default/zero bin
    assert plan.unbundle_split(2, 3, 4) == (3, 0, 1)
    assert plan.unbundle_split(2, 0, 9) == (4, 0, 1)
    # range tables: member ranges, default/tail slots harmless [0, B-1]
    rlo, rhi = plan.range_tables(16)
    assert rlo[2, 4] == 4 and rhi[2, 4] == 8
    assert rlo[2, 12] == 9 and rhi[2, 12] == 12
    assert rlo[2, 0] == 0 and rhi[2, 0] == 15
    assert rlo[0, 7] == 0 and rhi[0, 7] == 15


def test_efb_bundle_matrix_encoding_and_conflict_winner():
    plan = BundlePlan(
        n_features=3,
        col_fid=np.asarray([0], np.int32),
        bundles=[[1, 2]],
        member_lo=[[1, 4]],
        member_hi=[[3, 6]],
    )
    bins_t = np.asarray([
        [5, 5, 5, 5],
        [0, 2, 0, 3],   # member 1 (lo 1): orig bin b -> 1 + b - 1 = 0, 2, 0, 3
        [0, 0, 1, 2],   # member 2 (lo 4): orig bin b -> 4 + b - 1 = 0, 0, 4, 5
    ], np.int32)
    out = bundle_bin_matrix_t(bins_t, plan)
    np.testing.assert_array_equal(out[0], bins_t[0])
    # row 3 is a conflict row: the higher-offset member (fid 2) wins
    np.testing.assert_array_equal(out[1], [0, 2, 4, 5])


@pytest.mark.slow
def test_efb_noop_on_dense(tmp_path):
    """No mutually-exclusive columns -> no plan -> EFB on is literally the
    EFB-off program (byte-identical dumps)."""
    data = _dense_data()
    from ytklearn_tpu.gbdt.binning import build_bins

    bins = build_bins(
        data.X, data.weight,
        _params(tmp_path, model=ModelParams(data_path=str(tmp_path / "x"))),
    )
    nnz, mins = column_stats(data.X)
    assert build_bundle_plan(
        data.X.T, bins, 0, 64, nnz=nnz, mins=mins
    ) is None
    (tmp_path / "on").mkdir()
    (tmp_path / "off").mkdir()
    t_on = GBDTTrainer(
        _params(tmp_path / "on"), engine="device", wave=4, efb=True
    )
    r_on = t_on.train(train=_dense_data())
    t_off = GBDTTrainer(
        _params(tmp_path / "off"), engine="device", wave=4, efb=False
    )
    r_off = t_off.train(train=_dense_data())
    assert t_on._efb_plan is None
    assert r_on.model.dumps() == r_off.model.dumps()


@pytest.mark.slow
def test_efb_lossless_on_exclusive_block(tmp_path):
    """Conflict budget 0: bundled training must pick the same splits as
    unbundled training. int8 histogram sums are exact, so structure and
    sample counts match exactly; gains/leaves may differ in the last f32
    ULP (the range correction reorders float additions), so values are
    compared tightly instead of textually. The dumped model must
    reference only ORIGINAL feature names."""
    data = _sparse_data()
    (tmp_path / "on").mkdir()
    (tmp_path / "off").mkdir()
    t_on = GBDTTrainer(
        _params(tmp_path / "on"), engine="device", wave=4,
        hist_precision="int8", efb=True,
    )
    r_on = t_on.train(train=_sparse_data())
    t_off = GBDTTrainer(
        _params(tmp_path / "off"), engine="device", wave=4,
        hist_precision="int8", efb=False,
    )
    r_off = t_off.train(train=_sparse_data())
    plan = t_on._efb_plan
    assert plan is not None and len(plan.bundles) >= 1
    assert plan.n_cols < data.n_features
    for t_a, t_b in zip(r_on.model.trees, r_off.model.trees):
        assert t_a.feat == t_b.feat
        assert t_a.left == t_b.left and t_a.right == t_b.right
        assert t_a.sample_cnt == t_b.sample_cnt
        np.testing.assert_allclose(t_a.split, t_b.split, rtol=1e-6)
        np.testing.assert_allclose(t_a.leaf_value, t_b.leaf_value, rtol=1e-5,
                                   atol=1e-7)
        assert all(
            name in data.feature_names or name == ""
            for name in t_a.feat_name
        )
    assert r_on.train_loss == pytest.approx(r_off.train_loss, rel=1e-5)
    assert r_on.train_metrics["auc"] == pytest.approx(
        r_off.train_metrics["auc"], abs=1e-6
    )
    # the unbundled dump must evaluate on RAW feature values exactly like
    # the bundled engine scored on device (serving-path equivalence)
    from ytklearn_tpu.eval import EvalSet

    host_scores = r_on.model.predict_scores(data.X)
    host_auc = EvalSet(["auc"]).evaluate(
        1.0 / (1.0 + np.exp(-host_scores)), data.y, data.weight
    )["auc"]
    assert host_auc == pytest.approx(r_on.train_metrics["auc"], abs=1e-4)


@pytest.mark.slow
def test_efb_mesh8_matches_single(tmp_path, mesh8):
    """Bundled engine under shard_map: per-shard range-table slices +
    feature padding + pargmax merge must grow the single-device trees
    (int8 sums are exact, so structure/splits/counts match exactly; the
    recorded gain reduces the per-shard feature slice in a different f32
    order, so it is compared tightly rather than textually — same
    contract as the unbundled int8 mesh test)."""
    (tmp_path / "one").mkdir()
    (tmp_path / "eight").mkdir()
    r1 = GBDTTrainer(
        _params(tmp_path / "one", round_num=2), engine="device", wave=4,
        hist_precision="int8", efb=True,
    ).train(train=_sparse_data(n=1600))
    r8 = GBDTTrainer(
        _params(tmp_path / "eight", round_num=2), mesh=mesh8,
        engine="device", wave=4, hist_precision="int8", efb=True,
    ).train(train=_sparse_data(n=1600))
    assert len(r1.model.trees) == len(r8.model.trees)
    for t1, t8 in zip(r1.model.trees, r8.model.trees):
        assert t1.feat == t8.feat
        assert t1.left == t8.left and t1.right == t8.right
        assert t1.sample_cnt == t8.sample_cnt
        np.testing.assert_allclose(t1.split, t8.split, rtol=1e-6)
        np.testing.assert_allclose(
            t1.leaf_value, t8.leaf_value, rtol=1e-5, atol=1e-7
        )
        np.testing.assert_allclose(t1.gain, t8.gain, rtol=1e-4)
    assert r8.train_loss == pytest.approx(r1.train_loss, rel=1e-6)


@pytest.mark.slow
def test_goss_plus_efb_combined(tmp_path):
    """Both features together: bundled columns + sampled rows still learn
    the planted signal and keep the dumped model in original feature
    space."""
    t = GBDTTrainer(
        _params(tmp_path), engine="device", wave=4,
        hist_precision="int8", efb=True, goss=(0.4, 0.25),
    )
    res = t.train(train=_sparse_data(n=1600))
    assert t._efb_plan is not None
    n_kept = res.model.trees[0].sample_cnt[0]
    k_a = int(np.ceil(0.4 * 1600))
    assert n_kept == k_a + int(np.ceil(0.25 * (1600 - k_a)))
    assert res.train_metrics["auc"] > 0.8
    imp = res.model.feature_importance()
    assert all(name.startswith("f") for name in imp)
