"""GBST family (gbmlr/gbsdt/gbhmlr/gbhsdt) boosting tests on seeded rows
with a planted linear signal (PR 35: they read /root/reference's demo files
before and skipped where that directory is absent); PR 36: which id each slot
holds, read off the rows (`io/reader.py::constant_slots`), and a tree's
output on such rows as a product with the table's rows against the lookup."""

import copy
import os

import numpy as np
import pytest

from ytklearn_tpu.boost import GBSTTrainer
from ytklearn_tpu.config.params import CommonParams
from ytklearn_tpu.io.fs import LocalFileSystem
from ytklearn_tpu.io.reader import IngestResult, SparseDataset, constant_slots
from ytklearn_tpu.models.gbst import GBSTModel, heap_leaf_probs

NF = 13  # the bias and 12 dense features


def _ingest(n=1200, n_test=300, seed=3):
    rng = np.random.RandomState(seed)
    rows = n + n_test
    idx = np.tile(np.arange(NF, dtype=np.int32), (rows, 1))
    val = rng.randn(rows, NF).astype(np.float32)
    val[:, 0] = 1.0
    beta = rng.randn(NF).astype(np.float32)
    y = (val @ beta + 0.3 * rng.randn(rows) > 0).astype(np.float32)

    def ds(lo, hi):
        return SparseDataset(idx=idx[lo:hi], val=val[lo:hi], y=y[lo:hi],
                             weight=np.ones(hi - lo, np.float32), n_real=hi - lo, dim=NF)

    names = {"_bias_": 0, **{f"f{i}": i for i in range(1, NF)}}
    return IngestResult(train=ds(0, n), test=ds(n, rows), feature_map=names)


def _params(variant, tmp_path, **over):
    p = CommonParams()
    p.k = 4
    p.model.need_bias = True
    p.model.data_path = str(tmp_path / f"{variant}.model")
    p.loss.loss_function = "sigmoid"
    p.loss.evaluate_metric = ["auc"]
    p.line_search.lbfgs_max_iter = 10
    for k, v in over.items():
        setattr(p, k, v)
    return p


def test_heap_leaf_probs_is_distribution():
    import jax.numpy as jnp

    sig = jnp.asarray(np.random.RandomState(0).rand(7, 3), jnp.float32)
    p = heap_leaf_probs(sig)
    assert p.shape == (7, 4)
    np.testing.assert_allclose(np.asarray(p.sum(axis=-1)), np.ones(7), rtol=1e-6)
    # leaf 0 = left,left = sig[0]*sig[1]
    np.testing.assert_allclose(
        np.asarray(p[:, 0]), np.asarray(sig[:, 0] * sig[:, 1]), rtol=1e-6
    )
    # leaf 3 = right,right = (1-sig[0])*(1-sig[2])
    np.testing.assert_allclose(
        np.asarray(p[:, 3]), np.asarray((1 - sig[:, 0]) * (1 - sig[:, 2])), rtol=1e-6
    )


@pytest.mark.parametrize("variant", ["gbmlr", "gbsdt", "gbhmlr", "gbhsdt"])
def test_variant_trains_one_tree(variant, tmp_path, mesh8):
    p = _params(variant, tmp_path, tree_num=1)
    res = GBSTTrainer(p, variant, mesh=mesh8).train(ingest=_ingest())
    assert res.n_trees == 1
    assert np.isfinite(res.train_loss)
    assert res.train_loss < np.log(2.0)  # beats chance
    if variant in ("gbmlr", "gbhmlr"):  # linear experts find a linear signal
        assert res.train_metrics["auc"] > 0.97


def test_gbmlr_boosting_improves_and_resumes(tmp_path, mesh8):
    p = _params(
        "gbmlr", tmp_path, tree_num=3, learning_rate=0.5,
        instance_sample_rate=0.9, feature_sample_rate=0.8,
    )
    ingest = _ingest()
    res = GBSTTrainer(p, "gbmlr", mesh=mesh8).train(ingest=ingest)
    assert res.n_trees == 3
    assert res.train_loss < 0.3
    assert res.test_metrics["auc"] > 0.95

    # model dir layout: tree-info + tree-0000N/model-00000
    mdir = tmp_path / "gbmlr.model"
    assert (mdir / "tree-info").exists()
    assert (mdir / "tree-00002" / "model-00000").exists()
    info = (mdir / "tree-info").read_text()
    assert "finished_tree_num:3" in info
    first = (mdir / "tree-00000" / "model-00000").read_text().split("\n")
    assert first[0] == "k:4"
    # per-feature line: name + 2K-1=7 values + trailing delim
    cols = [c for c in first[1].split(",")]
    assert len(cols) == 1 + 7 + 1 and cols[-1] == ""

    # continue_train: add 2 more trees on top of the 3 dumped ones
    p2 = copy.deepcopy(p)
    p2.model.continue_train, p2.tree_num = True, 5
    res2 = GBSTTrainer(p2, "gbmlr", mesh=mesh8).train(ingest=ingest)
    assert res2.n_trees == 5
    assert res2.train_loss <= res.train_loss * 1.05 + 1e-6


def test_gbsdt_tree_roundtrip(tmp_path):
    p = _params("gbsdt", tmp_path, tree_num=1)
    ing = _ingest()
    GBSTTrainer(p, "gbsdt").train(ingest=ing)
    mdir = tmp_path / "gbsdt.model"
    text = (mdir / "tree-00000" / "model-00000").read_text().split("\n")
    assert text[0] == "k:4"
    assert len(text[1].split(",")) == 4  # bare leaf line

    m = GBSTModel(p, ing.train.dim, "gbsdt")
    w = m.load_tree(LocalFileSystem(), ing.feature_map, 0)
    assert w is not None
    assert np.any(w[:4] != 0)  # leaves loaded
    assert np.any(w[4:] != 0)  # gates loaded


def test_random_forest_type(tmp_path):
    p = _params("gbmlr", tmp_path, tree_num=2, gbst_type="random_forest")
    res = GBSTTrainer(p, "gbmlr").train(ingest=_ingest())
    assert res.n_trees == 2
    assert np.isfinite(res.train_loss)
    assert res.train_loss < np.log(2.0)


# -- PR 36: rows whose slots each hold one id ------------------------------


def _slot_rows(case):
    """(idx, val, the ids `constant_slots` must read) of 40 rows x 5 slots."""
    rng = np.random.RandomState(5)
    ids = np.array([0, 4, 2, 7, 3], np.int32)
    idx = np.tile(ids, (40, 1))
    val = (rng.rand(40, 5) + 0.5).astype(np.float32)
    want = ids.copy()
    if case == "pad_rows":  # zero-weight rows as `pad_rows_to` appends them
        idx = np.concatenate([idx, np.zeros((9, 5), np.int32)])
        val = np.concatenate([val, np.zeros((9, 5), np.float32)])
    elif case == "pad_slots":  # shorter rows: padded-ELL pads `(0, 0.0)` inside
        idx[3:17, 3:] = 0
        val[3:17, 3:] = 0.0
    elif case == "one_differs":
        idx[21, 2] = 6
        want[2] = -1
    elif case == "zero_slot":  # constant with any id: reads 0
        idx[:, 1] = rng.randint(0, 8, 40)
        val[:, 1] = 0.0
        want[1] = 0
    return idx, val, want


@pytest.mark.parametrize("where", ["numpy", "device"])
@pytest.mark.parametrize(
    "case", ["dense", "pad_rows", "pad_slots", "one_differs", "zero_slot"]
)
def test_constant_slots(case, where):
    import jax.numpy as jnp

    idx, val, want = _slot_rows(case)
    if where == "device":
        idx, val = jnp.asarray(idx), jnp.asarray(val)
    got = constant_slots(idx, val)
    assert isinstance(got, np.ndarray) and got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


VARIANTS = ["gbmlr", "gbsdt", "gbhmlr", "gbhsdt"]
# slot ids that are no `arange`: a permutation, and one id held by two slots
SLOT_IDS = {"permuted": [0, 5, 2, 8, 1, 3], "repeated": [0, 3, 5, 3, 8, 2]}


def _lookup_tree_output(m, w, idx, val, gate_mask):
    """The parent's `tree_output` (PR 35), kept here as the reference."""
    import jax.numpy as jnp

    K = m.K
    if m.scalar_leaves:
        U = w[K:].reshape(m.n_features, K - 1)
        gm = gate_mask[idx]
        Ur = U[idx]
        gate_in = jnp.einsum("nw,nwk->nk", val * gm, Ur)
        experts = w[:K]
        pi = m._gate_probs(gate_in)
        return pi @ experts
    W = w.reshape(m.n_features, 2 * K - 1)
    gm = gate_mask[idx]
    Wr = W[idx]
    gate_in = jnp.einsum("nw,nwk->nk", val * gm, Wr[..., : K - 1])
    experts = jnp.einsum("nw,nwk->nk", val, Wr[..., K - 1 :])
    pi = m._gate_probs(gate_in)
    return jnp.sum(pi * experts, axis=-1)


@pytest.mark.parametrize("ids", sorted(SLOT_IDS))
@pytest.mark.parametrize("variant", VARIANTS)
def test_product_equals_lookup(variant, ids, tmp_path):
    """Value and gradient of a tree's output on dense rows, by the product
    and by the lookup, under a gate mask with zeros; the product states its
    precision (at the TPU's default a float32 product is one bfloat16 pass)."""
    import jax
    import jax.numpy as jnp

    nf = 9
    c = np.asarray(SLOT_IDS[ids], np.int32)
    rng = np.random.RandomState(11)
    idx = jnp.asarray(np.tile(c, (64, 1)))
    val = jnp.asarray(rng.randn(64, len(c)).astype(np.float32))
    gate_mask = jnp.asarray([1, 0, 1, 1, 0, 1, 1, 1, 0], jnp.float32)
    p = _params(variant, tmp_path)
    dense = GBSTModel(p, nf, variant, dense_ids=c)
    looked_up = GBSTModel(p, nf, variant)
    assert looked_up.dense_ids is None
    w = jnp.asarray(rng.randn(dense.dim).astype(np.float32))
    cot = jnp.asarray(rng.randn(64).astype(np.float32))

    def of(fn):
        return jax.value_and_grad(lambda w: jnp.sum(cot * fn(w)))(w)

    v_d, g_d = of(lambda w: dense.tree_output(w, idx, val, gate_mask))
    v_l, g_l = of(lambda w: looked_up.tree_output(w, idx, val, gate_mask))
    v_p, g_p = of(lambda w: _lookup_tree_output(looked_up, w, idx, val, gate_mask))
    assert float(v_l) == float(v_p)  # no `dense_ids`: the parent's evaluation
    np.testing.assert_array_equal(np.asarray(g_l), np.asarray(g_p))
    scale = float(jnp.max(jnp.abs(g_l)))
    assert abs(float(v_d) - float(v_l)) <= 2e-5 * max(abs(float(v_l)), 1.0)
    np.testing.assert_allclose(np.asarray(g_d), np.asarray(g_l), rtol=0, atol=2e-5 * scale)
    # masked features: no gate gradient by either evaluation
    masked = np.flatnonzero(np.asarray(gate_mask) == 0)
    K = dense.K
    for f in masked:
        lo = K + f * (K - 1) if dense.scalar_leaves else f * (2 * K - 1)
        assert not np.any(np.asarray(g_d)[lo : lo + K - 1])
    # the rows' product, forward and its two transposes: float32 stated;
    # no (rows, width, stride) tensor anywhere
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda w, v: jnp.sum(cot * dense.tree_output(w, idx, v, gate_mask)),
        argnums=(0, 1)))(w, val)
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    rows_dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"
                 and any(v.aval.shape == val.shape for v in (*e.invars, *e.outvars))]
    assert len(rows_dots) == 3
    assert all(e.params["precision"] == highest for e in rows_dots), rows_dots
    assert not [v for e in jaxpr.eqns for v in e.outvars
                if len(v.aval.shape) == 3 and v.aval.shape[:2] == val.shape]


def _train(p, variant, ingest, observe=True):
    """One `GBSTTrainer` run -> (result, gauges, the `gbst.fold` spans' args,
    the models whose `tree_output` the fold programs were made of, what the
    fits were handed: `(fn, w0, batch)` a tree)."""
    import ytklearn_tpu.boost as boost_mod
    from ytklearn_tpu import obs

    fold_models, fits = [], []
    orig_make_rows, orig_minimize = boost_mod.make_rows, boost_mod.minimize_lbfgs

    def make_rows(fn, *a, **kw):
        fold_models.append(fn.__self__)
        return orig_make_rows(fn, *a, **kw)

    def minimize(fn, w0, cfg, batch=(), **kw):
        fits.append((fn, w0, batch))
        return orig_minimize(fn, w0, cfg, batch=batch, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(boost_mod, "make_rows", make_rows)
        mp.setattr(boost_mod, "minimize_lbfgs", minimize)
        if not observe:  # the observation finds no constant slot
            mp.setattr(boost_mod, "constant_slots",
                       lambda idx, val: np.full((idx.shape[1],), -1, np.int32))
        obs.configure(enabled=True)
        obs.reset()
        try:
            res = GBSTTrainer(p, variant).train(ingest=ingest)
            folds = [s["args"] for s in sorted(
                obs.spans_between(float("-inf"), float("inf")), key=lambda s: s["start"])
                if s["name"] == "gbst.fold"]
            gauges = dict(obs.snapshot()["gauges"])
        finally:
            obs.configure(enabled=False)
    return res, gauges, folds, fold_models, fits


def _dumped_values(p, n_trees):
    """The dumped trees' numbers, in the files' order."""
    out = []
    for t in range(n_trees):
        with open(os.path.join(p.model.data_path, f"tree-{t:05d}", "model-00000")) as f:
            lines = f.read().split("\n")[1:]
        out.append(np.array(
            [float(v) for ln in lines for v in ln.split(",") if v and v[0] in "-0123456789."]))
    return out


def _sparse_test(ingest):
    """The same ingest, the test rows with one slot whose id differs by row."""
    te = ingest.test
    idx = te.idx.copy()
    idx[::2, 5], idx[1::2, 5] = 7, 5
    test = SparseDataset(idx=idx, val=te.val, y=te.y, weight=te.weight,
                         n_real=te.n_real, dim=te.dim)
    return IngestResult(train=ingest.train, test=test, feature_map=ingest.feature_map)


@pytest.mark.parametrize("rows", ["whole", "chunked", "test_not_dense"])
def test_trainer_takes_the_product_on_dense_rows(rows, tmp_path, monkeypatch):
    """End to end on dense rows: the gauge reads the width, both folds are
    made of models that hold the ids, and the trees, the fits' losses and the
    ensemble's losses are those of a run whose observation finds nothing.
    Train rows dense, test rows not: the test fold looks up, the same holds."""
    if rows == "chunked":  # 1,200 and 300 rows padded to whole chunks of 256
        monkeypatch.setenv("YTK_ROW_CHUNK", "256")
    ingest = _ingest()
    if rows == "test_not_dense":
        ingest = _sparse_test(ingest)
    over = dict(tree_num=2, learning_rate=0.5, feature_sample_rate=0.7)
    p = _params("gbmlr", tmp_path / "dense", **over)
    p.line_search.lbfgs_max_iter = 4
    res, gauges, folds, fold_models, _ = _train(p, "gbmlr", ingest)
    assert gauges["gbst.stat.dense_slots"] == NF
    train_m, test_m = fold_models
    np.testing.assert_array_equal(train_m.dense_ids, np.arange(NF))
    if rows == "test_not_dense":
        assert test_m.dense_ids is None
    else:
        np.testing.assert_array_equal(test_m.dense_ids, np.arange(NF))
    if rows == "chunked":
        assert gauges["gbst.stat.chunks_per_pass"] == 5
    p0 = _params("gbmlr", tmp_path / "lookup", **over)
    p0.line_search.lbfgs_max_iter = 4
    res0, gauges0, folds0, fold_models0, _ = _train(p0, "gbmlr", ingest, observe=False)
    assert gauges0["gbst.stat.dense_slots"] == 0
    assert all(m.dense_ids is None for m in fold_models0)
    np.testing.assert_allclose(res.per_tree_loss, res0.per_tree_loss, rtol=1e-5)
    np.testing.assert_allclose(res.train_loss, res0.train_loss, rtol=1e-5)
    np.testing.assert_allclose(res.test_loss, res0.test_loss, rtol=1e-5)
    for a, b in zip(folds, folds0):
        np.testing.assert_allclose(a["train_loss"], b["train_loss"], rtol=1e-5)
        np.testing.assert_allclose(a["test_loss"], b["test_loss"], rtol=1e-5)
    for a, b in zip(_dumped_values(p, 2), _dumped_values(p0, 2)):
        assert a.shape == b.shape and np.any(a != 0)
        np.testing.assert_allclose(a, b, atol=2e-4)


@pytest.mark.parametrize("variant", VARIANTS)
def test_rows_that_do_not_qualify_keep_the_parents_program(variant, tmp_path):
    """One row with one other id in one slot: the gauge reads 0, no model
    holds ids, and the fit's traced program is the parent's."""
    import jax

    ingest = _ingest(n=300, n_test=60)
    ingest.train.idx[17, 4] = 9
    p = _params(variant, tmp_path, tree_num=1)
    p.line_search.lbfgs_max_iter = 2
    res, gauges, _, fold_models, fits = _train(p, variant, ingest)
    assert np.isfinite(res.train_loss)
    assert gauges["gbst.stat.dense_slots"] == 0
    assert fold_models[0].dense_ids is None
    np.testing.assert_array_equal(fold_models[1].dense_ids, np.arange(NF))  # the test rows
    fn, w0, batch = fits[0]
    model = fn.__self__
    assert model is fold_models[0]

    def parent_loss(w, idx, val, z, gate_mask, y, weight):
        fx = _lookup_tree_output(model, w, idx, val, gate_mask)
        return model._loss_of_scores(z + fx, y, weight)

    assert str(jax.make_jaxpr(fn)(w0, *batch)) == str(jax.make_jaxpr(parent_loss)(w0, *batch))


def test_benchmark_contract_fold_skipped_reaches_the_product(tmp_path, monkeypatch):
    """What perfbench/families/gbst.py's planted fault `fold_skipped` depends
    on: `GBSTModel.tree_output` and `GBSTModel.scores`, replaced on the
    class, are what the fit and both folds go through on dense rows too:
    the fit still fits, the folds leave z and the test rows' z as they were."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(root, "perfbench"))
    spec = importlib.util.spec_from_file_location(
        "perfbench_families_gbst", os.path.join(root, "perfbench", "families", "gbst.py"))
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    p = _params("gbmlr", tmp_path, tree_num=1)
    p.line_search.lbfgs_max_iter = 3
    mend = family.plant("fold_skipped")
    try:
        res, gauges, folds, _, _ = _train(p, "gbmlr", _ingest())
    finally:
        mend()
    assert gauges["gbst.stat.dense_slots"] == NF
    assert res.per_tree_loss[0] < 0.5  # the fit saw the tree's output
    at_base = float(np.log(2.0))  # the loss at z = the base score, 0
    np.testing.assert_allclose(
        [folds[0]["train_loss"], folds[0]["test_loss"], res.train_loss, res.test_loss],
        at_base, rtol=1e-6)
