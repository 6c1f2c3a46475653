"""GBST family (gbmlr/gbsdt/gbhmlr/gbhsdt) boosting tests on seeded rows
with a planted linear signal (PR 35: they read /root/reference's demo files
before and skipped where that directory is absent)."""

import copy

import numpy as np
import pytest

from ytklearn_tpu.boost import GBSTTrainer
from ytklearn_tpu.config.params import CommonParams
from ytklearn_tpu.io.fs import LocalFileSystem
from ytklearn_tpu.io.reader import IngestResult, SparseDataset
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
