"""The rows the convex cells are fed: the data set's shape as the
configuration's file states it, and the rule that a seed reorders the rows
and never changes them."""

import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from pb import manifest  # noqa: E402


def rows(seed, data_seed=7, n=1 << 16):
    cell = manifest.Cell(manifest.benchmark(), "fm_criteo.train")
    sizes = {**cell.sizes, "train_rows": n, "test_rows": 1 << 10}
    convex = manifest.load_module("families", "convex")
    (idx, val, y, wt), _ = convex.make_rows(seed, data_seed, sizes, cell.config["data"])
    return cell, np.asarray(idx), np.asarray(val), np.asarray(y)


def test_rows_have_the_criteo_shape():
    cell, idx, val, y = rows(2147483659)
    n_num = cell.sizes["numeric_columns"]
    cards = cell.config["data"]["categorical_cardinalities"]
    assert idx.shape[1] == cell.sizes["row_width"] == 1 + n_num + len(cards) == 40
    assert (idx[:, 0] == 0).all() and (val[:, 0] == 1).all()  # the bias slot
    assert idx.min() >= 0 and idx[:, 1:].min() >= 1 and idx.max() < cell.sizes["hashed_dim"]
    # a numeric column is one id with a value in [0, 1)
    assert (idx[:, 1:1 + n_num] == idx[0, 1:1 + n_num]).all()
    assert len(set(idx[0, 1:1 + n_num])) == n_num
    assert 0 <= val[:, 1:1 + n_num].min() and val[:, 1:1 + n_num].max() < 1
    assert (val[:, 1 + n_num:] == 1).all()
    # a categorical column follows Zipf's law over its own cardinality: the
    # first rank takes ln 2 / ln(C + 1) of the rows, and no more ids appear
    # than the column has values
    for c, card in enumerate(cards):
        ids, counts = np.unique(idx[:, 1 + n_num + c], return_counts=True)
        assert len(ids) <= card
        want = np.log(2.0) / np.log(card + 1.0)
        assert abs(counts.max() / len(idx) - want) < 0.02 + 0.1 * want, (c, card)
    assert 0.2 < y.mean() < 0.8


def test_a_seed_reorders_the_rows_and_does_not_change_them():
    _, a, va, ya = rows(1)
    _, b, vb, yb = rows(2)
    assert not np.array_equal(a, b)
    key = lambda idx, val, y: sorted(zip(idx[:, 20].tolist(), val[:, 3].tolist(), y.tolist()))
    assert key(a, va, ya) == key(b, vb, yb)
    _, c, _, _ = rows(1, data_seed=8)
    assert not np.array_equal(np.sort(a[:, 20]), np.sort(c[:, 20]))
