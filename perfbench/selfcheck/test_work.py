"""The work-count functions against values computed by hand at the two
configurations' sizes, and the rule that a share cannot pass 100%."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
from pb import work  # noqa: E402


def sizes(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["sizes"]


def test_gbdt_floor_at_higgs():
    s = sizes("gbdt_higgs")
    root = work.counter("gbdt_root_scan")(s)
    tree = work.counter("gbdt_tree")(s)
    assert root["bytes"] == 10_500_000 * 36 == 378_000_000
    assert tree["bytes"] == 10_500_000 * 44 == 462_000_000
    assert work.floor_seconds(tree, "TPU v5 lite") == pytest.approx(462e6 / 819e9)
    assert 1e3 * work.floor_seconds(tree, "TPU v5 lite") == pytest.approx(0.5641, abs=1e-4)
    # HBM-bound: the arithmetic is far under the compute peak
    assert tree["flops"] / 197e12 < 0.1 * tree["bytes"] / 819e9


def test_fm_floor_at_criteo():
    s = sizes("fm_criteo")
    w = work.counter("fm_pass")(s)
    assert s["train_rows"] == 5_242_880
    assert w["bytes"] == 5_242_880 * (40 * 8 + 8) + 2 * 262_144 * 9 * 4
    assert w["bytes"] == 1_719_664_640 + 18_874_368
    assert w["flops"] == 5_242_880 * 40 * 8 * 8
    assert 1e3 * work.floor_seconds(w, "TPU v5 lite") == pytest.approx(2.1228, abs=1e-3)
    # at the 2^22 rows ISSUE 28 reckoned with: 1.395 GB, 1.70 ms
    w22 = work.counter("fm_pass")({**s, "train_rows": 1 << 22})
    assert 1e3 * work.floor_seconds(w22, "TPU v5 lite") == pytest.approx(1.7028, abs=1e-3)


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        work.chip_peaks("TPU v99")
