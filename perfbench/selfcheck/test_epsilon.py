"""`gbdt_epsilon.train`'s `correct` has been shown to fail: the cell's faults
and its int8 control at a tiny wide size on the CPU, against the committed
limits, as `test_correct.py` keeps `gbdt_higgs.train`'s. The same were read
on the chip at the cell's own size (PERF.md section 2).

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/selfcheck/test_epsilon.py -q -p no:cacheprovider
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

import run as harness  # noqa: E402
from pb import manifest  # noqa: E402

NAME = "gbdt_epsilon.train"
# wide for its rows as the cell is (rows a leaf well above
# min_child_hessian_sum), at a size the dense twins hold on a CPU
TINY = dict(sizes={"train_rows": 32768, "test_rows": 4096, "features": 96},
            program={"round_num": 8}, seconds=0.2)


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)


def drive(fault=None, overrides=None, spec=TINY, seed=2147483693):
    cell = tiny.tiny_cell(NAME, spec["sizes"], spec["program"])
    family = manifest.load_module("families", cell.config["family"])
    mend = family.plant(fault) if fault else None
    try:
        return harness.drive(cell, seed, spec["seconds"], False, tiny.CPU_DEVICE,
                             overrides=overrides)
    finally:
        if mend is not None:
            mend()


def test_the_program_as_configured_is_correct():
    res = drive()
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize(
    "fault", manifest.load_module("families", "gbdt").FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault):
    res = drive(fault=fault)
    assert not res["correct"], (fault, res["compared"])
    failing = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    if fault == "dropped_features":
        assert "root_gain_gap" in failing, res["compared"]
    if fault == "coarse_bins":
        assert "root_thr_off" in failing, res["compared"]


def test_control_int8_histograms_reads_apart_from_the_program():
    """As at Higgs' width: at a size the CPU can hold the control's late
    trees read three times the program's or more in the two numbers that
    fail it at the cell's size."""
    spec = dict(sizes={"train_rows": 16384, "test_rows": 2048, "features": 96},
                program={"round_num": 60}, seconds=3600.0)
    control = tiny.tiny_cell(NAME, {}).config["control"]["overrides"]
    read = {}
    for what, overrides in (("program", {}), ("control", control)):
        res = drive(overrides=overrides, spec=spec)
        assert res["attempted"] == 60
        read[what] = {k: v["value"] for k, v in res["compared"].items()}
    for k in ("leaf_gap.last", "gain_gap.last"):
        assert read["control"][k] >= 3 * read["program"][k], (k, read)
