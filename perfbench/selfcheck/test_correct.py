"""`correct` has been shown to fail: on the CPU at a size a test run can
hold, the rest of a run is driven past the harness's look for a chip with
the timed path broken underneath (once for each fault a cell can have) and
with the lower-precision control in the program's place. The limits are the
committed ones. The same controls were read on the chip at the cells' own
sizes (PERF.md section 2); this keeps them as tests.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/selfcheck -q -p no:cacheprovider
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

import run as harness  # noqa: E402
from pb import manifest  # noqa: E402

TINY = {
    # leaves stay well above min_child_hessian_sum, as at the cell's size
    "gbdt_higgs.train": dict(
        sizes={"train_rows": 65536, "test_rows": 4096},
        program={"round_num": 8}, seconds=0.2),
    "fm_criteo.train": dict(
        sizes={"train_rows": 16384, "test_rows": 2048, "hashed_dim": 4096},
        program={}, seconds=0.2),
}


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)


def drive(name, fault=None, overrides=None, after=None, seed=2147483659, spec=None):
    spec = spec or TINY[name]
    cell = tiny.tiny_cell(name, spec["sizes"], spec["program"])
    cell.config["compare"]["reference_block_rows"] = 4096
    family = manifest.load_module("families", cell.config["family"])
    mend = family.plant(fault) if fault else None
    try:
        return harness.drive(cell, seed, spec["seconds"], False, tiny.CPU_DEVICE,
                             overrides=overrides, after=after)
    finally:
        if mend is not None:
            mend()


@pytest.mark.parametrize("name", sorted(TINY))
def test_the_program_as_configured_is_correct(name):
    res = drive(name)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"  # the numbers compared come last


def faults_of(name):
    family = tiny.tiny_cell(name, {}).config["family"]
    return manifest.load_module("families", family).FAULTS


@pytest.mark.parametrize("name,fault", [(n, f) for n in sorted(TINY) for f in faults_of(n)])
def test_a_planted_fault_comes_out_not_correct(name, fault):
    res = drive(name, fault=fault)
    assert not res["correct"], (fault, res["compared"])
    failing = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    # a histogram that leaves out features, or bins more coarsely, grows a
    # tree whose own statistics agree: only the root's check sees it
    if fault == "dropped_features":
        assert "root_gain_gap" in failing, res["compared"]
    if fault == "coarse_bins":
        assert "root_thr_off" in failing, res["compared"]


def test_gbdt_control_int8_histograms_reads_apart_from_the_program():
    """int8's absolute step drowns the small gradients of late trees in
    large leaves: at the cell's size the control fails the committed limits
    (PERF.md section 2: 0.068 against 0.02); at a size the CPU can hold the
    same two numbers read three times the program's or more, which is what
    this keeps, on 60 trees grown to the end."""
    name = "gbdt_higgs.train"
    spec = dict(sizes={"train_rows": 16384, "test_rows": 2048},
                program={"round_num": 60}, seconds=3600.0)
    control = tiny.tiny_cell(name, {}).config["control"]["overrides"]
    read = {}
    for what, overrides in (("program", {}), ("control", control)):
        res = drive(name, overrides=overrides, spec=spec)
        assert res["attempted"] == 60
        read[what] = {k: v["value"] for k, v in res["compared"].items()}
    assert read["program"]["leaf_gap.last"] > 0
    for k in ("leaf_gap.last", "gain_gap.last"):
        assert read["control"][k] >= 3 * read["program"][k], (k, read)


def test_fm_control_bfloat16_reference_is_not_correct():
    got = {}
    cell = tiny.tiny_cell("fm_criteo.train", {})
    family = manifest.load_module("families", "convex")

    def after(run, state):
        got.update(family.control_checks(run, state, cell.config["control"]))

    res = drive("fm_criteo.train", after=after)
    assert res["correct"]
    assert got and not harness.verdict(got), got
