"""The `ffm_criteo.train` cell's self-checks on the CPU, as
`test_correct.py` and `test_work.py` keep them for the cells before it: the
program as configured comes out `correct` at a tiny size against the
committed limits; each planted fault and the bfloat16 control put in the
program's place come out not correct; the three work counts against values
computed by hand at the configuration's sizes.

    JAX_PLATFORMS=cpu python3 -m pytest perfbench/selfcheck -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

import run as harness  # noqa: E402
from pb import manifest, work  # noqa: E402

NAME = "ffm_criteo.train"
SIZES = {"train_rows": 8192, "test_rows": 1024, "hashed_dim": 2048}


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)


def drive(fault=None, after=None, seed=2147483659):
    cell = tiny.tiny_cell(NAME, SIZES)
    cell.config["compare"]["reference_block_rows"] = 2048
    family = manifest.load_module("families", cell.config["family"])
    mend = family.plant(fault) if fault else None
    try:
        return harness.drive(cell, seed, 0.2, False, tiny.CPU_DEVICE, after=after)
    finally:
        if mend is not None:
            mend()


def test_the_program_as_configured_is_correct():
    res = drive()
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["compared"]) == {"loss_gap", "grad_gap", "dw_gap", "handback_gap"}
    assert set(res["metrics"]) == {"examples_per_s", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize(
    "fault", manifest.load_module("families", "ffm").FAULTS)
def test_a_planted_fault_comes_out_not_correct(fault):
    res = drive(fault=fault)
    assert not res["correct"], (fault, res["compared"])


def test_control_bfloat16_reference_is_not_correct():
    got = {}
    cell = tiny.tiny_cell(NAME, {})
    family = manifest.load_module("families", "ffm")

    def after(run, state):
        got.update(family.control_checks(run, state, cell.config["control"]))
        got["gauges"] = dict(run.gauges)

    res = drive(after=after)
    gauges = got.pop("gauges")
    assert res["correct"]
    assert got and not harness.verdict(got), got
    # what the new per-layer readers read of the program
    assert gauges["ffm.stat.gather_width"] == 157 and gauges["ffm.stat.fields"] == 39
    assert gauges["lbfgs.stat.state_bytes"] == (2 * 8 + 4) * 2048 * 157 * 4
    assert gauges["blocked.stat.chunks_per_pass"] >= 1


def sizes():
    with open(os.path.join(manifest.BENCH_DIR, "configs", "ffm_criteo.json")) as f:
        return json.load(f)["sizes"]


def test_ffm_floors_at_criteo():
    s = sizes()
    assert (s["train_rows"], s["hashed_dim"], s["fields"], s["latent_dim"]) == (
        1 << 20, 1 << 18, 39, 4)
    lookup = work.counter("ffm_lookup")(s)
    pair = work.counter("ffm_pair")(s)
    whole = work.counter("ffm_pass")(s)
    # a 157-float row and its index for each of 39 slots a row; the table twice
    assert lookup["bytes"] == (1 << 20) * 39 * (628 + 4) + 2 * (1 << 18) * 628
    assert lookup["bytes"] == 25_845_301_248 + 329_252_864 and lookup["flops"] == 0
    assert 1e3 * work.floor_seconds(lookup, "TPU v5 lite") == pytest.approx(31.959, abs=1e-3)
    # 741 pairs of 4-vectors a row: 2 k flops forward, twice that backward
    assert pair == {"bytes": 0, "flops": (1 << 20) * 741 * 4 * 2 * 3}
    assert 1e3 * work.floor_seconds(pair, "TPU v5 lite") == pytest.approx(0.09466, abs=1e-4)
    # the pass: idx, val, field of every slot, y and weight, the lookups
    assert whole["bytes"] == (1 << 20) * (40 * 12 + 8) + (1 << 20) * 39 * 628 + 2 * (1 << 18) * 628
    assert whole["flops"] == pair["flops"]
    assert 1e3 * work.floor_seconds(whole, "TPU v5 lite") == pytest.approx(32.384, abs=1e-3)
    # HBM-bound, and the parts never exceed the whole
    assert whole["flops"] / 197e12 < 0.01 * whole["bytes"] / 819e9
    assert lookup["bytes"] < whole["bytes"]
