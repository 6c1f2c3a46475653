"""Work count `gbdt_tree`: found by its name (see pb/work.py)."""

from __future__ import annotations

from pb.work import counter


def count(sizes: dict) -> dict:
    """A true lower bound on one boosted tree whatever its shape: the root
    scan plus one read and one write of the f32 train scores (rows * 8 B).
    Deeper levels' scans depend on the data (with the smaller-child rule at
    most half a scan a level) and are left out, so the share is conservative
    and stays under 100% for any tree.
    At Higgs: 10.5e6 * 44 = 462 MB, 0.564 ms at 819 GB/s.
    """
    n = int(sizes["train_rows"])
    root = counter("gbdt_root_scan")(sizes)
    return {"bytes": root["bytes"] + n * 8, "flops": root["flops"] + n * 2}
