"""Work count `gbst_pass`: found by its name (see pb/work.py)."""

from __future__ import annotations

from pb.work import counter


def count(sizes: dict) -> dict:
    """One loss+gradient pass of a boosted soft tree over all train rows
    must at least evaluate the tree's output and its gradient
    (`gbst_tree_output`) and stream z, y and the weight of every row once.

      bytes = gbst_tree_output.bytes + rows * 12
      flops = gbst_tree_output.flops
    At 10.5M rows x 29 slots, K 16: 1.218 GB + 0.126 GB = 1.344 GB, 1.64 ms
    at 819 GB/s; 56.6 GFLOP, 0.29 ms at the bf16 peak: HBM-bound. What any
    implementation must do, not what today's does: see `gbst_tree_output`.
    """
    out = counter("gbst_tree_output")(sizes)
    return {"bytes": out["bytes"] + int(sizes["train_rows"]) * 12, "flops": out["flops"]}
