"""Work count `gbdt_root_scan`: found by its name (see pb/work.py)."""

from __future__ import annotations


def count(sizes: dict) -> dict:
    """What the root histogram of one tree must read whatever kernel builds
    it: every row's F one-byte bins and its f32 gradient pair.

      bytes = rows * (F * 1 + 2 * 4)
      flops = rows * F * 2 * 2   (one add into the g and the h histogram per
                                  row and feature; the one-hot matmul the
                                  program uses spends B times that, which is
                                  the implementation's cost, not the
                                  algorithm's)
    At Higgs: 10.5e6 * 36 = 378 MB, 0.46 ms at 819 GB/s.
    """
    n, f = int(sizes["train_rows"]), int(sizes["features"])
    return {"bytes": n * (f + 8), "flops": n * f * 4}
