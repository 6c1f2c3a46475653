"""Work count `gbdt_part_scan`: found by its name (see pb/work.py)."""

from __future__ import annotations


def count(sizes: dict) -> dict:
    """What a tree's partitioned histogram passes must read whatever builds
    them: for every row one of their nodes holds (`part_rows_needed`, which
    the reader takes from the run's wave log, because how many rows late
    waves hold depends on the trees and not on the configuration alone) its
    F one-byte bins and its f32 gradient pair, and one add into the g and
    the h histogram a row and feature, as `gbdt_root_scan` counts a row.

      bytes = part_rows_needed * (F + 8)
      flops = part_rows_needed * F * 4
    At 2,000 columns and about 1.1M such rows a tree: 2.2 GB, 2.7 ms at 819
    GB/s.
    """
    r, f = float(sizes["part_rows_needed"]), int(sizes["features"])
    return {"bytes": r * (f + 8), "flops": r * f * 4}
