"""Work count `ffm_lookup`: found by its name (see pb/work.py)."""

from __future__ import annotations


def row_floats(sizes: dict) -> int:
    """Floats of the table row a slot looks up: the id's first-order weight
    and its latent vector for every field."""
    return 1 + int(sizes["fields"]) * int(sizes["latent_dim"])


def count(sizes: dict) -> dict:
    """The lookups of one FFM loss+gradient pass over all train rows: every
    slot but the bias's (whose latent row is masked and whose weight is one
    float) needs its id's whole table row, 1 + F k floats, because the row
    holds a vector for each of the F fields and a row with one feature a
    field pairs the slot with all of them. The table (2^18 x 157 x 4 B = 165
    MB) does not fit the chip's VMEM (128 MiB), so a row is read from HBM
    where it is needed; the gradient has the table's shape, so the table is
    read once and written once besides. The index of a slot (int32) is read
    to find the row.

      bytes = rows * (width - 1) * ((1 + F k) * 4 + 4) + 2 * ids * (1 + F k) * 4
      flops = 0   (a lookup computes nothing; the scatter-add's additions
                   are left out, which keeps the count a lower bound)
    At 2^20 rows x 39 slots, 2^18 ids, F 39, k 4: 25.85 GB + 0.33 GB =
    26.17 GB, 31.96 ms at 819 GB/s. The 13 numeric columns' ids are the same
    in every row and are counted as read a row all the same: the count
    takes the sizes alone, not the data's skew (PERF.md section 7).
    """
    n, wdt, ids = int(sizes["train_rows"]), int(sizes["row_width"]), int(sizes["hashed_dim"])
    row = row_floats(sizes) * 4
    return {"bytes": n * (wdt - 1) * (row + 4) + 2 * ids * row, "flops": 0}
