"""Work count `fm_pass`: found by its name (see pb/work.py)."""

from __future__ import annotations


def count(sizes: dict) -> dict:
    """One FM loss+gradient pass over all train rows must at least stream
    idx (int32) and val (f32) of every slot, y and weight of every row, and
    read and write the parameter table once each (the gradient has the
    table's shape).

      bytes = rows * (width * 8 + 8) + 2 * hashed_dim * (1 + k) * 4
      flops = rows * width * k * 8   (forward: v*x, its sum and sum of
                                      squares, 4 a slot and factor; backward
                                      the same again)
    At Criteo 2^22 x 40, 2^18 ids, k=8: 1.376 GB + 18.9 MB = 1.395 GB,
    1.70 ms at 819 GB/s; 10.7 GFLOP, 0.05 ms at the bf16 peak: HBM-bound.
    """
    n, wdt = int(sizes["train_rows"]), int(sizes["row_width"])
    dim, k = int(sizes["hashed_dim"]), int(sizes["latent_dim"])
    return {
        "bytes": n * (wdt * 8 + 8) + 2 * dim * (1 + k) * 4,
        "flops": n * wdt * k * 8,
    }
