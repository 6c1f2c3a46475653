"""Work count `ffm_pair`: found by its name (see pb/work.py)."""

from __future__ import annotations


def count(sizes: dict) -> dict:
    """The field-pair term of one FFM loss+gradient pass: a row with one
    feature a field has (width - 1)(width - 2) / 2 pairs of slots that
    count (the bias's latent row is masked), each a dot product of two
    k-vectors scaled by the two values: 2 k flops forward, and twice that
    backward (each of the two vectors gets the other, scaled).

      flops = rows * pairs * k * 2 * 3,  pairs = (width - 1)(width - 2) / 2
      bytes = 0   (its operands are the rows the lookup has read already)
    At 2^20 rows, width 40 (741 pairs), k 4: 18.6 GFLOP, 0.095 ms at the bf16
    peak (float32 on the vector units is slower; the count stays a lower
    bound).
    """
    n, wdt, k = int(sizes["train_rows"]), int(sizes["row_width"]), int(sizes["latent_dim"])
    pairs = (wdt - 1) * (wdt - 2) // 2
    return {"bytes": 0, "flops": n * pairs * k * 2 * 3}
