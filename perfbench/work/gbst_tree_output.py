"""Work count `gbst_tree_output`: found by its name (see pb/work.py)."""

from __future__ import annotations


def count(sizes: dict) -> dict:
    """A soft tree's output over all train rows and its gradient, as one
    loss+gradient pass needs them: the values of every slot (float32) are
    read once, the table (`row_width` rows of `stride` = 2K - 1 floats: a
    feature's K - 1 gate and K expert weights) is read once and its gradient
    written once; every slot meets every float of its feature's row in one
    multiply-add forward and two backward (the table's gradient, the
    value's part of the gate's).

      bytes = rows * width * 4 + 2 * width * stride * 4
      flops = 3 * rows * width * stride * 2
    At 10.5M rows x 29 slots, stride 31: 1.218 GB, 1.49 ms at 819 GB/s; 56.6
    GFLOP, 0.29 ms at the bf16 peak (float32 on the vector units is slower;
    the count stays a lower bound). NOT counted: the slots' ids and a table
    row a slot. These rows are dense, every row carries the same `width` ids
    in the same order, and an evaluation as `val @ W` reads neither; a count
    that held today's gathers to be needed would let a later implementation
    read over 100%. The softmax over K and the weighted sum are left out
    (about 5 K flops a row).
    """
    n, wdt, stride = int(sizes["train_rows"]), int(sizes["row_width"]), int(sizes["stride"])
    return {"bytes": n * wdt * 4 + 2 * wdt * stride * 4, "flops": 3 * n * wdt * stride * 2}
