"""Work count `ffm_pass`: found by its name (see pb/work.py)."""

from __future__ import annotations

from pb.work import counter


def count(sizes: dict) -> dict:
    """One FFM loss+gradient pass over all train rows must at least stream
    idx (int32), val (f32) and field (int32) of every slot and y and weight
    of every row once, make the lookups (`ffm_lookup`: a 628 B table row for
    every slot but the bias's, the table read and written once) and compute
    the field-pair term (`ffm_pair`).

      bytes = rows * (width * 12 + 8) - rows * (width - 1) * 4 + ffm_lookup.bytes
              (the index of a looked-up slot is counted in ffm_lookup, once)
      flops = ffm_pair.flops
    At 2^20 rows x 40 slots, 2^18 ids, F 39, k 4: 0.35 GB + 26.17 GB =
    26.52 GB, 32.4 ms at 819 GB/s; 18.6 GFLOP, 0.095 ms: HBM-bound.
    """
    n, wdt = int(sizes["train_rows"]), int(sizes["row_width"])
    lookup, pair = counter("ffm_lookup")(sizes), counter("ffm_pair")(sizes)
    stream = n * (wdt * 12 + 8) - n * (wdt - 1) * 4
    return {"bytes": stream + lookup["bytes"], "flops": pair["flops"]}
