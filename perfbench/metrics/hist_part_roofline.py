"""Floor time of the rows the partitioned passes needed (work count
`gbdt_part_scan`) over the device seconds under the subscope
`gbdt.hist.part`. The rows are the run's wave log's, a tree (gauges
`gbdt.stat.hist_part_rows_needed` over `gbdt.stat.trees_logged`: every tree
the run grew, the warm-up's six among them), times the window's trees."""
from pb import subscopes, work


def read(run):
    by = subscopes.subscope_seconds(run)
    rows = run.gauges.get("gbdt.stat.hist_part_rows_needed")
    trees = run.gauges.get("gbdt.stat.trees_logged")
    if by is None or not rows or not trees or run.window.steps <= 0:
        return None
    seconds = by.get("gbdt.hist.part")
    if not seconds:
        return None
    count = work.counter("gbdt_part_scan")(
        {**run.cell.sizes, "part_rows_needed": rows / trees})
    floor = work.floor_seconds(count, run.device["kind"])
    return 100.0 * floor * run.window.steps / seconds
