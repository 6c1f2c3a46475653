"""Row chunks a loss+gradient pass of a soft tree scans (gauge
`blocked.stat.chunks_per_pass`, set where the trainer chooses the chunk)."""


def read(run):
    return run.gauges.get("blocked.stat.chunks_per_pass")
