"""GiB the optimizer keeps on the device of its own once its history is
full (gauge `lbfgs.stat.state_bytes`: the m (s, y) pairs, w, g, the
direction and the trial point, from shapes)."""


def read(run):
    v = run.gauges.get("lbfgs.stat.state_bytes")
    return None if v is None else v / 2**30
