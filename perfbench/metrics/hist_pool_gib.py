"""GiB of the round program's histogram pool, `(max_nodes, F, B, 3)`
float32 (gauge `gbdt.stat.hist_pool_bytes`, from shapes): 0.041 at 28
columns, 2.9125 at 2,000, where it and not the rows is the memory."""


def read(run):
    v = run.gauges.get("gbdt.stat.hist_pool_bytes")
    return None if v is None else v / 2**30
