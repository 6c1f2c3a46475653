"""Seconds of binning and device placement (`gbdt.stat.preprocess`)."""


def read(run):
    return run.gauges.get("gbdt.stat.preprocess")
