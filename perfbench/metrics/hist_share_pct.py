"""Histogram kernels' share of the device busy time."""
from pb.readers import kernel_share_pct


def read(run):
    return kernel_share_pct(run, "hist")
