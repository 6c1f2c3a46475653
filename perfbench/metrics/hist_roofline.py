"""Root-scan floor a tree over the histogram kernels' device time a tree."""
from pb.readers import kernel_roofline_pct


def read(run):
    return kernel_roofline_pct(run, "hist")
