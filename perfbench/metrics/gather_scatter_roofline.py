"""Pass floor over the gather/scatter fusions' device time a pass."""
from pb.readers import kernel_roofline_pct


def read(run):
    return kernel_roofline_pct(run, "gather_scatter")
