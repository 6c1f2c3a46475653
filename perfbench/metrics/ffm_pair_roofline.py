"""Floor time of the window's field-pair terms (work count `ffm_pair`) over
the device seconds under the scope `ffm.pair`."""
from pb.scope_roofline import scope_roofline_pct


def read(run):
    return scope_roofline_pct(run, "ffm.pair")
