"""Floor time of the window's lookups (work count `ffm_lookup`) over the
device seconds under the scope `ffm.gather`."""
from pb.scope_roofline import scope_roofline_pct


def read(run):
    return scope_roofline_pct(run, "ffm.gather")
