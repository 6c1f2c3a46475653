"""Share of the device busy time under the program's scope `ffm.gather`:
the one lookup a slot and, through autodiff, its scatter-add with the sort
and the layout copies XLA puts beside them."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("ffm.gather",))
