"""Share of the device busy time under the program's scope `ffm.pair`: the
field-pair term, forward and backward."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("ffm.pair",))
