"""Share of the device busy time under the program's scope `gbst.mixture`:
the two contractions over a row's slots, the gate probabilities and the
weighted sum of a soft tree, forward and backward."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("gbst.mixture",))
