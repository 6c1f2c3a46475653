"""Share of the device busy time under the program's scope `gbdt.split`:
the split search over a wave's 2 x 64 children, F x B candidates a node. At
28 columns it is nothing (0.05); at 2,000 it is a layer of its own."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("gbdt.split",))
