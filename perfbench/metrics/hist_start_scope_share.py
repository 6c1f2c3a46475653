"""Share of the device busy time under the program's subscope
`gbdt.hist.start`: the root's and the slow start's histogram passes, the
narrow waves whose kernel factors the bin one-hot. It overlaps
`hist_scope_share`, which holds every histogram pass's kernel, these among
them. A program without the subscope has nothing to read: None."""
from pb.subscopes import subscope_share_pct


def read(run):
    return subscope_share_pct(run, ("gbdt.hist.start",))
