"""Share of the device busy time under the program's subscope
`gbdt.hist.part`: the partitioned histogram passes with their row
compaction and gathers. It overlaps `hist_scope_share`, which holds every
histogram pass's kernel, these among them."""
from pb.subscopes import subscope_share_pct


def read(run):
    return subscope_share_pct(run, ("gbdt.hist.part",))
