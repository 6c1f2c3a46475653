"""Share of the device busy time under the program's scope `gbdt.hist`
(the histogram build), by the scope map the program wrote at compile."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("gbdt.hist",))
