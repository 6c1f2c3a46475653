"""Share of the device busy time under the program's scope `gbdt.route`:
a wave's rows moved to their children, by the one-pass kernel `gbdt_route`
or, where its block does not hold the width, by a bins row a slot."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("gbdt.route",))
