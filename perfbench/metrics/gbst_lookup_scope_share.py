"""Share of the device busy time under the program's scope `gbst.lookup`:
the per-slot gathers of a soft tree's table (`W[idx]`, `gate_mask[idx]`)
and, through autodiff, their scatter-add with the sort XLA puts before it."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("gbst.lookup",))
