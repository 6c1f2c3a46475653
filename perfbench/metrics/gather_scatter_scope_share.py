"""Share of the device busy time under the program's scopes `fm.gather_w`
and `fm.gather_v`: the two gathers and, through autodiff, their transposes
(the two scatter-adds, with the sort XLA puts in front of the latent one)."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("fm.gather_w", "fm.gather_v"))
