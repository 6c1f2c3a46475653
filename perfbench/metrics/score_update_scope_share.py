"""Share of the device busy time under the program's scope
`gbdt.score_update`: each row's leaf value looked up at the end of a tree
and added into the train and test scores. It says when that lookup is the
per-index gather again (8.3 at the parent of PR 34, under 1.1 after)."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("gbdt.score_update",))
