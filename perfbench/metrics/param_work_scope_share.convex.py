"""Share of the device busy time under the program's scope
`blocked.prepare`: what a model derives from its parameters alone (FM's and
FFM's lookup table) and the turning back of the summed gradient, done once a
pass outside the chunk scan. It says when a model's `prepare` has grown."""
from pb.spans import scope_share_pct


def read(run):
    return scope_share_pct(run, ("blocked.prepare",))
