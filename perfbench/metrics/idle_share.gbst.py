"""Share of the traced window of soft trees in which no operation ran on the
device: the host's sync and callback an iteration and its part of a tree
boundary."""
from pb.readers import idle_share_pct as read  # noqa: F401
