"""The whole step: floor time of the algorithm's work over the window."""
from pb.readers import step_mfu_pct as read  # noqa: F401
