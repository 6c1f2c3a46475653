"""The whole step of the soft trees: floor time of the work a data pass needs
(work count `gbst_pass`) for the window's passes over the window's seconds."""
from pb.readers import step_mfu_pct as read  # noqa: F401
