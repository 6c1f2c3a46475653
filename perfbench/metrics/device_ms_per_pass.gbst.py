"""Device busy milliseconds a counted data pass in the traced window of soft
trees: the folds' forward evaluations and the uncounted trials of a failed
line search are in the busy seconds and not among the passes."""
from pb.readers import device_ms_per_step as read  # noqa: F401
