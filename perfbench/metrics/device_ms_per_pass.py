"""Device busy milliseconds a data pass in the traced window."""
from pb.readers import device_ms_per_step as read  # noqa: F401
