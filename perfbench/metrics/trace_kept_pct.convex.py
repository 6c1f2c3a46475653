"""Launches the program counted in the traced window whose module event the
device trace kept, in percent: under 100 where the profiler cut the trace
and the rest of the window would read as idle."""
from pb.kept import trace_kept_pct as read  # noqa: F401
