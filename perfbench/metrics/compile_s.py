"""Seconds of backend compilation (cache loads included) during set-up."""
from pb.readers import counter_in_setup


def read(run):
    return counter_in_setup(run, "compile.traces.backend_compile_secs")
