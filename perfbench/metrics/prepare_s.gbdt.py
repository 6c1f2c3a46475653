"""Seconds of the trainer's preparation between binning and the compile
(span `gbdt.prepare`, device-settled), in set-up."""
from pb.spans import seconds_of, setup_spans


def read(run):
    return seconds_of(setup_spans(run), ("gbdt.prepare",))
