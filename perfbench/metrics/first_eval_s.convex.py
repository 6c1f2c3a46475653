"""Seconds of the first loss+gradient evaluation, its compile or cache load
included (span `lbfgs.first_eval`, settled by its fetch), in set-up."""
from pb.spans import seconds_of, setup_spans


def read(run):
    return seconds_of(setup_spans(run), ("lbfgs.first_eval",))
