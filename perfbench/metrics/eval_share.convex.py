"""Percent of the window inside the callback's evaluations: the test loss of
every iteration (`train.test_loss`) and the metrics of every fifth
(`train.evaluate`)."""
from pb.spans import share_inside


def read(run):
    return share_inside(run, ("train.test_loss", "train.evaluate"))
