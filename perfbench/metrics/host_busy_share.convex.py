"""Percent of the window the host spent outside the spans in which it waits
for the device (`lbfgs.iteration`, `train.test_loss`, `train.evaluate`)."""
from pb.spans import share_outside


def read(run):
    return share_outside(run, ("lbfgs.iteration", "train.test_loss", "train.evaluate"))
