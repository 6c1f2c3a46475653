"""Percent of the window inside a tree boundary's spans: `gbst.fold` (the
tree folded into the train and test scores, the ensemble's losses; the host
waits for the device in it), `gbst.dump` and `gbst.masks` (the host's mask
draws, the weights' round trip, the re-init)."""
from pb.spans import share_inside


def read(run):
    return share_inside(run, ("gbst.fold", "gbst.dump", "gbst.masks"))
