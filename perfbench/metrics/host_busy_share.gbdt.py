"""Percent of the window the host did NOT stand in a `gbdt.sync`, the span
in which it waits for the device's loss: its own share of the loop, the
dispatches included. A dispatch (`gbdt.round`) that finds the runtime's queue
full blocks too, so this reads the host's work PLUS its wait in dispatch
(PERF.md section 5)."""
from pb.spans import share_outside


def read(run):
    return share_outside(run, ("gbdt.sync",))
