"""Device: 1 - (union of the intervals in which an operation ran on the
device) / (the traced window), in percent."""
from bench import readout


def read(rec):
    return readout.idle_share(rec)
