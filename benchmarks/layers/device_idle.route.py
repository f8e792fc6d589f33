"""Share of the traced sub-window in which no operation ran on the
chip, under closed-loop getroute load."""
from lib import readers


def read(run):
    return readers.device_idle(run)
