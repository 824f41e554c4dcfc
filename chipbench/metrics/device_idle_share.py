"""device: share of the traced window with no operation on the device, in %."""
from lib import readers


def read(run):
    return readers.idle_pct(run)
