"""model step: served requests' operations over the traced window at the int8 peak, in %."""
from lib import readers


def read(run):
    return readers.dispatch_mfu_pct(run)
