"""kernels: the residual add kernel's least time over its device time, in %."""
from lib import readers


def read(run):
    return readers.roofline_pct(run, "qadd")
