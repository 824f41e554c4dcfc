"""serving host work: step wall time less device busy time, open-loop cells."""
from lib import readers


def read(run):
    return readers.host_ms_per_dispatch(run, "open_loop")
