"""End to end: requests answered per second over a backlog window."""
from lib import readers


def read(run):
    return readers.throughput_rps(run)
