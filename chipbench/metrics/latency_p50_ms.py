"""End to end: median due-to-answer time, open loop."""
from lib import readers


def read(run):
    return readers.latency_ms(run, 50)
