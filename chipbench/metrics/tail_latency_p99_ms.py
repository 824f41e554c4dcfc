"""Serving layer: 99th percentile of due-to-answer time, open loop.

Per layer, not end to end: near the knee a request's wait grows as
1 / (1 - load), so a few per cent of host speed between processes moves
this tail by several times as much (PERF.md §2).
"""
from lib import readers


def read(run):
    return readers.latency_ms(run, 99)
