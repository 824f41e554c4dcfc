"""executor: device busy time per dispatch, open-loop cells."""
from lib import readers


def read(run):
    return readers.device_ms_per_dispatch(run, "open_loop")
