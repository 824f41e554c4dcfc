"""serving (admission): median due time to the start of the serving step."""
from lib import readers


def read(run):
    return readers.queue_wait_ms(run, 50)
