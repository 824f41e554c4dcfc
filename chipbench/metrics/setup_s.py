"""End to end: process start to the end of the engine's warm-up."""


def read(run):
    return run.setup["setup_s"]
