"""executor compile: engine construction to the end of the warm-up dispatches."""


def read(run):
    return run.setup["compile_s"]
