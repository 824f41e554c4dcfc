"""deploy layer: wall time of deploy.build (schedule search, plan, lowering)."""


def read(run):
    return run.setup["build_s"]
