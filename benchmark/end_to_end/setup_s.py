"""Process start to the first instant of the measured window: imports, build,
compile or cache load, export or artifact load, warm-up, and the ramp."""


def reduce(run):
    return run['setup']['setup_s']
