"""Samples trained per second of window over the whole cell (all its
chips): steps completed x global batch / window, the window closed by a
host read of the last step's loss."""


def reduce(run):
    return run['result']['samples_per_s']
