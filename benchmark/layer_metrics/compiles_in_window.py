"""Compile cache: real backend compiles (compile_cache.stats()
xla_compiles_net) inside the measured window. Anything but 0 makes the run
not correct."""


def reduce(run):
    return run['result']['compiles_in_window']
