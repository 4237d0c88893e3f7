"""Compile cache: real backend compiles during set-up (0 on a warm run)."""


def reduce(run):
    return run['setup']['setup_compiles_net']
