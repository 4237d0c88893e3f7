"""Output tokens delivered to consumers inside the window / window."""


def reduce(run):
    return run['result']['tokens_per_s']
