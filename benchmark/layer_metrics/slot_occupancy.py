"""Decode scheduler: active slot-steps / slot-steps over the window
(DecodeStats counters, as deltas)."""


def reduce(run):
    c = run['result']['counters_window']
    return 100.0 * c['active_slot_steps'] / c['slot_steps']
