"""Decode scheduler: milliseconds per dispatch in 'decode/d2h', the copy
of the fetched logits to the host after the device has finished (the wait
for the device is 'decode/device_wait' and is in no tick_* metric)."""
from . import _spans


def reduce(run):
    return _spans.tick_phase_ms(run, _spans.TICK_D2H)
