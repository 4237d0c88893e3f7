"""Decode scheduler: milliseconds per dispatch in 'decode/advance' and
'decode/first_token': host argmax (or beam scoring) over the fetched
logits, emit to the streams, finishing what ended. Argmax and emit
interleave per request in the code, so one span holds both."""
from . import _spans


def reduce(run):
    return _spans.tick_phase_ms(run, _spans.TICK_ADVANCE)
