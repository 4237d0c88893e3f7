"""Decode scheduler: bytes the step program's logits copy moves to the
host each tick — the median 'bytes' stat of the 'decode/d2h' spans whose
'program' is 'step'. A count: max_slots x vocab x 4."""
from . import _spans


def reduce(run):
    return _spans.stat_median(run, 'decode/d2h', 'bytes',
                              where=lambda st: st.get('program') == 'step')
