"""Decode scheduler: how many prompt slices a call of a chunk program carries
— over the calls of the chunked-prefill programs in the traced interval, the
mean number of real rows. The scheduler dispatches one prefill slice per
admitting request per tick; where the artifact holds a row program (the
largest chunk with a leading dimension of R) the slices that several
requests have due in one tick ride ONE call of it, and a slice alone takes
its own bucket's one-row program. The program's 'decode/dispatch' span
carries `program` ('chunk_128', 'chunk_128x4', 'step', ...) and, on a chunk
program's call, `rows`: the real rows of that call. Sum of `rows` over the
number of such spans; 1.0 where every slice is a call of its own. None where
no chunk call of the interval has the stat: the parent of the PR that added
it, an interval without a slice."""
from . import _spans


def reduce(run):
    rows = [int(st['rows']) for st in _spans.span_stats(run, 'decode/dispatch')
            if str(st.get('program', '')).startswith('chunk') and 'rows' in st]
    if not rows:
        return None
    return sum(rows) / len(rows)
