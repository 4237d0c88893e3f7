"""Decode scheduler: the share of the prefill slices dispatched that did NOT
start their prompt, in percent, over the rate part of the window, tracing
off. A prompt longer than the largest chunk program is prefilled in several
slices; every one after the first starts at start != 0: it attends the pages
the earlier ones wrote and, on a recurrent layer, starts from the state and
the convolution tail they left in the slot (`Start != 0`: the slot's rows
are kept, not born zero). The program's tick log carries, a tick, `slices`
(the slices it dispatched; DecodeStats.chunk_slices) and `slices_carried`
(those with start != 0; DecodeStats.slices_carried): 100 x sum of
`slices_carried` over sum of `slices`, over the rows that began in [t_open,
t_open + window_s). 0 where every prompt fits one slice (the reason_closed
cells); near 1 - 1 / (mean slices a prompt) where prompts are long. None
where the program keeps no tick log or its rows lack the column (the parent
of the PR that added it), or where the window dispatched no slice."""
from . import _oncpu


def reduce(run):
    rows = _oncpu.window_ticks(run)
    if rows is None or 'slices_carried' not in (rows.dtype.names or ()):
        return None
    slices = rows['slices'].sum()
    if not slices:
        return None
    return 100.0 * rows['slices_carried'].sum() / slices
