"""Op lowerings / kernels: the least time the chip could take over the
state-space layers' CHUNK form in one prefill slice, over the device time a
dispatch of the configuration's LARGEST prefill chunk program spent under
state_space/selective_scan/, in percent. The least time is the LARGER of
two, not their sum — the configuration's own ssd_chunk_flops(cfg, tokens)
over the chip's matrix peak and ssd_chunk_bytes(cfg, tokens) over its memory
bandwidth (the dual form at the published chunk size; the row's state once
read and once written, the slice's inputs and output once) — reckoned at the
MEAN REAL TOKENS OF A SLICE, not at the program's size: a kernel that skips
the sub-chunks past a row's length must not be able to read over 100, which
a yardstick at the full program would let it. Real tokens: over the requests
submitted in the rate part of the window, tracing off, the request log's
prompt positions that were prefilled (`prompt_len` - `prefix_covered`) over
its `slices` — the tick log's `slice_tokens` counts a slice at its BUCKET's
size (512 for every prompt of 160-512), which is the yardstick this reader
must not use. Time: the median over the dispatches inside the traced window
on the busiest chip of the operations under the scope. None where the trace
holds no provenance or no such dispatch, the program has no such scope, the
program keeps no request log (the parent of the PR that added it), or the
configuration's module has no ssd_chunk_flops / ssd_chunk_bytes."""
from . import _requests
from .linear_attention_roofline import scope_seconds
from .ssm_chunk_device_ms import largest_chunk_program
from .ssm_scan_device_share import SELECTIVE_SCAN


def mean_slice_tokens(run):
    """Real prompt tokens a prefill slice, over the window's requests; None
    without a request log or its columns."""
    rows = _requests.window_requests(run)
    names = () if rows is None else (rows.dtype.names or ())
    if not {'prompt_len', 'slices'} <= set(names):
        return None
    covered = rows['prefix_covered'] if 'prefix_covered' in names else 0
    slices = rows['slices'].sum()
    if not slices:
        return None
    return float((rows['prompt_len'] - covered).sum()) / float(slices)


def reduce(run):
    trace, ctx = run['trace'], run['ctx']
    flops_fn = getattr(ctx.model, 'ssd_chunk_flops', None)
    bytes_fn = getattr(ctx.model, 'ssd_chunk_bytes', None)
    largest = largest_chunk_program(ctx)
    if trace is None or None in (flops_fn, bytes_fn, largest):
        return None
    seconds = scope_seconds(trace, getattr(ctx.tracer, 'path', None),
                            SELECTIVE_SCAN, program=largest)
    tokens = mean_slice_tokens(run)
    if seconds is None or tokens is None:
        return None
    floor = max(flops_fn(ctx.cfg, tokens) / ctx.peaks['bf16_flops_per_s'],
                bytes_fn(ctx.cfg, tokens) / ctx.peaks['hbm_bytes_per_s'])
    return 100.0 * floor / seconds
