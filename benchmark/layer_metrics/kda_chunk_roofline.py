"""Op lowerings / kernels: the least time the chip could take over the Kimi
Delta Attention layers' CHUNKED rule in one prefill slice, over the device
time a dispatch of the configuration's LARGEST prefill chunk program spent
under linear_attention/delta_rule/, in percent. The least time is the LARGER
of two, not their sum — the configuration's own kda_chunk_flops(cfg, tokens)
over the chip's matrix peak and kda_chunk_bytes(cfg, tokens) over its memory
bandwidth (the chunked algorithm's products at one pass each; the row's
state once read and once written, the slice's q, k, v, g and beta once read
and its output once written) — reckoned at the MEAN REAL TOKENS OF A SLICE
(ssm_chunk_roofline.mean_slice_tokens: from the request log, not the
bucket's 512), so that a body that skips the sub-chunks past a row's length
cannot read over 100. Time: the median over the dispatches inside the traced
window on the busiest chip of the operations under the scope — the chunked
rule alone, whatever implements it (gated_delta_chunk's jnp body today). None
where the trace holds no provenance or no such dispatch, the program has no
such scope, the program keeps no request log, or the configuration's module
has no kda_chunk_flops / kda_chunk_bytes."""
from .linear_attention_roofline import DELTA_RULE, scope_seconds
from .ssm_chunk_device_ms import largest_chunk_program
from .ssm_chunk_roofline import mean_slice_tokens


def reduce(run):
    trace, ctx = run['trace'], run['ctx']
    flops_fn = getattr(ctx.model, 'kda_chunk_flops', None)
    bytes_fn = getattr(ctx.model, 'kda_chunk_bytes', None)
    largest = largest_chunk_program(ctx)
    if trace is None or None in (flops_fn, bytes_fn, largest):
        return None
    seconds = scope_seconds(trace, getattr(ctx.tracer, 'path', None),
                            DELTA_RULE, program=largest)
    tokens = mean_slice_tokens(run)
    if seconds is None or tokens is None:
        return None
    floor = max(flops_fn(ctx.cfg, tokens) / ctx.peaks['bf16_flops_per_s'],
                bytes_fn(ctx.cfg, tokens) / ctx.peaks['hbm_bytes_per_s'])
    return 100.0 * floor / seconds
