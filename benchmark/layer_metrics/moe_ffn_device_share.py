"""Op lowerings / kernels: the share of the decode step's device time spent
in operations that a routed feed-forward produced — the Fluid op type
moe_topk_ffn, which holds the float32 router and its top-k, the sort of
the (token, expert) pairs and the gather of their rows, the three grouped
matmuls and the weighted combine. An operation belongs to the Fluid op
whose type is a scope of its op_name (decode_attention_device_share says
how the program writes it). The grouped matmuls themselves are
lax.ragged_dot, which XLA's TPU backend rewrites into custom calls that
keep NO scope: their op_name reads a bare 'ragged-dot-none:' (read on the
chip, PR 26: 18 of them, 68 % of the step). An unscoped ragged dot is
therefore counted in where the program names a moe_topk_ffn op at all: the
decode programs have no other ragged dot. Read in the dispatches of the
cell's main program on the busiest chip. None where the trace holds no
provenance (no device plane: the cpu) or the program has no such op."""
import re

from .decode_attention_device_share import scope_share

MOE = re.compile(r'/moe_topk_ffn/')
# a ragged dot under no Fluid op's scope, as the chip's traces print it
UNSCOPED_RAGGED_DOT = re.compile(r'^ragged-dot')


def reduce(run):
    return scope_share(run['trace'],
                       getattr(run['ctx'].tracer, 'path', None), MOE,
                       also=UNSCOPED_RAGGED_DOT)
