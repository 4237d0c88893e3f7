"""Op lowerings / kernels: the share of the decode step's device time spent
in the projections AROUND a latent attention's trip through the pages —
the low-rank query (q_a, its norm, q_b, the rotary part), the latent
down-projection with its norm and rotary key, the key up-projection folded
into the query, and the value up-projection unfolded from the result. The
program lowers them under the scopes latent_attention/q_lora, kv_down,
q_absorb and v_expand (models/joyai_llm_flash.py: fluid.name_scope, which
core/lowering.py turns into jax.named_scope); the trip itself keeps the
kv_block_attention scope, which decode_attention_device_share and
decode_attention_roofline read. Read in the dispatches of the cell's main
program on the busiest chip. None where the trace holds no provenance (no
device plane: the cpu) or the program has no such scope."""
import re

from .decode_attention_device_share import scope_share

LATENT_PROJ = re.compile(
    r'/latent_attention/(?:q_lora|kv_down|q_absorb|v_expand)/')


def reduce(run):
    if run['trace'] is None:
        return None
    return scope_share(run['trace'],
                       getattr(run['ctx'].tracer, 'path', None), LATENT_PROJ)
