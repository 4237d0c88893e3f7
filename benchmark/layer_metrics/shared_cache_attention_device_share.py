"""Op lowerings / kernels: the share of the decode step's device time spent
in the attention ops of layers that keep no cache and read ANOTHER layer's
— a decoder-hybrid-decoder's cross-decoder, whose attention layers all read
the one K/V cache its self-decoder's full layer wrote. The program lowers
those ops under cross_decoder/ (models/phi4_flash.py), so that the one
cache's readers can be told from the layers that attend their own pools:
decode_attention_device_share holds both. Read in the dispatches of the
cell's main program on the busiest chip. None where the trace holds no
provenance (no device plane: the cpu) or the program has no such scope."""
import re

from .decode_attention_device_share import scope_share

CROSS_ATTENTION = re.compile(r'/cross_decoder/(?:[^/]+/)*kv_\w*attention\w*/')


def reduce(run):
    if run['trace'] is None:
        return None
    return scope_share(run['trace'],
                       getattr(run['ctx'].tracer, 'path', None),
                       CROSS_ATTENTION)
