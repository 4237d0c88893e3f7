"""transformer_base_lm: everything the decode runners ask a configuration
for — how the file becomes a decode artifact, what the traffic generator
and the warm-up need to know of it, what the plain reference
(benchmark/reference/decoder_lm.py) says a sequence scores, and what a
decode step has to move at the least."""
from __future__ import annotations

BOUND = 'memory'     # which roofline bounds the decode step
_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2, 'int8': 1}


def build_spec(cfg):
    """The decode program set, through the repo's own builder."""
    from models.transformer import build_decode_spec
    spec = build_decode_spec(
        vocab=int(cfg['vocab']), d_model=int(cfg['d_model']),
        n_head=int(cfg['n_head']), n_layer=int(cfg['n_layer']),
        d_ff=int(cfg['d_ff']), eos_id=int(cfg['eos_id']),
        block_size=int(cfg['block_size']),
        chunk_sizes=tuple(int(c) for c in cfg['chunk_sizes']),
        max_slots=int(cfg['max_slots']),
        max_cache_len=int(cfg['max_cache_len']),
        kv_cache_dtype=cfg['kv_cache_dtype'])
    spec['startup'].random_seed = int(cfg['weights_seed'])
    return spec


def vocab_size(cfg):
    """Token ids the traffic generator may draw lie in [2, vocab_size)."""
    return int(cfg['vocab'])


def chunk_sizes(cfg):
    """The prefill chunk programs' sizes, ascending: the warm-up request
    is cut so that it takes a slice of the largest and of the smallest."""
    return sorted(int(c) for c in cfg['chunk_sizes'])


def reference_logits(cfg, weights, ids):
    """[len(ids), vocab] float32 logits of the plain full forward pass
    over `ids` with these weights (host arrays, by the scope's names)."""
    from ..reference import decoder_lm
    return decoder_lm.logits(weights, ids, n_head=int(cfg['n_head']),
                             n_layer=int(cfg['n_layer']))


def step_weight_bytes(cfg):
    """Weight bytes one decode step has to read: every layer's matrices,
    biases and norms, the output projection, and one embedding and one
    position row per slot."""
    d, f, v = int(cfg['d_model']), int(cfg['d_ff']), int(cfg['vocab'])
    per_layer = 4 * d * d + 2 * d * f + f + d + 4 * d
    rows = 2 * int(cfg['max_slots']) * d
    return ((int(cfg['n_layer']) * per_layer + d * v + rows)
            * _DTYPE_BYTES[cfg['weights_dtype']])


def kv_row_bytes(cfg):
    """Bytes of one cached position: K and V in every layer."""
    return (2 * int(cfg['n_layer']) * int(cfg['d_model'])
            * _DTYPE_BYTES[cfg['kv_cache_dtype']])


def step_needed_bytes(cfg, cached_rows):
    """Bytes the algorithm needs for one decode step with `cached_rows`
    positions cached over all slots: the weights once and every cached K/V
    row once."""
    return step_weight_bytes(cfg) + cached_rows * kv_row_bytes(cfg)


def step_floor_seconds(cfg, peaks, cached_rows):
    return step_needed_bytes(cfg, cached_rows) / peaks['hbm_bytes_per_s']
