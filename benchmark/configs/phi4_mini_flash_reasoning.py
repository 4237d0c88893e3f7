"""phi4_mini_flash_reasoning: everything the decode runners ask a
configuration for — how the file becomes a decode artifact
(models/phi4_flash.py), what the traffic generator and the warm-up need to
know of it, what the plain reference (benchmark/reference/phi4_flash.py) says
a sequence scores, and what a decode step, its attention and its Mamba
layers' STATE have to move at the least. The model is held WHOLE: every
layer, the whole vocabulary, one chip."""
from __future__ import annotations

import types

import numpy as np

from .joyai_llm_flash import _sequence_rows

BOUND = 'memory'     # which roofline bounds the decode step
_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2}


def _types(cfg):
    from models.phi4_flash import layer_types
    return layer_types(int(cfg['num_hidden_layers']))


def _widths(cfg):
    """d hidden, h heads, kv K/V heads, dh a head, di Mamba channels, n
    d_state, k the convolution's width, rank dt_rank, f the MLP's."""
    d, h = int(cfg['hidden_size']), int(cfg['num_attention_heads'])
    return types.SimpleNamespace(
        d=d, h=h, kv=int(cfg['num_key_value_heads']), dh=d // h,
        di=int(cfg['mamba_expand']) * d, n=int(cfg['mamba_d_state']),
        k=int(cfg['mamba_d_conv']), rank=int(cfg['mamba_dt_rank']),
        f=int(cfg['intermediate_size']))


def build_spec(cfg):
    """The decode program set, through the repo's own builder."""
    from models.phi4_flash import build_decode_spec
    w = _widths(cfg)
    spec = build_decode_spec(
        vocab=int(cfg['vocab_size']), d_model=w.d, n_head=w.h,
        n_kv_head=w.kv, d_ff=w.f, n_layer=int(cfg['num_hidden_layers']),
        window=int(cfg['sliding_window']), d_state=w.n, d_conv=w.k,
        expand=int(cfg['mamba_expand']), dt_rank=w.rank,
        max_slots=int(cfg['max_slots']),
        max_cache_len=int(cfg['max_cache_len']),
        block_size=int(cfg['block_size']),
        chunk_sizes=tuple(int(c) for c in cfg['chunk_sizes']),
        eos_id=int(cfg['eos_id']), kv_cache_dtype=cfg['kv_cache_dtype'],
        weights_dtype=cfg['weights_dtype'], state_dtype=cfg['state_dtype'],
        norm_eps=float(cfg['layer_norm_eps']),
        init_std=float(cfg['init_std']), conv_std=float(cfg['conv_std']),
        embed_std=float(cfg['embed_std']),
        final_norm_std=(None if cfg.get('final_norm_std') is None
                        else float(cfg['final_norm_std'])),
        dt_range=tuple(float(t) for t in cfg['dt_range']),
        a_range=tuple(float(a) for a in cfg['a_range']))
    spec['startup'].random_seed = int(cfg['weights_seed'])
    return spec


def vocab_size(cfg):
    """Token ids the traffic generator may draw lie in [2, vocab_size)."""
    return int(cfg['vocab_size'])


def chunk_sizes(cfg):
    """The prefill chunk programs' sizes, ascending."""
    return sorted(int(c) for c in cfg['chunk_sizes'])


def _reference_kw(cfg):
    from models.phi4_flash import GMU, published_columns
    w, kinds = _widths(cfg), _types(cfg)
    return dict(n_head=w.h, n_kv_head=w.kv, n_layer=len(kinds),
                n_self=kinds.index(GMU), window=int(cfg['sliding_window']),
                dt_rank=w.rank, q_cols=published_columns(w.h, w.dh),
                kv_cols=published_columns(w.kv, w.dh),
                eps=float(cfg['layer_norm_eps']))


def reference_logits(cfg, weights, ids, **control):
    """[rows, vocab] float32 logits of the plain full forward pass over `ids`
    with these weights (host arrays, by the scope's names), for every row up
    to the sequence's last token (configs/joyai_llm_flash.py
    _sequence_rows); a bfloat16 leaf that np.savez brought back as two-byte
    void is viewed as bfloat16 again. `control`: the reference's own
    compute_dtype / state_dtype. There is no routing, so no tie rule: the
    harness's margin_eps alone decides a row."""
    import jax.numpy as jnp
    from ..reference import phi4_flash
    weights = {k: (v.view(jnp.bfloat16) if v.dtype.kind == 'V' else v)
               for k, v in weights.items()}
    ids = np.asarray(ids)
    _, held = _sequence_rows(ids)
    return phi4_flash.logits(weights, ids[:held],
                             **dict(_reference_kw(cfg), **control))


# -- what the chip holds, and what a step has to move ----------------------
def _count(cfg, kind):
    return sum(t == kind for t in _types(cfg))


def mlp_params(cfg):
    """gate, up and down of a layer's SwiGLU MLP, and its two LayerNorms."""
    w = _widths(cfg)
    return 3 * w.d * w.f + 4 * w.d


def mamba_params(cfg):
    """W_in, the convolution and its bias, W_x, W_dt and its bias, A_log, D
    and W_out of a Mamba layer."""
    w = _widths(cfg)
    return (w.d * 2 * w.di + w.k * w.di + w.di + w.di * (w.rank + 2 * w.n)
            + w.rank * w.di + w.di + w.n * w.di + w.di + w.di * w.d)


def attention_params(cfg, cross=False):
    """q and o (cross attention) and k and v (the self-decoder's), each
    with its bias, the four lambda vectors and the sub-layer norm."""
    w = _widths(cfg)
    own = 0 if cross else 2 * (w.d * w.kv * w.dh + w.kv * w.dh)
    return 2 * (w.d * w.d + w.d) + own + 4 * w.dh + 2 * w.dh


def gmu_params(cfg):
    w = _widths(cfg)
    return 2 * w.d * w.di


def weight_params(cfg):
    """Parameters held on this chip: the tied embedding table once, every
    layer's mixer and MLP, the final LayerNorm."""
    from models.phi4_flash import CROSS, FULL, GMU, MAMBA, WINDOW
    d = int(cfg['hidden_size'])
    return (int(cfg['vocab_size']) * d
            + int(cfg['num_hidden_layers']) * mlp_params(cfg)
            + _count(cfg, MAMBA) * mamba_params(cfg)
            + (_count(cfg, WINDOW) + _count(cfg, FULL))
            * attention_params(cfg)
            + _count(cfg, CROSS) * attention_params(cfg, cross=True)
            + _count(cfg, GMU) * gmu_params(cfg) + 2 * d)


def step_weight_bytes(cfg):
    """Weight bytes every decode step reads once: all of them (the tied
    table is the head; the few float32 vectors counted at the matrices'
    width: 2 MB of 7,705)."""
    return weight_params(cfg) * _DTYPE_BYTES[cfg['weights_dtype']]


def kv_row_bytes(cfg):
    """Bytes of one cached position in ONE caching layer: K and V."""
    w = _widths(cfg)
    return 2 * w.kv * w.dh * _DTYPE_BYTES[cfg['kv_cache_dtype']]


def attention_bytes(cfg, cached_rows, live):
    """K/V bytes one decode step's attention has to read with `cached_rows`
    positions cached over `live` decoding rows: every cached position of
    the ONE full layer once for each layer that attends it — itself and
    the cross-decoder's attention layers — and in each sliding-window layer
    the last `window` positions of each row (all of them, for a row shorter
    than the window)."""
    from models.phi4_flash import CROSS, FULL, WINDOW
    window = int(cfg['sliding_window'])
    in_window = live * min(window, cached_rows / live) if live else 0.0
    return kv_row_bytes(cfg) * (
        (_count(cfg, FULL) + _count(cfg, CROSS)) * cached_rows
        + _count(cfg, WINDOW) * in_window)


def state_slot_bytes(cfg):
    """Bytes ONE Mamba layer keeps for ONE slot: the scan's state (d_state x
    channels, `state_dtype`) and the convolution's tail (d_conv - 1 inputs
    of every channel, float32)."""
    w = _widths(cfg)
    return (w.di * w.n * _DTYPE_BYTES[cfg['state_dtype']]
            + (w.k - 1) * w.di * 4)


def ssm_state_bytes(cfg, live):
    """State bytes one decode step's Mamba layers have to move with `live`
    decoding rows: each live slot's state and tail in every Mamba layer
    ONCE READ AND ONCE WRITTEN — whatever implements the recurrence, and
    however long the sequence is."""
    from models.phi4_flash import MAMBA
    return 2 * live * _count(cfg, MAMBA) * state_slot_bytes(cfg)


def step_needed_bytes(cfg, cached_rows):
    """Bytes the algorithm needs for one decode step with `cached_rows`
    positions cached over all slots, every slot live (the closed loop holds
    occupancy near one): the weights once, the K/V rows attention_bytes
    counts, the Mamba layers' states read and written."""
    slots = int(cfg['max_slots'])
    return (step_weight_bytes(cfg) + attention_bytes(cfg, cached_rows, slots)
            + ssm_state_bytes(cfg, slots))


def step_floor_seconds(cfg, peaks, cached_rows):
    return step_needed_bytes(cfg, cached_rows) / peaks['hbm_bytes_per_s']
