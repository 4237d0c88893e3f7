"""granite_4_0_h_micro: everything the decode runners ask a configuration for
— how the file becomes a decode artifact (models/granite_hybrid.py), what the
traffic generator and the warm-up need to know of it, what the plain
reference (benchmark/reference/granite_hybrid.py) says a sequence scores, and
what a decode step, its attention, its Mamba-2 layers' STATE and a prefill
slice's SSD chunk have to move and multiply at the least. The model is held
WHOLE: every layer, the whole vocabulary, one chip."""
from __future__ import annotations

import types

import numpy as np

from .joyai_llm_flash import _sequence_rows

BOUND = 'memory'     # which roofline bounds the decode step
_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2}
MAMBA, ATTENTION = 'mamba', 'attention'


def _widths(cfg):
    """d hidden, h heads, kv K/V heads, dh a head, mh Mamba heads of p, n
    d_state, k the convolution's width, q the published chunk size, f the
    MLP's; di = mh p channels, xbc = di + 2 n what the convolution sees."""
    d, h = int(cfg['hidden_size']), int(cfg['num_attention_heads'])
    mh, p, n = (int(cfg['mamba_n_heads']), int(cfg['mamba_d_head']),
                int(cfg['mamba_d_state']))
    if mh * p != int(cfg['mamba_expand']) * d or cfg['mamba_n_groups'] != 1:
        raise ValueError('mamba_n_heads x mamba_d_head is mamba_expand x '
                         'hidden_size, in one group')
    return types.SimpleNamespace(
        d=d, h=h, kv=int(cfg['num_key_value_heads']), dh=d // h, mh=mh, p=p,
        n=n, k=int(cfg['mamba_d_conv']), q=int(cfg['mamba_chunk_size']),
        f=int(cfg['shared_intermediate_size']), di=mh * p,
        xbc=mh * p + 2 * n)


def _count(cfg, kind):
    return sum(t == kind for t in cfg['layer_types'])


def build_spec(cfg):
    """The decode program set, through the repo's own builder."""
    from models.granite_hybrid import build_decode_spec
    w = _widths(cfg)
    spec = build_decode_spec(
        vocab=int(cfg['vocab_size']), d_model=w.d, n_head=w.h,
        n_kv_head=w.kv, d_ff=w.f, n_layer=int(cfg['num_hidden_layers']),
        types=list(cfg['layer_types']), ssm_heads=w.mh, ssm_head_dim=w.p,
        d_state=w.n, d_conv=w.k, sub_chunk=w.q,
        embedding_multiplier=float(cfg['embedding_multiplier']),
        attention_multiplier=float(cfg['attention_multiplier']),
        residual_multiplier=float(cfg['residual_multiplier']),
        logits_scaling=float(cfg['logits_scaling']),
        max_slots=int(cfg['max_slots']),
        max_cache_len=int(cfg['max_cache_len']),
        block_size=int(cfg['block_size']),
        chunk_sizes=tuple(int(c) for c in cfg['chunk_sizes']),
        eos_id=int(cfg['eos_id']), kv_cache_dtype=cfg['kv_cache_dtype'],
        weights_dtype=cfg['weights_dtype'], state_dtype=cfg['state_dtype'],
        norm_eps=float(cfg['rms_norm_eps']),
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
    w = _widths(cfg)
    return dict(layer_types=tuple(cfg['layer_types']), n_head=w.h,
                n_kv_head=w.kv, ssm_heads=w.mh, d_state=w.n,
                embedding_multiplier=float(cfg['embedding_multiplier']),
                attention_multiplier=float(cfg['attention_multiplier']),
                residual_multiplier=float(cfg['residual_multiplier']),
                logits_scaling=float(cfg['logits_scaling']),
                eps=float(cfg['rms_norm_eps']))


def reference_logits(cfg, weights, ids, **control):
    """[rows, vocab] float32 logits of the plain full forward pass over `ids`
    with these weights (host arrays, by the scope's names), for every row up
    to the sequence's last token (configs/joyai_llm_flash.py
    _sequence_rows); a bfloat16 leaf that np.savez brought back as two-byte
    void is viewed as bfloat16 again. `control`: the reference's own
    compute_dtype / state_dtype. There is no routing, so no tie rule: the
    harness's margin_eps alone decides a row."""
    import jax.numpy as jnp
    from ..reference import granite_hybrid
    weights = {k: (v.view(jnp.bfloat16) if v.dtype.kind == 'V' else v)
               for k, v in weights.items()}
    ids = np.asarray(ids)
    _, held = _sequence_rows(ids)
    return granite_hybrid.logits(weights, ids[:held],
                                 **dict(_reference_kw(cfg), **control))


# -- what the chip holds, and what a step has to move ----------------------
def mlp_params(cfg):
    """W_in (to a and b) and W_out of a layer's SwiGLU MLP, and the layer's
    two RMSNorms."""
    w = _widths(cfg)
    return 3 * w.d * w.f + 2 * w.d


def mamba_params(cfg):
    """W_in (to z, xBC and dt), the convolution and its bias, A_log,
    dt_bias, D, the gated norm's weight and W_out of a Mamba-2 layer."""
    w = _widths(cfg)
    return (w.d * (w.di + w.xbc + w.mh) + w.k * w.xbc + w.xbc + 3 * w.mh
            + w.di + w.di * w.d)


def attention_params(cfg):
    """q and o, k and v of an attention layer: no bias, no norm."""
    w = _widths(cfg)
    return 2 * w.d * w.d + 2 * w.d * w.kv * w.dh


def weight_params(cfg):
    """Parameters held on this chip: the tied embedding table once, every
    layer's mixer and MLP, the final RMSNorm."""
    d = int(cfg['hidden_size'])
    return (int(cfg['vocab_size']) * d
            + int(cfg['num_hidden_layers']) * mlp_params(cfg)
            + _count(cfg, MAMBA) * mamba_params(cfg)
            + _count(cfg, ATTENTION) * attention_params(cfg) + d)


def step_weight_bytes(cfg):
    """Weight bytes every decode step reads once: all of them (the tied
    table is the head; the few float32 vectors counted at the matrices'
    width: 1 MB of 6,383)."""
    return weight_params(cfg) * _DTYPE_BYTES[cfg['weights_dtype']]


def kv_row_bytes(cfg):
    """Bytes of one cached position in ONE attention layer: K and V."""
    w = _widths(cfg)
    return 2 * w.kv * w.dh * _DTYPE_BYTES[cfg['kv_cache_dtype']]


def attention_bytes(cfg, cached_rows, live):
    """K/V bytes one decode step's attention has to read with `cached_rows`
    positions cached over `live` decoding rows: every cached position once
    in each attention layer (there is no window)."""
    return kv_row_bytes(cfg) * _count(cfg, ATTENTION) * cached_rows


def state_slot_bytes(cfg):
    """Bytes ONE Mamba-2 layer keeps for ONE slot: the recurrence's state
    (heads x head x d_state, `state_dtype`) and the convolution's tail
    (d_conv - 1 inputs of x | B | C, float32)."""
    w = _widths(cfg)
    return (w.mh * w.p * w.n * _DTYPE_BYTES[cfg['state_dtype']]
            + (w.k - 1) * w.xbc * 4)


def ssm_state_bytes(cfg, live):
    """State bytes one decode step's Mamba-2 layers have to move with `live`
    decoding rows: each live slot's state and tail in every Mamba layer ONCE
    READ AND ONCE WRITTEN — whatever implements the recurrence, and however
    long the sequence is."""
    return 2 * live * _count(cfg, MAMBA) * state_slot_bytes(cfg)


def _sub_chunks(cfg, chunk_len):
    """The lengths of the sub-chunks of at most mamba_chunk_size positions
    that hold `chunk_len` (possibly a mean: fractional) real positions."""
    q = _widths(cfg).q
    whole = int(chunk_len // q)
    rest = chunk_len - whole * q
    return [q] * whole + [rest] * (rest > 0)


def ssd_chunk_flops(cfg, chunk_len):
    """Floating-point operations the Mamba-2 layers of ONE prefill slice of
    `chunk_len` real positions need in the dual form at the published
    mamba_chunk_size: a sub-chunk of q positions is C B^T (2 q q N, once for
    all heads), (L * C B^T)(delta X) (2 q q P a head), C S_0 and the state's
    update (2 q N P a head each); all Mamba layers. Sub-chunks past
    chunk_len are NOT counted: a kernel that skips them cannot read over
    100 % of this."""
    w = _widths(cfg)
    one = sum(2 * q * q * w.n + w.mh * (2 * q * q * w.p
                                         + 4 * q * w.n * w.p)
              for q in _sub_chunks(cfg, chunk_len))
    return _count(cfg, MAMBA) * one


def ssd_chunk_bytes(cfg, chunk_len):
    """Bytes the Mamba-2 layers of ONE prefill slice of `chunk_len` real
    positions have to move, all Mamba layers: the row's state once read and
    once written, the chunk's inputs (x, B, C, dt: float32) once read and
    its output once written."""
    w = _widths(cfg)
    state = 2 * w.mh * w.p * w.n * _DTYPE_BYTES[cfg['state_dtype']]
    rows = chunk_len * (2 * w.di + 2 * w.n + w.mh) * 4
    return _count(cfg, MAMBA) * (state + rows)


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
