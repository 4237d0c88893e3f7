"""kimi_linear_48b_a3b: everything the decode runners ask a configuration for
— how the file becomes a decode artifact (models/kimi_linear.py), what the
traffic generator and the warm-up need to know of it, what the plain
reference (benchmark/reference/kimi_linear.py) says a sequence scores, and
what a decode step, its routed feed-forward, its latent attention, its Kimi
Delta Attention STATE and a prefill slice's chunked delta rule have to move
and multiply at the least. Every count below is of ONE CHIP'S SHARE of the
deployment the file states: the experts held, the vocabulary slice, the
layers kept. What the comparison makes of a row whose routing the reference
cannot decide is joyai_llm_flash's rule (every_way, nearest_way), with this
configuration's own thresholds."""
from __future__ import annotations

import numpy as np

from .joyai_llm_flash import _sequence_rows, every_way, nearest_way
from .k_exaone_236b_a23b import weakest_side

BOUND = 'memory'     # which roofline bounds the decode step
_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2}
# other sides of ties carried in one pass (configs/joyai_llm_flash.py: 256
# experts, 8 chosen, 32 held; here 12 routed layers)
_EITHER_WAY_ROWS = 1024
# a row with more NEAR ties than this (2^10 combinations) is left undecided
_MAX_TIES = 10
# ties up to this many times routing_gap_eps away count too, each ALONE
# (configs/joyai_llm_flash.py says why)
_FAR_TIES = 2.0
# the tokens of a sub-chunk the chunked rule's yardstick is reckoned at:
# ops/linear_attention_ops.py's own (gated_delta_chunk's sub_chunk)
_SUB_CHUNK = 64


def _kda(cfg):
    """(heads, head size, convolution width) of a KDA layer."""
    lin = cfg['linear_attn_config']
    return (int(lin['num_heads']), int(lin['head_dim']),
            int(lin['short_conv_kernel_size']))


def _layers(cfg):
    """(KDA layers, MLA layers) kept."""
    from models.kimi_linear import MLA, layer_types
    types = layer_types(int(cfg['num_hidden_layers']),
                        cfg['linear_attn_config']['full_attn_layers'])
    n_mla = sum(t == MLA for t in types)
    return len(types) - n_mla, n_mla


def _routed_layers(cfg):
    return int(cfg['num_hidden_layers']) - int(cfg['first_k_dense_replace'])


def build_spec(cfg):
    """The decode program set, through the repo's own builder."""
    from models.kimi_linear import build_decode_spec
    heads, dk, conv = _kda(cfg)
    spec = build_decode_spec(
        vocab=int(cfg['vocab_size']), d_model=int(cfg['hidden_size']),
        n_layer=int(cfg['num_hidden_layers']),
        full_attn_layers=tuple(
            cfg['linear_attn_config']['full_attn_layers']),
        kda_heads=heads, kda_head_dim=dk, conv_width=conv,
        n_head=int(cfg['num_attention_heads']),
        kv_lora_rank=int(cfg['kv_lora_rank']),
        d_nope=int(cfg['qk_nope_head_dim']),
        d_rope=int(cfg['qk_rope_head_dim']), d_v=int(cfg['v_head_dim']),
        d_dense=int(cfg['intermediate_size']),
        first_dense=int(cfg['first_k_dense_replace']),
        n_expert=int(cfg['num_experts_routed']),
        n_held=int(cfg['num_experts']),
        expert_offset=int(cfg['expert_offset']),
        d_expert=int(cfg['moe_intermediate_size']),
        top_k=int(cfg['num_experts_per_token']),
        n_shared=int(cfg['num_shared_experts']),
        routed_scaling_factor=float(cfg['routed_scaling_factor']),
        norm_topk_prob=bool(cfg['moe_renormalize']),
        max_slots=int(cfg['max_slots']),
        max_cache_len=int(cfg['max_cache_len']),
        block_size=int(cfg['block_size']),
        chunk_sizes=tuple(int(c) for c in cfg['chunk_sizes']),
        eos_id=int(cfg['eos_id']), kv_cache_dtype=cfg['kv_cache_dtype'],
        weights_dtype=cfg['weights_dtype'], state_dtype=cfg['state_dtype'],
        rms_eps=float(cfg['rms_norm_eps']), init_std=float(cfg['init_std']),
        bias_std=float(cfg['bias_std']), conv_std=float(cfg['conv_std']),
        decay=tuple(float(d) for d in cfg['decay_range']),
        dt_std=float(cfg['dt_std']), embed_std=float(cfg['embed_std']))
    spec['startup'].random_seed = int(cfg['weights_seed'])
    return spec


def vocab_size(cfg):
    """Token ids the traffic generator may draw lie in [2, vocab_size):
    the slice of the vocabulary held here."""
    return int(cfg['vocab_size'])


def chunk_sizes(cfg):
    """The prefill chunk programs' sizes, ascending."""
    return sorted(int(c) for c in cfg['chunk_sizes'])


def _reference_kw(cfg):
    return dict(n_layer=int(cfg['num_hidden_layers']),
                full_attn_layers=tuple(
                    cfg['linear_attn_config']['full_attn_layers']),
                kda_heads=_kda(cfg)[0],
                n_head=int(cfg['num_attention_heads']),
                d_nope=int(cfg['qk_nope_head_dim']),
                d_rope=int(cfg['qk_rope_head_dim']),
                d_v=int(cfg['v_head_dim']),
                first_dense=int(cfg['first_k_dense_replace']),
                top_k=int(cfg['num_experts_per_token']),
                expert_offset=int(cfg['expert_offset']),
                scaling=float(cfg['routed_scaling_factor']),
                norm_topk_prob=bool(cfg['moe_renormalize']),
                eps=float(cfg['rms_norm_eps']))


def reference_sides(cfg, weights, ids, far, **control):
    """(plain [rows, vocab held] float32 logits of the reference's full
    forward pass over `ids` up to the sequence's last token, the reference's
    own `either_way` record): for the sequence's last verify.max_new_tokens
    rows, the other side of every routing tie of a held expert within `far`
    of the choice's edge, one at a time. `control`: the reference's own
    compute_dtype / state_dtype / reset_every / scalar_decay /
    round_operands."""
    import jax.numpy as jnp
    from ..reference import kimi_linear
    weights = {k: (v.view(jnp.bfloat16) if v.dtype.kind == 'V' else v)
               for k, v in weights.items()}
    ids = np.asarray(ids)
    last, held = _sequence_rows(ids)
    rows = np.arange(max(last - int(cfg['verify']['max_new_tokens']), 0),
                     last)
    lg, alt = kimi_linear.logits(
        weights, ids[:held], either_way=(rows, far, _EITHER_WAY_ROWS),
        **dict(_reference_kw(cfg), **control))
    return np.array(lg), alt


def ways_at(lg, alt, gap):
    """{row: [ways, vocab] or None} of reference_sides' record at a routing
    gap of `gap` (the sides have to reach _FAR_TIES times as far):
    configs/qwen3_next_80b_a3b.py reference_ways' rule — for each row with
    a near tie, what its routing makes of it EVERY way (configs/
    joyai_llm_flash.py every_way over the other sides of the ties within
    `gap`, and the other side of each tie up to _FAR_TIES times as far
    away on its own), or None where the row is left undecided (more than
    _MAX_TIES near ties, or its sides did not fit the pass)."""
    ways = {}
    reach = alt['dist'] <= _FAR_TIES * gap
    for r in sorted(set(alt['row'][reach].tolist()) | set(alt['overflow'])):
        mine = reach & (alt['row'] == r)
        near = alt['logits'][mine & (alt['dist'] <= gap)]
        if r in alt['overflow'] or len(near) > _MAX_TIES:
            ways[r] = None
            continue
        ways[r] = np.concatenate([every_way(lg[r], near),
                                  alt['logits'][mine & (alt['dist'] > gap)]])
    return ways


def reference_ways(cfg, weights, ids, **control):
    """(plain logits, {row: ways or None}): reference_sides and ways_at at
    verify.routing_gap_eps."""
    gap = float(cfg['verify']['routing_gap_eps'])
    lg, alt = reference_sides(cfg, weights, ids, _FAR_TIES * gap, **control)
    return lg, ways_at(lg, alt, gap)


def reference_logits(cfg, weights, ids):
    """[rows, vocab held] float32 logits of the plain full forward pass
    over `ids` with these weights (host arrays, by the scope's names), given
    the same share, for every row up to the sequence's last token
    (configs/joyai_llm_flash.py _sequence_rows). A row whose routing the
    reference cannot DECIDE for a program of the stated precision is what
    its routing makes of it EVERY way (reference_ways), judged on the way
    nearest the token that was served (`ids` is the teacher-forced
    sequence, so ids[r + 1] is that token): configs/joyai_llm_flash.py
    reference_logits' rule, word for word, at this configuration's
    thresholds; an undecided row has margin 0 (weakest_side)."""
    lg, ways = reference_ways(cfg, weights, ids)
    for r, w in ways.items():
        lg[r] = (weakest_side([lg[r]], undecided=True) if w is None
                 else nearest_way(w, int(np.asarray(ids)[r + 1])))
    return lg


# -- what the share holds, and what a step has to move ---------------------
def kda_params(cfg):
    """A KDA layer's mixer: q, k, v, their three convolutions, the decay's
    low-rank pair with A_log and dt_bias, beta's projection, the output
    gate's low-rank pair, the head norm and o."""
    d = int(cfg['hidden_size'])
    heads, dk, conv = _kda(cfg)
    w = heads * dk
    return (3 * d * w + 3 * w * conv + 2 * (d * dk + dk * w) + d * heads
            + heads + w + dk + w * d)


def kda_proj_params(cfg):
    """What of kda_params a token multiplies by as matrices."""
    heads, dk, conv = _kda(cfg)
    return kda_params(cfg) - 3 * heads * dk * conv - heads - heads * dk - dk


def mla_params(cfg):
    """A MLA layer's mixer: the full-rank q, kv_a with its norm, kv_b, o."""
    d, h = int(cfg['hidden_size']), int(cfg['num_attention_heads'])
    r = int(cfg['kv_lora_rank'])
    dn, dr, dv = (int(cfg['qk_nope_head_dim']),
                  int(cfg['qk_rope_head_dim']), int(cfg['v_head_dim']))
    return (d * h * (dn + dr) + d * (r + dr) + r + r * h * (dn + dv)
            + h * dv * d)


def expert_params(cfg):
    """One SwiGLU expert: gate, up and down."""
    return 3 * int(cfg['hidden_size']) * int(cfg['moe_intermediate_size'])


def _unrouted_params(cfg):
    """What every token multiplies by: each layer's mixer and two norms,
    the leading dense SwiGLU, each routed layer's router with its bias and
    shared expert, the final norm, the untied head over the slice held."""
    d = int(cfg['hidden_size'])
    n_kda, n_mla = _layers(cfg)
    return (n_kda * kda_params(cfg) + n_mla * mla_params(cfg)
            + (n_kda + n_mla) * 2 * d
            + int(cfg['first_k_dense_replace']) * 3 * d
            * int(cfg['intermediate_size'])
            + _routed_layers(cfg)
            * (d * int(cfg['num_experts_routed'])
               + int(cfg['num_experts_routed'])
               + int(cfg['num_shared_experts']) * expert_params(cfg))
            + d + d * int(cfg['vocab_size']))


def weight_params(cfg):
    """Parameters held on this chip: the above, the held experts and the
    embedding rows of the slice."""
    return (_unrouted_params(cfg)
            + _routed_layers(cfg) * int(cfg['num_experts'])
            * expert_params(cfg)
            + int(cfg['hidden_size']) * int(cfg['vocab_size']))


def step_dense_bytes(cfg):
    """Weight bytes every decode step reads once whatever the routing: the
    unrouted weights (the few float32 vectors counted at the matrices'
    width) and one embedding row per slot."""
    return ((_unrouted_params(cfg)
             + int(cfg['max_slots']) * int(cfg['hidden_size']))
            * _DTYPE_BYTES[cfg['weights_dtype']])


def expected_distinct_experts(cfg, live_rows):
    """HELD experts that at least one of `live_rows` tokens picks, each
    picking k of the E routed uniformly and independently."""
    e, k = int(cfg['num_experts_routed']), int(cfg['num_experts_per_token'])
    return int(cfg['num_experts']) * (1.0 - (1.0 - k / e) ** float(live_rows))


def moe_expert_bytes(cfg, live_rows):
    """Routed-expert weight bytes one decode step has to read over all
    routed layers with `live_rows` rows live: the expected distinct held
    experts (30.4 of 32 at 96 rows), each with its gate, up and down
    matrices."""
    return (_routed_layers(cfg) * expected_distinct_experts(cfg, live_rows)
            * expert_params(cfg) * _DTYPE_BYTES[cfg['weights_dtype']])


def kv_row_bytes(cfg):
    """Bytes of one cached position in ONE MLA layer that the algorithm
    needs: the latent and the one shared key part (576 values; the pool
    stores the row 640 wide, whole lane tiles)."""
    return ((int(cfg['kv_lora_rank']) + int(cfg['qk_rope_head_dim']))
            * _DTYPE_BYTES[cfg['kv_cache_dtype']])


def attention_bytes(cfg, cached_rows, live):
    """Latent bytes one decode step's attention has to read with
    `cached_rows` positions cached over `live` decoding rows: every cached
    row of every live slot once a MLA layer — all heads read the same row;
    a KDA layer caches no position."""
    return kv_row_bytes(cfg) * _layers(cfg)[1] * cached_rows


def state_slot_bytes(cfg):
    """Bytes ONE KDA layer keeps for ONE slot: the rule's state (heads x dk
    x dv, `state_dtype`) and the convolutions' tail (K - 1 inputs of the 3
    x heads x dk channels, float32)."""
    heads, dk, conv = _kda(cfg)
    return (heads * dk * dk * _DTYPE_BYTES[cfg['state_dtype']]
            + 3 * heads * dk * (conv - 1) * 4)


def linear_state_bytes(cfg, live):
    """State bytes one decode step's KDA layers have to move with `live`
    decoding rows: each live slot's state and tail in every KDA layer ONCE
    READ AND ONCE WRITTEN — whatever implements the recurrence, and however
    long the sequence is."""
    return 2 * live * _layers(cfg)[0] * state_slot_bytes(cfg)


def _sub_chunks(tokens):
    """The sub-chunks `tokens` real positions fill: whole ones and what is
    left (a float where `tokens` is a mean)."""
    whole = int(tokens // _SUB_CHUNK)
    rest = tokens - whole * _SUB_CHUNK
    return [_SUB_CHUNK] * whole + ([rest] if rest > 0 else [])


def kda_chunk_flops(cfg, tokens):
    """Floating-point operations the KDA layers of ONE prefill slice of
    `tokens` real positions need in the chunked rule, each product at one
    pass, whatever implements it. A sub-chunk of n positions, a head: the
    two decayed products against the keys (k beta e^G)(k e^-G)^T and
    (q e^G)(k e^-G)^T, 2 n n dk each; the unit triangular solve of [v | k]
    (n n (dv + dk): half a product); (k S), (q S) and the state's update, 2
    n dk dv each; (q k^T) v_new, 2 n n dv. Sub-chunks past `tokens` are NOT
    counted: a kernel that skips them cannot read over 100 % of this."""
    heads, dk, _ = _kda(cfg)
    dv = dk
    one = sum(4 * n * n * dk + n * n * (dv + dk) + 2 * n * n * dv
              + 6 * n * dk * dv for n in _sub_chunks(tokens))
    return _layers(cfg)[0] * heads * one


def kda_chunk_bytes(cfg, tokens):
    """Bytes the KDA layers of ONE prefill slice of `tokens` real positions
    have to move, all KDA layers: the row's state once read and once
    written, the slice's q, k, v and g (heads x dk each) and beta (heads)
    once read and its output once written, float32."""
    heads, dk, _ = _kda(cfg)
    state = 2 * heads * dk * dk * _DTYPE_BYTES[cfg['state_dtype']]
    rows = tokens * (5 * heads * dk + heads) * 4
    return _layers(cfg)[0] * (state + rows)


def step_needed_bytes(cfg, cached_rows):
    """Bytes the algorithm needs for one decode step with `cached_rows`
    positions cached over all slots, every slot live (the closed loop
    holds occupancy near one): the unrouted weights once, the expected
    distinct held experts, the MLA layers' latent rows, and the KDA
    layers' states read and written."""
    slots = int(cfg['max_slots'])
    return (step_dense_bytes(cfg) + moe_expert_bytes(cfg, slots)
            + attention_bytes(cfg, cached_rows, slots)
            + linear_state_bytes(cfg, slots))


def step_floor_seconds(cfg, peaks, cached_rows):
    return step_needed_bytes(cfg, cached_rows) / peaks['hbm_bytes_per_s']
