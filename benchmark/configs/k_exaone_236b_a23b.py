"""k_exaone_236b_a23b: everything the decode runners ask a configuration
for — how the file becomes a decode artifact (models/exaone_moe.py), what
the traffic generator and the warm-up need to know of it, what the plain
reference (benchmark/reference/exaone_moe.py) says a sequence scores, and
what a decode step, its routed feed-forward and its paged attention have to
move at the least. Every count below is of ONE CHIP'S SHARE of the
deployment the file states: the experts held, the vocabulary slice, the
layers kept."""
from __future__ import annotations

BOUND = 'memory'     # which roofline bounds the decode step
_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2}
SLIDING = 'sliding_attention'


def _types(cfg):
    return list(cfg['layer_types'][:int(cfg['num_hidden_layers'])])


def _model_kw(cfg):
    """What models/exaone_moe.py and the reference both need."""
    return dict(n_head=int(cfg['num_attention_heads']),
                n_kv_head=int(cfg['num_key_value_heads']),
                n_layer=int(cfg['num_hidden_layers']), types=_types(cfg),
                window=int(cfg['sliding_window']),
                first_dense=int(cfg['first_k_dense_replace']),
                top_k=int(cfg['num_experts_per_tok']),
                expert_offset=int(cfg['expert_offset']))


def build_spec(cfg):
    """The decode program set, through the repo's own builder."""
    from models.exaone_moe import build_decode_spec
    spec = build_decode_spec(
        vocab=int(cfg['vocab_size']), d_model=int(cfg['hidden_size']),
        d_head=int(cfg['head_dim']), d_dense=int(cfg['intermediate_size']),
        n_expert=int(cfg['num_experts_routed']),
        n_held=int(cfg['num_experts']),
        d_expert=int(cfg['moe_intermediate_size']),
        n_shared=int(cfg['num_shared_experts']),
        routed_scaling_factor=float(cfg['routed_scaling_factor']),
        norm_topk_prob=bool(cfg['norm_topk_prob']),
        max_slots=int(cfg['max_slots']),
        max_cache_len=int(cfg['max_cache_len']),
        block_size=int(cfg['block_size']),
        chunk_sizes=tuple(int(c) for c in cfg['chunk_sizes']),
        eos_id=int(cfg['eos_id']), kv_cache_dtype=cfg['kv_cache_dtype'],
        weights_dtype=cfg['weights_dtype'],
        rms_eps=float(cfg['rms_norm_eps']),
        rope_theta=float(cfg['rope_parameters']['rope_theta']),
        init_std=float(cfg['init_std']), bias_std=float(cfg['bias_std']),
        **_model_kw(cfg))
    spec['startup'].random_seed = int(cfg['weights_seed'])
    return spec


def vocab_size(cfg):
    """Token ids the traffic generator may draw lie in [2, vocab_size):
    the slice of the vocabulary held here."""
    return int(cfg['vocab_size'])


def chunk_sizes(cfg):
    """The prefill chunk programs' sizes, ascending."""
    return sorted(int(c) for c in cfg['chunk_sizes'])


_EITHER_WAY_ROWS = 128     # other sides of near ties carried in one pass


def reference_logits(cfg, weights, ids):
    """[len(ids), vocab held] float32 logits of the plain full forward
    pass over `ids` with these weights (host arrays, by the scope's
    names), given the same share. np.savez keeps a bfloat16 array's bytes
    but not its dtype (it comes back as two-byte void): such a leaf is
    viewed as bfloat16 again.

    Where the reference itself cannot DECIDE a position's routing for a
    program of the stated precision — in some routed layer an expert
    held here is within verify.routing_gap_eps (in the router's logits)
    of entering or leaving the choice, so rounding upstream takes it in
    or out as often as not and its whole term comes or goes with it —
    the reference computes the position BOTH ways (reference/
    exaone_moe.py logits either_way; the file's verify block has the
    readings) and returns its weakest side: where every side names the
    same best token, the side with the least gap between its best two
    logits; where they do not, the row as computed with its best logit
    lowered onto its second (margin 0: the comparison's one way to leave
    a position out, counted with the rows under the margin). This is
    done on the rows a transcript check reads — the last
    verify.max_new_tokens positions in front of the sequence's last
    token, the zero padding behind it left aside; the ids are read for
    the forward pass and to find that padding, and for nothing else."""
    import jax.numpy as jnp
    import numpy as np
    from ..reference import exaone_moe
    weights = {k: (v.view(jnp.bfloat16) if v.dtype.kind == 'V' else v)
               for k, v in weights.items()}
    ids = np.asarray(ids)
    last = int(np.flatnonzero(ids).max(initial=0))
    rows = np.arange(max(last - int(cfg['verify']['max_new_tokens']), 0),
                     last)
    lg, alt = exaone_moe.logits(
        weights, ids, scaling=float(cfg['routed_scaling_factor']),
        norm_topk_prob=bool(cfg['norm_topk_prob']),
        eps=float(cfg['rms_norm_eps']),
        theta=float(cfg['rope_parameters']['rope_theta']),
        either_way=(rows, float(cfg['verify']['routing_gap_eps']),
                    _EITHER_WAY_ROWS), **_model_kw(cfg))
    lg = np.array(lg)
    for r in sorted(set(alt['row'].tolist()) | set(alt['overflow'])):
        lg[r] = weakest_side([lg[r]] + list(alt['logits'][alt['row'] == r]),
                             undecided=r in alt['overflow'])
    return lg


def weakest_side(sides, undecided=False):
    """Of one position's logits computed every way its routing may fall
    (`sides`, the first as the reference chose): the side with the least
    gap between its best two logits, if every side names the same best
    token; else (or if `undecided`: sides were left uncomputed) the
    first with its best logit lowered onto its second — margin 0."""
    def margin(row):
        top2 = np.partition(row, -2)[-2:]
        return top2[1] - top2[0]

    import numpy as np
    if undecided or len({int(s.argmax()) for s in sides}) > 1:
        out = np.array(sides[0])
        out[out.argmax()] = np.partition(out, -2)[-2]
        return out
    return min(sides, key=margin)


def _routed_layers(cfg):
    return int(cfg['num_hidden_layers']) - int(cfg['first_k_dense_replace'])


def expected_distinct_experts(cfg, live_rows):
    """HELD experts that at least one of `live_rows` tokens picks, each
    picking k of the E routed uniformly and independently: held * (1 -
    (1 - k/E)^rows)."""
    e, k = int(cfg['num_experts_routed']), int(cfg['num_experts_per_tok'])
    return int(cfg['num_experts']) * (1.0 - (1.0 - k / e) ** float(live_rows))


def moe_expert_bytes(cfg, live_rows):
    """Routed-expert weight bytes one decode step has to read over all
    routed layers with `live_rows` rows live: the expected distinct held
    experts, each with its gate, up and down matrices."""
    d, f = int(cfg['hidden_size']), int(cfg['moe_intermediate_size'])
    return (_routed_layers(cfg) * expected_distinct_experts(cfg, live_rows)
            * 3 * d * f * _DTYPE_BYTES[cfg['weights_dtype']])


def attention_params(cfg):
    """q, k, v and o of one layer."""
    d, dh = int(cfg['hidden_size']), int(cfg['head_dim'])
    q = int(cfg['num_attention_heads']) * dh
    kv = int(cfg['num_key_value_heads']) * dh
    return d * (q + 2 * kv) + q * d


def _unrouted_params(cfg):
    """What every token multiplies by: each layer's q/k/v/o, the
    leading dense SwiGLU, each routed layer's router and shared expert,
    the untied head over the slice held (norm vectors left out: 0.1 M)."""
    d = int(cfg['hidden_size'])
    f = int(cfg['moe_intermediate_size'])
    return (int(cfg['num_hidden_layers']) * attention_params(cfg)
            + int(cfg['first_k_dense_replace']) * 3 * d
            * int(cfg['intermediate_size'])
            + _routed_layers(cfg)
            * (d * int(cfg['num_experts_routed'])
               + int(cfg['num_shared_experts']) * 3 * d * f)
            + d * int(cfg['vocab_size']))


def weight_params(cfg):
    """Parameters held on this chip: the above, the held experts and
    the embedding rows of the slice."""
    d = int(cfg['hidden_size'])
    return (_unrouted_params(cfg)
            + _routed_layers(cfg) * int(cfg['num_experts']) * 3 * d
            * int(cfg['moe_intermediate_size'])
            + d * int(cfg['vocab_size']))


def step_dense_bytes(cfg):
    """Weight bytes every decode step reads once whatever the routing:
    the unrouted weights and one embedding row per slot."""
    return ((_unrouted_params(cfg)
             + int(cfg['max_slots']) * int(cfg['hidden_size']))
            * _DTYPE_BYTES[cfg['weights_dtype']])


def kv_row_bytes(cfg):
    """Bytes of one cached position in ONE layer: K and V."""
    return (2 * int(cfg['num_key_value_heads']) * int(cfg['head_dim'])
            * _DTYPE_BYTES[cfg['kv_cache_dtype']])


def attention_bytes(cfg, cached_rows, live):
    """K/V bytes one decode step's attention has to read with
    `cached_rows` positions cached over `live` decoding rows: every
    cached position once in each full-attention layer, and in each
    sliding-window layer the last `window` positions of each row (all of
    them, for a row shorter than the window)."""
    types = _types(cfg)
    n_window = sum(t == SLIDING for t in types)
    window = int(cfg['sliding_window'])
    in_window = (live * min(window, cached_rows / live) if live else 0.0)
    return kv_row_bytes(cfg) * ((len(types) - n_window) * cached_rows
                                + n_window * in_window)


def step_needed_bytes(cfg, cached_rows):
    """Bytes the algorithm needs for one decode step with `cached_rows`
    positions cached over all slots: the non-routed weights once, the
    expected distinct held experts with every slot live (the closed loop
    holds occupancy near one), and the K/V rows attention_bytes counts
    with every slot live."""
    slots = int(cfg['max_slots'])
    return (step_dense_bytes(cfg) + moe_expert_bytes(cfg, slots)
            + attention_bytes(cfg, cached_rows, slots))


def step_floor_seconds(cfg, peaks, cached_rows):
    return step_needed_bytes(cfg, cached_rows) / peaks['hbm_bytes_per_s']
