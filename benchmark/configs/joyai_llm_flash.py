"""joyai_llm_flash: everything the decode runners ask a configuration for —
how the file becomes a decode artifact (models/joyai_llm_flash.py), what
the traffic generator and the warm-up need to know of it, what the plain
reference (benchmark/reference/joyai_llm_flash.py) says a sequence scores,
and what a decode step, its routed feed-forward and its latent attention
have to move and multiply at the least. Every count below is of ONE CHIP'S
SHARE of the deployment the file states: the experts held, the vocabulary
slice, the layers kept. The routed layer is k_exaone_236b_a23b's; what the
comparison makes of a row whose routing the reference cannot decide is
said here (every_way, nearest_way)."""
from __future__ import annotations

import numpy as np

from .k_exaone_236b_a23b import weakest_side

BOUND = 'memory'     # which roofline bounds the decode step
_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2}
# other sides of ties carried in one pass: with 256 experts, 8 chosen, 32
# held and 10 routed layers a row has ~5 held experts within
# verify.routing_gap_eps (0.05) of the choice's edge and ~9 within twice
# that: 365 a prompt's 48 rows at 0.08 on average and 507 at the most (48
# prompts), so ~460 / ~640 at 0.10 (PERF.md 6, PR 40)
_EITHER_WAY_ROWS = 768
# a row with more NEAR ties than this (2^9 combinations) is left undecided:
# 2.3-2.7 % of the rows at routing_gap_eps 0.05
_MAX_TIES = 9
# ties up to this many times routing_gap_eps away count too, each ALONE:
# of ~78,000 served rows three re-routed past 0.02 (0.02-0.03, 0.03-0.04,
# 0.0506 - one side each), so the distances have a tail, but every
# combination of ties that far out would make half the vocabulary's
# runners-up admissible (at 0.07 the control's one mismatch of a seed is
# explained away, at 0.06 not: PERF.md 6)
_FAR_TIES = 2.0


def _model_kw(cfg):
    """What models/joyai_llm_flash.py and the reference both need."""
    return dict(n_head=int(cfg['num_attention_heads']),
                d_nope=int(cfg['qk_nope_head_dim']),
                d_rope=int(cfg['qk_rope_head_dim']),
                d_v=int(cfg['v_head_dim']),
                n_layer=int(cfg['num_hidden_layers']),
                first_dense=int(cfg['first_k_dense_replace']),
                top_k=int(cfg['num_experts_per_tok']),
                expert_offset=int(cfg['expert_offset']))


def build_spec(cfg):
    """The decode program set, through the repo's own builder."""
    from models.joyai_llm_flash import build_decode_spec
    spec = build_decode_spec(
        vocab=int(cfg['vocab_size']), d_model=int(cfg['hidden_size']),
        q_lora_rank=int(cfg['q_lora_rank']),
        kv_lora_rank=int(cfg['kv_lora_rank']),
        d_dense=int(cfg['intermediate_size']),
        n_expert=int(cfg['n_experts_routed']),
        n_held=int(cfg['n_routed_experts']),
        d_expert=int(cfg['moe_intermediate_size']),
        n_shared=int(cfg['n_shared_experts']),
        routed_scaling_factor=float(cfg['routed_scaling_factor']),
        norm_topk_prob=bool(cfg['norm_topk_prob']),
        max_slots=int(cfg['max_slots']),
        max_cache_len=int(cfg['max_cache_len']),
        block_size=int(cfg['block_size']),
        chunk_sizes=tuple(int(c) for c in cfg['chunk_sizes']),
        eos_id=int(cfg['eos_id']), kv_cache_dtype=cfg['kv_cache_dtype'],
        weights_dtype=cfg['weights_dtype'],
        rms_eps=float(cfg['rms_norm_eps']),
        rope_theta=float(cfg['rope_theta']),
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


def _sequence_rows(ids):
    """(index of the last token, rows the pass has to hold): the harness
    pads every sequence with zeros to verify.pad_to, and a causal pass
    owes the pad nothing — the reference runs over the smallest of 512,
    1,024, 2,048 ... rows that holds the sequence (a few shapes to
    compile; a 300-token prompt costs a 512-row pass, not a 4,096-row
    one), never over more than it was given."""
    last = int(np.flatnonzero(ids).max(initial=0))
    rows = 512
    while rows < last + 1:
        rows *= 2
    return last, min(rows, len(ids))


def every_way(plain, sides):
    """[2^k, vocab]: one position's logits under every COMBINATION of its
    k near ties, the plain row first. `sides` are the k rows the
    reference computed with ONE tie on its other side each; two ties that
    fall the other way together (in two layers, or two experts of one)
    are taken to first order — the sides' differences from the plain row
    ADD: each is a held expert's term entering or leaving the residual
    stream, and what one does to the other's input is second order. A
    served row that re-routes twice is the commonest mismatch the single
    sides do not explain (PERF.md 6, PR 40's review round)."""
    deltas = np.asarray(sides, plain.dtype).reshape(-1, len(plain)) - plain
    k = len(deltas)
    picks = ((np.arange(2 ** k)[:, None] >> np.arange(k)) & 1)
    return plain[None] + picks.astype(plain.dtype) @ deltas


def nearest_way(ways, token):
    """Of one position's logits every way its routing may fall: the way
    on which `token` — the one that was served, which the teacher-forced
    sequence names — stands nearest the best logit (it IS the best on
    that way if it is on any). The reference cannot decide such a row's
    routing for a program of the stated precision, so every way is
    admissible; a token that is the best on none is held to the way
    kindest to it, and verify_transcripts' margin rule does the rest."""
    ways = np.asarray(ways)
    return ways[int(np.argmin(ways.max(axis=1) - ways[:, token]))]


def reference_logits(cfg, weights, ids):
    """[rows, vocab held] float32 logits of the plain full forward pass
    over `ids` with these weights (host arrays, by the scope's names),
    given the same share, for every row up to the sequence's last token
    (_sequence_rows: the zero pad behind it is not computed); a bfloat16
    leaf that np.savez brought back as two-byte void is viewed as bfloat16
    again.

    Where the reference cannot DECIDE a position's routing for a program
    of the stated precision — held experts within verify.routing_gap_eps
    (in the router's logits) of the choice's edge: configs/
    k_exaone_236b_a23b.py reference_logits says why — the row is what the
    routing makes of it EVERY way (every_way: each near tie's other side
    from the reference, their combinations to first order; a tie up to
    _FAR_TIES times as far away on its own) and the way returned is the
    one nearest the token that was served (nearest_way; `ids` is the
    teacher-forced sequence, so ids[r + 1] is that token): an
    admissible-token comparison, which verify_transcripts' margin rule
    then judges like any row. A row with more than _MAX_TIES near ties, or
    whose sides did not fit the pass, is left undecided (weakest_side:
    margin 0, skipped and counted)."""
    import jax.numpy as jnp
    from ..reference import joyai_llm_flash
    weights = {k: (v.view(jnp.bfloat16) if v.dtype.kind == 'V' else v)
               for k, v in weights.items()}
    ids = np.asarray(ids)
    last, held = _sequence_rows(ids)
    rows = np.arange(max(last - int(cfg['verify']['max_new_tokens']), 0),
                     last)
    gap = float(cfg['verify']['routing_gap_eps'])
    lg, alt = joyai_llm_flash.logits(
        weights, ids[:held], scaling=float(cfg['routed_scaling_factor']),
        norm_topk_prob=bool(cfg['norm_topk_prob']),
        eps=float(cfg['rms_norm_eps']), theta=float(cfg['rope_theta']),
        either_way=(rows, _FAR_TIES * gap, _EITHER_WAY_ROWS),
        **_model_kw(cfg))
    lg = np.array(lg)
    for r in sorted(set(alt['row'].tolist()) | set(alt['overflow'])):
        mine = alt['row'] == r
        near = alt['logits'][mine & (alt['dist'] <= gap)]
        if r in alt['overflow'] or len(near) > _MAX_TIES:
            lg[r] = weakest_side([lg[r]], undecided=True)
            continue
        ways = np.concatenate([every_way(lg[r], near),
                               alt['logits'][mine & (alt['dist'] > gap)]])
        lg[r] = nearest_way(ways, int(ids[r + 1]))
    return lg


def _routed_layers(cfg):
    return int(cfg['num_hidden_layers']) - int(cfg['first_k_dense_replace'])


def expected_distinct_experts(cfg, live_rows):
    """HELD experts that at least one of `live_rows` tokens picks, each
    picking k of the E routed uniformly and independently."""
    e, k = int(cfg['n_experts_routed']), int(cfg['num_experts_per_tok'])
    return (int(cfg['n_routed_experts'])
            * (1.0 - (1.0 - k / e) ** float(live_rows)))


def moe_expert_bytes(cfg, live_rows):
    """Routed-expert weight bytes one decode step has to read over all
    routed layers with `live_rows` rows live: the expected distinct held
    experts, each with its gate, up and down matrices."""
    d, f = int(cfg['hidden_size']), int(cfg['moe_intermediate_size'])
    return (_routed_layers(cfg) * expected_distinct_experts(cfg, live_rows)
            * 3 * d * f * _DTYPE_BYTES[cfg['weights_dtype']])


def latent_proj_params(cfg):
    """The low-rank projections of one layer: q_a, q_b, kv_a and kv_b
    (kv_b is what q_absorb and v_expand multiply by)."""
    d, h = int(cfg['hidden_size']), int(cfg['num_attention_heads'])
    ql, kl = int(cfg['q_lora_rank']), int(cfg['kv_lora_rank'])
    dn, dr, dv = (int(cfg['qk_nope_head_dim']),
                  int(cfg['qk_rope_head_dim']), int(cfg['v_head_dim']))
    return d * ql + ql * h * (dn + dr) + d * (kl + dr) + kl * h * (dn + dv)


def attention_params(cfg):
    """The low-rank projections and o of one layer."""
    return (latent_proj_params(cfg) + int(cfg['num_attention_heads'])
            * int(cfg['v_head_dim']) * int(cfg['hidden_size']))


def _unrouted_params(cfg):
    """What every token multiplies by: each layer's attention, the leading
    dense SwiGLU, each routed layer's router and shared expert, the untied
    head over the slice held (norm vectors left out)."""
    d = int(cfg['hidden_size'])
    f = int(cfg['moe_intermediate_size'])
    return (int(cfg['num_hidden_layers']) * attention_params(cfg)
            + int(cfg['first_k_dense_replace']) * 3 * d
            * int(cfg['intermediate_size'])
            + _routed_layers(cfg)
            * (d * int(cfg['n_experts_routed'])
               + int(cfg['n_shared_experts']) * 3 * d * f)
            + d * int(cfg['vocab_size']))


def weight_params(cfg):
    """Parameters held on this chip: the above, the held experts and the
    embedding rows of the slice."""
    d = int(cfg['hidden_size'])
    return (_unrouted_params(cfg)
            + _routed_layers(cfg) * int(cfg['n_routed_experts']) * 3 * d
            * int(cfg['moe_intermediate_size'])
            + d * int(cfg['vocab_size']))


def step_dense_bytes(cfg):
    """Weight bytes every decode step reads once whatever the routing:
    the unrouted weights and one embedding row per slot."""
    return ((_unrouted_params(cfg)
             + int(cfg['max_slots']) * int(cfg['hidden_size']))
            * _DTYPE_BYTES[cfg['weights_dtype']])


def kv_row_bytes(cfg):
    """Bytes of one cached position in ONE layer that the algorithm needs:
    the latent and the one rotary key (576 values; the pool stores the row
    640 wide, whole lane tiles: the file's assumed.cache_row)."""
    return ((int(cfg['kv_lora_rank']) + int(cfg['qk_rope_head_dim']))
            * _DTYPE_BYTES[cfg['kv_cache_dtype']])


def attention_bytes(cfg, cached_rows, live):
    """Latent bytes one decode step's attention has to read with
    `cached_rows` positions cached over `live` decoding rows: every cached
    row of every live slot once a layer — all heads read the same row."""
    return kv_row_bytes(cfg) * int(cfg['num_hidden_layers']) * cached_rows


def attention_flops(cfg, cached_rows):
    """Multiply-adds x 2 of the absorbed attention over `cached_rows`
    positions in every layer: each of the heads scores a latent + rotary
    row and sums a latent one."""
    r, dr = int(cfg['kv_lora_rank']), int(cfg['qk_rope_head_dim'])
    return (2 * int(cfg['num_attention_heads']) * (2 * r + dr)
            * int(cfg['num_hidden_layers']) * cached_rows)


def step_needed_bytes(cfg, cached_rows):
    """Bytes the algorithm needs for one decode step with `cached_rows`
    positions cached over all slots: the non-routed weights once, the
    expected distinct held experts with every slot live (the closed loop
    holds occupancy near one), and the latent rows."""
    slots = int(cfg['max_slots'])
    return (step_dense_bytes(cfg) + moe_expert_bytes(cfg, slots)
            + attention_bytes(cfg, cached_rows, slots))


def step_floor_seconds(cfg, peaks, cached_rows):
    return step_needed_bytes(cfg, cached_rows) / peaks['hbm_bytes_per_s']
