"""olmoe_1b_7b: everything the decode runners ask a configuration for —
how the file becomes a decode artifact (models/olmoe.py), what the traffic
generator and the warm-up need to know of it, what the plain reference
(benchmark/reference/olmoe.py) says a sequence scores, and what a decode
step and its routed feed-forward have to move at the least."""
from __future__ import annotations

BOUND = 'memory'     # which roofline bounds the decode step
_DTYPE_BYTES = {'float32': 4, 'bfloat16': 2}


def build_spec(cfg):
    """The decode program set, through the repo's own builder."""
    from models.olmoe import build_decode_spec
    if int(cfg['num_key_value_heads']) != int(cfg['num_attention_heads']):
        raise ValueError('models/olmoe.py has no grouped K/V heads')
    spec = build_decode_spec(
        vocab=int(cfg['vocab_size']), d_model=int(cfg['hidden_size']),
        n_head=int(cfg['num_attention_heads']),
        n_layer=int(cfg['num_hidden_layers']),
        n_expert=int(cfg['num_experts']),
        d_expert=int(cfg['intermediate_size']),
        top_k=int(cfg['num_experts_per_tok']),
        max_slots=int(cfg['max_slots']),
        max_cache_len=int(cfg['max_cache_len']),
        block_size=int(cfg['block_size']),
        chunk_sizes=tuple(int(c) for c in cfg['chunk_sizes']),
        eos_id=int(cfg['eos_id']), kv_cache_dtype=cfg['kv_cache_dtype'],
        weights_dtype=cfg['weights_dtype'],
        rms_eps=float(cfg['rms_norm_eps']),
        rope_theta=float(cfg['rope_theta']),
        norm_topk_prob=bool(cfg['norm_topk_prob']),
        init_std=float(cfg['init_std']))
    spec['startup'].random_seed = int(cfg['weights_seed'])
    return spec


def vocab_size(cfg):
    """Token ids the traffic generator may draw lie in [2, vocab_size)."""
    return int(cfg['vocab_size'])


def chunk_sizes(cfg):
    """The prefill chunk programs' sizes, ascending."""
    return sorted(int(c) for c in cfg['chunk_sizes'])


def reference_logits(cfg, weights, ids):
    """[len(ids), vocab] float32 logits of the plain full forward pass
    over `ids` with these weights (host arrays, by the scope's names).
    np.savez keeps a bfloat16 array's bytes but not its dtype (it comes
    back as two-byte void): such a leaf is viewed as bfloat16 again. The
    reference upcasts one layer at a time, so the float32 copy of the
    model is never resident beside the serving pool."""
    import jax.numpy as jnp
    from ..reference import olmoe
    weights = {k: (v.view(jnp.bfloat16) if v.dtype.kind == 'V' else v)
               for k, v in weights.items()}
    return olmoe.logits(weights, ids,
                        n_head=int(cfg['num_attention_heads']),
                        n_layer=int(cfg['num_hidden_layers']),
                        top_k=int(cfg['num_experts_per_tok']),
                        eps=float(cfg['rms_norm_eps']),
                        theta=float(cfg['rope_theta']))


def expected_distinct_experts(cfg, live_rows):
    """Experts that at least one of `live_rows` tokens picks, each picking
    k of E uniformly and independently: E * (1 - (1 - k/E)^rows)."""
    e, k = int(cfg['num_experts']), int(cfg['num_experts_per_tok'])
    return e * (1.0 - (1.0 - k / e) ** float(live_rows))


def moe_expert_bytes(cfg, live_rows):
    """Expert-weight bytes one decode step has to read over all layers
    with `live_rows` rows live: the expected distinct experts, each with
    its gate, up and down matrices."""
    d, f = int(cfg['hidden_size']), int(cfg['intermediate_size'])
    return (int(cfg['num_hidden_layers'])
            * expected_distinct_experts(cfg, live_rows) * 3 * d * f
            * _DTYPE_BYTES[cfg['weights_dtype']])


def step_dense_bytes(cfg):
    """Weight bytes every decode step reads once whatever the routing:
    each layer's q/k/v/o matrices, router and norm weights, the final
    norm, the untied head, and one embedding row per slot."""
    d, v = int(cfg['hidden_size']), int(cfg['vocab_size'])
    wb = _DTYPE_BYTES[cfg['weights_dtype']]
    per_layer = (4 * d * d + d * int(cfg['num_experts'])) * wb + 4 * d * 4
    return (int(cfg['num_hidden_layers']) * per_layer + d * 4
            + (d * v + int(cfg['max_slots']) * d) * wb)


def kv_row_bytes(cfg):
    """Bytes of one cached position: K and V in every layer."""
    return (2 * int(cfg['num_hidden_layers']) * int(cfg['hidden_size'])
            * _DTYPE_BYTES[cfg['kv_cache_dtype']])


def step_needed_bytes(cfg, cached_rows):
    """Bytes the algorithm needs for one decode step with `cached_rows`
    positions cached over all slots: attention, router and head weights
    once, the expected distinct experts with every slot live (the closed
    loop holds occupancy near one: 99.2-99.9 % in the builder's runs),
    and every cached K/V row once."""
    return (step_dense_bytes(cfg)
            + moe_expert_bytes(cfg, int(cfg['max_slots']))
            + cached_rows * kv_row_bytes(cfg))


def step_floor_seconds(cfg, peaks, cached_rows):
    return step_needed_bytes(cfg, cached_rows) / peaks['hbm_bytes_per_s']
