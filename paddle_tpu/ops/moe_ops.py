"""Mixture-of-experts FFN (switch/top-1 routing) — TPU-native extension
for the mesh 'ep' axis (the reference has no MoE; expert parallelism is
part of the framework's first-class distributed design, SURVEY §2.4
extension).

GShard/Switch formulation: routing + dispatch are einsums over a STATIC
[tokens, experts, capacity] one-hot, so the whole layer is dense algebra —
sharding the expert dimension of the weights over 'ep'
(parallel.shard_embedding / shard_parameter) makes GSPMD insert the
dispatch/combine all-to-alls over ICI; no data-dependent shapes anywhere.
Tokens routed beyond an expert's capacity are dropped (output 0 for them)
— standard switch-transformer behavior, capacity_factor controls the
head-room.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register
from ..core import amp


@register('switch_moe_ffn', diff_inputs=('X', 'GateW', 'W1', 'W2'))
def _switch_moe_ffn(ctx, ins):
    x_in = ins['X'][0]                       # [..., D]
    gate_w = ins['GateW'][0]                 # [D, E]
    w1 = ins['W1'][0]                        # [E, D, F]
    w2 = ins['W2'][0]                        # [E, F, D]
    cap_factor = float(ctx.attr('capacity_factor', 1.25))

    lead = x_in.shape[:-1]
    d = x_in.shape[-1]
    e = gate_w.shape[-1]
    x = x_in.reshape(-1, d)                  # [N, D] token view
    n = x.shape[0]
    cap = max(1, int(-(-n * cap_factor // e)))   # ceil(N/E * factor)

    # router in f32 (softmax), matching the norm/softmax AMP policy
    logits = jnp.matmul(amp.promote_f32(x), amp.promote_f32(gate_w))
    gates = jax.nn.softmax(logits, axis=-1)      # [N, E]
    idx = jnp.argmax(gates, axis=-1)             # top-1 expert per token
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)      # [N, E]
    gate_val = jnp.sum(gates * onehot, axis=-1)             # [N]

    # position of each token within its expert's capacity (arrival order)
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot      # [N, E] 0-based
    keep = (pos < cap) & (onehot > 0)
    pos_oh = jax.nn.one_hot(jnp.sum(pos, axis=-1).astype(jnp.int32), cap,
                            dtype=jnp.float32)              # [N, C]
    dispatch = (keep.astype(jnp.float32).sum(-1)[:, None, None]
                * onehot[:, :, None] * pos_oh[:, None, :])  # [N, E, C]

    xt = x.astype(jnp.float32)
    expert_in = jnp.einsum('nec,nd->ecd', dispatch, xt)     # all-to-all in
    h = jax.nn.relu(jnp.einsum('ecd,edf->ecf',
                               expert_in.astype(w1.dtype), w1))
    out_e = jnp.einsum('ecf,efd->ecd', h, w2)               # [E, C, D]
    combined = jnp.einsum('nec,ecd->nd', dispatch,
                          out_e.astype(jnp.float32))        # all-to-all out
    out = combined * gate_val[:, None]
    # aux load-balancing loss (Switch Transformer eq. 4): E * sum_e
    # (fraction of tokens to e) * (mean router prob of e)
    frac = jnp.mean(onehot, axis=0)
    prob = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(frac * prob)
    out = amp.restore(out.astype(x_in.dtype), x_in)
    return {'Out': [out.reshape(*lead, d)],
            'AuxLoss': [aux.reshape(1)]}


@register('moe_topk_ffn', diff_inputs=('X', 'RouterW', 'WGate', 'WUp',
                                       'WDown'))
def _moe_topk_ffn(ctx, ins):
    """Dropless top-k routed SwiGLU FFN: X [..., D], RouterW [D, E],
    WGate / WUp [E, D, F], WDown [E, F, D] -> Out [..., D] float32.

    p = softmax(X RouterW) over the E experts in float32 (the product at
    'highest': a router that rounds its input picks other experts); the
    top-k values and indices (ties to the lower index, lax.top_k's
    rule), renormalised only when attr norm_topk_prob; out = sum_k p_k *
    WDown_e(silu(WGate_e x) * WUp_e x). No capacity and no
    [tokens, experts, capacity] tensor: the N * k (token, expert) pairs
    are sorted by expert and every expert multiplies its own contiguous
    rows (lax.ragged_dot, operands in the weights' dtype, float32
    accumulation), so no pair is dropped however uneven the routing.
    Each token's k partial results are summed in its own top-k order, so
    a row's output does not depend on what else is in the batch."""
    from .llm_ops import swiglu
    x_in = ins['X'][0]
    router_w, w_gate, w_up, w_down = (ins[n][0] for n in
                                      ('RouterW', 'WGate', 'WUp', 'WDown'))
    k = int(ctx.attr('k'))
    e, d, _ = w_gate.shape
    x = x_in.reshape(-1, d)
    n = x.shape[0]
    with jax.named_scope('router'):
        logits = jnp.matmul(x.astype(jnp.float32),
                            router_w.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        vals, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if ctx.attr('norm_topk_prob', False):
            vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    with jax.named_scope('dispatch'):
        expert = idx.reshape(-1)                       # [N * k]
        order = jnp.argsort(expert, stable=True)
        rows = x.astype(w_gate.dtype)[order // k]      # sorted by expert
        sizes = jnp.bincount(expert, length=e).astype(jnp.int32)
    with jax.named_scope('experts'):
        def grouped(a, w):
            return jax.lax.ragged_dot(a, w, sizes,
                                      preferred_element_type=jnp.float32)
        h = swiglu(grouped(rows, w_gate), grouped(rows, w_up))
        y = grouped(h.astype(w_down.dtype), w_down)    # [N * k, D]
    with jax.named_scope('combine'):
        y = y[jnp.argsort(order)].reshape(n, k, d)     # back to top-k order
        out = jnp.sum(y * vals[..., None], axis=1)
    return {'Out': [out.reshape(x_in.shape)]}
