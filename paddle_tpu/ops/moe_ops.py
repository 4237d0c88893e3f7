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


def _grouped_body(ctx, rows, w_in, w_out, sizes):
    """grouped(a, w) for the three products of one moe_topk_ffn: the
    platform switch (the Pallas kernel on a TPU, lax.ragged_dot anywhere
    else) where pgm.refuses takes both weight shapes, lax.ragged_dot
    alone where it names a rule — chosen from the operands and the trace
    mesh, never from a knob, and told to the Tracer (lowered_bodies:
    'grouped_kernel' | 'ragged_dot') as kv_block_attention tells its
    own."""
    from . import pallas_grouped_matmul as pgm
    tracer = getattr(ctx, 'tracer', None)
    if tracer is None:      # shape inference: no Tracer, and any body will do
        kernel = False
    else:
        h = jax.ShapeDtypeStruct((rows.shape[0], w_in.shape[2]), w_out.dtype)
        kernel = (pgm.refuses(rows, w_in, sizes)
                  or pgm.refuses(h, w_out, sizes)) is None
        tracer.lowered_bodies.append(
            ('moe_topk_ffn', 'grouped_kernel' if kernel else 'ragged_dot'))
    body = pgm.kernel_or_ragged_dot if kernel else pgm.ragged_dot
    return lambda a, w: body(a, w, sizes)


@register('moe_topk_ffn', diff_inputs=('X', 'RouterW', 'WGate', 'WUp',
                                       'WDown'))
def _moe_topk_ffn(ctx, ins):
    """Dropless top-k routed SwiGLU FFN: X [..., D], RouterW [D, E],
    WGate / WUp [H, D, F], WDown [H, F, D] -> Out [..., D] float32.

    s = softmax(X RouterW) over the E experts in float32 (the product at
    'highest': a router that rounds its input picks other experts), or,
    with attr scoring 'sigmoid', the logits' sigmoid; the top-k values
    and indices (ties to the lower index, lax.top_k's rule) — chosen by
    s + RouterBias [E] where that input is given, the VALUES always s's
    own; renormalised only when attr norm_topk_prob (a sigmoid router's
    sum takes the family's + 1e-20: its scores can all underflow), then
    times attr routed_scaling_factor; out = sum_k s_k *
    WDown_e(silu(WGate_e x) * WUp_e x). No capacity and no
    [tokens, experts, capacity] tensor: the N * k (token, expert) pairs
    are sorted by expert and every expert multiplies its own contiguous
    rows (a grouped matmul, operands in the weights' dtype, float32
    accumulation), so no pair is dropped however uneven the routing.
    Each token's k partial results are summed in its own top-k order, so
    a row's output does not depend on what else is in the batch.

    Two bodies for the three grouped matmuls (_grouped_body). Every
    platform but a TPU — and on a TPU float32 experts, a width that is
    no multiple of 128 or a sharded trace (pgm.refuses names the rule) —
    multiplies with lax.ragged_dot. Compiled for a TPU,
    ops/pallas_grouped_matmul.py's kernel streams each expert's weights
    through VMEM once, group boundaries read from cumsum(sizes) and the
    row tiles behind sum(sizes) skipped: the same products on the same
    bfloat16 operands, summed in float32 (on the chip its results equal
    ragged_dot's to the bit at the three benchmark configurations'
    shapes: PERF.md, PR 41). silu(gate) * up stays on the float32
    results and h is cast to WDown's dtype in front of the third product
    in both. The gradient is ragged_dot's on every platform.
    lowered_bodies says 'grouped_kernel' / 'ragged_dot', one entry an op.

    The op HOLDS the H = WGate.shape[0] experts [expert_offset,
    expert_offset + H) of the E the router scores (one chip's share of
    an expert-parallel layer; H = E and offset 0: all of them). Routing
    is over all E; a pair whose expert is not held sorts behind the held
    groups, multiplies nothing and adds exactly zero — the op returns
    its own experts' part of the layer, and nothing stands in for the
    rest."""
    from .llm_ops import swiglu
    x_in = ins['X'][0]
    router_w, w_gate, w_up, w_down = (ins[n][0] for n in
                                      ('RouterW', 'WGate', 'WUp', 'WDown'))
    bias = (ins.get('RouterBias') or [None])[0]
    k = int(ctx.attr('k'))
    sigmoid = ctx.attr('scoring', 'softmax') == 'sigmoid'
    scaling = float(ctx.attr('routed_scaling_factor', 1.0))
    offset = int(ctx.attr('expert_offset', 0))
    e, d, _ = w_gate.shape
    partial = offset != 0 or e != router_w.shape[1]
    x = x_in.reshape(-1, d)
    n = x.shape[0]
    with jax.named_scope('router'):
        logits = jnp.matmul(x.astype(jnp.float32),
                            router_w.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if sigmoid
                  else jax.nn.softmax(logits, axis=-1))
        if bias is None:
            vals, idx = jax.lax.top_k(scores, k)
        else:
            _, idx = jax.lax.top_k(
                scores + bias.astype(jnp.float32).reshape(1, -1), k)
            vals = jnp.take_along_axis(scores, idx, axis=-1)
        if ctx.attr('norm_topk_prob', False):
            total = jnp.sum(vals, axis=-1, keepdims=True)
            vals = vals / (total + 1e-20 if sigmoid else total)
        if scaling != 1.0:
            vals = vals * scaling
    with jax.named_scope('dispatch'):
        expert = idx.reshape(-1)                       # [N * k]
        if partial:
            # pairs of experts that live elsewhere: group `e`, behind
            # every held group, outside the grouped matmuls' sizes
            held = (expert >= offset) & (expert < offset + e)
            expert = jnp.where(held, expert - offset, e)
        order = jnp.argsort(expert, stable=True)
        rows = x.astype(w_gate.dtype)[order // k]      # sorted by expert
        sizes = jnp.bincount(expert, length=e).astype(jnp.int32)
    with jax.named_scope('experts'):
        grouped = _grouped_body(ctx, rows, w_gate, w_down, sizes)
        h = swiglu(grouped(rows, w_gate), grouped(rows, w_up))
        y = grouped(h.astype(w_down.dtype), w_down)    # [N * k, D]
    with jax.named_scope('combine'):
        if partial:
            # what the grouped matmuls leave in the rows past their
            # sizes is not the op's to read
            y = jnp.where(held[order][:, None], y, 0.0)
        y = y[jnp.argsort(order)].reshape(n, k, d)     # back to top-k order
        out = jnp.sum(y * vals[..., None], axis=1)
    return {'Out': [out.reshape(x_in.shape)]}
