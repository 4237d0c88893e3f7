"""Plain reference for the k_exaone_236b_a23b configuration: the full forward
pass of the K-EXAONE decoder over a whole sequence, in float32 jax.numpy at
'highest' matmul precision. No cache, no blocks, no batching, no sort, no
kernel, nothing of paddle_tpu: every held expert is computed densely for
every token and masked by the router's choice. Weights come in as a dict of
arrays under the names models/exaone_moe.py gives them — the served bfloat16
weights, upcast here to float32 where each is used, a few experts (or a
third of the dense layer's width) at a time, so that the model's float32
copy is never resident; attention runs 256 queries at a time, one block
after the other, and the head 2,048 rows at a time, so that a 6,000-token
sequence fits beside a serving replica's pool.

The config's keys do not fix every equation; what they leave open follows
the family's files in `transformers` 4.57.6 (no exaone_moe modeling file is
installed here): models/exaone4/modeling_exaone4.py (Exaone4DecoderLayer,
Exaone4Attention: post-norms before the residual add, per-head QK-norm,
rotary on sliding layers only) and models/deepseek_v3/modeling_deepseek_v3.py
(DeepseekV3TopkRouter, DeepseekV3MoE: the keys scoring_func, n_group,
topk_group, norm_topk_prob, routed_scaling_factor are its own), n_group =
topk_group = 1, so no group limit:

    RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w               (float32)
    q, k, v = x W_q, x W_k, x W_v             (no bias; n_head, n_kv_head)
    q_h, k_g = RMSNorm(q_h; w_qn), RMSNorm(k_g; w_kn)   per head, [d_head]
    sliding layers only: q, k = RoPE(q), RoPE(k)    rotate-half, theta
    Attn: head h reads K/V head h // (n_head / n_kv_head), scale
          d_head^-1/2, causal, sliding layers 0 <= pos - j < window
    h = x + RMSNorm(Attn W_o; w_post_attn)
    layers < first_dense:  m = (silu(h W_g) * h W_u) W_d
    others: s = sigmoid(h W_r) in float32;  T = top-k of s + b;
            g_e = scaling * s_e / (sum_{e in T} s_e + 1e-20)
            m = sum_{e in T, e held} g_e E_e(h) + E_shared(h)
    y = h + RMSNorm(m; w_post_ff);   logits = RMSNorm(y_L; w_final) W_head

THE SHARE. The weights are one chip's share of an expert-parallel layer:
W_r and b cover all experts, the expert matrices only those held
(`expert_offset` on). Pairs routed to an expert that is not held add
nothing, here as in the program, and the partial result goes on to the next
layer; the head's columns are the vocabulary slice held.

Departures from those files, each as the program under test has it
(models/exaone_moe.py):
  * matrices are stored [in, out] (x @ W), not torch's [out, in];
  * among equal router scores the lower expert index wins (jax.lax.top_k);
    torch.topk leaves a tie undefined;
  * the attention mask is causal (and windowed) only: one sequence, no
    padding mask;
  * the multi-token-prediction layer is not part of the forward pass.

`compute_dtype=bfloat16` runs the same expressions one precision below what
the configuration states and exists for one purpose: the bound on the served
programs' logit error has to be one that THIS fails (chip_smoke.py phase X).

What is compared and how closely: configs/k_exaone_236b_a23b.json "verify".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SLIDING = 'sliding_attention'
_EXPERT_GROUP = 4       # experts on the device at a time
_QUERY_BLOCK = 256      # attention rows at a time
_HEAD_BLOCK = 2048      # logits rows at a time


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


def head_norm(x, w, heads, eps):
    """RMSNorm over each head's channels: x [T, heads * d_head], w
    [d_head]."""
    t, d = x.shape
    return rms_norm(x.reshape(t, heads, d // heads), w, eps).reshape(t, d)


def rope(x, pos, n_head, theta):
    """x [T, n_head * d_head] at positions pos [T]: rotate-half."""
    t, d = x.shape
    dh = d // n_head
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32)
                                / dh))
    freqs = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    xh = x.reshape(t, n_head, dh)
    half = jnp.concatenate([-xh[..., dh // 2:], xh[..., :dh // 2]], axis=-1)
    return (xh * jnp.cos(emb) + half * jnp.sin(emb)).reshape(t, d)


def attention(x, w, n_head, n_kv_head, eps, theta, window, rotary):
    """x [T, D] -> [T, D]: grouped-head causal attention, `window` > 0
    keeping the last `window` positions only. Queries go _QUERY_BLOCK
    rows at a time, one block after the other (lax.map), each over all
    the keys under its mask: at most [n_head, _QUERY_BLOCK, T] scores
    are alive at once."""
    t = x.shape[0]
    dt = x.dtype
    g = n_head // n_kv_head
    q = head_norm(x @ w['q_w'].astype(dt), w['q_norm_w'].astype(dt),
                  n_head, eps)
    k = head_norm(x @ w['k_w'].astype(dt), w['k_norm_w'].astype(dt),
                  n_kv_head, eps)
    v = x @ w['v_w'].astype(dt)
    dh = k.shape[1] // n_kv_head
    if rotary:
        pos = jnp.arange(t)
        q = rope(q, pos, n_head, theta).astype(dt)
        k = rope(k, pos, n_kv_head, theta).astype(dt)
    blocks = -(-t // _QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * _QUERY_BLOCK - t), (0, 0)))
    q = q.reshape(blocks, _QUERY_BLOCK, n_kv_head, g, dh)
    k = k.reshape(t, n_kv_head, dh)
    v = v.reshape(t, n_kv_head, dh)
    j = jnp.arange(t)[None, :]

    def block(args):
        qb, lo = args
        i = lo + jnp.arange(_QUERY_BLOCK)[:, None]
        seen = j <= i
        if window:
            seen = seen & (i - j < window)
        s = jnp.einsum('qkgd,jkd->kgqj', qb, k) * jnp.asarray(dh ** -0.5, dt)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum('kgqj,jkd->qkgd', jax.nn.softmax(s, axis=-1), v)

    a = jax.lax.map(block, (q, jnp.arange(blocks) * _QUERY_BLOCK))
    return a.reshape(-1, n_head * dh)[:t] @ w['o_w'].astype(dt)


def attention_rows(xr, pos, x, w, n_head, n_kv_head, eps, theta, window,
                   rotary):
    """attention() for single rows whose own state is not the
    sequence's: xr [A, D] at positions pos [A], each attending the
    sequence x [T, D] BELOW its position (inside the window) and
    itself."""
    dt = x.dtype
    a, t = xr.shape[0], x.shape[0]
    g = n_head // n_kv_head

    def qkv(y, p):
        q = head_norm(y @ w['q_w'].astype(dt), w['q_norm_w'].astype(dt),
                      n_head, eps)
        k = head_norm(y @ w['k_w'].astype(dt), w['k_norm_w'].astype(dt),
                      n_kv_head, eps)
        if rotary:
            q = rope(q, p, n_head, theta).astype(dt)
            k = rope(k, p, n_kv_head, theta).astype(dt)
        return q, k, y @ w['v_w'].astype(dt)

    q, k_own, v_own = qkv(xr, pos)
    _, k, v = qkv(x, jnp.arange(t))
    dh = k.shape[1] // n_kv_head
    q = q.reshape(a, n_kv_head, g, dh)
    k_own, v_own = (y.reshape(a, n_kv_head, dh) for y in (k_own, v_own))
    k, v = (y.reshape(t, n_kv_head, dh) for y in (k, v))
    scale = jnp.asarray(dh ** -0.5, dt)
    j = jnp.arange(t)[None, :]
    seen = j < pos[:, None]
    if window:
        seen = seen & (pos[:, None] - j < window)
    s = jnp.where(seen[:, None, None, :],
                  jnp.einsum('akgd,jkd->akgj', q, k) * scale, -jnp.inf)
    own = jnp.einsum('akgd,akd->akg', q, k_own) * scale
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], axis=-1),
                       axis=-1)
    out = (jnp.einsum('akgj,jkd->akgd', p[..., :-1], v)
           + p[..., -1:] * v_own[:, :, None, :])
    return out.reshape(a, n_head * dh) @ w['o_w'].astype(dt)


def router_weights(x, router_w, bias, top_k, scaling, norm_topk_prob,
                   force=None):
    """[T, E]: each token's gate g_e at its chosen experts, zero
    elsewhere. The scores in float32 whatever x is (the family's router
    casts its input up); the choice by s + b, the gates from s alone.
    `force` [T, E] is added to s + b for the choice only: +inf takes an
    expert in (and the k-th best out), -inf takes it out (and the
    (k+1)-th in) — the other side of a near tie (logits either_way)."""
    n_expert = router_w.shape[1]
    s = jax.nn.sigmoid(x.astype(jnp.float32)
                       @ router_w.astype(jnp.float32))
    c = s + bias.astype(jnp.float32)[None, :]
    _, idx = jax.lax.top_k(c if force is None else c + force, top_k)
    vals = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        vals = vals / (jnp.sum(vals, axis=-1, keepdims=True) + 1e-20)
    vals = vals * scaling
    return jnp.sum(jax.nn.one_hot(idx, n_expert, dtype=jnp.float32)
                   * vals[..., None], axis=1).astype(x.dtype)


def routing_distances(x, router_w, bias, top_k, first, held):
    """([T, held] distance, [T, held] chosen): how far each expert held
    here ([first, first + held)) is from changing sides in each token's
    choice, in units of the router's LOGITS z = x W_r. The choice orders
    c_e = sigmoid(z_e) + b_e: a held expert that is chosen leaves when
    c_e falls under the (k+1)-th best, one that is not enters when c_e
    passes the k-th best. Moving the two logits against each other by d
    moves c_e - c_other by d * (s_e (1 - s_e) + s_o (1 - s_o)) to first
    order, so the distance is |c_e - c_other| over that slope. A
    distance below what the stated precision perturbs a logit by is a
    choice the reference cannot make for the served programs: the held
    expert's whole term comes or goes with it."""
    z = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    s = jax.nn.sigmoid(z)
    slope = s * jax.nn.sigmoid(-z)
    c = s + bias.astype(jnp.float32)[None, :]
    vals, idx = jax.lax.top_k(c, top_k + 1)
    edge = vals[:, top_k - 1:]                              # k-th, (k+1)-th
    edge_slope = jnp.take_along_axis(slope, idx[:, top_k - 1:], axis=-1)
    mine, mine_slope = (a[:, first:first + held] for a in (c, slope))
    chosen = mine >= edge[:, :1]
    other = jnp.where(chosen, edge[:, 1:], edge[:, :1])
    other_slope = jnp.where(chosen, edge_slope[:, 1:], edge_slope[:, :1])
    return (jnp.abs(mine - other) / (mine_slope + other_slope + 1e-30),
            chosen)


def routing_gap(x, router_w, bias, top_k, first, held):
    """[T]: each token's least routing_distances over the experts held."""
    return jnp.min(routing_distances(x, router_w, bias, top_k, first,
                                     held)[0], axis=-1)


def experts(x, w_gate, w_up, w_down, weight):
    """sum over the given experts e of weight[:, e] * expert_e(x): every
    one of them over every token. w_* [E', ...], weight [T, E']."""
    dt = x.dtype

    def one(acc, ew):
        wg, wu, wd, col = ew
        y = (jax.nn.silu(x @ wg.astype(dt)) * (x @ wu.astype(dt))) \
            @ wd.astype(dt)
        return acc + col[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (w_gate, w_up, w_down, weight.T))
    return out


def ffn(x, w_gate, w_up, w_down, parts=1):
    """(silu(x W_g) * x W_u) W_d, the width taken `parts` slices at a
    time (the same sum, and a third of the float32 copy at once)."""
    d, f = w_gate.shape
    sliced = (w_gate.reshape(d, parts, f // parts).transpose(1, 0, 2),
              w_up.reshape(d, parts, f // parts).transpose(1, 0, 2),
              w_down.reshape(parts, f // parts, d))
    return experts(x, *sliced, jnp.ones((x.shape[0], parts), x.dtype))


@functools.partial(jax.jit, static_argnames=(
    'n_head', 'n_kv_head', 'eps', 'theta', 'window', 'rotary'))
def _attend(x, w, n_head, n_kv_head, eps, theta, window, rotary):
    a = attention(x, w, n_head, n_kv_head, eps, theta, window, rotary)
    return x + rms_norm(a, w['post_attn_norm_w'].astype(x.dtype), eps)


@functools.partial(jax.jit, static_argnames=(
    'n_head', 'n_kv_head', 'eps', 'theta', 'window', 'rotary'))
def _attend_rows(xr, pos, x, w, n_head, n_kv_head, eps, theta, window,
                 rotary):
    a = attention_rows(xr, pos, x, w, n_head, n_kv_head, eps, theta, window,
                       rotary)
    return xr + rms_norm(a, w['post_attn_norm_w'].astype(x.dtype), eps)


_route = jax.jit(router_weights,
                 static_argnames=('top_k', 'scaling', 'norm_topk_prob'))
_gap = jax.jit(routing_gap, static_argnames=('top_k', 'first', 'held'))
_distances = jax.jit(routing_distances,
                     static_argnames=('top_k', 'first', 'held'))
_experts = jax.jit(experts)
_ffn = jax.jit(ffn, static_argnames=('parts',))


@functools.partial(jax.jit, static_argnames=('eps',))
def _finish(h, m, norm_w, eps):
    return h + rms_norm(m, norm_w.astype(h.dtype), eps)


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(x, norm_w, head_w, eps):
    return (rms_norm(x, norm_w.astype(x.dtype), eps)
            @ head_w.astype(x.dtype)).astype(jnp.float32)


def feed_forward(h, weights, i, first_dense, top_k, expert_offset, scaling,
                 norm_topk_prob, shared=True, force=None):
    """m of layer i for h [T, D]: the dense SwiGLU of a leading layer;
    else the held experts' part of the routed sum (+ the shared expert,
    unless `shared` is False: the share test counts it once). `force`:
    router_weights'."""
    p = 'l%d_' % i
    if i < first_dense:
        return _ffn(h, *(jnp.asarray(weights[p + 'ff_%s_w' % n])
                         for n in ('gate', 'up', 'down')), parts=3)
    weight = _route(h, jnp.asarray(weights[p + 'moe_router']),
                    jnp.asarray(weights[p + 'moe_router_bias']),
                    top_k=top_k, scaling=scaling,
                    norm_topk_prob=norm_topk_prob, force=force)
    held = weights[p + 'moe_gate'].shape[0]
    m = jnp.zeros_like(h)
    for e in range(0, held, _EXPERT_GROUP):
        group = slice(e, min(e + _EXPERT_GROUP, held))
        m = m + _experts(
            h, *(jnp.asarray(weights[p + 'moe_' + n][group])
                 for n in ('gate', 'up', 'down')),
            weight[:, expert_offset + group.start:
                   expert_offset + group.stop])
    if shared and p + 'shared_gate_w' in weights:
        m = m + _ffn(h, *(jnp.asarray(weights[p + 'shared_%s_w' % n])
                          for n in ('gate', 'up', 'down')), parts=1)
    return m


def logits(weights, ids, n_head, n_kv_head, n_layer, types, window,
           first_dense, top_k, expert_offset=0, scaling=2.5,
           norm_topk_prob=True, eps=1e-5, theta=1e6,
           compute_dtype=jnp.float32, routing_gaps=False, either_way=None):
    """[len(ids), vocab held] float32 logits: row p scores the token at
    p + 1. `weights` may hold bfloat16 (or float32) host or device
    arrays; `types[i]` is layer i's attention kind. With `routing_gaps`
    also [len(ids)], each position's smallest routing_gap over the
    routed layers.

    `either_way` = (rows, dist, capacity) also returns the OTHER SIDE of
    every near tie on those rows: for each row p of `rows`, routed layer
    i and held expert e with routing_distances <= dist, row p computed
    again from layer i on with e on the other side of the choice (p's
    state alone changes: it attends the sequence as it was), one at a
    time — {'row', 'layer', 'expert': [n] ints, 'logits': [n, vocab],
    'overflow': rows with near ties beyond the `capacity` such rows that
    are carried}. The carried rows ride behind the sequence through each
    feed-forward, finish and head, so no weight is read twice."""
    ids = jnp.asarray(ids, jnp.int32)
    t = ids.shape[0]
    gaps = jnp.full(ids.shape, jnp.inf, jnp.float32)
    attn_keys = ('q_w', 'k_w', 'v_w', 'o_w', 'q_norm_w', 'k_norm_w',
                 'post_attn_norm_w')
    rows, dist, cap = either_way or ((), 0.0, 0)
    rows = np.asarray(rows, np.int64)
    alt = {'row': np.zeros(cap, np.int32), 'layer': np.zeros(cap, np.int32),
           'expert': np.zeros(cap, np.int32)}
    n_alt, overflow = 0, set()
    with jax.default_matmul_precision('highest'):
        x = jnp.asarray(weights['embed_w'])[ids].astype(compute_dtype)
        x = jnp.concatenate([x, jnp.zeros((cap, x.shape[1]), x.dtype)])
        for i in range(n_layer):
            p = 'l%d_' % i
            sliding = types[i] == SLIDING
            lw = {k: jnp.asarray(weights[p + k]) for k in attn_keys}
            attn = dict(n_head=n_head, n_kv_head=n_kv_head, eps=eps,
                        theta=theta, window=int(window) if sliding else 0,
                        rotary=sliding)
            h = _attend(x[:t], lw, **attn)
            force = None
            if i >= first_dense:
                router = (jnp.asarray(weights[p + 'moe_router']),
                          jnp.asarray(weights[p + 'moe_router_bias']))
                held = dict(top_k=top_k, first=expert_offset,
                            held=weights[p + 'moe_gate'].shape[0])
                if routing_gaps:
                    gaps = jnp.minimum(gaps, _gap(h, *router, **held))
            if cap:
                ha = _attend_rows(x[t:], jnp.asarray(alt['row']), x[:t], lw,
                                  **attn)
                force = np.zeros((t + cap, router[0].shape[1]),
                                 np.float32) if i >= first_dense else None
                if force is not None and len(rows):
                    d, chosen = (np.asarray(a) for a in _distances(
                        h[rows], *router, **held))
                    for r, e in np.argwhere(d <= dist):
                        if n_alt == cap:
                            overflow.add(int(rows[r]))
                            continue
                        alt['row'][n_alt], alt['layer'][n_alt] = rows[r], i
                        alt['expert'][n_alt] = expert_offset + e
                        force[t + n_alt, expert_offset + e] = (
                            -np.inf if chosen[r, e] else np.inf)
                        ha = ha.at[n_alt].set(h[rows[r]])
                        n_alt += 1
                h = jnp.concatenate([h, ha])
            m = feed_forward(h, weights, i, first_dense, top_k,
                             expert_offset, scaling, norm_topk_prob,
                             force=None if force is None
                             else jnp.asarray(force))
            x = _finish(h, m, jnp.asarray(weights[p + 'post_ff_norm_w']),
                        eps=eps)
        norm_w = jnp.asarray(weights['final_norm_w'])
        head_w = jnp.asarray(weights['lm_head_w'])
        out = jnp.concatenate(
            [_head(x[lo:lo + _HEAD_BLOCK], norm_w, head_w, eps=eps)
             for lo in range(0, x.shape[0], _HEAD_BLOCK)], axis=0)
    found = [out[:t]] + ([gaps] if routing_gaps else [])
    if either_way:
        found.append(dict({k: v[:n_alt] for k, v in alt.items()},
                          logits=np.asarray(out[t:t + n_alt]),
                          overflow=sorted(overflow)))
    return found[0] if len(found) == 1 else tuple(found)
