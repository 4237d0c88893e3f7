"""Plain reference for the kimi_linear_48b_a3b configuration: the full forward
pass of the Kimi Linear decoder (moonshotai/Kimi-Linear-48B-A3B-Instruct,
model_type kimi_linear; arXiv:2510.26692) over a whole sequence, in float32
jax.numpy at 'highest' matmul precision, with the Kimi Delta Attention
layers as the TOKEN-BY-TOKEN RECURRENCE FROM A ZERO STATE over the whole
sequence and the latent attention layers in their PUBLISHED, EXPANDED form
(every head's own keys and values made from the latent by kv_b_proj) — so
that the served programs' chunked rule, their old-state step, their state
carried from slice to slice and their absorbed attention over the paged
latent (models/kimi_linear.py) are held to other mathematics than their
own. No cache, no blocks, no batching, no kernel, nothing of paddle_tpu:
every held expert is computed densely for every token and masked by the
router's choice. Weights come in as a dict of arrays under the names
models/kimi_linear.py gives them — the served bfloat16 weights, upcast here
where each is used, a few experts at a time; attention runs 256 queries at
a time and the head 2,048 rows at a time, so that a 6,000-token sequence
fits beside a serving replica's pools and states.

`transformers` 4.57.6 has no modeling_kimi.py, so the equations are the
paper's (section 3 and appendix), the config's keys and the family's
convention (DeepSeek-V3's MLA and router, whose keys these are); what the
config does not settle is listed in configs/kimi_linear_48b_a3b.json
"assumed". N is RMSNorm (x * rsqrt(mean(x^2) + eps) * w, eps 1e-5), layer i
1-based:

    block: h = x + Mixer(N(x)); y = h + FFN(N(h)); logits = N(y_L) W_head
    layer i is MLA where i is in linear_attn_config.full_attn_layers
    (4, 8, 12, ...), else KDA
    KDA (H heads, dk = dv = linear_attn_config.head_dim, conv of width K):
        q = silu(conv_K(xn W_q)), k = silu(conv_K(xn W_k)),
        v = silu(conv_K(xn W_v))       causal, depthwise, no bias
        q_h <- q_h rsqrt(sum q_h^2 + 1e-6) / sqrt(dk), k_h likewise unscaled
        g_h = -exp(A_log_h) softplus((xn W_fa W_fb)_h + dt_bias_h)  in R^dk
        beta_h = sigmoid((xn W_b)_h)
        per head, S [dk, dv] from ZERO:
            S <- Diag(e^{g}) S; m = S^T k; delta = beta (v - m);
            S <- S + k delta^T; o = S^T q
        y_h = N(o_h; w[dv]) * sigmoid((xn W_ga W_gb)_h);  out = [y_h] W_o
    MLA (H heads, dn | dp | dv, r = kv_lora_rank, q_lora_rank null,
        mla_use_nope):
        q = xn W_q -> H x [q_n dn | q_p dp];  [c | k_p] = xn W_dkv (r + dp)
        c <- N(c; w_kva);  [k_n,h | v_h] = c W_ukv (H x (dn + dv))
        score = (q_n,h . k_n,h + q_p,h . k_p) / sqrt(dn + dp)   NO rotation
        causal softmax, o_h = sum p v_h, out = [o_h] W_o
    FFN: layer 1 dense SwiGLU; else s = sigmoid(hn W_r) in float32; T =
        top-k of s + b; w_e = scaling s_e / (sum_T s + 1e-20);
        sum_{e in T, e held} w_e E_e(hn) + E_shared(hn)

THE SHARE and the routed layer are k_exaone_236b_a23b's: reference/
exaone_moe.py's feed_forward (router_weights, experts, ffn, the shared
expert), _distances and _head are used as they are, as is its way with a
near tie (logits either_way: PERF.md section 6, PR 30 and PR 40).

Departures, each as the program under test has it:
  * matrices are stored [in, out] (x @ W); a convolution's weight is [K,
    channels], row j multiplying the input K - 1 - j positions back;
  * the recurrence runs over everything token by token; the family's code
    runs a chunked form over a prompt: the same numbers in exact
    arithmetic;
  * among equal router scores the lower expert index wins (jax.lax.top_k);
  * the attention mask is causal only: one sequence, no padding mask.

The CONTROLS exist for one purpose, the bounds on the served programs' logit
error are read against them (chip_smoke.py phase L, tests/
test_kimi_linear.py): `compute_dtype=bfloat16` runs the same expressions one
precision below what the configuration states; `state_dtype=bfloat16` keeps
everything as stated but ROUNDS THE RECURRENT STATE to bfloat16 after every
token; `reset_every=C` ZEROES it before every C-th position (a state lost
between two slices of C tokens); `scalar_decay=True` gives every channel of
a head the head's MEAN log-decay (the rule of a Gated DeltaNet: what a
program that took g for a scalar would compute); `round_operands` names
parts — 'kda' (the q, k, v, beta, gate and output projections), 'decay' (the
two products of the decay's projection), 'mla' (the latent layers' four) —
whose matrix products take their LEFT OPERAND ROUNDED TO bfloat16 and
accumulate in float32, as the served programs multiply: what share of the
served error each part's operands explain.

What is compared and how closely: configs/kimi_linear_48b_a3b.json "verify".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import exaone_moe
from .exaone_moe import _HEAD_BLOCK, _QUERY_BLOCK, _distances, _head, rms_norm

KDA_KEYS = ('input_norm_w',) + tuple('kda_' + n for n in (
    'q_w', 'k_w', 'v_w', 'b_w', 'q_conv_w', 'k_conv_w', 'v_conv_w', 'f_a_w',
    'f_b_w', 'a_log', 'dt_bias', 'g_a_w', 'g_b_w', 'norm_w', 'o_w'))
MLA_KEYS = ('input_norm_w', 'q_w', 'kv_a_w', 'kv_a_norm_w', 'kv_b_w', 'o_w')


def _mm(x, w, rounded=False):
    """x @ w in x's precision; `rounded`: x through bfloat16 first (the
    round_operands control)."""
    if rounded:
        x = x.astype(jnp.bfloat16).astype(x.dtype)
    return x @ w.astype(x.dtype)


def is_full(i, full_attn_layers):
    """Layer i (0-based) is MLA where i + 1 is in the published list."""
    return i + 1 in {int(n) for n in full_attn_layers}


# -- latent attention, expanded, no position term ---------------------------
def _qkv(xn, w, n_head, d_nope, d_rope, d_v, eps, rnd=False):
    """q, k [T, H, dn + dp] (the one shared key part under every head) and
    v [T, H, dv] of normed rows."""
    dt = xn.dtype
    t = xn.shape[0]
    r = w['kv_a_norm_w'].shape[0]
    q = _mm(xn, w['q_w'], rnd).reshape(t, n_head, d_nope + d_rope)
    ckp = _mm(xn, w['kv_a_w'], rnd)
    c = rms_norm(ckp[:, :r], w['kv_a_norm_w'].astype(dt), eps)
    kv = _mm(c, w['kv_b_w'], rnd).reshape(t, n_head, d_nope + d_v)
    k = jnp.concatenate(
        [kv[..., :d_nope],
         jnp.broadcast_to(ckp[:, None, r:], (t, n_head, d_rope))], axis=-1)
    return q, k, kv[..., d_nope:]


def attention(x, w, n_head, d_nope, d_rope, d_v, eps, round_operands=()):
    """x [T, D] -> Attn(N(x)) [T, D]: causal, queries _QUERY_BLOCK rows at
    a time (lax.map), each over all the keys."""
    t = x.shape[0]
    dt = x.dtype
    rnd = 'mla' in round_operands
    q, k, v = _qkv(rms_norm(x, w['input_norm_w'].astype(dt), eps), w, n_head,
                   d_nope, d_rope, d_v, eps, rnd)
    scale = jnp.asarray((d_nope + d_rope) ** -0.5, dt)
    blocks = -(-t // _QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * _QUERY_BLOCK - t), (0, 0), (0, 0)))
    q = q.reshape(blocks, _QUERY_BLOCK, n_head, d_nope + d_rope)
    j = jnp.arange(t)[None, :]

    def block(args):
        qb, lo = args
        seen = j <= lo + jnp.arange(_QUERY_BLOCK)[:, None]
        s = jnp.einsum('qhd,jhd->hqj', qb, k) * scale
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum('hqj,jhd->qhd', jax.nn.softmax(s, axis=-1), v)

    a = jax.lax.map(block, (q, jnp.arange(blocks) * _QUERY_BLOCK))
    return _mm(a.reshape(-1, n_head * d_v)[:t], w['o_w'], rnd)


def attention_rows(xr, pos, x, w, n_head, d_nope, d_rope, d_v, eps,
                   round_operands=()):
    """attention() for single rows whose own state is not the sequence's:
    xr [A, D] at positions pos [A], each attending the sequence x [T, D]
    BELOW its position and itself."""
    dt = x.dtype
    rnd = 'mla' in round_operands
    norm_w = w['input_norm_w'].astype(dt)
    q, k_own, v_own = _qkv(rms_norm(xr, norm_w, eps), w, n_head, d_nope,
                           d_rope, d_v, eps, rnd)
    _, k, v = _qkv(rms_norm(x, norm_w, eps), w, n_head, d_nope, d_rope, d_v,
                   eps, rnd)
    scale = jnp.asarray((d_nope + d_rope) ** -0.5, dt)
    seen = jnp.arange(x.shape[0])[None, :] < pos[:, None]
    s = jnp.where(seen[:, None, :],
                  jnp.einsum('ahd,jhd->ahj', q, k) * scale, -jnp.inf)
    own = jnp.einsum('ahd,ahd->ah', q, k_own) * scale
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], axis=-1),
                       axis=-1)
    out = (jnp.einsum('ahj,jhd->ahd', p[..., :-1], v) + p[..., -1:] * v_own)
    return _mm(out.reshape(xr.shape[0], n_head * d_v), w['o_w'], rnd)


# -- Kimi Delta Attention ----------------------------------------------------
def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + jnp.asarray(1e-6, x.dtype))


def _kda_inputs(xn, w, heads, scalar_decay=False, round_operands=()):
    """(u [T, 3 W] the convolutions' inputs q | k | v, gate [T, H, dv],
    beta [T, H], g [T, H, dk]) of normed rows."""
    dt = xn.dtype
    t = xn.shape[0]
    rnd, rnd_f = 'kda' in round_operands, 'decay' in round_operands
    u = jnp.concatenate([_mm(xn, w['kda_%s_w' % n], rnd)
                         for n in 'qkv'], axis=-1)
    beta = jax.nn.sigmoid(_mm(xn, w['kda_b_w'], rnd))
    f = _mm(_mm(xn, w['kda_f_a_w'], rnd_f), w['kda_f_b_w'], rnd_f)
    g = -jnp.exp(w['kda_a_log'].astype(jnp.float32))[:, None] \
        * jax.nn.softplus((f.astype(jnp.float32)
                           + w['kda_dt_bias'].astype(jnp.float32))
                          .reshape(t, heads, -1))
    if scalar_decay:        # the control: one decay a head
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    gate = jax.nn.sigmoid(
        _mm(_mm(xn, w['kda_g_a_w'], rnd), w['kda_g_b_w'], rnd))
    return u, gate.reshape(t, heads, -1), beta, g.astype(dt)


def _conv_weight(w):
    return jnp.concatenate([w['kda_%s_conv_w' % n] for n in 'qkv'], axis=1)


def _rule_inputs(u, heads):
    """The convolved u [T, 3 W] as q, k (normalised, q scaled) and v, each
    [T, H, d]."""
    t = u.shape[0]
    q, k, v = (p.reshape(t, heads, -1) for p in jnp.split(u, 3, axis=-1))
    return (_l2norm(q) * jnp.asarray(q.shape[-1] ** -0.5, u.dtype),
            _l2norm(k), v)


def _rule_step(S, q, k, v, g, beta, state_dtype):
    """The recurrence for one token: S [H, dk, dv], g [H, dk]."""
    S = S.astype(q.dtype) * jnp.exp(g)[:, :, None]
    m = jnp.einsum('hkv,hk->hv', S, k)
    delta = beta[:, None] * (v - m)
    S = S + k[:, :, None] * delta[:, None, :]
    return S.astype(state_dtype), jnp.einsum('hkv,hk->hv', S, q)


def _finish_kda(o, gate, w, eps, round_operands=()):
    """[T, H, dv] rule outputs -> [T, D]: the head norm, the sigmoid gate,
    the output projection."""
    dt = o.dtype
    y = rms_norm(o, w['kda_norm_w'].astype(dt), eps) * gate
    return _mm(y.reshape(o.shape[0], -1), w['kda_o_w'],
               'kda' in round_operands)


def kda(x, w, heads, eps, lo=0, keep=0, state_dtype=None, reset_every=0,
        scalar_decay=False, round_operands=()):
    """x [T, D] -> (KDA(N(x)) [T, D], u [T, 3 W] the convolutions' inputs,
    states [keep, H, dk, dv]: the state BEFORE positions lo .. lo + keep -
    1, for kda_rows). One lax.scan over the T tokens from a zero state."""
    dt = x.dtype
    sdt = dt if state_dtype is None else state_dtype
    t = x.shape[0]
    xn = rms_norm(x, w['input_norm_w'].astype(dt), eps)
    u, gate, beta, g = _kda_inputs(xn, w, heads, scalar_decay,
                                   round_operands)
    c = _conv_weight(w).astype(dt)
    width = c.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, u.shape[1]), dt), u])
    conv = jax.nn.silu(sum(c[j] * padded[j:j + t] for j in range(width)))
    q, k, v = _rule_inputs(conv, heads)

    def one(carry, xs):
        S, buf = carry
        q_t, k_t, v_t, g_t, b_t, i = xs
        if reset_every:     # the control: a state lost between slices
            S = jnp.where(i % reset_every == 0, jnp.zeros_like(S), S)
        if keep:
            at = jnp.clip(i - lo, 0, keep - 1)
            inside = (i >= lo) & (i < lo + keep)
            cur = jax.lax.dynamic_index_in_dim(buf, at, 0, keepdims=False)
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, jnp.where(inside, S, cur), at, 0)
        S, o_t = _rule_step(S, q_t, k_t, v_t, g_t, b_t, sdt)
        return (S, buf), o_t

    zero = jnp.zeros((heads,) + (q.shape[-1], v.shape[-1]), sdt)
    (_, states), o = jax.lax.scan(
        one, (zero, jnp.zeros((max(keep, 1),) + zero.shape, sdt)),
        (q, k, v, g, beta, jnp.arange(t)))
    return _finish_kda(o.astype(dt), gate, w, eps, round_operands), u, states


def kda_rows(xr, pos, u_seq, states, lo, w, heads, eps, state_dtype=None,
             scalar_decay=False, round_operands=()):
    """kda() for single rows whose own state is not the sequence's: xr [A,
    D] at positions pos [A] (each in [lo, lo + len(states))), each one
    token on from the SEQUENCE's state and convolution inputs below its
    position."""
    dt = xr.dtype
    sdt = dt if state_dtype is None else state_dtype
    xn = rms_norm(xr, w['input_norm_w'].astype(dt), eps)
    u, gate, beta, g = _kda_inputs(xn, w, heads, scalar_decay,
                                   round_operands)
    c = _conv_weight(w).astype(dt)
    width = c.shape[0]
    conv = c[width - 1] * u
    for j in range(width - 1):
        at = pos - (width - 1) + j
        prev = jnp.where((at >= 0)[:, None],
                         u_seq[jnp.clip(at, 0, u_seq.shape[0] - 1)], 0.0)
        conv = conv + c[j] * prev
    q, k, v = _rule_inputs(jax.nn.silu(conv), heads)
    at = jnp.clip(pos - lo, 0, states.shape[0] - 1)
    # a carried row's state is 2 MB at the published widths: 32 at a time
    o = jax.lax.map(
        lambda xs: _rule_step(states[xs[0]], *xs[1:], state_dtype=sdt)[1],
        (at, q, k, v, g, beta), batch_size=32)
    return _finish_kda(o.astype(dt), gate, w, eps, round_operands)


_MLA_STATIC = ('n_head', 'd_nope', 'd_rope', 'd_v', 'eps', 'round_operands')
_KDA_STATIC = ('heads', 'eps', 'keep', 'state_dtype', 'reset_every',
               'scalar_decay', 'round_operands')


@functools.partial(jax.jit, static_argnames=_MLA_STATIC)
def _attend(x, w, **kw):
    return x + attention(x, w, **kw)


@functools.partial(jax.jit, static_argnames=_MLA_STATIC)
def _attend_rows(xr, pos, x, w, **kw):
    return xr + attention_rows(xr, pos, x, w, **kw)


@functools.partial(jax.jit, static_argnames=_KDA_STATIC)
def _mix(x, w, lo, **kw):
    out, u, states = kda(x, w, lo=lo, **kw)
    return x + out, u, states


@functools.partial(jax.jit, static_argnames=tuple(
    k for k in _KDA_STATIC if k not in ('keep', 'reset_every')))
def _mix_rows(xr, pos, u_seq, states, lo, w, **kw):
    return xr + kda_rows(xr, pos, u_seq, states, lo, w, **kw)


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(h, norm_w, eps):
    return rms_norm(h, norm_w.astype(h.dtype), eps)


def mixer(x, weights, i, full_attn_layers, kda_heads, n_head, d_nope,
          d_rope, d_v, eps=1e-5):
    """x + Mixer_i(N(x)) for the whole sequence x [T, D]: what the share
    test counts once."""
    p = 'l%d_' % i
    if is_full(i, full_attn_layers):
        return _attend(x, {k: jnp.asarray(weights[p + k]) for k in MLA_KEYS},
                       n_head=n_head, d_nope=d_nope, d_rope=d_rope, d_v=d_v,
                       eps=eps)
    return _mix(x, {k: jnp.asarray(weights[p + k]) for k in KDA_KEYS}, 0,
                heads=kda_heads, eps=eps, keep=0, state_dtype=None,
                reset_every=0, scalar_decay=False)[0]


def logits(weights, ids, n_layer, full_attn_layers, kda_heads, n_head,
           d_nope, d_rope, d_v, first_dense, top_k, expert_offset=0,
           scaling=2.446, norm_topk_prob=True, eps=1e-5,
           compute_dtype=jnp.float32, state_dtype=None, reset_every=0,
           scalar_decay=False, round_operands=(), either_way=None):
    """[len(ids), vocab held] float32 logits: row p scores the token at
    p + 1. `weights` may hold bfloat16 (or float32) host or device arrays.
    `state_dtype`, `reset_every`, `scalar_decay`, `round_operands`: the
    module's CONTROLS.
    `either_way` = (rows, dist, capacity) is reference/exaone_moe.logits'
    own — the OTHER SIDE of every near tie of a held expert on `rows`
    (consecutive positions), one at a time, the carried rows riding behind
    the sequence through each feed-forward and the head, each with `dist`,
    how near a tie it was; a carried row takes the SEQUENCE's keys, values,
    recurrent state and convolution inputs below its position and its own
    from there."""
    ids = jnp.asarray(ids, jnp.int32)
    t = ids.shape[0]
    rows, dist, cap = either_way or ((), 0.0, 0)
    rows = np.asarray(rows, np.int64)
    if len(rows) and (np.diff(rows) != 1).any():
        raise ValueError('either_way rows must be consecutive positions')
    lo, keep = (int(rows[0]), len(rows)) if cap and len(rows) else (0, 0)
    alt = {'row': np.full(cap, lo, np.int32),
           'layer': np.zeros(cap, np.int32),
           'expert': np.zeros(cap, np.int32),
           'dist': np.zeros(cap, np.float32)}
    n_alt, overflow = 0, set()
    rounded = tuple(sorted(round_operands))
    mla = dict(n_head=n_head, d_nope=d_nope, d_rope=d_rope, d_v=d_v, eps=eps,
               round_operands=rounded)
    lin = dict(heads=kda_heads, eps=eps, scalar_decay=bool(scalar_decay),
               round_operands=rounded,
               state_dtype=(None if state_dtype is None
                            else jnp.dtype(state_dtype)))
    with jax.default_matmul_precision('highest'):
        x = jnp.asarray(weights['embed_w'])[ids].astype(compute_dtype)
        x = jnp.concatenate([x, jnp.zeros((cap, x.shape[1]), x.dtype)])
        for i in range(n_layer):
            p = 'l%d_' % i
            full_layer = is_full(i, full_attn_layers)
            lw = {k: jnp.asarray(weights[p + k])
                  for k in (MLA_KEYS if full_layer else KDA_KEYS)}
            post_w = jnp.asarray(weights[p + 'post_attn_norm_w'])
            pos = jnp.asarray(alt['row'])
            if full_layer:
                h = _attend(x[:t], lw, **mla)
                ha = _attend_rows(x[t:], pos, x[:t], lw, **mla) \
                    if cap else None
            else:
                h, u, states = _mix(x[:t], lw, lo, keep=keep,
                                    reset_every=int(reset_every), **lin)
                ha = _mix_rows(x[t:], pos, u, states, lo, lw, **lin) \
                    if cap else None
                del u, states
            hn = _norm(h, post_w, eps=eps)
            force = None
            if cap:
                if i >= first_dense:
                    router = (jnp.asarray(weights[p + 'moe_router']),
                              jnp.asarray(weights[p + 'moe_router_bias']))
                    force = np.zeros((t + cap, router[0].shape[1]),
                                     np.float32)
                if force is not None and len(rows):
                    d, chosen = (np.asarray(a) for a in _distances(
                        hn[rows], *router, top_k=top_k, first=expert_offset,
                        held=weights[p + 'moe_gate'].shape[0]))
                    for r, e in np.argwhere(d <= dist):
                        if n_alt == cap:
                            overflow.add(int(rows[r]))
                            continue
                        alt['row'][n_alt], alt['layer'][n_alt] = rows[r], i
                        alt['expert'][n_alt] = expert_offset + e
                        alt['dist'][n_alt] = d[r, e]
                        force[t + n_alt, expert_offset + e] = (
                            -np.inf if chosen[r, e] else np.inf)
                        ha = ha.at[n_alt].set(h[rows[r]])
                        n_alt += 1
                h = jnp.concatenate([h, ha])
                hn = _norm(h, post_w, eps=eps)
            x = h + exaone_moe.feed_forward(
                hn, weights, i, first_dense, top_k, expert_offset, scaling,
                norm_topk_prob,
                force=None if force is None else jnp.asarray(force))
        norm_w = jnp.asarray(weights['final_norm_w'])
        head_w = jnp.asarray(weights['lm_head_w'])
        out = jnp.concatenate(
            [_head(x[s:s + _HEAD_BLOCK], norm_w, head_w, eps=eps)
             for s in range(0, x.shape[0], _HEAD_BLOCK)], axis=0)
    if not either_way:
        return out[:t]
    return out[:t], dict({k: v[:n_alt] for k, v in alt.items()},
                         logits=np.asarray(out[t:t + n_alt]),
                         overflow=sorted(overflow))
