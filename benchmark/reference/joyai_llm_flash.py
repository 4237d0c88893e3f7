"""Plain reference for the joyai_llm_flash configuration: the full forward
pass of the JoyAI-LLM-Flash decoder over a whole sequence, in float32
jax.numpy at 'highest' matmul precision, with the attention in its
PUBLISHED, EXPANDED form — every head's own keys and values made from the
latent by kv_b_proj — so that the served programs' absorbed path (models/
joyai_llm_flash.py) is held to other mathematics than its own. No cache, no
blocks, no batching, no kernel, nothing of paddle_tpu: every held expert is
computed densely for every token and masked by the router's choice. Weights
come in as a dict of arrays under the names models/joyai_llm_flash.py gives
them — the served bfloat16 weights, upcast here where each is used, a few
experts (or a quarter of the dense layer's width) at a time; attention runs
256 queries at a time, one block after the other, and the head 2,048 rows
at a time, so that a 4,048-token sequence fits beside a serving replica's
pool.

The config's keys are DeepSeek-V3's; every equation is `transformers`
4.57.6 models/deepseek_v3/modeling_deepseek_v3.py's (line numbers below):

    RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w   (float32)     :56-70
    block: h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h))       :477-497
    cq = RMSNorm(xn W_dq); q = cq W_uq -> H x [q_nope | q_rope]    :396-399
    [c | k_r] = xn W_dkv;  c <- RMSNorm(c)                         :401-404
    [k_nope_i | v_i] = c W_ukv (H x (dn + dv))                     :404-405
    q_rope_i, k_r <- RoPE: pairs (2j, 2j+1) turned by pos * theta^(-2j/dr)
                     (rope_interleave), k_r ONE key for all heads  :283-318, :409-413
    score = (q_nope_i . k_nope_i + q_rope_i . k_r) / sqrt(dn + dr),
    causal softmax, o_i = sum p v_i, a = [o_1 .. o_H] W_o          :260-280, :437-451
    layers < first_dense: FFN = (silu(x W_g) * x W_u) W_d          :155-168
    others: s = sigmoid(x W_r) in float32; T = top-k of s + b;
            g_e = scaling * s_e / (sum_T s + 1e-20)                :115-152
            FFN = sum_{e in T, e held} g_e E_e(x) + E_shared(x)    :171-207
    logits = RMSNorm(y_L; w_final) W_head

THE SHARE and the routed layer are k_exaone_236b_a23b's (the same keys, the
same file of transformers): reference/exaone_moe.py's router_weights,
routing_distances, experts, ffn and feed_forward are used as they are, as
is its way with a near tie (logits either_way: PERF.md section 6, PR 30).

Departures from that file, each as the program under test has it:
  * matrices are stored [in, out] (x @ W), not torch's [out, in];
  * the rotation turns each neighbouring pair IN PLACE; the file moves the
    pairs to the two halves first (apply_rotary_pos_emb_interleave), the
    same permutation of q_rope's and k_r's channels, so every q . k is the
    same number and nothing else reads those channels;
  * rope_scaling is null in this model's config: no YaRN factor in the
    softmax scale;
  * among equal router scores the lower expert index wins (jax.lax.top_k);
  * the attention mask is causal only: one sequence, no padding mask;
  * the multi-token-prediction layer is not part of the forward pass.

`compute_dtype=bfloat16` runs the same expressions one precision below what
the configuration states and exists for one purpose: the bound on the served
programs' logit error has to be one that THIS fails (chip_smoke.py phase J).

What is compared and how closely: configs/joyai_llm_flash.json "verify".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import exaone_moe
from .exaone_moe import _distances, _ffn, _gap, _head, rms_norm

_QUERY_BLOCK = 256      # attention rows at a time

ATTN_KEYS = ('input_norm_w', 'q_a_w', 'q_a_norm_w', 'q_b_w', 'kv_a_w',
             'kv_a_norm_w', 'kv_b_w', 'o_w')


def rope_pairs(x, pos, n_head, theta):
    """x [T, n_head * d] at positions pos [T]: within each head the
    neighbouring pair (2j, 2j+1) turns by pos * theta^(-2j/d), in place."""
    t, width = x.shape
    d = width // n_head
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]   # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xh = x.reshape(t, n_head, d // 2, 2)
    even, odd = xh[..., 0], xh[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(t, width)


def _qkv(xn, pos, w, n_head, d_nope, d_rope, d_v, eps, theta):
    """The published projections of normed rows xn [T, D] at positions
    pos: q [T, H, dn + dr], k [T, H, dn + dr] (the one rotary key under
    every head), v [T, H, dv]."""
    dt = xn.dtype
    t = xn.shape[0]
    r = w['kv_a_norm_w'].shape[0]
    cq = rms_norm(xn @ w['q_a_w'].astype(dt), w['q_a_norm_w'].astype(dt),
                  eps)
    q = (cq @ w['q_b_w'].astype(dt)).reshape(t, n_head, d_nope + d_rope)
    q_rope = rope_pairs(q[..., d_nope:].reshape(t, n_head * d_rope), pos,
                        n_head, theta).astype(dt)
    q = jnp.concatenate([q[..., :d_nope],
                         q_rope.reshape(t, n_head, d_rope)], axis=-1)
    ckr = xn @ w['kv_a_w'].astype(dt)
    c = rms_norm(ckr[:, :r], w['kv_a_norm_w'].astype(dt), eps)
    k_r = rope_pairs(ckr[:, r:], pos, 1, theta).astype(dt)       # [T, dr]
    kv = (c @ w['kv_b_w'].astype(dt)).reshape(t, n_head, d_nope + d_v)
    k = jnp.concatenate(
        [kv[..., :d_nope],
         jnp.broadcast_to(k_r[:, None, :], (t, n_head, d_rope))], axis=-1)
    return q, k, kv[..., d_nope:]


def attention(x, w, n_head, d_nope, d_rope, d_v, eps, theta):
    """x [T, D] -> Attn(RMSNorm(x)) [T, D], expanded: causal, queries
    _QUERY_BLOCK rows at a time (lax.map), each over all the keys."""
    t = x.shape[0]
    dt = x.dtype
    xn = rms_norm(x, w['input_norm_w'].astype(dt), eps)
    q, k, v = _qkv(xn, jnp.arange(t), w, n_head, d_nope, d_rope, d_v, eps,
                   theta)
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
    return a.reshape(-1, n_head * d_v)[:t] @ w['o_w'].astype(dt)


def attention_rows(xr, pos, x, w, n_head, d_nope, d_rope, d_v, eps, theta):
    """attention() for single rows whose own state is not the sequence's:
    xr [A, D] at positions pos [A], each attending the sequence x [T, D]
    BELOW its position and itself."""
    dt = x.dtype
    norm_w = w['input_norm_w'].astype(dt)
    q, k_own, v_own = _qkv(rms_norm(xr, norm_w, eps), pos, w, n_head,
                           d_nope, d_rope, d_v, eps, theta)
    _, k, v = _qkv(rms_norm(x, norm_w, eps), jnp.arange(x.shape[0]), w,
                   n_head, d_nope, d_rope, d_v, eps, theta)
    scale = jnp.asarray((d_nope + d_rope) ** -0.5, dt)
    seen = jnp.arange(x.shape[0])[None, :] < pos[:, None]
    s = jnp.where(seen[:, None, :],
                  jnp.einsum('ahd,jhd->ahj', q, k) * scale, -jnp.inf)
    own = jnp.einsum('ahd,ahd->ah', q, k_own) * scale
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], axis=-1),
                       axis=-1)
    out = (jnp.einsum('ahj,jhd->ahd', p[..., :-1], v)
           + p[..., -1:] * v_own)
    return out.reshape(xr.shape[0], n_head * d_v) @ w['o_w'].astype(dt)


def absorbed_attention(x, w, n_head, d_nope, d_rope, d_v, eps, theta):
    """attention() in the ABSORBED form — scores and sums over the latent
    itself, W_uk folded into the query and W_uv into the result — for the
    test that says the two are the same numbers. The reference's forward
    pass never calls it."""
    t = x.shape[0]
    dt = x.dtype
    r = w['kv_a_norm_w'].shape[0]
    xn = rms_norm(x, w['input_norm_w'].astype(dt), eps)
    q, k, _ = _qkv(xn, jnp.arange(t), w, n_head, d_nope, d_rope, d_v, eps,
                   theta)
    c = rms_norm((xn @ w['kv_a_w'].astype(dt))[:, :r],
                 w['kv_a_norm_w'].astype(dt), eps)
    w_ukv = w['kv_b_w'].astype(dt).reshape(r, n_head, d_nope + d_v)
    q_lat = jnp.einsum('thd,rhd->thr', q[..., :d_nope], w_ukv[..., :d_nope])
    s = (jnp.einsum('thr,jr->htj', q_lat, c)
         + jnp.einsum('thd,jd->htj', q[..., d_nope:], k[:, 0, d_nope:]))
    s = s * jnp.asarray((d_nope + d_rope) ** -0.5, dt)
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    o_lat = jnp.einsum('htj,jr->thr', jax.nn.softmax(s, axis=-1), c)
    o = jnp.einsum('thr,rhd->thd', o_lat, w_ukv[..., d_nope:])
    return o.reshape(t, n_head * d_v) @ w['o_w'].astype(dt)


def feed_forward(hn, weights, i, first_dense, *routed, **kw):
    """FFN of layer i for normed rows hn: the dense SwiGLU of a leading
    layer (a quarter of its width at a time), else reference/exaone_moe.
    feed_forward's held experts + shared expert."""
    if i < first_dense:
        return _ffn(hn, *(jnp.asarray(weights['l%d_ff_%s_w' % (i, n)])
                          for n in ('gate', 'up', 'down')), parts=4)
    return exaone_moe.feed_forward(hn, weights, i, first_dense, *routed,
                                   **kw)


_STATIC = ('n_head', 'd_nope', 'd_rope', 'd_v', 'eps', 'theta')


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attend(x, w, **kw):
    return x + attention(x, w, **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attend_rows(xr, pos, x, w, **kw):
    return xr + attention_rows(xr, pos, x, w, **kw)


@functools.partial(jax.jit, static_argnames=('eps',))
def _norm(h, norm_w, eps):
    return rms_norm(h, norm_w.astype(h.dtype), eps)


def logits(weights, ids, n_head, d_nope, d_rope, d_v, n_layer, first_dense,
           top_k, expert_offset=0, scaling=2.5, norm_topk_prob=True,
           eps=1e-6, theta=32e6, compute_dtype=jnp.float32,
           routing_gaps=False, either_way=None):
    """[len(ids), vocab held] float32 logits: row p scores the token at
    p + 1. `weights` may hold bfloat16 (or float32) host or device arrays.
    `routing_gaps` and `either_way` = (rows, dist, capacity) are
    reference/exaone_moe.logits' own: each position's least distance to a
    routing change, and the OTHER SIDE of every near tie on `rows`
    computed one at a time, the carried rows riding behind the sequence
    through each feed-forward and the head — each with `dist`, how near
    a tie it was (what verify.routing_gap_eps is read from)."""
    ids = jnp.asarray(ids, jnp.int32)
    t = ids.shape[0]
    gaps = jnp.full(ids.shape, jnp.inf, jnp.float32)
    rows, dist, cap = either_way or ((), 0.0, 0)
    rows = np.asarray(rows, np.int64)
    alt = {'row': np.zeros(cap, np.int32), 'layer': np.zeros(cap, np.int32),
           'expert': np.zeros(cap, np.int32),
           'dist': np.zeros(cap, np.float32)}
    n_alt, overflow = 0, set()
    attn = dict(n_head=n_head, d_nope=d_nope, d_rope=d_rope, d_v=d_v,
                eps=eps, theta=theta)
    with jax.default_matmul_precision('highest'):
        x = jnp.asarray(weights['embed_w'])[ids].astype(compute_dtype)
        x = jnp.concatenate([x, jnp.zeros((cap, x.shape[1]), x.dtype)])
        for i in range(n_layer):
            p = 'l%d_' % i
            lw = {k: jnp.asarray(weights[p + k]) for k in ATTN_KEYS}
            post_w = jnp.asarray(weights[p + 'post_attn_norm_w'])
            h = _attend(x[:t], lw, **attn)
            hn = _norm(h, post_w, eps=eps)
            force = None
            if i >= first_dense:
                router = (jnp.asarray(weights[p + 'moe_router']),
                          jnp.asarray(weights[p + 'moe_router_bias']))
                held = dict(top_k=top_k, first=expert_offset,
                            held=weights[p + 'moe_gate'].shape[0])
                if routing_gaps:
                    gaps = jnp.minimum(gaps, _gap(hn, *router, **held))
            if cap:
                ha = _attend_rows(x[t:], jnp.asarray(alt['row']), x[:t], lw,
                                  **attn)
                force = np.zeros((t + cap, router[0].shape[1]),
                                 np.float32) if i >= first_dense else None
                if force is not None and len(rows):
                    d, chosen = (np.asarray(a) for a in _distances(
                        hn[rows], *router, **held))
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
            x = h + feed_forward(hn, weights, i, first_dense, top_k,
                                 expert_offset, scaling, norm_topk_prob,
                                 force=None if force is None
                                 else jnp.asarray(force))
        norm_w = jnp.asarray(weights['final_norm_w'])
        head_w = jnp.asarray(weights['lm_head_w'])
        out = jnp.concatenate(
            [_head(x[lo:lo + 2048], norm_w, head_w, eps=eps)
             for lo in range(0, x.shape[0], 2048)], axis=0)
    found = [out[:t]] + ([gaps] if routing_gaps else [])
    if either_way:
        found.append(dict({k: v[:n_alt] for k, v in alt.items()},
                          logits=np.asarray(out[t:t + n_alt]),
                          overflow=sorted(overflow)))
    return found[0] if len(found) == 1 else tuple(found)
