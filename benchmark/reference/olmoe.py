"""Plain reference for the olmoe_1b_7b configuration: the full forward pass
of the OLMoE decoder over a whole sequence, in float32 jax.numpy at
'highest' matmul precision. No cache, no blocks, no batching, no sort, no
kernel, nothing of paddle_tpu: every expert is computed densely for every
token and masked by the top-k. Weights come in as a dict of arrays under
the names models/olmoe.py gives them — the served bfloat16 weights, upcast
here to float32 where each is used (eight experts of one layer are on the
device at a time), so that the model's float32 copy is never resident.

Follows `transformers`' modeling_olmoe.py (OlmoeDecoderLayer, OlmoeAttention,
OlmoeSparseMoeBlock) for allenai/OLMoE-1B-7B-0125-Instruct:

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    RMSNorm(x) = x * rsqrt(mean(x^2) + eps) * w              (float32)
    q, k, v = x W_q, x W_k, x W_v                            (no bias)
    q, k = RMSNorm_q(q), RMSNorm_k(k)   over the WHOLE projection width,
                                        before the split into heads
    q, k = RoPE(q), RoPE(k)             rotate-half, theta 10000
    Attn = softmax(causal(q k^T / sqrt(d_head))) v  W_o
    p = softmax(x W_r) over the experts in float32; top-k values and
        indices, NOT renormalised (norm_topk_prob false)
    MoE(x) = sum_k p_k * W_down,e (silu(W_gate,e x) * W_up,e x)
    logits = RMSNorm(x_L) W_head       (untied head)

Departures from modeling_olmoe.py, each as the program under test has it
(models/olmoe.py):
  * matrices are stored [in, out] (x @ W), not torch's [out, in];
  * among equal router probabilities the lower expert index wins
    (jax.lax.top_k); torch.topk leaves a tie undefined;
  * clip_qkv is null in the published config and is not implemented;
  * the attention mask is causal only: one sequence, no padding mask.

`compute_dtype=bfloat16` runs the same expressions one precision below what
the configuration states — activations, matmul results, the router and the
softmaxes all bfloat16 — and exists for one purpose: the bound on the served
programs' logit error has to be one that THIS fails (chip_smoke.py phase M).

What is compared and how closely: configs/olmoe_1b_7b.json "verify".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LAYER_KEYS = ('in_norm_w', 'q_w', 'k_w', 'v_w', 'o_w', 'q_norm_w',
               'k_norm_w', 'post_norm_w', 'moe_router', 'moe_gate',
               'moe_up', 'moe_down')


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * w


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


def attention(x, w, n_head, eps, theta):
    t, d = x.shape
    dh = d // n_head
    dt = x.dtype
    q = rms_norm(x @ w['q_w'].astype(dt), w['q_norm_w'].astype(dt), eps)
    k = rms_norm(x @ w['k_w'].astype(dt), w['k_norm_w'].astype(dt), eps)
    pos = jnp.arange(t)
    q = rope(q, pos, n_head, theta).astype(dt).reshape(t, n_head, dh)
    k = rope(k, pos, n_head, theta).astype(dt).reshape(t, n_head, dh)
    v = (x @ w['v_w'].astype(dt)).reshape(t, n_head, dh)
    s = jnp.einsum('qhd,khd->hqk', q, k) * jnp.asarray(dh ** -0.5, dt)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    a = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)
    return a.reshape(t, d) @ w['o_w'].astype(dt)


def router_weights(x, router_w, top_k, norm_topk_prob=False):
    """[T, E]: each token's top-k router probabilities at its chosen
    experts, zero elsewhere."""
    dt = x.dtype
    n_expert = router_w.shape[1]
    p = jax.nn.softmax(x @ router_w.astype(dt), axis=-1)
    vals, idx = jax.lax.top_k(p, top_k)
    if norm_topk_prob:
        vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, n_expert, dtype=dt)
                   * vals[..., None], axis=1)


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


def moe(x, w, top_k, norm_topk_prob=False):
    """Every expert over every token, weighted by the router's top-k
    probabilities (zero for the experts a token did not choose)."""
    weight = router_weights(x, w['moe_router'], top_k, norm_topk_prob)
    return experts(x, w['moe_gate'], w['moe_up'], w['moe_down'], weight)


# One layer in three jitted parts, so that logits() can hand the experts
# over in groups: a layer's 64 experts are 0.8 GB in bfloat16 and twice
# that in float32, which must not sit beside a serving replica's pool.
@functools.partial(jax.jit, static_argnames=('n_head', 'eps', 'theta'))
def _attend(x, w, n_head, eps, theta):
    return x + attention(rms_norm(x, w['in_norm_w'].astype(x.dtype), eps),
                         w, n_head, eps, theta)


@functools.partial(jax.jit, static_argnames=('top_k', 'eps'))
def _route(h, norm_w, router_w, top_k, eps):
    n = rms_norm(h, norm_w.astype(h.dtype), eps)
    return n, router_weights(n, router_w, top_k)


_experts = jax.jit(experts)
_EXPERT_GROUP = 8


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(x, norm_w, head_w, eps):
    return (rms_norm(x, norm_w.astype(x.dtype), eps)
            @ head_w.astype(x.dtype)).astype(jnp.float32)


def logits(weights, ids, n_head, n_layer, top_k, eps=1e-5, theta=10000.0,
           compute_dtype=jnp.float32):
    """[len(ids), vocab] float32 logits: row p scores the token at p + 1.
    `weights` may hold bfloat16 (or float32) host or device arrays."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision('highest'):
        x = jnp.asarray(weights['embed_w'])[ids].astype(compute_dtype)
        for i in range(n_layer):
            w = {k: weights['l%d_%s' % (i, k)] for k in _LAYER_KEYS}
            h = _attend(x, {k: jnp.asarray(v) for k, v in w.items()
                            if not k.startswith('moe_')},
                        n_head=n_head, eps=eps, theta=theta)
            n, weight = _route(h, jnp.asarray(w['post_norm_w']),
                               jnp.asarray(w['moe_router']), top_k=top_k,
                               eps=eps)
            x = h
            for e in range(0, weight.shape[1], _EXPERT_GROUP):
                group = slice(e, e + _EXPERT_GROUP)
                x = x + _experts(n, jnp.asarray(w['moe_gate'][group]),
                                 jnp.asarray(w['moe_up'][group]),
                                 jnp.asarray(w['moe_down'][group]),
                                 weight[:, group])
        return _head(x, jnp.asarray(weights['final_norm_w']),
                     jnp.asarray(weights['lm_head_w']), eps=eps)
