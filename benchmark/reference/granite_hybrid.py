"""Plain reference for the granite_4_0_h_micro configuration: the full forward
pass of a Granite 4.0-H decoder (ibm-granite/granite-4.0-h-micro, model_type
granitemoehybrid with num_local_experts 0) over a whole sequence, in float32
jax.numpy at 'highest' matmul precision. No cache, no blocks, no batching, no
kernel, nothing of paddle_tpu or models/: the Mamba-2 layers run POSITION
AFTER POSITION FROM A ZERO STATE over the whole sequence (one lax.scan over
the recurrence itself), so the served programs' matrix (dual) form over
sub-chunks of 256, their state and convolution tail carried from slice to
slice and from step to step are held to other mathematics than their own;
attention is a masked full softmax over the published 32 / 8 heads of 64, so
the served programs' padded heads of 128 (models/granite_hybrid.py) are
tested and not copied.

THE MODEL (every number from the published config.json; N is RMSNorm with a
weight at eps 1e-5, x a row of the residual stream, NO positional term
anywhere: position_embedding_type "nope"):

    h_0 = 12 E[ids]                                  embedding_multiplier
    layer i:  h <- h + 0.22 mixer_i(N(h))            residual_multiplier
              h <- h + 0.22 MLP(N(h))
    MLP(x) = W_out (silu(a) * b),  [a | b] = W_in x  2,048 -> 2 x 8,192
              (shared_intermediate_size; num_local_experts 0: no routed part)
    logits = N(h_L) E^T / 8                          logits_scaling, tied E

    layer_types[i] == "attention" (layers 5, 15, 25, 35): q, k, v = W_q xn,
        W_k xn, W_v xn, 32 query and 8 K/V heads of 64, no bias; causal
        softmax at scale attention_multiplier = 1/64 (NOT 64^-1/2), query
        head h over K/V head h // 4; W_o.
    "mamba" (the other 36), Mamba-2 / SSD (Dao & Gu, arXiv:2405.21060):
        [z | xBC | dt] = W_in xn, widths 4,096 | 4,352 | 64 in that order
        xBC_t <- silu(sum_j c_j xBC_{t-3+j} + b_c)   depthwise, causal, 4 taps
        [x | B | C] = xBC, widths 4,096 | 128 | 128; x as 64 heads of 64, B
        and C shared by every head (mamba_n_groups 1). Per head h:
            delta_t = softplus(dt_t + dt_bias);  a_t = exp(-delta_t exp(A_log))
            S_t = a_t S_{t-1} + (delta_t x_t) B_t^T     S [64, 128] float32
            y_t = S_t C_t + D_h x_t
        y <- N(y * silu(z)) over all 4,096 channels, with a weight; W_out
        4,096 -> 2,048 (mamba_proj_bias false, mamba_conv_bias true).

Departures from transformers' modeling_granitemoehybrid.py, each as the
program under test has it:
  * matrices are stored [in, out] (x @ W); the convolution's weight is [K,
    channels], row j multiplying the input K - 1 - j positions back;
  * the recurrence is written as the recurrence (the library's torch path
    computes the same sums chunk by chunk at mamba_chunk_size 256; its
    fused path calls a kernel): time_step_limit is the library's default
    (0, inf), so delta is not clamped;
  * the gated norm is the library's GraniteMoeHybridRMSNormGated: the gate
    FIRST, then the norm over all channels (n_groups 1);
  * head_dim is hidden_size / num_attention_heads = 64 (the config carries
    no head_dim);
  * the attention mask is causal only: one sequence, no padding mask; no
    dropout (inference).

Weights come in as a dict of arrays under the names models/granite_hybrid.py
gives them — the served bfloat16 weights, raised to float32 where each is
used, a layer at a time; attention runs 256 queries at a time and the head
2,048 rows by 25,088 vocabulary rows at a time, each block fetched to the
host.

`compute_dtype=bfloat16` runs the same expressions one precision below what
the configuration states — the recurrence and its state in bfloat16 too —
and `state_dtype=bfloat16` keeps everything as stated but ROUNDS THE STATE to
bfloat16 after every position; both exist for one purpose: the limit on the
served tokens is read against them (chip_smoke.py phase H,
tests/test_granite_hybrid.py).

What is compared and how closely: configs/granite_4_0_h_micro.json "verify".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 256      # attention rows at a time
_HEAD_ROWS = 2048       # logits rows at a time
_HEAD_VOCAB = 25088     # vocabulary rows at a time (100,352 / 4)

LAYER_KEYS = ('ln1_w', 'ln2_w', 'ff_in_w', 'ff_out_w')
MAMBA_KEYS = tuple('ssm_' + k for k in (
    'in_w', 'conv_w', 'conv_b', 'a_log', 'dt_b', 'd', 'norm_w', 'out_w'))
ATTN_KEYS = ('q_w', 'k_w', 'v_w', 'o_w')


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * w.astype(x.dtype)


def mlp(h, w, eps, r_mult):
    dt = h.dtype
    a, b = jnp.split(rms_norm(h, w['ln2_w'], eps) @ w['ff_in_w'].astype(dt),
                     2, axis=-1)
    return h + r_mult * ((jax.nn.silu(a) * b) @ w['ff_out_w'].astype(dt))


def mamba(xn, w, n_head, d_state, eps, state_dtype=None):
    """Normed rows xn [T, D] -> the Mamba-2 mixer's output [T, D]: one
    lax.scan over the T positions from a zero state."""
    dt = xn.dtype
    sdt = dt if state_dtype is None else state_dtype
    t = xn.shape[0]
    n = int(d_state)
    zxd = xn @ w['ssm_in_w'].astype(dt)
    d_inner = (zxd.shape[1] - 2 * n - n_head) // 2
    z, xbc, step = (zxd[:, :d_inner], zxd[:, d_inner:2 * d_inner + 2 * n],
                    zxd[:, 2 * d_inner + 2 * n:])
    c = w['ssm_conv_w'].astype(dt)
    width = c.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1]), dt), xbc])
    xbc = jax.nn.silu(sum(c[j] * padded[j:j + t] for j in range(width))
                      + w['ssm_conv_b'].astype(dt))
    x = xbc[:, :d_inner].reshape(t, n_head, -1)                 # [T, H, P]
    b_in, c_out = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    delta = jax.nn.softplus(step + w['ssm_dt_b'].astype(dt))    # [T, H]
    decay = jnp.exp(-delta * jnp.exp(w['ssm_a_log'].astype(jnp.float32))
                    .astype(dt))

    def one(s, xs):
        x_t, delta_t, decay_t, b_t, c_t = xs
        s = (decay_t[:, None, None] * s.astype(dt)
             + (delta_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s.astype(sdt), s @ c_t                           # [H, P]

    _, y = jax.lax.scan(
        one, jnp.zeros((n_head, x.shape[2], n), sdt),
        (x, delta, decay, b_in, c_out))
    y = y.astype(dt) + w['ssm_d'].astype(dt)[:, None] * x
    y = rms_norm(y.reshape(t, -1) * jax.nn.silu(z), w['ssm_norm_w'], eps)
    return y @ w['ssm_out_w'].astype(dt)


def attention(xn, w, n_head, n_kv_head, scale):
    """Normed rows xn [T, D] -> causal softmax attention's output [T, D]:
    query head h over K/V head h // (H / KV), no positional term, queries
    _QUERY_BLOCK rows at a time."""
    dt = xn.dtype
    t = xn.shape[0]
    g = n_kv_head
    q = (xn @ w['q_w'].astype(dt)).reshape(t, n_head, -1)
    k = (xn @ w['k_w'].astype(dt)).reshape(t, g, -1)
    v = (xn @ w['v_w'].astype(dt)).reshape(t, g, -1)
    d = q.shape[-1]
    blocks = -(-t // _QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * _QUERY_BLOCK - t), (0, 0), (0, 0)))
    q = q.reshape(blocks, _QUERY_BLOCK, g, n_head // g, d)
    j = jnp.arange(t)[None, :]
    scale = jnp.asarray(scale, dt)

    def block(args):
        qb, lo = args
        i = lo + jnp.arange(_QUERY_BLOCK)[:, None]
        s = jnp.einsum('qgrd,jgd->grqj', qb, k) * scale
        s = jnp.where((j <= i)[None, None], s, -jnp.inf)
        return jnp.einsum('grqj,jgd->qgrd', jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q, jnp.arange(blocks) * _QUERY_BLOCK))
    return out.reshape(-1, n_head * d)[:t] @ w['o_w'].astype(dt)


_STATIC = ('eps', 'n_head', 'n_kv_head', 'ssm_heads', 'd_state',
           'state_dtype', 'a_mult', 'r_mult')


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mamba_layer(x, w, **kw):
    a = mamba(rms_norm(x, w['ln1_w'], kw['eps']), w, kw['ssm_heads'],
              kw['d_state'], kw['eps'], kw['state_dtype'])
    return mlp(x + kw['r_mult'] * a, w, kw['eps'], kw['r_mult'])


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attention_layer(x, w, **kw):
    a = attention(rms_norm(x, w['ln1_w'], kw['eps']), w, kw['n_head'],
                  kw['n_kv_head'], kw['a_mult'])
    return mlp(x + kw['r_mult'] * a, w, kw['eps'], kw['r_mult'])


@functools.partial(jax.jit, static_argnames=('eps',))
def _final_norm(x, w, eps):
    return rms_norm(x, w, eps)


@functools.partial(jax.jit, static_argnames=('scaling',))
def _head(xn, table, scaling):
    return ((xn @ table.astype(xn.dtype).T) / scaling).astype(jnp.float32)


def logits(weights, ids, layer_types, n_head, n_kv_head, ssm_heads, d_state,
           embedding_multiplier, attention_multiplier, residual_multiplier,
           logits_scaling, eps=1e-5, compute_dtype=jnp.float32,
           state_dtype=None):
    """[len(ids), vocab] float32 logits (a host array): row p scores the
    token at p + 1. `weights` may hold bfloat16 (or float32) host or device
    arrays under models/granite_hybrid.py's names; `layer_types` the
    published list ('mamba' | 'attention' a layer). `state_dtype`: what the
    recurrence's state is rounded to after every position (default:
    compute_dtype)."""
    table = np.asarray(weights['embed_w'])
    kw = dict(eps=float(eps), n_head=int(n_head), n_kv_head=int(n_kv_head),
              ssm_heads=int(ssm_heads), d_state=int(d_state),
              a_mult=float(attention_multiplier),
              r_mult=float(residual_multiplier),
              state_dtype=(None if state_dtype is None
                           else jnp.dtype(state_dtype)))

    def layer(i, keys):
        return {k: jnp.asarray(weights['l%d_%s' % (i, k)])
                for k in LAYER_KEYS + keys}

    with jax.default_matmul_precision('highest'):
        # the rows looked up on the host: the table goes to the device
        # once, for the head
        x = (jnp.asarray(table[np.asarray(ids)]).astype(compute_dtype)
             * jnp.asarray(embedding_multiplier, compute_dtype))
        for i, kind in enumerate(layer_types):
            if kind == 'mamba':
                x = _mamba_layer(x, layer(i, MAMBA_KEYS), **kw)
            elif kind == 'attention':
                x = _attention_layer(x, layer(i, ATTN_KEYS), **kw)
            else:
                raise ValueError('layer %d: no mixer %r' % (i, kind))
        xn = _final_norm(x, jnp.asarray(weights['final_ln_w']),
                         eps=float(eps))
        table = jnp.asarray(table)
        return np.concatenate([
            np.concatenate([np.asarray(_head(xn[r:r + _HEAD_ROWS],
                                             table[c:c + _HEAD_VOCAB],
                                             scaling=float(logits_scaling)))
                            for c in range(0, table.shape[0], _HEAD_VOCAB)],
                           axis=1)
            for r in range(0, xn.shape[0], _HEAD_ROWS)], axis=0)
