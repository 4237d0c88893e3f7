"""Plain reference for the phi4_mini_flash_reasoning configuration: the full
forward pass of the SambaY decoder-hybrid-decoder (Ren et al.,
arXiv:2507.06607; microsoft/Phi-4-mini-flash-reasoning) over a whole
sequence, in float32 jax.numpy at 'highest' matmul precision. No cache, no
blocks, no batching, no kernel, nothing of paddle_tpu: the Mamba layers run
TOKEN BY TOKEN FROM A ZERO STATE over the whole sequence (one lax.scan), so
the served programs' chunk form, their state and convolution tail carried
from slice to slice and from step to step are held to other mathematics than
their own; attention is a masked full softmax; differential attention is the
FOUR-PRODUCT form of public code over the PUBLISHED column layout, so the
served programs' one-pass form over their own layout (models/phi4_flash.py)
is tested and not copied; the cross-decoder runs at every position.

Equations (LN is LayerNorm with bias, x a row of the residual stream, no
positional encoding anywhere; n_self the first layer of the cross-decoder):

    h = x + Mixer(LN(x));  y = h + (silu(LN(h) W_g) * (LN(h) W_u)) W_d
    logits = LN(y_L) E^T                               (tied embedding E)
    even i < n_self: Mamba-1 (Gu & Dao, arXiv:2312.00752)
        [u z] = xn W_in;  u_t <- silu(sum_j c_j u_{t-3+j} + b_c)
        [dt B C] = u W_x;  delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
        s_t = exp(delta_t A) * s_{t-1} + (delta_t u_t) B_t^T   [channels, N]
        m_t = s_t C_t + D * u_t;   out = (m * silu(z)) W_out
    odd i < n_self: DiffAttn, keys j with j > t - window on all but the last
    even i >= n_self: GMU  (m * silu(xn W_1)) W_2,  m layer (n_self - 2)'s
    odd i >= n_self: DiffAttn with q = xn W_q + b alone, over layer
        (n_self - 1)'s K and V

    DiffAttn (Ye et al., arXiv:2410.05258): q_1, q_2 the first and the
    second half of the H query heads, k_1, k_2, v_1, v_2 of the KV key and
    value heads (stripes); Attn(q, k, v) causal softmax at d_head^-1/2, a
    query head of a half over its half's key head h // (H / KV);
        a_1 = [Attn(q_1,k_1,v_1) | Attn(q_1,k_1,v_2)]       heads of 2 d_head
        a_2 = [Attn(q_2,k_2,v_1) | Attn(q_2,k_2,v_2)]
        l = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + l_init,
        l_init = 0.8 - 0.6 exp(-0.3 i)
        out = ((1 - l_init) RMSNorm(a_1 - l a_2; w_subln)) W_o + b_o

Departures from the paper and the public implementation, each as the program
under test has it:
  * matrices are stored [in, out] (x @ W); the convolution's weight is [K,
    channels], row j multiplying the input K - 1 - j positions back; A_log
    is stored [N, channels] (transposed here to the published [channels, N]);
  * the programs keep W_q, W_k and W_v with their head columns in the order
    their attention op wants (models/phi4_flash.py program_heads); `q_cols`
    and `kv_cols` say where each PUBLISHED column lies, and everything below
    that gather is in the published layout;
  * the gate_up projection is two matrices (a relabelling of columns);
  * the attention projections carry a bias (the catalog's config is silent;
    a zero bias is the model without one);
  * the attention mask is causal (and windowed) only: one sequence, no
    padding mask.

Weights come in as a dict of arrays under the names models/phi4_flash.py
gives them — the served bfloat16 weights, raised to float32 where each is
used, a layer at a time; attention runs 256 queries at a time and the head
2,048 rows by 25,008 vocabulary rows at a time, each block fetched to the
host: beside a serving replica that holds ~12 GB of the chip a [4096,
200064] float32 result (3.3 GB) lives in host memory only.

`compute_dtype=bfloat16` runs the same expressions one precision below what
the configuration states, and `state_dtype=bfloat16` keeps everything as
stated but ROUNDS THE SCAN'S STATE to bfloat16 after every token; both exist
for one purpose: the limit on the served tokens is read against them
(chip_smoke.py phase F, tests/test_phi4_flash.py).

What is compared and how closely: configs/phi4_mini_flash_reasoning.json
"verify".
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .exaone_moe import rms_norm

_QUERY_BLOCK = 256      # attention rows at a time
_HEAD_ROWS = 2048       # logits rows at a time
_HEAD_VOCAB = 25008     # vocabulary rows at a time (200,064 / 8)

LN_KEYS = ('ln1_w', 'ln1_b', 'ln2_w', 'ln2_b')
FF_KEYS = ('ff_gate_w', 'ff_up_w', 'ff_down_w')
MAMBA_KEYS = tuple('ssm_' + k for k in (
    'in_w', 'conv_w', 'conv_b', 'x_w', 'dt_w', 'dt_b', 'a_log', 'd',
    'out_w'))
LAMBDA_KEYS = ('lambda_q1', 'lambda_k1', 'lambda_q2', 'lambda_k2', 'subln_w')
ATTN_KEYS = ('q_w', 'q_b', 'k_w', 'k_b', 'v_w', 'v_b', 'o_w', 'o_b') \
    + LAMBDA_KEYS
CROSS_KEYS = ('q_w', 'q_b', 'o_w', 'o_b') + LAMBDA_KEYS
GMU_KEYS = ('gmu_in_w', 'gmu_out_w')


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(x.dtype) \
        + b.astype(x.dtype)


def mlp(h, w, eps):
    dt = h.dtype
    hn = layer_norm(h, w['ln2_w'], w['ln2_b'], eps)
    return h + (jax.nn.silu(hn @ w['ff_gate_w'].astype(dt))
                * (hn @ w['ff_up_w'].astype(dt))) @ w['ff_down_w'].astype(dt)


def mamba(x, w, dt_rank, eps, state_dtype=None):
    """x [T, D] -> (Mamba(LN(x)) [T, D], m [T, channels] the scan's output
    before the gate): one lax.scan over the T tokens from a zero state."""
    dt = x.dtype
    sdt = dt if state_dtype is None else state_dtype
    t = x.shape[0]
    xn = layer_norm(x, w['ln1_w'], w['ln1_b'], eps)
    uz = xn @ w['ssm_in_w'].astype(dt)
    u, z = jnp.split(uz, 2, axis=-1)
    c = w['ssm_conv_w'].astype(dt)
    width = c.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, u.shape[1]), dt), u])
    u = jax.nn.silu(sum(c[j] * padded[j:j + t] for j in range(width))
                    + w['ssm_conv_b'].astype(dt))
    dbc = u @ w['ssm_x_w'].astype(dt)
    n = (dbc.shape[1] - dt_rank) // 2
    delta = jax.nn.softplus(dbc[:, :dt_rank] @ w['ssm_dt_w'].astype(dt)
                            + w['ssm_dt_b'].astype(dt))
    b_in, c_out = dbc[:, dt_rank:dt_rank + n], dbc[:, dt_rank + n:]
    a = -jnp.exp(w['ssm_a_log'].astype(jnp.float32)).T.astype(dt)  # [Di, N]

    def one(s, xs):
        u_t, delta_t, b_t, c_t = xs
        s = (jnp.exp(delta_t[:, None] * a) * s.astype(dt)
             + (delta_t * u_t)[:, None] * b_t[None, :])
        return s.astype(sdt), s @ c_t

    _, m = jax.lax.scan(one, jnp.zeros((u.shape[1], n), sdt),
                        (u, delta, b_in, c_out))
    m = m.astype(dt) + w['ssm_d'].astype(dt) * u
    return (m * jax.nn.silu(z)) @ w['ssm_out_w'].astype(dt), m


def _attention(q, k, v, window):
    """Masked softmax attention: q [T, H, d], k [T, G, d], v [T, G, dv],
    query head h over key / value head h // (H / G), row t over the keys j
    <= t (and j > t - window where window > 0); queries _QUERY_BLOCK rows
    at a time."""
    t, h, d = q.shape
    g = k.shape[1]
    blocks = -(-t // _QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * _QUERY_BLOCK - t), (0, 0), (0, 0)))
    q = q.reshape(blocks, _QUERY_BLOCK, g, h // g, d)
    j = jnp.arange(t)[None, :]
    scale = jnp.asarray(d ** -0.5, q.dtype)

    def block(args):
        qb, lo = args
        i = lo + jnp.arange(_QUERY_BLOCK)[:, None]
        seen = j <= i
        if window:
            seen = seen & (j > i - window)
        s = jnp.einsum('qgrd,jgd->grqj', qb, k) * scale
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum('grqj,jgd->qgrd', jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(block, (q, jnp.arange(blocks) * _QUERY_BLOCK))
    return out.reshape(-1, h, v.shape[-1])[:t]


def project_kv(xn, w, kv_cols, n_kv_head):
    """(k, v) [T, KV, d_head] of normed rows, in the PUBLISHED head order."""
    dt = xn.dtype
    k = xn @ w['k_w'].astype(dt)[:, kv_cols] + w['k_b'].astype(dt)[kv_cols]
    v = xn @ w['v_w'].astype(dt)[:, kv_cols] + w['v_b'].astype(dt)[kv_cols]
    t = xn.shape[0]
    return k.reshape(t, n_kv_head, -1), v.reshape(t, n_kv_head, -1)


def differential_attention(xn, k, v, w, lam0, n_head, q_cols, window, eps):
    """DiffAttn of a layer whose l_init is `lam0` for normed rows xn [T, D]
    over keys and values k, v [T, KV, d_head] (published order): the four
    products."""
    dt = xn.dtype
    t = xn.shape[0]
    q = (xn @ w['q_w'].astype(dt)[:, q_cols]
         + w['q_b'].astype(dt)[q_cols]).reshape(t, n_head, -1)
    q1, q2 = q[:, :n_head // 2], q[:, n_head // 2:]
    half = k.shape[1] // 2
    k1, k2, v1, v2 = k[:, :half], k[:, half:], v[:, :half], v[:, half:]
    a1 = jnp.concatenate([_attention(q1, k1, v1, window),
                          _attention(q1, k1, v2, window)], axis=-1)
    a2 = jnp.concatenate([_attention(q2, k2, v1, window),
                          _attention(q2, k2, v2, window)], axis=-1)
    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(w['lambda_q1'].astype(f32)
                           * w['lambda_k1'].astype(f32)))
           - jnp.exp(jnp.sum(w['lambda_q2'].astype(f32)
                             * w['lambda_k2'].astype(f32)))
           + lam0).astype(dt)
    out = rms_norm(a1 - lam * a2, w['subln_w'].astype(dt), eps) \
        * (1.0 - lam0).astype(dt)
    return out.reshape(t, -1) @ w['o_w'].astype(dt) + w['o_b'].astype(dt)


_STATIC = ('eps', 'dt_rank', 'state_dtype', 'n_head', 'n_kv_head',
           'window')


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mamba_layer(x, w, **kw):
    out, m = mamba(x, w, kw['dt_rank'], kw['eps'], kw['state_dtype'])
    return mlp(x + out, w, kw['eps']), m


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attention_layer(x, w, lam0, q_cols, kv_cols, **kw):
    xn = layer_norm(x, w['ln1_w'], w['ln1_b'], kw['eps'])
    k, v = project_kv(xn, w, kv_cols, kw['n_kv_head'])
    a = differential_attention(xn, k, v, w, lam0, kw['n_head'], q_cols,
                               kw['window'], kw['eps'])
    return mlp(x + a, w, kw['eps']), k, v


@functools.partial(jax.jit, static_argnames=_STATIC)
def _cross_layer(x, k, v, w, lam0, q_cols, **kw):
    xn = layer_norm(x, w['ln1_w'], w['ln1_b'], kw['eps'])
    a = differential_attention(xn, k, v, w, lam0, kw['n_head'], q_cols,
                               0, kw['eps'])
    return mlp(x + a, w, kw['eps'])


@functools.partial(jax.jit, static_argnames=_STATIC)
def _gmu_layer(x, m, w, **kw):
    dt = x.dtype
    xn = layer_norm(x, w['ln1_w'], w['ln1_b'], kw['eps'])
    a = (m * jax.nn.silu(xn @ w['gmu_in_w'].astype(dt))) \
        @ w['gmu_out_w'].astype(dt)
    return mlp(x + a, w, kw['eps'])


@functools.partial(jax.jit, static_argnames=('eps',))
def _final_norm(x, w, b, eps):
    return layer_norm(x, w, b, eps)


@jax.jit
def _head(xn, table):
    return (xn @ table.astype(xn.dtype).T).astype(jnp.float32)


def logits(weights, ids, n_head, n_kv_head, n_layer, n_self, window,
           dt_rank, q_cols, kv_cols, eps=1e-5, compute_dtype=jnp.float32,
           state_dtype=None):
    """[len(ids), vocab] float32 logits (a host array): row p scores the
    token at p + 1. `weights` may hold bfloat16 (or float32) host or device
    arrays under models/phi4_flash.py's names; `q_cols` / `kv_cols`: the
    programs' column of each published q / k-and-v column
    (models/phi4_flash.py published_columns). `state_dtype`: what the
    scan's state is rounded to after every token (default:
    compute_dtype)."""
    table = np.asarray(weights['embed_w'])
    q_cols, kv_cols = jnp.asarray(q_cols), jnp.asarray(kv_cols)
    kw = dict(eps=float(eps), dt_rank=int(dt_rank), n_head=int(n_head),
              n_kv_head=int(n_kv_head), window=0,
              state_dtype=(None if state_dtype is None
                           else jnp.dtype(state_dtype)))

    def layer(i, keys):
        return {k: jnp.asarray(weights['l%d_%s' % (i, k)])
                for k in LN_KEYS + FF_KEYS + keys}

    with jax.default_matmul_precision('highest'):
        # the rows looked up on the host: the table goes to the device
        # once, for the head
        x = jnp.asarray(table[np.asarray(ids)]).astype(compute_dtype)
        m = k = v = None
        for i in range(n_layer):
            lam0 = jnp.float32(lambda_init(i))  # traced: one compile a kind
            if i < n_self and i % 2 == 0:
                x, m = _mamba_layer(x, layer(i, MAMBA_KEYS), **kw)
            elif i < n_self:
                x, k, v = _attention_layer(
                    x, layer(i, ATTN_KEYS), lam0, q_cols, kv_cols,
                    **dict(kw, window=int(window) if i < n_self - 1 else 0))
            elif i % 2 == 0:
                x = _gmu_layer(x, m, layer(i, GMU_KEYS), **kw)
            else:
                x = _cross_layer(x, k, v, layer(i, CROSS_KEYS), lam0, q_cols,
                                 **kw)
        xn = _final_norm(x, jnp.asarray(weights['final_ln_w']),
                         jnp.asarray(weights['final_ln_b']), eps=float(eps))
        table = jnp.asarray(table)
        return np.concatenate([
            np.concatenate([np.asarray(_head(xn[r:r + _HEAD_ROWS],
                                             table[c:c + _HEAD_VOCAB]))
                            for c in range(0, table.shape[0], _HEAD_VOCAB)],
                           axis=1)
            for r in range(0, xn.shape[0], _HEAD_ROWS)], axis=0)
