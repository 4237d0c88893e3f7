"""Plain reference for the transformer_base_lm configuration: the full
forward pass of the decoder-only language model over a whole sequence, in
float32 jax.numpy at 'highest' matmul precision. No cache, no blocks, no
batching, nothing of paddle_tpu: weights come in as a dict of arrays read
from the program's scope by name (saved beside the artifact, because the
artifact bakes them).

Follows Vaswani et al. 2017 (arXiv:1706.03762), section 3 and Table 3
'base': embedding scaled by sqrt(d_model) plus sinusoidal positions, N
blocks of masked multi-head self-attention and a ReLU feed-forward, each
followed by residual + layer norm (POST-LN, as published), and a linear
map to the vocabulary.

Departures from the paper, each as the program under test has it
(models/transformer.py:build_decode_spec):
  * decoder stack only: no encoder and no cross-attention sub-layer;
  * the position table holds sin in the first half of the channels and cos
    in the second (the paper interleaves them): the same frequencies;
  * q/k/v/o projections and the output map have no bias; the output map
    does not share the embedding matrix;
  * layer-norm epsilon 1e-5.

What is compared and how closely: configs/transformer_base_lm.json
"verify" (tokens at positions whose reference top-two margin exceeds
margin_eps must match).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_EPS = 1e-5


def position_table(length, d_model):
    half = d_model // 2
    pos = np.arange(length, dtype=np.float32)[:, None]
    div = np.power(np.float32(10000.0),
                   np.arange(half, dtype=np.float32) / np.float32(half))
    return np.concatenate([np.sin(pos / div), np.cos(pos / div)],
                          axis=1).astype(np.float32)


def _ln(x, scale, bias):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + _EPS) * scale + bias


@functools.partial(jax.jit, static_argnames=('n_head', 'n_layer'))
def _forward(w, ids, pe, n_head, n_layer):
    t = ids.shape[0]
    d = w['dec_emb_w'].shape[1]
    dh = d // n_head
    x = w['dec_emb_w'][ids] * jnp.float32(d ** 0.5) + pe
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(n_layer):
        p = 'l%d_' % i
        q = (x @ w[p + 'q_w']).reshape(t, n_head, dh)
        k = (x @ w[p + 'k_w']).reshape(t, n_head, dh)
        v = (x @ w[p + 'v_w']).reshape(t, n_head, dh)
        s = jnp.einsum('qhd,khd->hqk', q, k) * jnp.float32(dh ** -0.5)
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)
        x = _ln(x + a.reshape(t, d) @ w[p + 'o_w'],
                w[p + 'ln1_s'], w[p + 'ln1_b'])
        h = jax.nn.relu(x @ w[p + 'f1_w'] + w[p + 'f1_b'])
        x = _ln(x + h @ w[p + 'f2_w'] + w[p + 'f2_b'],
                w[p + 'ln2_s'], w[p + 'ln2_b'])
    return x @ w['out_w']


def logits(weights, ids, n_head, n_layer):
    """[len(ids), vocab] float32 logits: row p scores the token at p + 1."""
    ids = jnp.asarray(ids, jnp.int32)
    w = {k: jnp.asarray(v, jnp.float32) for k, v in weights.items()
         if k != 'pos_enc_w'}
    pe = jnp.asarray(position_table(ids.shape[0],
                                    weights['dec_emb_w'].shape[1]))
    with jax.default_matmul_precision('highest'):
        return _forward(w, ids, pe, n_head=n_head, n_layer=n_layer)
