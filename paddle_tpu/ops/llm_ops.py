"""Ops of the pre-norm decoder block today's open language models share
(TPU-native extension; the reference era had layer_norm and sinusoid
tables only): RMSNorm, rotate-half rotary position embedding with the
positions fed, and the SwiGLU gate. Each computes in float32 whatever
its input holds — norm statistics and the rotation are the numerically
sensitive part of the block (core/amp.py's policy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register


def rms_norm(x, weight, eps):
    """x * rsqrt(mean(x^2, -1) + eps) * weight, in float32."""
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def swiglu(gate, up):
    return jax.nn.silu(gate) * up


@register('rms_norm', diff_inputs=('X', 'Scale'))
def _rms_norm(ctx, ins):
    """X [..., D], Scale [D] -> Y [..., D] float32: root-mean-square
    norm over the last axis (no mean subtraction, no bias)."""
    x = ins['X'][0]
    return {'Y': [rms_norm(x, ins['Scale'][0].reshape(x.shape[-1]),
                           float(ctx.attr('epsilon', 1e-5)))]}


@register('rotary_embedding', diff_inputs=('X',))
def _rotary_embedding(ctx, ins):
    """X [..., n_head * d_head] with Pos holding one position per row of
    X's leading axes (any shape of that many elements): every head's
    (first half, second half) pairs rotate by pos * theta^(-2i/d_head)
    — the rotate-half convention: out = x * cos + rotate_half(x) * sin
    with rotate_half(x) = concat(-x2, x1). Attr interleave: the pairs are
    the NEIGHBOURS (2i, 2i + 1) instead, each turned in place by the same
    angle (the latent-attention family's rope_interleave; its files move
    the pairs to the halves first, the same permutation of q's and k's
    channels, so every q . k is the same number)."""
    x = ins['X'][0]
    n_head = int(ctx.attr('n_head'))
    theta = float(ctx.attr('theta', 10000.0))
    interleave = bool(ctx.attr('interleave', False))
    lead, d = x.shape[:-1], x.shape[-1]
    dh = d // n_head
    pos = ins['Pos'][0].reshape(lead).astype(jnp.float32)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32)
                                / dh))
    ang = pos[..., None] * inv_freq                      # [..., dh/2]
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    if interleave:
        xh = x.astype(jnp.float32).reshape(lead + (n_head, dh // 2, 2))
        x1, x2 = xh[..., 0], xh[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return {'Out': [out.reshape(x.shape)]}
    xh = x.astype(jnp.float32).reshape(lead + (n_head, 2, dh // 2))
    x1, x2 = xh[..., 0, :], xh[..., 1, :]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-2)
    return {'Out': [out.reshape(x.shape)]}


@register('swiglu', diff_inputs=('Gate', 'Up'))
def _swiglu(ctx, ins):
    """silu(Gate) * Up, elementwise: the gated FFN's activation."""
    return {'Out': [swiglu(ins['Gate'][0], ins['Up'][0])]}
