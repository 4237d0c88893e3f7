"""Fused BN-apply Pallas kernel (normalize + scale + activation, one HBM
pass) — the experiment VERDICT r3 item 2 names.

Measured verdict (PERF_NOTES.md has the full ablation table): on v5e the
XLA FMA formulation in nn_ops._batch_norm already emits exactly this
fusion, so the kernel is at parity, not ahead — the ceiling on ResNet BN
cost is the forced second HBM read (stats must complete before any
normalize; the activation exceeds VMEM, so no kernel can revisit tiles
without re-reading HBM). Kept opt-in (PTPU_PALLAS_BN=1) as the measured
evidence and as a template for genuinely fusible patterns.

Layout: x viewed as [N, C, H*W]; grid over (N, C/8, HW/512); per-channel
k,b scalars ride VMEM blocks. Backward is plain XLA (dx = dy*mask*k — an
elementwise chain XLA fuses; the fwd read path was the only candidate)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _kernel(x_ref, k_ref, b_ref, o_ref, *, act):
    x = x_ref[...]                       # [1, Ct, T]
    k = k_ref[...].astype(x.dtype)[None]  # [Ct, 1] -> [1, Ct, 1]
    b = b_ref[...].astype(x.dtype)[None]
    y = x * k + b
    if act == 'relu':
        y = jnp.maximum(y, jnp.zeros_like(y))
    o_ref[...] = y


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_bn_apply(x, k, b, act='relu'):
    """y = act(x * k[c] + b[c]) over NCHW x, one fused HBM pass."""
    return _fwd_impl(x, k, b, act)


def _fwd_impl(x, k, b, act):
    from jax.experimental import pallas as pl

    n, c, h, w = x.shape
    hw = h * w
    ct = 8 if c % 8 == 0 else 1
    tile = 512 if hw % 512 == 0 else (128 if hw % 128 == 0 else hw)
    xv = x.reshape(n, c, hw)
    y = pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid=(n, c // ct, hw // tile),
        in_specs=[
            pl.BlockSpec((1, ct, tile), lambda i, j, t: (i, j, t)),
            pl.BlockSpec((ct, 1), lambda i, j, t: (j, 0)),
            pl.BlockSpec((ct, 1), lambda i, j, t: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, ct, tile), lambda i, j, t: (i, j, t)),
        out_shape=jax.ShapeDtypeStruct((n, c, hw), x.dtype),
    )(xv, k.astype(jnp.float32).reshape(c, 1),
      b.astype(jnp.float32).reshape(c, 1))
    return y.reshape(n, c, h, w)


def _fwd(x, k, b, act):
    y = _fwd_impl(x, k, b, act)
    return y, (x, k, y)


def _bwd(act, res, dy):
    x, k, y = res
    if act == 'relu':
        dy = dy * (y > 0).astype(dy.dtype)
    kb = k.astype(dy.dtype).reshape(1, -1, 1, 1)
    dx = dy * kb
    red = (0, 2, 3)
    dk = jnp.sum((dy * x).astype(jnp.float32), axis=red).astype(k.dtype)
    db = jnp.sum(dy.astype(jnp.float32), axis=red).astype(k.dtype)
    return dx, dk, db


fused_bn_apply.defvjp(_fwd, _bwd)
