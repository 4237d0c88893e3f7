"""Ring attention: sequence/context parallelism for long sequences
(TPU-native extension; the reference has no context-parallel path —
SURVEY §2.4 lists SP as absent upstream, and the build brief makes
long-context first-class).

Design: q/k/v are sharded over the sequence axis of the mesh ('sp').
Under shard_map each device holds S/P of the sequence; the kernel loops P
steps, attending the local queries against a k/v block that rotates
around the ring via lax.ppermute (one ICI hop per step, overlapped by XLA
with the block's matmuls), accumulating with the online-softmax recurrence
(running max / denominator / output — the flash-attention math at ring
granularity). Peak memory per device is O(S·S/P) for one block of scores
instead of O(S²); ICI traffic is the k/v rotation, 2·S·D·(P-1)/P per
device — the all-to-all-free formulation of Liu et al.'s Ring Attention.

Causal masking is block-level: global q/k positions are derived from the
ring rank and rotation step, so the same kernel serves encoder and
decoder attention.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


def ring_attention(q, k, v, mesh, causal=False, scale=1.0,
                   seq_axis=SEQ_AXIS, batch_axis=DATA_AXIS,
                   head_axis=MODEL_AXIS):
    """Attention over [B, H, S, D] with S sharded on `seq_axis` of `mesh`.
    B additionally shards over `batch_axis` and H over `head_axis` when
    those axes exist in the mesh. Returns [B, H, S, D], S-sharded."""
    from jax import shard_map

    nsp = int(mesh.shape[seq_axis])
    if q.shape[2] % nsp != 0:
        raise ValueError(
            "ring attention: sequence length %d must divide the %r mesh "
            "axis (size %d)" % (q.shape[2], seq_axis, nsp))
    for dim, ax in ((0, batch_axis), (1, head_axis)):
        n = int(mesh.shape.get(ax, 1))
        if n > 1 and q.shape[dim] % n != 0:
            raise ValueError(
                "ring attention: q dim %d (size %d) must divide the %r "
                "mesh axis (size %d)" % (dim, q.shape[dim], ax, n))
    b_ax = batch_axis if mesh.shape.get(batch_axis, 1) > 1 else None
    h_ax = head_axis if mesh.shape.get(head_axis, 1) > 1 else None
    spec = P(b_ax, h_ax, seq_axis, None)
    perm = [(i, (i + 1) % nsp) for i in range(nsp)]

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    def ring(ql, kl, vl):
        rank = jax.lax.axis_index(seq_axis)
        sl = ql.shape[2]
        qf = ql.astype(jnp.float32) * scale
        pos_q = rank * sl + jnp.arange(sl)

        def block(o, mx, l, kb, vb, t):
            """Fold one rotating k/v block into the online-softmax state."""
            s = jnp.einsum('bhqd,bhkd->bhqk', qf, kb.astype(jnp.float32))
            if causal:
                src = (rank - t) % nsp          # whose block we hold now
                pos_k = src * sl + jnp.arange(sl)
                s = jnp.where(pos_k[None, None, None, :]
                              <= pos_q[None, None, :, None], s, -jnp.inf)
            m_new = jnp.maximum(mx, jnp.max(s, axis=-1))
            # -inf guards: a row with no unmasked key yet has mx=-inf (no
            # prior mass -> correction 0) and possibly m_new=-inf (this
            # block all-masked too -> contribution 0)
            corr = jnp.where(jnp.isneginf(mx), 0.0, jnp.exp(mx - m_new))
            p = jnp.where(jnp.isneginf(m_new)[..., None], 0.0,
                          jnp.exp(s - m_new[..., None]))
            l = l * corr + jnp.sum(p, axis=-1)
            o = o * corr[..., None] + jnp.einsum(
                'bhqk,bhkd->bhqd', p, vb.astype(jnp.float32))
            return o, m_new, l

        def body(carry, t):  # lax.scan: reverse-differentiable for training
            o, mx, l, kb, vb = carry
            # rotate FIRST: the local block was consumed before the scan,
            # so exactly nsp-1 ICI hops happen — no wasted final rotation
            kb = jax.lax.ppermute(kb, seq_axis, perm)
            vb = jax.lax.ppermute(vb, seq_axis, perm)
            o, mx, l = block(o, mx, l, kb, vb, t)
            return (o, mx, l, kb, vb), None

        b, h = ql.shape[0], ql.shape[1]
        o0 = jnp.zeros((b, h, sl, ql.shape[3]), jnp.float32)
        m0 = jnp.full((b, h, sl), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, sl), jnp.float32)
        o, mx, l = block(o0, m0, l0, kl, vl, 0)   # own (diagonal) block
        (o, mx, l, _, _), _ = jax.lax.scan(body, (o, mx, l, kl, vl),
                                           jnp.arange(1, nsp))
        out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
        return out.astype(ql.dtype)

    return ring(q, k, v)
