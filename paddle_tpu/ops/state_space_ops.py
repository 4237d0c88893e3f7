"""Ops of a selective state-space (Mamba-1) layer in decode serving (Gu &
Dao, arXiv:2312.00752; the mixer of models/phi4_flash.py's self-decoder): as
a linear-attention layer's (ops/linear_attention_ops.py), the layer's memory
is a STATE of fixed size per request — per channel a vector h of d_state
float32 numbers, and the last `width - 1` inputs of a short causal
convolution (that module's causal_conv_step / _chunk, here with a bias) —
kept PER SLOT ([max_slots, ...], unpaged: no block, no table).

Per token, with x the convolved input, dt the projected step size and B, C
the token's input and output vectors (d_state each):

    delta = softplus(dt + dt_bias);   A = -exp(A_log)
    h <- exp(delta A) * h + (delta x) B^T;   y = h C + D x

THE STATE LIES [d_state, channels]: the channels (thousands) on the lanes
and the d_state (16) rows on the sublanes. The published layout, [channels,
d_state], would pad each 16-wide row to a 128-lane tile: eight times the
memory and eight times the bytes a step moves. A_log is kept the same way.

Two forms. The STEP (selective_scan_step) takes one token a slot against the
carried state. The CHUNK (selective_scan_chunk) takes C tokens of one
request from a carried state to a carried state BY THE SAME RECURRENCE, one
position after another (a lax.scan whose carry is the row's state, 327 KB at
5,120 channels): the discretised [C, channels, d_state] terms an
associative scan or a cumulative-product form would build are 168 MB of
float32 a row at C = 512, read and written log C times, and the ratio form
(exp of a difference of running sums) is a [C, C] mask more; a position is
a few thousand multiply-adds over a state that never leaves fast memory.
(A kernel for it is ROADMAP.md Reach's.)

WHO OWNS A STATE ROW is linear_attention_ops.py's rule word for word: the
step's row r is slot r and steps its state only where it is LIVE (its block
table's first entry is not the trash block); a chunk row is told its slot
(StateSlot; outside [0, max_slots) is nobody's), is born ZERO where Start is
0, and leaves the state at ChunkLen: positions from there on neither decay
nor write.

Everything here is float32 and elementwise, through lax primitives where
jax.numpy offers a jitted library function (linear_attention_ops.py's last
paragraph: the state-space metrics read these ops by scope).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register
from .linear_attention_ops import (_put_rows, _slot_rows, _softplus, _where,
                                   live_rows)

_UNROLL = 8


def discretise(dt, dt_bias, a_log):
    """(delta [..., Di], A [N, Di]) of projected step sizes dt [..., Di]."""
    delta = _softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    return delta, -jnp.exp(a_log.astype(jnp.float32))


def scan_step(x, delta, a, b, c, d, h):
    """One token a row: x, delta [R, Di], a [N, Di], b, c [R, N], d [Di],
    h [R, N, Di] float32 -> (y [R, Di], the new state)."""
    h = (jnp.exp(delta[:, None, :] * a) * h
         + b[:, :, None] * (delta * x)[:, None, :])
    return jnp.sum(h * c[:, :, None], axis=1) + d * x, h


@register('selective_scan_step', no_grad=True, lod='none')
def _selective_scan_step(ctx, ins):
    """One token a slot through the selective scan. X, Dt [S, Di] (the
    convolved input; the projected step size before its bias), B, C [S, N],
    ALog [N, Di], DtBias, D [Di], State [S, N, Di] (float32; StateOut
    aliases it, in place on the persistable state), BlockTable [S, MAXB]:
    a row whose first entry is the trash block is idle and its state is
    left as it is. Out [S, Di] float32: h C + D x, before any gate."""
    state = ins['State'][0]
    x = ins['X'][0].astype(jnp.float32)
    delta, a = discretise(ins['Dt'][0], ins['DtBias'][0], ins['ALog'][0])
    y, new = scan_step(x, delta, a, ins['B'][0].astype(jnp.float32),
                       ins['C'][0].astype(jnp.float32),
                       ins['D'][0].astype(jnp.float32),
                       state.astype(jnp.float32))
    live = live_rows(ins['BlockTable'][0])[:, None, None]
    return {'Out': [y],
            'StateOut': [_where(live, new.astype(state.dtype), state)]}


@register('selective_scan_chunk', no_grad=True, lod='none')
def _selective_scan_chunk(ctx, ins):
    """C tokens a row through the same recurrence, from the state of the
    row's slot (zero where Start is 0) to the state after ChunkLen tokens,
    written back to that slot. X, Dt [R, C, Di], B, C [R, C, N], ALog [N,
    Di], DtBias, D [Di], State [S, N, Di], Start, ChunkLen, StateSlot [R,
    1] int32. Out [R, C, Di] float32 (rows from ChunkLen on are unread)."""
    state = ins['State'][0]
    x = ins['X'][0].astype(jnp.float32)
    delta, a = discretise(ins['Dt'][0], ins['DtBias'][0], ins['ALog'][0])
    start, clen, slot = (ins[n][0].reshape(-1)
                         for n in ('Start', 'ChunkLen', 'StateSlot'))
    # a position past the row's length has delta 0: exp(0) keeps the
    # state and (delta x) B writes nothing
    real = (jnp.arange(x.shape[1])[None, :] < clen[:, None])[..., None]
    delta = _where(real, delta, 0.0)
    d = ins['D'][0].astype(jnp.float32)

    def one(h, xs):     # position-major: [R, ...] a position
        x_t, delta_t, b_t, c_t = xs
        y_t, h = scan_step(x_t, delta_t, a, b_t, c_t, d, h)
        return h, y_t

    by_position = lambda v: jnp.swapaxes(v.astype(jnp.float32), 0, 1)
    h, y = jax.lax.scan(
        one, _slot_rows(state, slot, start).astype(jnp.float32),
        (by_position(x), by_position(delta), by_position(ins['B'][0]),
         by_position(ins['C'][0])),
        unroll=min(_UNROLL, x.shape[1]))
    return {'Out': [jnp.swapaxes(y, 0, 1)],
            'StateOut': [_put_rows(state, slot, h)]}
