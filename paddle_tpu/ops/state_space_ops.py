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

THE SECOND PAIR: MAMBA-2 / SSD (Dao & Gu, arXiv:2405.21060; the mixer of
models/granite_hybrid.py). The channels are H heads of P, the decay is a
SCALAR a head and B, C (d_state N each) are shared by every head of the one
group; per token and head, with the state S [P, N] float32:

    delta = softplus(dt + dt_bias);   a = exp(-delta exp(A_log))
    S <- a S + (delta x) B^T;   y = S C + D x

THE STATE LIES [H, P, N] as published: d_state (128) is the lanes' axis, one
whole tile, and the P rows of a head the sublanes — no padding either way.
The STEP (ssd_step) is that line a slot. The argument above for running a
slice position after position TURNS ROUND here: the carry is H P N floats a
row (2 MB at 64 x 64 x 128) in every layer, read and written a position,
while the scalar decay makes the whole slice a few MATRIX products — the
CHUNK (ssd_chunk) is the dual form over sub-chunks of Q positions
(`sub_chunk`, the published mamba_chunk_size), with l_t the running sum of
log a inside the sub-chunk and S_0 the state it starts from:

    Y   = (L * C B^T)(delta X) + exp(l) * (C S_0)     L_ts = exp(l_t - l_s),
    S_Q = exp(l_Q) S_0 + sum_s exp(l_Q - l_s) (delta x_s) B_s^T   s <= t

C B^T is one [Q, Q] product for all heads, every exponent is <= 0, and the
sub-chunks follow one another UNROLLED IN PYTHON (a chunk of 512 is two of
256): an op inside a lax.scan body loses its scope in the device trace
(PERF.md 7, "From PR 48" a), and the state-space metrics read these ops by
scope. A position past ChunkLen has delta 0: log a = 0 keeps the state and
(delta x) writes nothing, in either form. Both say ('ssd_step' |
'ssd_chunk', 'jnp') to their Tracer (lowered_bodies): a kernel is ROADMAP.md
Reach's, and will say its own name there.

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
_HI = jax.lax.Precision.HIGHEST


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


# -- Mamba-2 / SSD ----------------------------------------------------------
def _said(ctx, op_type):
    tracer = getattr(ctx, 'tracer', None)
    if tracer is not None:
        tracer.lowered_bodies.append((op_type, 'jnp'))


def _ssd_terms(ins, n_head):
    """(x [..., H, P], delta [..., H], log a [..., H], b, c [..., N], d
    [H, 1]) of an SSD op's inputs, float32."""
    x = ins['X'][0].astype(jnp.float32)
    x = x.reshape(x.shape[:-1] + (n_head, -1))
    delta = _softplus(ins['Dt'][0].astype(jnp.float32)
                      + ins['DtBias'][0].astype(jnp.float32))
    log_a = -delta * jnp.exp(ins['ALog'][0].astype(jnp.float32))
    return (x, delta, log_a, ins['B'][0].astype(jnp.float32),
            ins['C'][0].astype(jnp.float32),
            ins['D'][0].astype(jnp.float32)[:, None])


def ssd_scan_step(x, delta, log_a, b, c, d, s):
    """One token a row: x [R, H, P], delta, log_a [R, H], b, c [R, N], d
    [H, 1], s [R, H, P, N] float32 -> (y [R, H, P], the new state)."""
    s = (jnp.exp(log_a)[..., None, None] * s
         + (delta[..., None] * x)[..., None] * b[:, None, None, :])
    return jnp.sum(s * c[:, None, None, :], axis=-1) + d * x, s


@register('ssd_step', no_grad=True, lod='none')
def _ssd_step(ctx, ins):
    """One token a slot through a Mamba-2 layer's recurrence. X [S, H * P]
    (the convolved input), Dt [S, H] (the projected step size before its
    bias), B, C [S, N], ALog, DtBias, D [H], State [S, H, P, N] (float32;
    StateOut aliases it, in place on the persistable state), BlockTable [S,
    MAXB]: a row whose first entry is the trash block is idle and its state
    is left as it is. Attr n_head = H. Out [S, H * P] float32: S C + D x,
    before the gate."""
    _said(ctx, 'ssd_step')
    state = ins['State'][0]
    x, delta, log_a, b, c, d = _ssd_terms(ins, int(ctx.attr('n_head')))
    y, new = ssd_scan_step(x, delta, log_a, b, c, d,
                           state.astype(jnp.float32))
    live = live_rows(ins['BlockTable'][0])[:, None, None, None]
    return {'Out': [y.reshape(y.shape[0], -1)],
            'StateOut': [_where(live, new.astype(state.dtype), state)]}


def ssd_sub_chunk(x, delta, log_a, b, c, s):
    """Q tokens a row in the dual form, from the state `s` [R, H, P, N]: x
    [R, Q, H, P], delta, log_a [R, Q, H], b, c [R, Q, N] -> (y [R, Q, H,
    P] without the D x term, the state after the Q tokens)."""
    q = x.shape[1]
    run = jax.lax.cumsum(log_a, axis=1)                     # l_t [R, Q, H]
    by_head = jnp.swapaxes(run, 1, 2)                       # [R, H, Q]
    at = jnp.arange(q)
    # exp(l_t - l_s) for s <= t: the exponent is <= 0 there, so mask it
    # BEFORE the exponential (above the diagonal it may overflow)
    decay = jnp.exp(_where(at[:, None] >= at[None, :],
                           by_head[..., :, None] - by_head[..., None, :],
                           -jnp.inf))                       # [R, H, Q, Q]
    cb = jnp.einsum('rtn,rsn->rts', c, b, precision=_HI)
    dx = delta[..., None] * x                               # [R, Q, H, P]
    y = jnp.einsum('rhts,rshp->rthp', decay * cb[:, None], dx,
                   precision=_HI)
    y = y + jnp.exp(run)[..., None] * jnp.einsum(
        'rtn,rhpn->rthp', c, s, precision=_HI)
    last = run[:, -1]                                       # l_Q [R, H]
    s = jnp.exp(last)[..., None, None] * s + jnp.einsum(
        'rshp,rsn->rhpn', jnp.exp(last[:, None] - run)[..., None] * dx, b,
        precision=_HI)
    return y, s


@register('ssd_chunk', no_grad=True, lod='none')
def _ssd_chunk(ctx, ins):
    """C tokens a row through ssd_step's recurrence in its dual (matrix)
    form, sub-chunks of attr sub_chunk positions one after another, from
    the state of the row's slot (zero where Start is 0) to the state after
    ChunkLen tokens, written back to that slot. X [R, C, H * P], Dt [R, C,
    H], B, C [R, C, N], ALog, DtBias, D [H], State [S, H, P, N], Start,
    ChunkLen, StateSlot [R, 1] int32. Out [R, C, H * P] float32 (rows from
    ChunkLen on are unread)."""
    _said(ctx, 'ssd_chunk')
    state = ins['State'][0]
    x, delta, log_a, b, c, d = _ssd_terms(ins, int(ctx.attr('n_head')))
    start, clen, slot = (ins[n][0].reshape(-1)
                         for n in ('Start', 'ChunkLen', 'StateSlot'))
    n = x.shape[1]
    real = (jnp.arange(n)[None, :] < clen[:, None])[..., None]
    delta, log_a = _where(real, delta, 0.0), _where(real, log_a, 0.0)
    s = _slot_rows(state, slot, start).astype(jnp.float32)
    sub = min(int(ctx.attr('sub_chunk', 256)), n)
    ys = []
    for lo in range(0, n, sub):     # unrolled: the module's docstring
        part = slice(lo, min(lo + sub, n))
        y, s = ssd_sub_chunk(x[:, part], delta[:, part], log_a[:, part],
                             b[:, part], c[:, part], s)
        ys.append(y)
    y = jnp.concatenate(ys, axis=1) + d * x
    return {'Out': [y.reshape(y.shape[:2] + (-1,))],
            'StateOut': [_put_rows(state, slot, s)]}
