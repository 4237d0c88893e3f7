"""The CHUNKED gated delta rule as a Pallas TPU kernel (ISSUE 48).

`gated_delta_chunk`'s TPU lowering: C tokens a row from the state of the
row's slot to the state after ChunkLen tokens (ops/linear_attention_ops.py
has the mathematics and delta_chunk, the other body and the reference). Of
the 9.3 ms nine layers' rule cost a 512-token slice as XLA lowered that
expression, 6.2 were ONE custom call a layer — what XLA makes of the unit
triangular solve, InvertDiagBlocksLowerTriangular, 0.69 ms — and the rest
products near the matrix unit's peak with the state going out to memory
and back between sub-chunks (PERF.md 6, PR 48). Here ONE program takes a
row's value head through all its sub-chunks with the head's [d_k, d_v]
state in fast memory from the first to the last: the state is read from
the slot once (not at all where Start is 0) and written once, and nothing
of size [sub-chunks, rows, heads, n, n] ever exists.

Layout. The grid is (row, value head). q, k and v stay as the op receives
them, [R, C, heads * d]: a head is a 128-lane block of the last axis, and a
value head reads its KEY head's q and k through the index map (h // (Hv /
Hk)), so nothing is repeated per value head. g and beta come as rows,
[R, Hv, C / n, n]: a sub-chunk's are one sublane of the block, and what
the rule needs of them as COLUMNS (the cumulative decay down the tokens,
beta to scale k and v) is taken from the rows with a mask and a sum over
lanes. The state stays where it lies ([S, Hv, dk, dv], aliased to the
output): a program copies its slot's head in and out itself, so a slot
outside [0, S) writes nothing.

Per sub-chunk of n tokens (G the running sum of g, `decay` = e^{G_i - G_j}
on and below the diagonal, L = strictly-lower(beta k k^T * decay)):

    T      = (I + L)^-1
    v_new  = T (v beta - (k beta e^G) S)
    o      = (q e^G) S + (q k^T * decay) v_new
    S     <- e^{G_last} S + (k e^{G_last - G})^T v_new

delta_chunk's sums in another order (T is applied to the difference, not to
both terms; the two products against k^T are one product of [k; q], the two
against S one of [k beta e^G; q e^G]). T is EXACT — unit_lower_inverses:
substitution inside the 16-row diagonal blocks, then pairwise merges by
products; no power of L is ever formed. Every product is float32 at
Precision.HIGHEST.

Rows from ChunkLen on are written ZERO (the op calls them unread), and a
sub-chunk that lies wholly there is skipped: its g, beta and k are zero by
the op's masks, so the state passes it unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import mlir

_LANES = 128
# tokens a sub-chunk INSIDE the kernel (every length is the same
# mathematics; the op's `sub_chunk` attribute is the jnp body's). 128 on
# the chip's reading (PERF.md 6, PR 48: 0.51 ms a call against 0.69 at 64 —
# a product of 64 rows fills half the matrix unit), and a sub-chunk's
# [n, n] matrices are then whole vector registers of lanes
_SUB = 128
_BLOCK = 16             # rows of a diagonal block inverted by substitution
_GROUP = 2              # sub-chunks prepared together
_VMEM_LIMIT_BYTES = 32 << 20
_HI = jax.lax.Precision.HIGHEST


def refuses(state, q, C):
    """None where the kernel takes this state [S, Hv, dk, dv], these keys
    [.., Hk * dk] and a chunk of C tokens, else why not (the op then lowers
    the jnp body on every platform)."""
    _, n_head, dk, dv = state.shape
    if state.dtype != jnp.float32:
        return 'the state is %s, not float32' % state.dtype
    if dk % _LANES or dv % _LANES:
        return ('a head\'s keys and values [%d, %d] are no whole number of '
                '%d-lane blocks' % (dk, dv, _LANES))
    if q.shape[-1] % dk or n_head % (q.shape[-1] // dk):
        return '%d value heads over keys %d wide' % (n_head, q.shape[-1])
    if C % _SUB:
        return ('a chunk of %d tokens is no whole number of the kernel\'s '
                'sub-chunks of %d' % (C, _SUB))
    # q, k, v, o blocks twice (the pipeline's), the state, a sub-chunk's
    # temporaries
    need = 4 * (2 * C * (2 * dk + 2 * dv) + dk * dv
                + 16 * _SUB * max(dk, dv, _SUB))
    if need > _VMEM_LIMIT_BYTES // 2:
        return 'a head\'s chunk needs %d bytes of fast memory' % need
    return None


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):
    return _dot(a, b, ((0,), (0,)))


def unit_lower_inverses(lowers):
    """[(I + L)^-1 for L in lowers], each L strictly lower triangular
    [n, n], n a whole vector register of lanes, exactly: substitution
    inside the diagonal blocks of 16, every block at once, then pairwise
    merges. The matrices are independent chains of dependent steps, so
    every step is issued for all of them before the next: the matrix unit
    runs one's product while another's waits for its operand."""
    n = lowers[0].shape[0]
    nb = n // _BLOCK
    ri = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    blocks = (ri // _BLOCK) == (ci // _BLOCK)
    # the diagonal blocks side by side, packed[i, 16 b + j] = L_b[i, j],
    # so a step of the substitution X <- X - L[:, j] X[j, :], j = 0..14,
    # is two vector registers for ALL blocks. It wants column j of each
    # block spread over the block's 16 lanes: one product of the columns,
    # each masked to its own lane, with the 0/1 block matrix — every sum
    # has a single term, so HIGHEST's three-piece split makes it exact
    local = jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, n), 1) % _BLOCK
    ones = jnp.where(blocks, 1.0, 0.0)
    spreads = []
    for lower in lowers:
        packed = jnp.sum(jnp.where(blocks, lower, 0.0).reshape(
            nb, _BLOCK, n), axis=0)
        spreads.append(_nn(jnp.concatenate(
            [jnp.where(local == j, packed, 0.0)
             for j in range(_BLOCK - 1)], axis=0), ones))   # [15 * 16, n]
    eye = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, n), 0)
                    == local, 1.0, 0.0)
    xs = [eye] * len(lowers)
    for j in range(_BLOCK - 1):
        xs = [x - spread[j * _BLOCK:(j + 1) * _BLOCK] * x[j:j + 1, :]
              for x, spread in zip(xs, spreads)]
    ts = [jnp.where(blocks, jnp.concatenate([x] * nb, axis=0), 0.0)
          for x in xs]
    # [[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]: a merge
    # changes the rows of the ODD blocks alone, and both its products take
    # just those rows
    size = _BLOCK
    while size < n:
        odd = [slice(lo, lo + size) for lo in range(size, n, 2 * size)]
        zero = jnp.zeros((size, n), jnp.float32)

        def pick(a):        # the odd blocks' rows, [n / 2, n]
            return jnp.concatenate([a[rows] for rows in odd], axis=0)

        def put(a):         # ... back in their places, zero between
            return jnp.concatenate(
                [piece for i in range(len(odd))
                 for piece in (zero, a[i * size:(i + 1) * size])], axis=0)

        below = ((ri // (2 * size)) == (ci // (2 * size))) \
            & ((ri // size) != (ci // size))
        b_a = [_nn(pick(jnp.where(below, lower, 0.0)), t)
               for lower, t in zip(lowers, ts)]
        d_b_a = [_nn(pick(t), put(x)) for t, x in zip(ts, b_a)]
        ts = [t - put(x) for t, x in zip(ts, d_b_a)]
        size *= 2
    return ts


def _prepare(sub_chunks):
    """What a sub-chunk's step needs that does not depend on the state, for
    several sub-chunks at once: [(q, k [n, dk], v [n, dv], g_row, b_row
    [1, n])] -> [(T, q k^T * decay [n, n], k beta e^G, q e^G [n, dk],
    v beta [n, dv], k e^{G_last - G} [n, dk], e^{G_last} [1, 1])]."""
    n = sub_chunks[0][0].shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye, on_below = ri == ci, ri >= ci
    lowers, rest = [], []
    for q, k, v, g_row, b_row in sub_chunks:
        # as COLUMNS: the running sum of g, and beta
        G = jnp.sum(jnp.where(on_below, g_row, 0.0), axis=1, keepdims=True)
        beta = jnp.sum(jnp.where(eye, b_row, 0.0), axis=1, keepdims=True)
        G_row = jnp.sum(jnp.where(eye, G, 0.0), axis=0, keepdims=True)
        # e^{G_i - G_j}, i >= j: the exponent is <= 0 there; masked before
        # the exponential (above the diagonal it may overflow)
        decay = jnp.where(on_below,
                          jnp.exp(jnp.where(on_below, G - G_row, 0.0)), 0.0)
        e_G = jnp.exp(G)
        g_last = G[n - 1:n, :]                              # [1, 1]
        both = _nt(jnp.concatenate([k, q], axis=0), k)      # [2 n, n]
        lowers.append(jnp.where(ri > ci, both[:n] * beta * decay, 0.0))
        rest.append((both[n:] * decay, k * (beta * e_G), q * e_G, v * beta,
                     k * jnp.exp(g_last - G), jnp.exp(g_last)))
    return [(T,) + r for T, r in zip(unit_lower_inverses(lowers), rest)]


def _advance(prepared, state):
    """One sub-chunk from `state` [dk, dv] on: (o [n, dv], the state after
    it)."""
    T, qk, k_in, q_in, v_beta, k_tail, s_decay = prepared
    n = T.shape[0]
    both = _nn(jnp.concatenate([k_in, q_in], axis=0), state)
    v_new = _nn(T, v_beta - both[:n])
    return (both[n:] + _nn(qk, v_new),
            state * s_decay + _tn(k_tail, v_new))


def _kernel(start_ref, clen_ref, slot_ref, q_ref, k_ref, v_ref, g_ref,
            b_ref, s_hbm, o_ref, so_hbm, s_ref, sem, *, n, n_slot):
    r, h = pl.program_id(0), pl.program_id(1)
    slot, clen = slot_ref[r], clen_ref[r]
    at = jnp.minimum(jnp.maximum(slot, 0), n_slot - 1)

    def head(ref):      # s_hbm and so_hbm are ONE buffer (aliased)
        return ref.at[at, h]

    @pl.when(start_ref[r] != 0)
    def _():
        copy = pltpu.make_async_copy(head(s_hbm), s_ref, sem)
        copy.start()
        copy.wait()

    @pl.when(start_ref[r] == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def rows(c):
        return slice(c * n, (c + 1) * n)

    def dead(c):
        o_ref[0, rows(c), :] = jnp.zeros((n, o_ref.shape[2]), o_ref.dtype)

    def live(c, prepared):
        o, s_ref[...] = _advance(prepared, s_ref[...])
        token = c * n + jax.lax.broadcasted_iota(jnp.int32, o.shape, 0)
        o_ref[0, rows(c), :] = jnp.where(token < clen, o, 0.0)

    # sub-chunks in groups of _GROUP: what does not depend on the state is
    # prepared for a group at once, then the state walks through it
    n_sub = q_ref.shape[1] // n
    for first in range(0, n_sub, _GROUP):
        group = range(first, min(first + _GROUP, n_sub))

        @pl.when(first * n < clen)
        def _(group=group):
            prepared = _prepare([
                (q_ref[0, rows(c), :], k_ref[0, rows(c), :],
                 v_ref[0, rows(c), :], g_ref[0, 0, c:c + 1, :],
                 b_ref[0, 0, c:c + 1, :]) for c in group])
            for c, p in zip(group, prepared):
                pl.when(c * n < clen)(functools.partial(live, c, p))
                pl.when(c * n >= clen)(functools.partial(dead, c))

        @pl.when(first * n >= clen)
        def _(group=group):
            for c in group:
                dead(c)

    @pl.when((slot >= 0) & (slot < n_slot))
    def _():
        copy = pltpu.make_async_copy(s_ref, head(so_hbm), sem)
        copy.start()
        copy.wait()


def delta_chunk(q, k, v, g, beta, state, start, clen, slot, *,
                interpret=False):
    """linear_attention_ops' chunk, slot to slot: q, k [R, C, Hk * dk]
    normalised (q scaled), v [R, C, Hv * dv], g, beta [R, C, Hv] — zero,
    as k, from ChunkLen on — state [S, Hv, dk, dv] float32, start, clen,
    slot [R] int32 -> (o [R, C, Hv * dv], the state with the rows' slots
    written). `refuses` must give None."""
    n_slot, n_head, dk, dv = state.shape
    R, C, _ = q.shape
    rep = n_head // (q.shape[-1] // dk)
    n = _SUB

    def rows_of(x):     # [R, C, Hv] -> [R, Hv, C / n, n]
        return x.transpose(0, 2, 1).reshape(R, n_head, C // n, n)

    key_spec = pl.BlockSpec((1, C, dk), lambda r, h, *_: (r, 0, h // rep))
    val_spec = pl.BlockSpec((1, C, dv), lambda r, h, *_: (r, 0, h))
    row_spec = pl.BlockSpec((1, 1, C // n, n), lambda r, h, *_: (r, h, 0, 0))
    state_spec = pl.BlockSpec(memory_space=pl.ANY)
    o, new = pl.pallas_call(
        functools.partial(_kernel, n=n, n_slot=n_slot),
        out_shape=(jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, n_head),
            in_specs=[key_spec, key_spec, val_spec, row_spec, row_spec,
                      state_spec],
            out_specs=(val_spec, state_spec),
            scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        # operands count the prefetched scalars: the state is the ninth
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name='gated_delta_chunk',
        interpret=interpret,
    )(start.astype(jnp.int32), clen.astype(jnp.int32),
      slot.astype(jnp.int32), q, k, v, rows_of(g), rows_of(beta), state)
    return o, new


def jnp_chunk(q, k, v, g, beta, state, start, clen, slot, *, sub):
    """The other body, and the reference: linear_attention_ops' expression
    (the slot's rows gathered, delta_chunk, the rows put back)."""
    from . import linear_attention_ops as lao
    n_head, dk = state.shape[1], state.shape[2]
    R, C, _ = q.shape
    n_key = q.shape[-1] // dk
    q, k = (lao.per_value_head(x.reshape(R, C, n_key, dk), n_head)
            for x in (q, k))
    s0 = lao._slot_rows(state, slot, start).astype(jnp.float32)
    o, s1 = lao.delta_chunk(q, k, v.reshape(R, C, n_head, -1), g, beta, s0,
                            sub=sub)
    return o.reshape(R, C, -1), lao._put_rows(state, slot, s1)


# The platform switch, pallas_delta_rule.py's idiom: a primitive whose TPU
# rule lowers the kernel and whose default rule lowers the jnp body.
_chunk_p = Primitive('gated_delta_chunk')
_chunk_p.multiple_results = True
_chunk_p.def_abstract_eval(
    lambda q, k, v, g, beta, state, start, clen, slot, *, sub: (
        jax.core.ShapedArray(v.shape, jnp.float32),
        jax.core.ShapedArray(state.shape, state.dtype)))
_chunk_p.def_impl(lambda *args, sub: jax.jit(
    functools.partial(_chunk_p.bind, sub=sub))(*args))
mlir.register_lowering(
    _chunk_p, mlir.lower_fun(lambda *args, sub: delta_chunk(*args),
                             multiple_results=True), platform='tpu')
mlir.register_lowering(
    _chunk_p, mlir.lower_fun(jnp_chunk, multiple_results=True))


def kernel_or_jnp(q, k, v, g, beta, state, start, clen, slot, sub):
    """`delta_chunk` where the program runs on a TPU, the jnp body (in
    sub-chunks of `sub`) anywhere else."""
    return _chunk_p.bind(q, k, v, g, beta, state, start, clen, slot,
                         sub=int(sub))
