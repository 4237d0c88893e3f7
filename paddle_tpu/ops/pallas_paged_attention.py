"""Block-paged decode attention as a Pallas TPU kernel (ISSUE 25).

`kv_block_attention`'s TPU lowering: each slot's K and V pages are read
straight from the block pool [NB, BS, D] through its block-table row,
pages 0 .. pos // BS only, with an online softmax — no gathered
[S, MAXB*BS, D] view, no [.., n_head, d_head] re-layout, no work for
rows past `pos`. Bytes read are the tokens cached, rounded up to a page.

One call handles every slot. `pos` and the flattened table ride in SMEM
(scalar prefetch); the pools stay in HBM and are copied one page per
async copy into VMEM, PAGES pages to a compute block, K and V double
buffered: while block i computes, block i + 1 — or the next slot's first
block — loads. A slot's trip count is pos // (PAGES * BS) + 1; an idle
slot (pos 0, a table of trash blocks) costs one page.

D stays on the lanes. The query becomes block-diagonal [n_head, D] (head
h non-zero only in its own d_head lanes), so every head's scores are one
[n_head, D] x [D, PAGES*BS] product and the output one [n_head, PAGES*BS]
x [PAGES*BS, D] product whose diagonal blocks are kept: any d_head that
divides D works, and nothing is re-laid-out.

Contract (the op's): rows j > pos get -inf before the running max, so
their weight is exactly zero, and V rows past `pos` are zeroed before
the weighted sum, so whatever a page or a stale buffer holds there (NaN
included) never reaches the result. A slot's output depends only on its
own pages and `pos` — not on co-resident slots, not on where its pages
sit in the pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.core import Primitive
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import mlir

# positions per compute block: PAGES = _BLOCK_ROWS // BS pages of one
# async copy each; two [_BLOCK_ROWS, D] buffers each for K and V
_BLOCK_ROWS = 256
# Both in-kernel products at full float32: the jnp body's einsums come
# out of XLA's TPU backend float32-exact, and Mosaic's default would
# round the operands to bfloat16 (3e-3 relative on the chip against
# 5e-7, for a third less time: PERF.md, PR 24's reading)
_PRECISION = lax.Precision.HIGHEST


def supports(q, k_cache, v_cache, n_head):
    """The shape rule: what the kernel can read. float32 or bfloat16
    pools whose pages are whole (sublane, lane) tiles — D a multiple of
    128 lanes, BS a multiple of the dtype's sublane packing (8 rows of
    float32, 16 of bfloat16) — a float32 query and heads that divide D.
    Anything else runs the jnp body."""
    if k_cache.ndim != 3 or k_cache.shape != v_cache.shape:
        return False
    if k_cache.dtype != v_cache.dtype or q.dtype != jnp.float32:
        return False
    sublanes = {jnp.dtype(jnp.float32): 8,
                jnp.dtype(jnp.bfloat16): 16}.get(jnp.dtype(k_cache.dtype))
    if sublanes is None:
        return False
    _, bs, d = k_cache.shape
    return (d % 128 == 0 and bs % sublanes == 0 and d % n_head == 0
            and _BLOCK_ROWS % bs == 0)


def _kernel(pos_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            *, n_head, scale, bs, maxb, pages):
    n_slot, d = q_ref.shape
    rows = pages * bs

    def n_pages(s, i):
        """Pages of block i of slot s that hold a position <= pos."""
        return jnp.minimum(pos_ref[s] // bs + 1 - i * pages, pages)

    def each_page(s, i, buf, act):
        """Start, or wait for, the K and V copies of block i of slot s
        into buffer `buf`: one async copy per page."""
        def body(j, carry):
            page = tab_ref[s * maxb + i * pages + j]
            for pool, dst, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                copy = pltpu.make_async_copy(
                    pool.at[page],
                    dst.at[buf, pl.ds(pl.multiple_of(j * bs, bs), bs), :],
                    sem.at[which, buf])
                getattr(copy, act)()
            return carry
        lax.fori_loop(0, n_pages(s, i), body, 0)

    # head h owns lanes [h * d_head, (h + 1) * d_head)
    own = (lax.broadcasted_iota(jnp.int32, (n_head, d), 1) // (d // n_head)
           == lax.broadcasted_iota(jnp.int32, (n_head, d), 0))
    col = lax.broadcasted_iota(jnp.int32, (n_head, rows), 1)
    row = lax.broadcasted_iota(jnp.int32, (rows, d), 0)

    def slot(s, buf):
        pos = pos_ref[s]
        nblk = pos // rows + 1
        q = q_ref[pl.ds(s, 1), :].astype(jnp.float32)          # [1, D]
        qbd = jnp.where(own, jnp.broadcast_to(q, (n_head, d)), 0.0)

        def block(i, carry):
            m, l, acc, buf = carry

            # what computes next loads meanwhile: this slot's next block,
            # or after its last the next slot's first
            last = i + 1 == nblk
            nxt = s + last.astype(jnp.int32)

            @pl.when(nxt < n_slot)
            def _():
                each_page(nxt, jnp.where(last, 0, i + 1), 1 - buf, 'start')

            each_page(s, i, buf, 'wait')
            k = kbuf[buf].astype(jnp.float32)                   # [rows, D]
            sc = lax.dot_general(
                qbd, k, (((1,), (1,)), ((), ())), precision=_PRECISION,
                preferred_element_type=jnp.float32) * scale     # [H, rows]
            sc = jnp.where(i * rows + col <= pos, sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            v = jnp.where(i * rows + row <= pos,
                          vbuf[buf].astype(jnp.float32), 0.0)
            acc = alpha * acc + jnp.dot(
                p, v, precision=_PRECISION,
                preferred_element_type=jnp.float32)             # [H, D]
            return m_new, l, acc, 1 - buf

        m0 = jnp.full((n_head, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((n_head, 1), jnp.float32)
        acc0 = jnp.zeros((n_head, d), jnp.float32)
        _, l, acc, buf = lax.fori_loop(0, nblk, block, (m0, l0, acc0, buf))
        out = jnp.sum(jnp.where(own, acc / l, 0.0), axis=0, keepdims=True)
        o_ref[pl.ds(s, 1), :] = out.astype(o_ref.dtype)
        return buf

    each_page(0, 0, 0, 'start')
    lax.fori_loop(0, n_slot, slot, 0)


def paged_attention(q, k_cache, v_cache, pos, table, *, n_head, scale,
                    interpret=False):
    """Q [S, D] float32, KCache/VCache [NB, BS, D], pos [S] int32, table
    [S, MAXB] int32 -> [S, D]: slot s attends positions 0 .. pos[s] of
    its table's pages. `supports` must hold."""
    n_slot, d = q.shape
    n_block, bs, _ = k_cache.shape
    maxb = table.shape[1]
    pages = min(_BLOCK_ROWS // bs, maxb)
    rows = pages * bs
    # the kernel indexes SMEM and HBM with these: clamp as the jnp body's
    # take does, so a bad feed reads a wrong page, never past the pool
    pos = jnp.clip(pos.astype(jnp.int32), 0, maxb * bs - 1)
    table = jnp.clip(table.astype(jnp.int32), 0, n_block - 1)
    kernel = functools.partial(_kernel, n_head=n_head, scale=scale, bs=bs,
                               maxb=maxb, pages=pages)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_slot, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[pl.BlockSpec((n_slot, d), lambda i, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((n_slot, d), lambda i, *_: (0, 0)),
            scratch_shapes=[pltpu.VMEM((2, rows, d), k_cache.dtype),
                            pltpu.VMEM((2, rows, d), v_cache.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        name='kv_block_paged_attention',
        interpret=interpret,
    )(pos, table.reshape(-1), q, k_cache, v_cache)


# The platform switch. lax.platform_dependent (ops/quant_ops.py's idiom)
# cannot carry a pallas_call through the cpu+tpu jax.export the decode
# artifacts are made with (jax 0.9.0): its cond lowers every kept branch
# for ALL of the module's platforms, and pallas_call's cpu rule raises.
# A primitive's own per-platform rules are each lowered under their
# platform alone, so one exported module still holds both bodies and
# picks by the platform it is run on — and a program lowered for one
# platform holds that platform's body only.
_attend_p = Primitive('kv_block_attention')
_attend_p.def_abstract_eval(
    lambda q, *_, **__: jax.core.ShapedArray(q.shape, q.dtype))
_attend_p.def_impl(lambda *args, **params: jax.jit(
    functools.partial(_attend_p.bind, **params))(*args))
mlir.register_lowering(
    _attend_p, lambda ctx, *args, tpu, default: mlir.lower_fun(
        tpu, multiple_results=False)(ctx, *args), platform='tpu')
mlir.register_lowering(
    _attend_p, lambda ctx, *args, tpu, default: mlir.lower_fun(
        default, multiple_results=False)(ctx, *args))


def tpu_or_default(q, k_cache, v_cache, pos, table, *, tpu, default):
    """tpu(q, k_cache, v_cache, pos, table) where the program runs on a
    TPU, default(...) anywhere else; both give [S, D] like q."""
    return _attend_p.bind(q, k_cache, v_cache, pos, table, tpu=tpu,
                          default=default)
