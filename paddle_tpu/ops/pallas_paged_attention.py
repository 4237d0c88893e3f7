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


def supports(q, k_cache, v_cache, n_head, n_kv_head=None):
    """The shape rule: what the kernel can read. float32 or bfloat16
    pools whose pages are whole (sublane, lane) tiles — D a multiple of
    128 lanes, BS a multiple of the dtype's sublane packing (8 rows of
    float32, 16 of bfloat16) — a float32 query and heads that divide D.
    Grouped K/V heads (n_kv_head < n_head, Q wider than the pool) also
    need a head of whole 128-lane tiles: the query heads of a group are
    laid side by side at tile boundaries. Anything else runs the jnp
    body."""
    if k_cache.ndim != 3 or k_cache.shape != v_cache.shape:
        return False
    if k_cache.dtype != v_cache.dtype or q.dtype != jnp.float32:
        return False
    sublanes = {jnp.dtype(jnp.float32): 8,
                jnp.dtype(jnp.bfloat16): 16}.get(jnp.dtype(k_cache.dtype))
    if sublanes is None:
        return False
    _, bs, d = k_cache.shape
    n_kv = n_kv_head or n_head
    if n_head % n_kv or d % n_kv:
        return False
    if n_kv != n_head and (d // n_kv) % 128:
        return False
    return (d % 128 == 0 and bs % sublanes == 0
            and q.shape[-1] == n_head * (d // n_kv)
            and _BLOCK_ROWS % bs == 0)


def _kernel(pos_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            *, n_head, n_kv_head, window, scale, bs, maxb, pages):
    n_slot = q_ref.shape[0]
    d = kbuf.shape[-1]
    dh = d // n_kv_head
    group = n_head // n_kv_head
    rows = pages * bs

    def first_page(s):
        """The page that holds the first row slot s attends — with a
        window, the page of pos - window + 1. Block i of a slot is the
        `pages` pages from there on. (No window: page 0, and no call.)"""
        return jnp.maximum(pos_ref[s] - window + 1, 0) // bs

    def n_pages(s, i):
        """Pages of block i of slot s that hold a position <= pos."""
        held = pos_ref[s] // bs + 1
        if window:
            held = held - first_page(s)
        return jnp.minimum(held - i * pages, pages)

    def each_page(s, i, buf, act):
        """Start, or wait for, the K and V copies of block i of slot s
        into buffer `buf`: one async copy per page."""
        def body(j, carry):
            page = s * maxb + i * pages + j
            if window:
                page = page + first_page(s)
            page = tab_ref[page]
            for pool, dst, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                copy = pltpu.make_async_copy(
                    pool.at[page],
                    dst.at[buf, pl.ds(pl.multiple_of(j * bs, bs), bs), :],
                    sem.at[which, buf])
                getattr(copy, act)()
            return carry
        lax.fori_loop(0, n_pages(s, i), body, 0)

    # query head h owns the d_head lanes of K/V head h // group
    kv_head = lax.broadcasted_iota(jnp.int32, (n_head, d), 1) // dh
    head = lax.broadcasted_iota(jnp.int32, (n_head, d), 0)
    own = kv_head == (head if group == 1 else head // group)
    col = lax.broadcasted_iota(jnp.int32, (n_head, rows), 1)
    row = lax.broadcasted_iota(jnp.int32, (rows, d), 0)

    def slot(s, buf):
        pos = pos_ref[s]
        if window:
            origin = first_page(s) * bs   # position of block 0's row 0
            nblk = (pos - origin) // rows + 1
        else:
            nblk = pos // rows + 1
        if group == 1:
            q = q_ref[pl.ds(s, 1), :].astype(jnp.float32)      # [1, D]
            q = jnp.broadcast_to(q, (n_head, d))
        else:
            # [n_head, d_head] -> each head's lanes repeated under every
            # K/V head; `own` keeps its group's
            q = jnp.concatenate(
                [q_ref[s].astype(jnp.float32)] * n_kv_head, axis=1)
        qbd = jnp.where(own, q, 0.0)

        def seen(i, j):
            """Whether row j of block i holds a position the slot
            attends."""
            if not window:
                return i * rows + j <= pos
            at = origin + i * rows + j
            return (at <= pos) & (at > pos - window)

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
            sc = jnp.where(seen(i, col), sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            v = jnp.where(seen(i, row), vbuf[buf].astype(jnp.float32), 0.0)
            acc = alpha * acc + jnp.dot(
                p, v, precision=_PRECISION,
                preferred_element_type=jnp.float32)             # [H, D]
            return m_new, l, acc, 1 - buf

        m0 = jnp.full((n_head, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((n_head, 1), jnp.float32)
        acc0 = jnp.zeros((n_head, d), jnp.float32)
        _, l, acc, buf = lax.fori_loop(0, nblk, block, (m0, l0, acc0, buf))
        kept = jnp.where(own, acc / l, 0.0)
        if group == 1:
            o_ref[pl.ds(s, 1), :] = jnp.sum(
                kept, axis=0, keepdims=True).astype(o_ref.dtype)
        else:
            out = kept[:, :dh]
            for g in range(1, n_kv_head):
                out = out + kept[:, g * dh:(g + 1) * dh]
            o_ref[s] = out.astype(o_ref.dtype)                  # [H, dh]
        return buf

    each_page(0, 0, 0, 'start')
    lax.fori_loop(0, n_slot, slot, 0)


def paged_attention(q, k_cache, v_cache, pos, table, *, n_head, scale,
                    n_kv_head=None, window=0, interpret=False):
    """Q [S, n_head * d_head] float32, KCache/VCache [NB, BS, D =
    n_kv_head * d_head], pos [S] int32, table [S, MAXB] int32 -> [S,
    n_head * d_head]: slot s attends positions 0 .. pos[s] of its
    table's pages — with window > 0 positions pos[s] - window + 1 ..
    pos[s], reading no page below the first of them. `supports` must
    hold."""
    n_slot = q.shape[0]
    n_block, bs, d = k_cache.shape
    n_kv_head = n_kv_head or n_head
    maxb = table.shape[1]
    pages = min(_BLOCK_ROWS // bs, maxb)
    rows = pages * bs
    # the kernel indexes SMEM and HBM with these: clamp as the jnp body's
    # take does, so a bad feed reads a wrong page, never past the pool
    pos = jnp.clip(pos.astype(jnp.int32), 0, maxb * bs - 1)
    table = jnp.clip(table.astype(jnp.int32), 0, n_block - 1)
    kernel = functools.partial(_kernel, n_head=n_head, n_kv_head=n_kv_head,
                               window=int(window), scale=scale, bs=bs,
                               maxb=maxb, pages=pages)
    if n_kv_head != n_head:
        # one [n_head, d_head] tile stack per slot: a free re-view here,
        # a re-layout inside the kernel
        q = q.reshape(n_slot, n_head, d // n_kv_head)
    qspec = pl.BlockSpec(q.shape, lambda i, *_: (0,) * q.ndim)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[qspec,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((2, rows, d), k_cache.dtype),
                            pltpu.VMEM((2, rows, d), v_cache.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        name='kv_block_paged_attention',
        interpret=interpret,
    )(pos, table.reshape(-1), q, k_cache, v_cache)
    return out.reshape(n_slot, -1)


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
