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
divides D works, and nothing is re-laid-out. Both products are exact to
float32 in the form the pool's dtype calls for (`_times`): a float32
pool's at HIGHEST; a bfloat16 pool's pages go to the MXU as they lie in
VMEM, under the float32 query and softmax weights split into three
bfloat16 pieces (`_pieces`) — the same result, a sixth of the passes,
and no [rows, D] block lifted to float32.

Contract (the op's): rows j > pos get -inf before the running max, so
their weight is exactly zero, and V rows past `pos` are zeroed before
the weighted sum, so whatever a page or a stale buffer holds there (NaN
included) never reaches the result. A slot's output depends only on its
own pages and `pos` — not on co-resident slots, not on where its pages
sit in the pool.

A second kernel, `latent_paged_attention` (ISSUE 40), reads a LATENT pool:
one row a position that is key and value both (every query head scores the
whole row and sums its first v_width channels — attention in the absorbed
form), so a page is copied once and multiplied twice, on operands of the
pool's dtype. Same contract; one grid step a slot, because n_head rows of
the pool's width a slot make the whole batch's query too large to hold at
once; and its copies run TWO blocks ahead over three page halves, across
slot boundaries (ISSUE 49: `_latent_kernel`). A slot's FULL blocks and its
last one take different bodies there (ISSUE 44): every block before the
last holds a compute block's pages of rows that are all <= pos, so its
copies are issued in straight-line code and waited for once and its
products take the rows unmasked, where the last block — the one that can
hold fewer pages or a row past pos — keeps the page loops and both masks.
A select of every row and a loop of a constant trip count are the
identity, so the result is the one-bodied kernel's to the bit; the two
loops' scalar work overlapped nothing and was a quarter of a full block's
time (the masks, measured, none of it: PERF.md, PR 44).

What the paging contract is made of is written ONCE and both kernels call
it: the rule a pool's pages obey (`_pool_rule`), the clamping of `pos`
and the table (`_clamped`), a page's async copy through the table
(`_each_page`) and the online softmax (`_softmax_start`,
`_softmax_step`). What differs stays in each kernel: which rows a slot
attends (a window), how the query meets the row (block-diagonal heads
over K and V pools; every head over one whole latent row), the grid (one
step for all slots; a step a slot) and how far ahead of the block that
computes the copies run (one block, `_load_next_then_wait`; two).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
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
# 5e-7, for a third less time: PERF.md, PR 24's reading). On this chip
# HIGHEST is six bfloat16 passes of the MXU, a1 b1 + a1 b2 + a2 b1 +
# a1 b3 + a2 b2 + a3 b1 over three-way splits of both operands: what a
# float32 pool needs, and three passes over zeros for a page that came
# out of a bfloat16 pool (b2 = b3 = 0) — `_times` gives that pool the
# other three as ONE pass (PERF.md, PR 43)
_PRECISION = lax.Precision.HIGHEST


_SUBLANES = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}


def _pool_rule(cache):
    """The rule both kernels put to a pool [NB, BS, D], by the name of
    the first part it breaks, or None: float32 or bfloat16, rows of whole
    128-lane tiles (a page cannot be copied out of a row that ends
    inside a tile) and pages of whole sublane tiles that divide a
    compute block."""
    sublanes = _SUBLANES.get(jnp.dtype(cache.dtype))
    if sublanes is None:
        return 'a pool that is neither float32 nor bfloat16'
    _, bs, d = cache.shape
    if d % 128:
        return 'a row width that is no multiple of 128'
    if bs % sublanes or _BLOCK_ROWS % bs:
        return 'pages that are not whole sublane tiles'
    return None


def refuses(q, k_cache, v_cache, n_head, n_kv_head=None):
    """Why `paged_attention` cannot read these pools — the name of the
    first rule they break — or None. float32 or bfloat16 pools whose
    pages are whole (sublane, lane) tiles — D a multiple of 128 lanes, BS
    a multiple of the dtype's sublane packing (8 rows of float32, 16 of
    bfloat16) — a float32 query and heads that divide D. Grouped K/V
    heads (n_kv_head < n_head, Q wider than the pool) also need a head of
    whole 128-lane tiles: the query heads of a group are laid side by
    side at tile boundaries. K and V are two pools, each copied: a row
    that holds both (K = V pages, a latent pool) is
    `latent_paged_attention`'s, whose rule is `refuses_latent`."""
    if k_cache.ndim != 3 or k_cache.shape != v_cache.shape:
        return 'K and V pools of different shapes'
    if k_cache.dtype != v_cache.dtype or q.dtype != jnp.float32:
        return 'pools of two dtypes, or a query that is not float32'
    broken = _pool_rule(k_cache)
    if broken:
        return broken
    d = k_cache.shape[2]
    n_kv = n_kv_head or n_head
    if n_head % n_kv or d % n_kv:
        return 'heads that do not divide the row'
    if n_kv != n_head and (d // n_kv) % 128:
        return 'grouped heads whose width is no multiple of 128'
    if q.shape[-1] != n_head * (d // n_kv):
        return 'a query that is not n_head heads wide'
    return None


def supports(q, k_cache, v_cache, n_head, n_kv_head=None):
    """The shape rule: whether the kernel can read these pools
    (`refuses` names what it cannot). Anything else runs the jnp
    body."""
    return refuses(q, k_cache, v_cache, n_head, n_kv_head) is None


def refuses_latent(q, cache, n_head, v_width):
    """Why `latent_paged_attention` cannot read this pool, or None: a
    float32 or bfloat16 pool of whole (sublane, lane) tiles — a page
    cannot be copied out of a row that ends inside a tile: the chip's
    layout holds a 576-wide row in five 128-lane tiles and Mosaic
    refuses the 576-of-640 slice, so a latent row is stored padded to
    640 (models/joyai_llm_flash.py) — a float32 query of n_head rows as
    wide as the pool's, and values that are the row's first v_width
    channels, whole 128-lane tiles."""
    if cache.ndim != 3 or q.dtype != jnp.float32:
        return 'a pool that is not [blocks, rows, width], or a query that ' \
               'is not float32'
    broken = _pool_rule(cache)
    if broken:
        return broken
    d = cache.shape[2]
    if v_width % 128 or v_width > d:
        return 'values that are not whole 128-lane tiles of the row'
    if q.shape[-1] != n_head * d:
        return 'a query that is not n_head rows of the pool\'s width'
    return None


def _clamped(pos, table, n_block, bs):
    """(pos, table, pages to a compute block): the kernels index SMEM and
    HBM with these, so they are clamped as the jnp body's take does — a
    bad feed reads a wrong page, never past the pool."""
    maxb = table.shape[1]
    return (jnp.clip(pos.astype(jnp.int32), 0, maxb * bs - 1),
            jnp.clip(table.astype(jnp.int32), 0, n_block - 1),
            min(_BLOCK_ROWS // bs, maxb))


def _each_page(tab_ref, first, count, pools, buf, act, bs):
    """Start, or wait for (`act`), one async copy a page: the `count`
    pages named by the table from entry `first` on, out of each pool of
    `pools` — (pool in HBM, its [halves, rows, D] buffer, the semaphore of
    a half) — into rows j * bs of the buffer's half `buf`. A traced count
    is a loop of one page a trip; a Python int is that many copies in
    straight-line code, their rows static."""
    static = isinstance(count, int)

    def body(j, carry):
        page = tab_ref[first + j]
        for pool, dst, sem_of in pools:
            row = j * bs if static else pl.multiple_of(j * bs, bs)
            copy = pltpu.make_async_copy(
                pool.at[page], dst.at[buf, pl.ds(row, bs), :], sem_of(buf))
            getattr(copy, act)()
        return carry
    if static:
        for j in range(count):
            body(j, 0)
    else:
        lax.fori_loop(0, count, body, 0)


def _load_next_then_wait(each_page, s, i, nblk, n_slot, buf):
    """Before block i of slot s computes out of half `buf`: what computes
    next starts loading into the other half — this slot's next block, or
    after its last the next slot's first — and block i's own copies are
    waited for."""
    last = i + 1 == nblk
    nxt = s + last.astype(jnp.int32)

    @pl.when(nxt < n_slot)
    def _():
        each_page(nxt, jnp.where(last, 0, i + 1), 1 - buf, 'start')

    each_page(s, i, buf, 'wait')


def _softmax_start(n_head, width):
    """(running max, running sum, weighted sum [n_head, width]) before a
    slot's first block."""
    return (jnp.full((n_head, 1), -jnp.inf, jnp.float32),
            jnp.zeros((n_head, 1), jnp.float32),
            jnp.zeros((n_head, width), jnp.float32))


def _softmax_step(m, l, acc, sc, weigh):
    """The online softmax over one more block: sc [n_head, rows] its
    scores, -inf where a row is not attended (so its weight is exactly
    zero); weigh(p) the block's values summed under the weights p."""
    m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(sc - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    return m_new, l, alpha * acc + weigh(p)


def _pieces(x):
    """float32 x [n, w] as three bfloat16 pieces, one under the other
    [3 n, w]: hi = bf16(x), mid = bf16(x - hi), lo = bf16(x - hi - mid).
    Three 8-bit significands hold float32's 24, so hi + mid + lo is x."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _times(a, b, contract):
    """a times b, contracting (a's dimension, b's), exact to float32 in
    the form b's dtype calls for. b float32 (a too): one product at
    HIGHEST. b bfloat16 as a pool stores it, a the `_pieces` of a float32
    operand: ONE pass of the stack over b under float32 sums, its three
    row groups added, smallest first — HIGHEST's three terms that do not
    multiply a zero, and nothing of b lifted to float32."""
    dims = ((contract[:1], contract[1:]), ((), ()))
    if b.dtype == jnp.float32:
        return lax.dot_general(a, b, dims, precision=_PRECISION,
                               preferred_element_type=jnp.float32)
    by3 = lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)
    n = by3.shape[0] // 3
    return by3[2 * n:] + by3[n:2 * n] + by3[:n]


def _query_rows(n_head, dtype):
    """Rows of a slot's query block inside `_kernel`: n_head, and over a
    bfloat16 pool whole sublane tiles of 16 (heads past n_head are zero
    rows), so that the three `_pieces` lie one under the other at tile
    boundaries."""
    if dtype == jnp.float32:
        return n_head
    tile = _SUBLANES[jnp.dtype(jnp.bfloat16)]
    return -(-n_head // tile) * tile


def _kernel(pos_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
            *, n_head, n_kv_head, window, scale, bs, maxb, pages):
    n_slot = q_ref.shape[0]
    d = kbuf.shape[-1]
    dh = d // n_kv_head
    group = n_head // n_kv_head
    rows = pages * bs
    # the operands of both products follow the pool's dtype (`_times`)
    split = kbuf.dtype != jnp.float32
    n_head = _query_rows(n_head, kbuf.dtype)

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

    pools = ((k_hbm, kbuf, lambda buf: sem.at[0, buf]),
             (v_hbm, vbuf, lambda buf: sem.at[1, buf]))

    def each_page(s, i, buf, act):
        """The K and V copies of block i of slot s into half `buf`."""
        first = s * maxb + i * pages
        if window:
            first = first + first_page(s)
        _each_page(tab_ref, first, n_pages(s, i), pools, buf, act, bs)

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
        if split:
            qbd = _pieces(qbd)              # once a slot: [3 n_head, D]

        def seen(i, j):
            """Whether row j of block i holds a position the slot
            attends."""
            if not window:
                return i * rows + j <= pos
            at = origin + i * rows + j
            return (at <= pos) & (at > pos - window)

        def block(i, carry):
            m, l, acc, buf = carry
            _load_next_then_wait(each_page, s, i, nblk, n_slot, buf)
            sc = _times(qbd, kbuf[buf], (1, 1)) * scale         # [H, rows]
            sc = jnp.where(seen(i, col), sc, -jnp.inf)

            def weigh(p):
                v = jnp.where(seen(i, row), vbuf[buf], 0.0)     # [rows, D]
                return _times(_pieces(p) if split else p, v,
                              (1, 0))                           # [H, D]

            return _softmax_step(m, l, acc, sc, weigh) + (1 - buf,)

        _, l, acc, buf = lax.fori_loop(
            0, nblk, block, _softmax_start(n_head, d) + (buf,))
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
    pos, table, pages = _clamped(pos, table, n_block, bs)
    rows = pages * bs
    kernel = functools.partial(_kernel, n_head=n_head, n_kv_head=n_kv_head,
                               window=int(window), scale=scale, bs=bs,
                               maxb=maxb, pages=pages)
    pad = 0
    if n_kv_head != n_head:
        # one [n_head, d_head] tile stack per slot: a free re-view here,
        # a re-layout inside the kernel
        q = q.reshape(n_slot, n_head, d // n_kv_head)
        pad = _query_rows(n_head, k_cache.dtype) - n_head
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
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
    if pad:
        out = out[:, :n_head]
    return out.reshape(n_slot, -1)


def _latent_kernel(pos_ref, tab_ref, q_ref, c_hbm, o_ref, cbuf, sem, at,
                   *, v_width, scale, bs, maxb, pages):
    """`_kernel` over ONE pool whose row is both key and value: every
    query head (the rows of q_ref[0], [n_head, D]) scores the whole
    D-wide row and sums its first v_width channels, so a page is copied
    once and multiplied twice. One grid step a slot — n_head rows of D a
    slot make the whole batch's query too large to hold at once, so the
    pipeline brings a slot's in while the last one computes. The products
    take the pool's dtype as operands with float32 sums (bfloat16 x
    bfloat16 is the MXU's own product: n_head x D x 2 operations a cached
    row is 4.5 times a K/V row's, and six passes of it would be the
    step); a float32 pool keeps full float32.

    A slot is its FULL blocks, then its last one. Block i is full where
    (i + 1) * rows <= pos: `pages` pages, every row a position <= pos —
    all of a slot's blocks but the last, and never the last. Told from
    `pos` alone, on the side that starts a block's copies and the side
    that waits for them alike (`each_page`), so the bytes a half's
    semaphore is given and the bytes it is asked for are one count.

    The copies run TWO blocks ahead (ISSUE 49). The call's blocks are ONE
    sequence, slot after slot, over three page halves: block n computes
    out of half n % 3 while n + 1 and n + 2 load, and n + 2 is started
    between n's two products — behind the wait, in the products' own
    basic block, the compiler lays the sixteen descriptors' scalar work
    beside the matrix unit's (0.73 -> 0.68 us a full block on the chip;
    started in front of the wait, where a look-ahead of ONE block has
    to start them to cover their 0.9 us, they overlap nothing, and the
    third half alone reads 0.72: PERF.md, PR 49). Two ahead of a slot's block i is its block i + 2 while it has
    one; from its second-to-last block on it is another slot's — the
    next one's first, then its second or, behind a slot of one block,
    the first of the slot after — which is what `at` (SMEM, kept across
    grid steps: the half the next block computes out of, then the slot
    and block of the first block NOT yet started) is for. Nothing is
    started past the last slot's last block, and a block waits for its
    own copies before it reads them, so none is left in flight when the
    call ends."""
    s = pl.program_id(0)
    n_slot = pl.num_programs(0)
    _, n_head, d = q_ref.shape
    rows = pages * bs
    mult = cbuf.dtype
    precision = _PRECISION if mult == jnp.float32 else None

    pools = ((c_hbm, cbuf, lambda buf: sem.at[buf]),)

    def each_page(s, i, buf, act, full=None):
        """The copies of block i of slot s into half `buf`. A full
        block's are `pages`, started in straight-line code and waited
        for ONCE, by the bytes of the whole half (a DMA semaphore counts
        bytes, and a wait needs neither the table nor a page's address);
        a last block's are its pages that hold a position <= pos, a loop
        trip each. `full` True or False: the caller knows which."""
        def some(count):
            _each_page(tab_ref, s * maxb + i * pages, count, pools, buf, act,
                       bs)

        def last():
            some(pos_ref[s] // bs + 1 - i * pages)

        def whole():
            if act == 'start':
                some(pages)
            else:
                half = cbuf.at[buf]
                pltpu.make_async_copy(half, half, sem.at[buf]).wait()

        if full is None:
            full = (i + 1) * rows <= pos_ref[s]
            pl.when(full)(whole)
            pl.when(jnp.logical_not(full))(last)
        elif full:
            whole()
        else:
            last()

    def start_next(buf):
        """Start the call's first unstarted block, if it has one, into
        half `buf`, and move `at` on: to the slot's next block or, past
        its last, the next slot's first."""
        to, i = at[1], at[2]

        @pl.when(to < n_slot)
        def _():
            each_page(to, i, buf, 'start')
            more = (i + 1) * rows <= pos_ref[to]
            at[1] = jnp.where(more, to, to + 1)
            at[2] = jnp.where(more, i + 1, 0)

    @pl.when(s == 0)
    def _():
        at[0] = at[1] = at[2] = 0
        start_next(0)
        start_next(1)

    pos = pos_ref[s]
    nblk = pos // rows + 1
    q = (q_ref[0] * scale).astype(mult)                         # [H, D]

    def two_on(full):
        """Block i + 2 of this slot: a full one, or its last."""
        return lambda i, buf: each_page(s, i + 2, buf, 'start', full=full)

    def next_slots(i, buf):
        """Behind this slot's second-to-last block: the next slot's
        first, which is where `at` takes over."""
        at[1] = s + 1
        at[2] = 0
        start_next(buf)

    def block(i, carry, full, ahead):
        """Block i out of half `buf`, with the block two on started
        (`ahead`) between its products: a full one (every block before
        the slot's last) takes its rows as they lie; the last one masks
        what lies past pos, scores and values both."""
        m, l, acc, buf = carry
        each_page(s, i, buf, 'wait', full=full)
        c = cbuf[buf]                                           # [rows, D]
        sc = lax.dot_general(
            q, c, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)                 # [H, rows]
        # the half block n - 1 has just left is n + 2's
        ahead(i, jnp.where(buf == 0, 2, buf - 1))
        if not full:
            col = lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(i * rows + col <= pos, sc, -jnp.inf)

        def weigh(p):
            v = c[:, :v_width]
            if not full:
                row = lax.broadcasted_iota(jnp.int32, v.shape, 0)
                v = jnp.where(i * rows + row <= pos, v, jnp.zeros((), mult))
            return jnp.dot(p.astype(mult), v, precision=precision,
                           preferred_element_type=jnp.float32)  # [H, dv]

        return _softmax_step(m, l, acc, sc, weigh) + (
            jnp.where(buf == 2, 0, buf + 1),)

    # the full blocks by what lies two on — a full block of this slot (the
    # sixteen descriptors in the products' own basic block), then its
    # last block, then the next slot's first: a trip each of the last two
    # where the slot has the block
    carry = _softmax_start(n_head, v_width) + (at[0],)
    for first, end, ahead in (
            (0, nblk - 3, two_on(True)),
            (jnp.maximum(nblk - 3, 0), nblk - 2, two_on(False)),
            (jnp.maximum(nblk - 2, 0), nblk - 1, next_slots)):
        carry = lax.fori_loop(
            first, end, functools.partial(block, full=True, ahead=ahead),
            carry)
    _, l, acc, buf = block(nblk - 1, carry, full=False,
                           ahead=lambda i, buf: start_next(buf))
    at[0] = buf
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def latent_paged_attention(q, cache, pos, table, *, n_head, v_width, scale,
                           interpret=False):
    """Q [S, n_head * D] float32, the latent pool [NB, BS, D], pos [S]
    int32, table [S, MAXB] int32 -> [S, n_head * v_width]: slot s's
    n_head query rows each attend positions 0 .. pos[s] of its table's
    pages, scoring over the whole row and summing its first v_width
    channels (attention in the absorbed form: the caller has folded the
    key up-projection into Q and unfolds the value one from the result).
    `refuses_latent` must give None."""
    n_slot = q.shape[0]
    n_block, bs, d = cache.shape
    maxb = table.shape[1]
    pos, table, pages = _clamped(pos, table, n_block, bs)
    rows = pages * bs
    kernel = functools.partial(_latent_kernel, v_width=int(v_width),
                               scale=scale, bs=bs, maxb=maxb, pages=pages)
    q = q.reshape(n_slot, n_head, d)
    out_shape = (n_slot, n_head, int(v_width))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_slot,),
            in_specs=[pl.BlockSpec((1, n_head, d), lambda i, *_: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n_head, int(v_width)),
                                   lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((3, rows, d), cache.dtype),
                            pltpu.SemaphoreType.DMA((3,)),
                            pltpu.SMEM((3,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        name='kv_block_latent_paged_attention',
        interpret=interpret,
    )(pos, table.reshape(-1), q, cache)
    return out.reshape(n_slot, -1)


def full_block_share(pos):
    """How often the latent kernel's full-block body engages: of the
    blocks it runs for slots at positions `pos` (the live ones), the
    share that are full — sum(pos // rows) over sum(pos // rows + 1).
    Plain numpy, for a probe to print beside the kernel's time; nothing
    that serves reads it."""
    full = np.asarray(pos, np.int64) // _BLOCK_ROWS
    return float(full.sum() / (full + 1).sum())


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
    lambda q, *_, out_width=None, **__: jax.core.ShapedArray(
        q.shape[:-1] + (out_width or q.shape[-1],), q.dtype))
_attend_p.def_impl(lambda *args, **params: jax.jit(
    functools.partial(_attend_p.bind, **params))(*args))
mlir.register_lowering(
    _attend_p, lambda ctx, *args, tpu, default, **_: mlir.lower_fun(
        tpu, multiple_results=False)(ctx, *args), platform='tpu')
mlir.register_lowering(
    _attend_p, lambda ctx, *args, tpu, default, **_: mlir.lower_fun(
        default, multiple_results=False)(ctx, *args))


def tpu_or_default(q, k_cache, v_cache, pos, table, *, tpu, default,
                   out_width=None):
    """tpu(q, k_cache, v_cache, pos, table) where the program runs on a
    TPU, default(...) anywhere else; both give [S, D] like q — or
    [S, out_width] where the values are narrower than the keys (a latent
    pool)."""
    if out_width is None:
        return _attend_p.bind(q, k_cache, v_cache, pos, table, tpu=tpu,
                              default=default)
    return _attend_p.bind(q, k_cache, v_cache, pos, table, tpu=tpu,
                          default=default, out_width=int(out_width))
