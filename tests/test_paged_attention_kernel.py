"""kv_block_attention's paged Pallas kernel (ops/pallas_paged_attention.py)
against the jnp body it stands in for on a TPU — _paged_attention_body over
_block_view — on the cpu through Pallas interpret mode; the rule that picks
between them; what a cpu program and an exported artifact hold; and one
compile of the kernel for the real chip at the benchmark's width.

Every pool here is NaN wherever no slot attends: rows past pos in a slot's
last page, the trash block past its row 0, blocks nobody owns. The jnp body
is asked about the same pool with those NaN replaced (it gives a masked row
weight 0.0, and 0.0 * NaN is NaN); the kernel has to come out finite and
equal, so a row it must not use cannot have reached its output.

Tolerance: the kernel's online softmax rescales a running sum block by
block (256 positions a block) where the body takes one softmax over the
whole span, all in float32: 1e-5 relative and absolute."""
import functools
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, export_decode
from paddle_tpu.inference import decoding
from paddle_tpu.ops import decode_ops
from paddle_tpu.ops import pallas_paged_attention as ppa

TOL = dict(rtol=1e-5, atol=1e-5)


def _attrs(n_head, **more):
    """What an op lowering is handed (core/lowering.py OpCtx), as far as
    these ops ask: attrs, and the Tracer's record of chosen bodies."""
    attrs = dict(more, n_head=n_head)
    return types.SimpleNamespace(
        attr=lambda name, default=None: attrs.get(name, default),
        abstract=False,
        tracer=types.SimpleNamespace(lowered_bodies=[]))


@functools.partial(jax.jit, static_argnames=('n_head',))
def _kernel(q, kc, vc, pos, table, n_head):
    return ppa.paged_attention(q, kc, vc, pos, table, n_head=n_head,
                               scale=(q.shape[1] // n_head) ** -0.5,
                               interpret=True)


@functools.partial(jax.jit, static_argnames=('n_head',))
def _body(q, kc, vc, pos, table, n_head):
    """Today's expression, spelled out: the slot-paged body over each
    slot's gathered view."""
    kview = jax.vmap(lambda r: decode_ops._block_view(kc, r))(table)
    vview = jax.vmap(lambda r: decode_ops._block_view(vc, r))(table)
    return decode_ops._paged_attention_body(_attrs(n_head), q, kview, vview,
                                            pos)


def _lower_op(ctx, q, kc, vc, pos, table):
    """The op as a program lowers it (core/lowering.py run_op)."""
    return decode_ops._kv_block_attention(
        ctx, {'Q': [q], 'KCache': [kc], 'VCache': [vc], 'Pos': [pos],
              'BlockTable': [table]})['Out'][0]


@functools.partial(jax.jit, static_argnames=('n_head',))
def _op(q, kc, vc, pos, table, n_head):
    return _lower_op(_attrs(n_head), q, kc, vc, pos, table)


class _Pool(object):
    """A block pool with block 0 the trash block, and tables built the
    way the scheduler builds them: live columns first, trash after.
    Blocks come off a shuffled free list, so tables are never monotone."""

    def __init__(self, seed, s, bs, d, maxb, dtype):
        self.s, self.bs, self.d, self.maxb = s, bs, d, maxb
        self.dtype = dtype
        self.nb = s * maxb + 2
        self.rng = np.random.RandomState(seed)
        self.q = self.rng.randn(s, d).astype(np.float32)
        self.free = list(self.rng.permutation(np.arange(1, self.nb - 1)))
        self.nobody = self.nb - 1           # a block no slot ever owns
        self.pos = np.zeros(s, np.int32)
        self.table = np.zeros((s, maxb), np.int32)

    def live(self, slot, pos, blocks=()):
        n = pos // self.bs + 1
        blocks = list(blocks)[:n]
        blocks += [self.free.pop() for _ in range(n - len(blocks))]
        self.pos[slot] = pos
        self.table[slot] = 0
        self.table[slot, :n] = blocks
        return blocks

    def arrays(self):
        """(poisoned, clean) argument tuples: the same q, pos and table;
        K/V finite exactly on the rows some slot attends, NaN elsewhere
        in `poisoned`, random elsewhere in `clean`."""
        shape = (self.nb, self.bs, self.d)
        attended = np.zeros(shape[:2], bool)
        for s in range(self.s):
            for p in range(self.pos[s] + 1):
                attended[self.table[s, p // self.bs], p % self.bs] = True
        pools = []
        for _ in 'kv':
            clean = self.rng.randn(*shape).astype(np.float32)
            pools.append((np.where(attended[..., None], clean, np.nan),
                          clean))

        def args(which):
            return (jnp.asarray(self.q),
                    jnp.asarray(pools[0][which], self.dtype),
                    jnp.asarray(pools[1][which], self.dtype),
                    jnp.asarray(self.pos), jnp.asarray(self.table))
        return args(0), args(1)


def _all_at(pos):
    def case(p):
        for slot in range(p.s):
            p.live(slot, pos(p))
    return case


def _mixed(p):
    """Another pos in every slot: 0, the page edges, the kernel's compute
    block edge (256 rows), the last row of a full table."""
    for slot, pos in enumerate((0, p.bs - 1, p.bs, 3 * p.bs + 5, 256,
                                p.maxb * p.bs - 1)):
        p.live(slot, pos)


def _idle_beside_live(p):
    """Even slots idle — pos 0 and a table of trash blocks — between live
    ones."""
    for slot in range(1, p.s, 2):
        p.live(slot, (slot + 1) * p.bs + slot)


def _shared_prefix(p):
    """Two slots share their first blocks (a prefix-cache hit) and end in
    blocks of their own; the rest do not share."""
    first = p.live(0, 5 * p.bs + 3)
    p.live(1, 4 * p.bs, blocks=first[:3])
    for slot in range(2, p.s):
        p.live(slot, slot * p.bs + 1)


def _permuted_table(p):
    """Physical order against logical order: descending blocks, then an
    interleave of low and high ones."""
    n = 5
    ids = sorted(p.free.pop() for _ in range(2 * n))
    p.live(0, n * p.bs - 2, blocks=ids[:n][::-1])
    p.live(1, n * p.bs - 1, blocks=[ids[n + (i // 2 if i % 2 else
                                             n - 1 - i // 2)]
                                    for i in range(n)])
    for slot in range(2, p.s):
        p.live(slot, 2 * p.bs + slot)


def _foreign_columns(p):
    """Table columns past pos // BS hold other slots' live blocks and a
    block of NaN nobody owns, where the scheduler would leave trash."""
    theirs = p.live(1, 6 * p.bs)
    p.live(0, p.bs + 2)
    p.table[0, 2:2 + len(theirs)] = theirs
    p.table[0, 2 + len(theirs):] = p.nobody
    for slot in range(2, p.s):
        p.live(slot, slot)
        p.table[slot, 1:] = p.nobody


_CASES = {
    'pos_0': _all_at(lambda p: 0),
    'pos_page_last_row': _all_at(lambda p: p.bs - 1),           # 15
    'pos_page_first_row': _all_at(lambda p: p.bs),              # 16
    'pos_page_second_row': _all_at(lambda p: p.bs + 1),         # 17
    'pos_mid_table': _all_at(lambda p: p.maxb * p.bs // 2 + 3),
    'pos_compute_block_edge': _all_at(lambda p: 255),
    'pos_table_end': _all_at(lambda p: p.maxb * p.bs - 1),
    'mixed_pos': _mixed,
    'idle_beside_live': _idle_beside_live,
    'shared_prefix': _shared_prefix,
    'permuted_table': _permuted_table,
    'foreign_columns': _foreign_columns,
}
# (S, BS, D, H, MAXB, pool dtype): MAXB * BS = 320 > 256, so a full slot
# takes more than one compute block
_SHAPES = {'bs16_h8_f32': (6, 16, 128, 8, 20, np.float32),
           'bs8_h1_f32': (6, 8, 128, 1, 40, np.float32),
           'bs16_h2_bf16': (6, 16, 128, 2, 20, jnp.bfloat16)}


@pytest.mark.parametrize('case', sorted(_CASES))
@pytest.mark.parametrize('shape', sorted(_SHAPES))
def test_kernel_matches_jnp_body(shape, case):
    s, bs, d, h, maxb, dtype = _SHAPES[shape]
    pool = _Pool(7, s, bs, d, maxb, dtype)
    _CASES[case](pool)
    poisoned, clean = pool.arrays()
    got = np.asarray(_kernel(*poisoned, n_head=h))
    want = np.asarray(_body(*clean, n_head=h))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, **TOL)


def test_a_slots_output_is_its_own():
    """A slot's output with its neighbours changed — other queries, other
    positions, other tables, other pages — is bit-identical: per-slot
    math never mixes rows."""
    p = _Pool(11, 4, 16, 128, 20, np.float32)
    mine = p.live(1, 17 * p.bs + 5)          # spans two compute blocks
    p.live(0, 3)
    p.live(2, 9 * p.bs)
    _, first = p.arrays()
    p.live(0, 0)
    p.table[0] = 0
    p.live(2, 19 * p.bs + 1)
    p.live(3, 2 * p.bs)
    q = p.rng.randn(*p.q.shape).astype(np.float32)
    q[1] = p.q[1]
    p.q = q
    _, second = p.arrays()
    for which in (1, 2):                     # K, V: keep my pages
        pool = np.asarray(second[which]).copy()
        pool[mine] = np.asarray(first[which])[mine]
        second = second[:which] + (jnp.asarray(pool),) + second[which + 1:]
    a = np.asarray(_kernel(*first, n_head=4))
    b = np.asarray(_kernel(*second, n_head=4))
    assert np.array_equal(a[1], b[1])
    assert not np.array_equal(a[2], b[2])


# -- grouped K/V heads and the window (ISSUE 30) -------------------------------

@functools.partial(jax.jit, static_argnames=('n_head', 'n_kv_head', 'window'))
def _kernel_gw(q, kc, vc, pos, table, n_head, n_kv_head, window):
    return ppa.paged_attention(
        q, kc, vc, pos, table, n_head=n_head, n_kv_head=n_kv_head,
        window=window, scale=(kc.shape[2] // n_kv_head) ** -0.5,
        interpret=True)


@functools.partial(jax.jit, static_argnames=('n_head', 'n_kv_head', 'window'))
def _body_gw(q, kc, vc, pos, table, n_head, n_kv_head, window):
    return decode_ops._kv_block_attention_jnp(
        _attrs(n_head, n_kv_head=n_kv_head, window=window), q, kc, vc, pos,
        table)


# positions on both sides of a page edge (16), of the window's length
# (128: the first position that drops row 0), of the kernel's compute
# block (256) and of a window that starts inside a page
_WINDOW_POS = (0, 15, 16, 127, 128, 129, 143, 144, 255, 256, 300, 383)


@pytest.mark.parametrize('window', [0, 128])
@pytest.mark.parametrize('heads,dtype', [((8, 2), np.float32),
                                         ((8, 2), jnp.bfloat16),
                                         ((4, 1), np.float32),
                                         ((2, 2), jnp.bfloat16)])
def test_grouped_heads_and_window_match_jnp_body(heads, dtype, window):
    """n_kv_head < n_head (the query wider than the pool) and a window:
    the kernel against the jnp body, on a pool that is NaN wherever the
    slot does not attend — rows past pos, and with a window every row
    under pos - 127 and every page under the window's first, which the
    table may no longer name (the scheduler gave them back)."""
    n_head, n_kv = heads
    dh, bs, maxb = 128, 16, 24
    s, d = len(_WINDOW_POS), n_kv * dh
    rng = np.random.RandomState(13)
    nb = s * maxb + 2
    q = rng.randn(s, n_head * dh).astype(np.float32)
    pos = np.asarray(_WINDOW_POS, np.int32)
    table = rng.permutation(np.arange(1, nb - 1)).reshape(s, maxb) \
        .astype(np.int32)
    attended = np.zeros((nb, bs), bool)
    for i, p in enumerate(pos):
        lo = max(p - window + 1, 0) if window else 0
        if window:
            table[i, :lo // bs] = nb - 1          # given back: nobody's
        for j in range(lo, p + 1):
            attended[table[i, j // bs], j % bs] = True
    pools = []
    for _ in 'kv':
        clean = rng.randn(nb, bs, d).astype(np.float32)
        pools.append((np.where(attended[..., None], clean, np.nan), clean))
    args = lambda which: (jnp.asarray(q),
                          jnp.asarray(pools[0][which], dtype),
                          jnp.asarray(pools[1][which], dtype),
                          jnp.asarray(pos), jnp.asarray(table))
    kw = dict(n_head=n_head, n_kv_head=n_kv, window=window)
    got = np.asarray(_kernel_gw(*args(0), **kw))
    want = np.asarray(_body_gw(*args(1), **kw))
    assert got.shape == (s, n_head * dh) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, **TOL)


def test_window_drops_exactly_the_rows_it_passed():
    """Row pos - 128 carries a huge V: inside no window, it must not move
    the output; row pos - 127 is the window's first and must."""
    rng = np.random.RandomState(17)
    bs, d, maxb = 16, 256, 16
    q = rng.randn(1, 512).astype(np.float32)
    kc = rng.randn(maxb + 1, bs, d).astype(np.float32)
    vc = rng.randn(maxb + 1, bs, d).astype(np.float32)
    pos = np.asarray([200], np.int32)
    table = np.arange(1, maxb + 1, dtype=np.int32)[None]
    kw = dict(n_head=4, n_kv_head=2, window=128)
    base = np.asarray(_kernel_gw(q, kc, vc, pos, table, **kw))
    for row, moves in ((200 - 128, False), (200 - 127, True)):
        v2 = vc.copy()
        v2[table[0, row // bs], row % bs] += 1e4
        out = np.asarray(_kernel_gw(q, kc, jnp.asarray(v2), pos, table, **kw))
        assert (not np.array_equal(out, base)) == moves, row
        np.testing.assert_allclose(
            out, np.asarray(_body_gw(q, kc, jnp.asarray(v2), pos, table,
                                     **kw)), rtol=1e-4, atol=1e-2)


# -- a bfloat16 pool: ONE pass over the query's three pieces (ISSUE 43) -------
#
# What a bfloat16 pool stores goes to the MXU as it lies, under a float32
# query (and float32 softmax weights) split into three bfloat16 pieces: the
# float32 product, exactly. Held to the jnp body in float32 at HIGHEST to
# 2e-6 of the largest output — the online softmax's own rounding is 1e-7
# to 1e-6 here — on inputs a dropped piece shows on: every query element a
# whole 24-bit mantissa under an exponent of its own out of 2^-10 .. 2^10,
# scores spread over +-8 so that softmax weights reach down past 1e-6.
# The controls drop the last piece (16 of the 24 bits stay) and the last
# two (plain bfloat16: what PR 24 refused) and must FAIL that bound.

EXACT = 2e-6
# (n_head, n_kv_head, d_head): as many K/V heads as query heads, below and
# at bfloat16's sublane tile of 16 rows; grouped heads of 256 — 8 / 2
# below the tile, 16 / 2 (qwen3_next_80b_a3b's) at it
_PIECE_HEADS = {'h2': (2, 2, 128), 'h16': (16, 16, 128),
                'h8_kv2': (8, 2, 256), 'h16_kv2': (16, 2, 256)}


def _wide_range_case(n_head, n_kv, dh, window):
    """(q, K pool, V pool, pos, table) over a bfloat16 pool for
    _WINDOW_POS's slots, and the jnp body's answer in float32."""
    bs, maxb = 16, 24
    s, d = len(_WINDOW_POS), n_kv * dh
    rng = np.random.RandomState(43)
    nb = s * maxb + 1
    q = rng.randn(s, n_head, dh) * np.exp2(rng.randint(-10, 11,
                                                       (s, n_head, dh)))
    # a head's scores: scale * q . k, k of unit variance — spread 3
    q *= 3 * dh ** 0.5 / np.linalg.norm(q, axis=-1, keepdims=True)
    q = q.reshape(s, n_head * dh).astype(np.float32)
    assert (q.view(np.uint32) & 0xffff).any()       # not bfloat16 values
    table = rng.permutation(np.arange(1, nb)).reshape(s, maxb) \
        .astype(np.int32)
    args = (jnp.asarray(q),
            jnp.asarray(rng.randn(nb, bs, d), jnp.bfloat16),
            jnp.asarray(rng.randn(nb, bs, d), jnp.bfloat16),
            jnp.asarray(_WINDOW_POS, jnp.int32), jnp.asarray(table))
    # the last slot's head 0 over its 384 rows: weights down past 1e-6
    rows = np.asarray(decode_ops._block_view(args[1], args[4][-1])
                      .astype(jnp.float32))[:384, :dh]
    weights = np.exp(rows @ q[-1, :dh] * dh ** -0.5)
    assert weights.min() < 1e-6 * weights.max()
    kw = dict(n_head=n_head, n_kv_head=n_kv, window=window)
    with jax.default_matmul_precision('highest'):
        want = np.asarray(_body_gw(*args, **kw))
    return args, kw, want


def _relative(got, want):
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize('window', [0, 128])
@pytest.mark.parametrize('heads', sorted(_PIECE_HEADS))
def test_bfloat16_pool_kernel_is_float32_exact(heads, window):
    args, kw, want = _wide_range_case(*_PIECE_HEADS[heads], window=window)
    assert _relative(np.asarray(_kernel_gw(*args, **kw)), want) <= EXACT


@pytest.mark.parametrize('kept', [1, 2])
@pytest.mark.parametrize('heads', ['h16', 'h16_kv2'])
def test_a_dropped_piece_fails_the_float32_bound(heads, kept, monkeypatch):
    """The control: the same kernel with the query's (and the weights')
    last piece, or last two, zeroed — kept = 1 is a plain bfloat16
    operand — is told apart by the bound the kernel is held to."""
    args, kw, want = _wide_range_case(*_PIECE_HEADS[heads], window=0)
    whole = ppa._pieces

    def fewer(x):
        stack = whole(x)
        n = x.shape[0]
        return stack.at[kept * n:].set(0)
    monkeypatch.setattr(ppa, '_pieces', fewer)
    d_head = want.shape[1] // kw['n_head']
    got = np.asarray(jax.jit(lambda *a: ppa.paged_attention(
        *a, scale=d_head ** -0.5, interpret=True, **kw))(*args))
    assert _relative(got, want) > (2 if kept == 2 else 100) * EXACT


# a float32 pool's kernel is the parent's program (ISSUE 43): the text of
# paged_attention's jaxpr, the kernel's own equations inside it, hashed as
# the parent of PR 43 traced it — HIGHEST on float32 operands, token for
# token. (tests/test_decode_ids.py's StableHLO pins are of toy widths,
# which the kernel refuses: they hold the jnp body.)
_PARENT_FLOAT32_JAXPR = {
    'the_benchmarks': ((128, 16385, 16, 512, 8, 8, 0, 128),
                       '45dfc5df143a63f0'),
    'one_head_pages_of_8': ((8, 257, 8, 128, 1, 1, 0, 32),
                            'dec517288b541ff4'),
    'grouped_heads': ((8, 257, 16, 256, 4, 2, 0, 32), 'ea383203c0d2508a'),
    'grouped_heads_window': ((8, 257, 16, 256, 4, 2, 128, 32),
                             'e27b07a2f53777f5'),
}


# and a bfloat16 pool's (ISSUE 44, whose latent kernel shares _each_page,
# _load_next_then_wait and the online softmax with this one and gives
# _each_page a second form): hashed as the parent of PR 44 traced it, so
# that "the other decode cells run the parent's program" is a test
_PARENT_BFLOAT16_JAXPR = {
    'olmoes': ((32, 8193, 16, 2048, 16, 16, 0, 256), 'a5e26fd590f2a121'),
    'grouped_heads_window': ((8, 257, 16, 256, 4, 2, 128, 32),
                             '6cd624c043ec7e2d'),
}


def _jaxpr_hash(shape, dtype):
    import hashlib
    import re
    s, nb, bs, d, h, n_kv, window, maxb = shape
    jaxpr = jax.make_jaxpr(functools.partial(
        ppa.paged_attention, n_head=h, n_kv_head=n_kv, window=window,
        scale=(d // n_kv) ** -0.5))(
            _sds((s, h * (d // n_kv)), np.float32),
            _sds((nb, bs, d), dtype), _sds((nb, bs, d), dtype),
            _sds((s,), np.int32), _sds((s, maxb), np.int32))
    text = jaxpr.pretty_print(source_info=False, name_stack=False)
    # the call's own name and where it was written: this file's lines
    text = re.sub(r'name_and_src_info=[^\n]*', '', text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize('case', sorted(_PARENT_FLOAT32_JAXPR))
def test_float32_pool_kernel_is_the_parents_program(case):
    shape, want = _PARENT_FLOAT32_JAXPR[case]
    assert _jaxpr_hash(shape, np.float32) == want


@pytest.mark.parametrize('case', sorted(_PARENT_BFLOAT16_JAXPR))
def test_bfloat16_pool_kernel_is_the_parents_program(case):
    shape, want = _PARENT_BFLOAT16_JAXPR[case]
    assert _jaxpr_hash(shape, jnp.bfloat16) == want


# -- which body ---------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize('why,q,pool,n_head,want', [
    ('the benchmark configuration', _sds((128, 512), np.float32),
     _sds((16385, 16, 512), np.float32), 8, True),
    ('a bfloat16 pool', _sds((8, 128), np.float32),
     _sds((65, 16, 128), jnp.bfloat16), 2, True),
    ('the rehearsal model: D = 32 is not whole lanes',
     _sds((8, 32), np.float32), _sds((65, 8, 32), np.float32), 4, False),
    ('an int8 pool', _sds((8, 128), np.float32),
     _sds((65, 16, 128), np.int8), 2, False),
    ('a page of 4 rows is not whole sublanes', _sds((8, 128), np.float32),
     _sds((65, 4, 128), np.float32), 2, False),
    ('bfloat16 packs 16 rows', _sds((8, 128), np.float32),
     _sds((65, 8, 128), jnp.bfloat16), 2, False),
    ('heads that do not divide D', _sds((8, 128), np.float32),
     _sds((65, 8, 128), np.float32), 3, False),
])
def test_shape_rule(why, q, pool, n_head, want):
    assert ppa.supports(q, pool, pool, n_head) is want, why


@pytest.mark.parametrize('why,q,pool,n_head,n_kv,want', [
    ('k_exaone: 64 query heads over 8 K/V heads of 128',
     _sds((64, 8192), np.float32), _sds((49153, 16, 1024), jnp.bfloat16),
     64, 8, True),
    ('grouped heads of 64 lanes are not whole tiles',
     _sds((8, 256), np.float32), _sds((65, 16, 128), np.float32), 4, 2,
     False),
    ('a query that is not n_head heads of the pool\'s size',
     _sds((8, 384), np.float32), _sds((65, 16, 256), np.float32), 4, 2,
     False),
    ('n_head not a multiple of n_kv_head',
     _sds((8, 768), np.float32), _sds((65, 16, 512), np.float32), 6, 4,
     False),
    ('n_kv_head = n_head is the ungrouped rule',
     _sds((8, 128), np.float32), _sds((65, 16, 128), np.float32), 4, 4,
     True),
])
def test_shape_rule_for_grouped_heads(why, q, pool, n_head, n_kv, want):
    assert ppa.supports(q, pool, pool, n_head, n_kv) is want, why


def _op_args(d, bs=8, s=3, maxb=6):
    p = _Pool(5, s, bs, d, maxb, np.float32)
    for slot in range(s):
        p.live(slot, slot * bs + 2)
    return p.arrays()[1]


def test_cpu_program_is_todays_expression_bit_for_bit():
    """A shape the kernel takes, compiled for the cpu: the switch is in
    the jaxpr, no custom call is in the program, and the result equals
    the parent's expression bit for bit."""
    args = _op_args(128)
    ctx = _attrs(2)
    jaxpr = jax.make_jaxpr(functools.partial(_lower_op, ctx))(*args)
    assert ctx.tracer.lowered_bodies == [('kv_block_attention', 'kernel')]
    assert 'kv_block_attention' in str(jaxpr)
    text = _op.lower(*args, n_head=2).as_text()
    assert 'custom_call' not in text and 'custom-call' not in text
    assert np.array_equal(np.asarray(_op(*args, n_head=2)),
                          np.asarray(_body(*args, n_head=2)))


def test_refused_shape_takes_the_jnp_body():
    """D = 32 is not whole lanes: the op lowers straight to the jnp body,
    with no switch in between — the parent's program."""
    args = _op_args(32)
    ctx = _attrs(2)
    jaxpr = jax.make_jaxpr(functools.partial(_lower_op, ctx))(*args)
    assert ctx.tracer.lowered_bodies == [('kv_block_attention', 'jnp')]
    assert 'kv_block_attention' not in str(jaxpr)
    assert np.array_equal(np.asarray(_op(*args, n_head=2)),
                          np.asarray(_body(*args, n_head=2)))


# -- the artifact and the predictor -------------------------------------------

VOCAB, SLOTS, CACHE = 50, 4, 64


def _export(tmp, d_model, **kw):
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(
            vocab=VOCAB, d_model=d_model, n_head=2, n_layer=2, d_ff=32,
            max_slots=SLOTS, max_cache_len=CACHE, eos_id=1,
            chunk_sizes=(8, 16), **kw)
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'])
        export_decode(spec, tmp, scope=scope)
    return tmp


@pytest.fixture(scope='module')
def arts(tmp_path_factory):
    t = tmp_path_factory.mktemp('paged')
    return {'block128': _export(str(t / 'block128'), 128, block_size=8),
            'block32': _export(str(t / 'block32'), 32, block_size=8)}


def _signature(art):
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        return json.load(f)


@pytest.mark.parametrize('art,body', [('block128', 'kernel'),
                                      ('block32', 'jnp')])
def test_signature_names_the_body_each_attention_op_holds(arts, art, body):
    """... and the exported module agrees: the kernel's custom call is in
    the step or not, and never in a chunk program."""
    sig = _signature(arts[art])
    assert sig['step']['attention'] == {'kv_block_attention': {body: 2}}
    for chunk in sig['chunk'].values():
        # ... and the body its K and V writes took (ISSUE 54): chunks of
        # 8 and 16 over pages of 8 are whole pages
        assert chunk['attention'] == {
            'kv_block_chunk_attention': {'gathered': 2},
            'kv_block_chunk_write': {'pages': 4}}
    with open(os.path.join(arts[art], decoding._STEP_DIR,
                           'module.jaxexport'), 'rb') as f:
        assert (b'tpu_custom_call' in f.read()) == (body == 'kernel')
    for chunk in ('prefill_chunk_00008', 'prefill_chunk_00016'):
        with open(os.path.join(arts[art], chunk, 'module.jaxexport'),
                  'rb') as f:
            assert b'tpu_custom_call' not in f.read()


@pytest.mark.parametrize('art', ['block128', 'block32'])
def test_a_loaded_artifact_names_only_block_attention_ops(arts, art):
    """One cache layout: every attention op of every program a predictor
    loaded is a kv_block_* op."""
    with DecodingPredictor(arts[art]) as pred:
        bodies = pred.attention_bodies
    assert set(bodies) == {'step', 'chunk_8', 'chunk_16', 'chunk_16x4'}
    for by_op in bodies.values():
        assert by_op and all(op.startswith('kv_block_') for op in by_op)


def test_on_the_cpu_the_kernels_artifact_serves_the_jnp_body(arts):
    """An artifact whose step holds the kernel for a TPU runs the jnp
    body here: the predictor says so, and it serves what the parent's
    block artifact served (PR 27, where that equalled the slot tier bit
    for bit)."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(2, VOCAB, n) for n in (3, 9, 14, 6)]
    want = [[30, 4, 30, 4, 4, 30, 4],
            [30, 20, 16, 26, 11, 16, 26],
            [4, 4, 4, 4, 4, 4, 4],
            [39, 10, 26, 11, 2, 26, 11]]
    with DecodingPredictor(arts['block128']) as pb:
        assert pb.attention_bodies['step'] == {
            'kv_block_attention': {'jnp': 2}}
        assert pb.stats.snapshot()['attention'] == 'jnp'
        streams = [pb.submit(p, max_new_tokens=7) for p in prompts]
        got = [list(s.result(120)) for s in streams]
    assert got == want


# -- the real chip's compiler, without the chip --------------------------------

@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('s,nb,bs,d,h,maxb,dtype', [
    (128, 16385, 16, 512, 8, 128, np.float32),     # the benchmark's
    (8, 257, 16, 512, 8, 32, np.float32),          # chip_smoke phase B's
    (8, 257, 16, 128, 2, 32, jnp.bfloat16),
    (8, 257, 8, 128, 1, 32, np.float32),
    # olmoe_1b_7b: 16 heads of 128, as many K/V heads
    (32, 8193, 16, 2048, 16, 256, jnp.bfloat16),
    # k_exaone_236b_a23b: 64 query / 8 K/V heads, a full and a window layer
    (64, 49153, 16, 1024, (64, 8, 0), 768, jnp.bfloat16),
    (64, 2625, 16, 1024, (64, 8, 128), 768, jnp.bfloat16),
    # qwen3_next_80b_a3b: 16 query / 2 K/V heads of 256
    (128, 36865, 16, 512, (16, 2, 0), 288, jnp.bfloat16),
    # phi4_mini_flash_reasoning: differential attention's 40 padded query
    # heads over 10 [k_1 | k_2] tiles of 128, the full and a window layer
    (64, 18433, 16, 1280, (40, 10, 0), 288, jnp.bfloat16),
    (64, 4161, 16, 1280, (40, 10, 512), 288, jnp.bfloat16),
    # granite_4_0_h_micro: 32 query heads of 64 padded to 128 over the 4
    # tiles [k_2t | k_2t+1] of its 8 K/V heads
    (64, 18433, 16, 512, (32, 4, 0), 288, jnp.bfloat16)])
def test_kernel_compiles_for_v5e(one_chip, s, nb, bs, d, h, maxb, dtype):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    h, n_kv, window = h if isinstance(h, tuple) else (h, h, 0)
    fn = jax.jit(functools.partial(ppa.paged_attention, n_head=h,
                                   n_kv_head=n_kv, window=window,
                                   scale=(d // n_kv) ** -0.5))
    compiled = fn.lower(sds((s, h * (d // n_kv)), np.float32),
                        sds((nb, bs, d), dtype),
                        sds((nb, bs, d), dtype), sds((s,), np.int32),
                        sds((s, maxb), np.int32)).compile()
    assert 'tpu_custom_call' in compiled.as_text()


# kv_block_chunk_write's page write (ops/decode_ops.py, ISSUE 54;
# tests/test_decode_ops.py has the rest) lives here for the chip's compiler,
# which one test file loads: a chunk program donates its pools, and the
# write has to update them in place — a copy of phi4's full pool is 3.7 ms
# a layer and the cell runs at 16.5 of 17.2 GB
@pytest.mark.parametrize('nb,d,dtype,maxb,rows,c', [
    (18433, 1280, jnp.bfloat16, 288, 1, 512),   # phi4: layer 17's pool
    (4161, 1280, jnp.bfloat16, 288, 1, 512),    # ... a window layer's
    (4161, 1280, jnp.bfloat16, 288, 1, 32),
    (8193, 2048, jnp.bfloat16, 256, 1, 512),    # olmoe_1b_7b
    (49153, 1024, jnp.bfloat16, 768, 1, 512),   # k_exaone_236b_a23b
    (2625, 1024, jnp.bfloat16, 768, 1, 128),    # ... a window layer's
    (36865, 640, jnp.bfloat16, 288, 1, 512),    # joyai_llm_flash: latent
    (36865, 512, jnp.bfloat16, 288, 1, 512),    # qwen3_next_80b_a3b
    (18433, 512, jnp.bfloat16, 288, 1, 512),    # granite_4_0_h_micro
    (16385, 512, np.float32, 128, 4, 128),      # transformer_base_lm
    (16385, 512, np.float32, 128, 1, 32)])
def test_the_page_write_keeps_the_pool_in_place_on_v5e(one_chip, nb, d,
                                                       dtype, maxb, rows,
                                                       c):
    import re

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def write(cache, kv, start, tables):
        return decode_ops._kv_block_chunk_write(_attrs(1), {
            'Cache': [cache], 'KV': [kv], 'Start': [start],
            'BlockTable': [tables]})['Out'][0]
    text = jax.jit(write, donate_argnums=0).lower(
        sds((nb, 16, d), dtype), sds((rows, c, d), np.float32),
        sds((rows, 1), np.int32), sds((rows, maxb), np.int32)
    ).compile().as_text()
    assert re.search(r'input_output_alias=\{ \{\}: \(0, \{\}', text)
    pool = '%s[%d,16,%d]' % ('f32' if dtype is np.float32 else 'bf16', nb, d)
    # nothing but parameters, the two updates and the branch between them
    # has the pool's shape: no copy, no convert, no select over it
    made = re.findall(r'^\s*(?:ROOT )?%%\S+ = %s\S* ([\w-]+)\('
                      % re.escape(pool), text, re.M)
    assert made and set(made) <= {
        'parameter', 'scatter', 'fusion', 'bitcast', 'conditional',
        'get-tuple-element'}, made


# moe_topk_ffn's grouped matmul kernel (ops/pallas_grouped_matmul.py,
# ISSUE 41; tests/test_grouped_matmul.py has the rest) lives here for the
# chip's compiler, which one test file loads: every (rows, K, N, held
# experts) the three MoE cells' programs multiply — a decode step's pairs
# and a 512-token slice's, the gate / up product and the down product
@pytest.mark.parametrize('m,k,n,e', [
    (1024, 2048, 768, 32), (1024, 768, 2048, 32),      # joyai_llm_flash
    (4096, 2048, 768, 32), (4096, 768, 2048, 32),
    (256, 2048, 1024, 64), (256, 1024, 2048, 64),      # olmoe_1b_7b
    (4096, 2048, 1024, 64), (4096, 1024, 2048, 64),
    (512, 6144, 2048, 16), (512, 2048, 6144, 16),      # k_exaone_236b_a23b
    (4096, 6144, 2048, 16), (4096, 2048, 6144, 16)])
def test_grouped_matmul_compiles_for_v5e(one_chip, m, k, n, e):
    from paddle_tpu.ops import pallas_grouped_matmul as pgm

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows, w, sizes = (sds((m, k), jnp.bfloat16), sds((e, k, n), jnp.bfloat16),
                      sds((e,), np.int32))
    assert pgm.refuses(rows, w, sizes) is None
    # the platform switch, lowered for the described chip: its TPU body
    compiled = jax.jit(pgm.kernel_or_ragged_dot).lower(
        rows, w, sizes).compile()
    assert compiled.as_text().count('tpu_custom_call') == 1


# a Gated DeltaNet layer's rule (ops/linear_attention_ops.py, ISSUE 42;
# tests/test_qwen3_next.py has the rest) at qwen3_next_80b_a3b's widths —
# 128 slots x 32 value heads of a [128, 128] float32 state; the step's
# recurrence, and the chunked form over a 512-token and a 128-token slice
# (a batched unit-triangular solve of 64 x 64 blocks among its products) —
# for the chip's compiler, which one test file loads
@pytest.mark.parametrize('tokens', [0, 128, 512])
def test_gated_delta_rule_compiles_for_v5e(one_chip, tokens):
    import types
    from paddle_tpu.ops import linear_attention_ops as lao
    slots, hk, hv, dk, dv = 128, 16, 32, 128, 128
    ctx = types.SimpleNamespace(
        attr=lambda n, d=None: {'n_key_head': hk, 'n_value_head': hv}.get(
            n, d))

    def sds(shape, dt=np.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lead = (1, tokens) if tokens else (slots,)
    ins = {'Q': sds(lead + (hk * dk,)), 'K': sds(lead + (hk * dk,)),
           'V': sds(lead + (hv * dv,)), 'A': sds(lead + (hv,)),
           'B': sds(lead + (hv,)), 'ALog': sds((hv,)), 'DtBias': sds((hv,))}
    if tokens:
        ins.update({n: sds((1, 1), np.int32)
                    for n in ('Start', 'ChunkLen', 'StateSlot')})
        op = lao._gated_delta_chunk
    else:
        # with a Tracer the op takes the primitive whose TPU rule is the
        # Pallas step kernel (ops/pallas_delta_rule.py)
        ins['BlockTable'] = sds((slots, 288), np.int32)
        ctx.tracer = types.SimpleNamespace(lowered_bodies=[])
        op = lao._gated_delta_step

    def fn(ins, state):
        out = op(ctx, dict({k: [v] for k, v in ins.items()}, State=[state]))
        return out['Out'][0], out['StateOut'][0]
    compiled = jax.jit(fn, donate_argnums=1).lower(
        ins, sds((slots, hv, dk, dv))).compile()
    # the state is updated in place: no second copy of its 268 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 160e6
    if not tokens:
        assert ctx.tracer.lowered_bodies == [('gated_delta_step', 'kernel')]
        assert compiled.as_text().count('tpu_custom_call') == 1


# the chunked rule as a program exported for a TPU lowers it (ISSUE 48): with
# a Tracer the op takes the primitive whose TPU rule is the Pallas chunk
# kernel (ops/pallas_delta_chunk.py), at the cell's widths and both slices
@pytest.mark.parametrize('tokens', [128, 512])
def test_gated_delta_chunk_kernel_compiles_for_v5e(one_chip, tokens):
    import types
    from paddle_tpu.ops import linear_attention_ops as lao
    slots, hk, hv, dk, dv = 128, 16, 32, 128, 128
    ctx = types.SimpleNamespace(
        attr=lambda n, d=None: {'n_key_head': hk, 'n_value_head': hv}.get(
            n, d), tracer=types.SimpleNamespace(lowered_bodies=[]))

    def sds(shape, dt=np.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lead = (1, tokens)
    ins = {'Q': sds(lead + (hk * dk,)), 'K': sds(lead + (hk * dk,)),
           'V': sds(lead + (hv * dv,)), 'A': sds(lead + (hv,)),
           'B': sds(lead + (hv,)), 'ALog': sds((hv,)), 'DtBias': sds((hv,))}
    ins.update({n: sds((1, 1), np.int32)
                for n in ('Start', 'ChunkLen', 'StateSlot')})

    def fn(ins, state):
        out = lao._gated_delta_chunk(
            ctx, dict({k: [v] for k, v in ins.items()}, State=[state]))
        return out['Out'][0], out['StateOut'][0]
    compiled = jax.jit(fn, donate_argnums=1).lower(
        ins, sds((slots, hv, dk, dv))).compile()
    assert ctx.tracer.lowered_bodies == [('gated_delta_chunk', 'kernel')]
    assert compiled.as_text().count('tpu_custom_call') == 1
    # the state is updated in place, and nothing of the jnp body's
    # [sub-chunks, rows, heads, n, n] temporaries is left
    assert compiled.memory_analysis().temp_size_in_bytes < 40e6


# the two state-space op families (ops/state_space_ops.py; the rest is in
# tests/test_phi4_flash.py, ISSUE 47, and tests/test_granite_hybrid.py, ISSUE
# 55) at their configurations' widths — the step, and the chunk form over a
# 512-token and a 128-token slice — for the chip's compiler, which one test
# file loads
def _compile_scan_for_v5e(one_chip, step, chunk, tokens, wide, narrow,
                          state, attrs=()):
    """The compiled module of a scan op at 64 slots: `step` for tokens 0,
    `chunk` over one row of `tokens`; {input: trailing shape} `wide` a
    token, `narrow` a layer, the per-slot `state` donated."""
    import types
    attrs = dict(attrs)
    ctx = types.SimpleNamespace(attr=lambda name, d=None: attrs.get(name, d))

    def sds(shape, dt=np.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    lead = (1, tokens) if tokens else (64,)
    ins = {k: sds(lead + shape) for k, shape in wide.items()}
    ins.update({k: sds(shape) for k, shape in narrow.items()})
    if tokens:
        ins.update({k: sds((1, 1), np.int32)
                    for k in ('Start', 'ChunkLen', 'StateSlot')})
    else:
        ins['BlockTable'] = sds((64, 288), np.int32)
    op = chunk if tokens else step

    def fn(ins, state):
        out = op(ctx, dict({k: [v] for k, v in ins.items()}, State=[state]))
        return out['Out'][0], out['StateOut'][0]
    return jax.jit(fn, donate_argnums=1).lower(
        ins, sds((64,) + state)).compile()


@pytest.mark.parametrize('tokens', [0, 128, 512])
def test_selective_scan_compiles_for_v5e(one_chip, tokens):
    """phi4_mini_flash_reasoning's Mamba-1 scan: 64 slots x [16, 5120]
    float32 of state."""
    from paddle_tpu.ops import state_space_ops as sso
    di, n = 5120, 16
    compiled = _compile_scan_for_v5e(
        one_chip, sso._selective_scan_step, sso._selective_scan_chunk,
        tokens, {'X': (di,), 'Dt': (di,), 'B': (n,), 'C': (n,)},
        {'ALog': (n, di), 'DtBias': (di,), 'D': (di,)}, (n, di))
    # the state (21 MB) is updated in place, and a slice's discretised
    # terms ([512, 5120, 16] float32: 168 MB) are never built
    assert compiled.memory_analysis().temp_size_in_bytes < 64e6


@pytest.mark.parametrize('tokens', [0, 128, 512])
def test_ssd_compiles_for_v5e(one_chip, tokens):
    """granite_4_0_h_micro's Mamba-2 / SSD ops: 64 slots x [64, 64, 128]
    float32 of state, 134 MB a layer; the chunk's matrix form in two
    sub-chunks of 256."""
    from paddle_tpu.ops import state_space_ops as sso
    heads, p, n = 64, 64, 128
    compiled = _compile_scan_for_v5e(
        one_chip, sso._ssd_step, sso._ssd_chunk, tokens,
        {'X': (heads * p,), 'Dt': (heads,), 'B': (n,), 'C': (n,)},
        {'ALog': (heads,), 'DtBias': (heads,), 'D': (heads,)},
        (heads, p, n), attrs={'n_head': heads, 'sub_chunk': 256})
    # the states are updated in place: no second copy, and a sub-chunk's
    # [64, 256, 256] decay (16.8 MB) is the largest temporary
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 64 * heads * p * n * 4
    assert mem.temp_size_in_bytes < 64e6
