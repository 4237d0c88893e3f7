"""What every decoder builder of models/ shares when it builds a decode
spec for `inference.export_decode`: the step, chunked-prefill, verify and
row programs' feeds and samples, the block pools, the matrix / linear /
norm / embedding helpers, the cache ops each program uses, and the spec
dict. A model gives `DecodeSpecBuilder.build` its own
`block(b, x, i, nfd, pos)` — one decoder layer over x ([S, D] with nfd 1,
[R, C, D] with 2), writing and attending through `b.write` / `b.attend` —
and `logits(b, x)`; models/transformer.py, whose embedding is not
`b.embed`, its `embed(b, ids)` as well.

Layers come in two kinds (inference/kv_blocks.py): a full-attention
layer's cache vars live in the pool every position of a request stays
in, a sliding-window layer's (`window_layers`, `window`) in a pool of
their own, addressed through a second table feed ('window_tables' /
'window_table') from which the scheduler drops what the window has
passed. A model with full layers only has neither the pool nor the feed.

What a layer caches is said ONCE, by `DecodeSpecBuilder.cache_names`: a K
pool and a V pool, each `kv_width` wide, or — with `v_width` — ONE pool
whose `kv_width`-wide row is key and value both (a latent row: the value is
its first `v_width` channels; models/joyai_llm_flash.py). `_caches`,
`write`, `attend`, the spec's 'cache_vars' and through them the export's
state programs all follow that list.

A THIRD kind keeps no rows at all (models/qwen3_next.py): a RECURRENT
layer's (`recurrent`: {layer: {state name: (shape, dtype)}}) memory is a
state of fixed size PER SLOT — `cache_names` lists `rec_<name>_<i>`
variables [max_slots, *shape], unpaged, addressed by no table. The step's
row r is slot r and steps its state only where its table is not the idle
row's; a chunk program is told the slot its row prefills by one feed more,
'state_slot' [R, 1] (`b.state(i)` hands a layer its variables and
`b.rows` what the program being built knows of its rows). Such a spec has
no row program, and its 'recurrent' entry names the variables that no
block pair copies.

A layer may also keep NOTHING and attend ANOTHER layer's pools
(`shared_pools`: {reader layer: owner layer}; models/phi4_flash.py, whose
cross-decoder's seven layers read one layer's K/V): `cache_names` of a
reader is empty, it writes nothing, `pools` hands it the owner's pools as
the owner's `write` left them and `attend` reads them through the owner's
table (inside the owner's window, if it has one), so the spec's
'cache_vars', the pools' bytes and the block accounting see ONE layer. A
spec's 'shared_pools' names the readers. A layer that keeps nothing and
attends nothing either is named in `no_cache`.

Where the layers from `last_only_from` on keep nothing (each a reader or in
`no_cache`), a chunk program owes them to the position its logits are read
at and no other: it runs the layers below over the slice's C positions and
those from there on over the row's LAST VALID position (x [1, 1, D] at
start + chunk_len - 1, which is where their `attend` then reads the cache;
`b.at_last` hands a block what a layer below left for it, at that
position). The step is unchanged.

Three more things are a SPEC's and not a model's, and are said here once:
the int8 pool (`kv_cache_dtype='int8'`: int8 pages, one float32 scale a
cache position in `kv_ks_<i>` / `kv_vs_<i>`, the `_quant` ops), the verify
program of speculative decoding (`draft_k=K`: [S, K + 1] rows scored in
one dispatch) and `mp_shard=k` (the pools' D axis over the 'mp' mesh axis;
a model annotates its weights through `b.shard` and pins its activations
through `b.hint`). The ops behind the first two take full layers only:
beside a window, a latent pool, recurrent layers or a shared pool either
is refused here, by the kind's name; so is `mp_shard` beside a shared pool
(a reader's query heads would have to follow the owner's partition).
"""
from __future__ import annotations

import re

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.inference.kv_blocks import window_blocks_per_slot
from paddle_tpu.ops.decode_ops import chunk_row_program
from paddle_tpu.parallel import shard_parameter


def chunk_positions(start, C, R):
    """The positions `start[r] + i` of a chunk program's rows: [R, C],
    and at R = 1 the [C] expression every one-row program has held (its
    StableHLO is pinned, tests/test_decode_ids.py)."""
    L = fluid.layers
    cidx = L.range(0, C, 1, 'int32')
    if R == 1:
        return L.elementwise_add(cidx, L.reshape(start, shape=[1]))
    return L.elementwise_add(start, cidx)


def last_logits(x, clen, C, R, D, logits_fn):
    """[R, V] logits of a chunk program's rows: `logits_fn` of `x`
    [R, C, D] at each row's LAST VALID position, `clen[r] - 1` (the
    scheduler reads them only from a prompt's final chunk; a pad row's,
    chunk_len 0, are the position before its first: unread).

    EACH ROW THROUGH THE ONE-ROW EXPRESSION, from the slice of `x` on: a
    request's first token is the argmax of these and must not tell who
    admitted beside it. On a TPU an [R, D] x [D, V] product is compiled
    to another algorithm than a [1, D] one (PERF.md section 6, PR 39:
    2e-3 apart in logits of 0.7, while every K/V row the [R * C, D]
    products wrote was equal to the bit)."""
    L = fluid.layers

    def row(x_r, clen_r):       # [1, C, D], [1, 1]: the one-row program's
        return logits_fn(L.gather(
            L.reshape(x_r, shape=[C, D]),
            L.elementwise_sub(clen_r, L.fill_constant([1], 'int32', 1))))
    if R == 1:
        return row(x, clen)
    return L.concat([row(L.slice(x, axes=[0], starts=[r], ends=[r + 1]),
                         L.slice(clen, axes=[0], starts=[r], ends=[r + 1]))
                     for r in range(R)], axis=0)


class DecodeSpecBuilder(object):
    def __init__(self, vocab, d_model, kv_width, n_layer, max_slots,
                 max_cache_len, block_size, chunk_sizes, num_blocks, eos_id,
                 kv_cache_dtype, weights_dtype='float32', rms_eps=1e-6,
                 init_std=0.02, window_layers=(), window=0, v_width=0,
                 recurrent=None, embed_std=None, draft_k=0, mp_shard=0,
                 shared_pools=None, no_cache=(), last_only_from=None):
        if kv_cache_dtype not in ('float32', 'bfloat16', 'int8'):
            raise ValueError("kv_cache_dtype must be 'float32', 'bfloat16' "
                             "or 'int8', got %r" % (kv_cache_dtype,))
        self.S, self.T, self.D, self.BS = (int(max_slots),
                                           int(max_cache_len), int(d_model),
                                           int(block_size))
        if not 0 <= int(draft_k) <= self.T - 2:
            raise ValueError('draft_k must be in [0, max_cache_len - 2], '
                             'got %r' % (draft_k,))
        if not 1 <= self.BS <= self.T:
            raise ValueError('block_size must be in [1, max_cache_len]')
        self.MAXB = -(-self.T // self.BS)
        self.NB = (int(num_blocks) if num_blocks is not None
                   else self.S * self.MAXB + 1)
        if self.NB < 2:
            raise ValueError('num_blocks must be >= 2 (block 0 is the '
                             'reserved trash block)')
        self.chunks = sorted({int(c) for c in chunk_sizes})
        if not self.chunks or self.chunks[0] < 1 or self.chunks[-1] > self.T:
            raise ValueError('chunk_sizes must be in [1, max_cache_len]')
        self.vocab, self.kv_width, self.n_layer = (int(vocab), int(kv_width),
                                                   int(n_layer))
        self.eos_id = int(eos_id)
        self.kv_cache_dtype, self.weights_dtype = kv_cache_dtype, weights_dtype
        self.int8 = kv_cache_dtype == 'int8'
        self.draft_k, self.mp = int(draft_k), int(mp_shard or 0)
        self.rms_eps, self.init_std = float(rms_eps), float(init_std)
        # the embedding table's own scale (models/qwen3_next.py says why a
        # model whose mixers write O(1) updates seeds one): init_std's
        # unless given
        self.embed_std = float(init_std if embed_std is None else embed_std)
        self.v_width = int(v_width)
        if not 0 <= self.v_width <= self.kv_width:
            raise ValueError('v_width must be in [0, kv_width]')
        self.window_layers = frozenset(int(i) for i in window_layers)
        self.window = int(window) if self.window_layers else 0
        if self.window_layers and self.window < 1:
            raise ValueError('window layers need a window >= 1')
        # the window layers' pool: every slot's worst case plus trash
        self.NBW = (self.S * window_blocks_per_slot(
            self.window, self.chunks[-1], self.BS) + 1
            if self.window_layers else 0)
        # {layer: {name: (shape, dtype)}}: what a recurrent layer keeps a
        # slot, in the order `state` hands it over
        self.recurrent = {int(i): dict(states)
                          for i, states in (recurrent or {}).items()}
        if set(self.recurrent) & self.window_layers:
            raise ValueError('a layer is recurrent or a window layer')
        # {reader: owner}: layers that keep nothing and attend the pools
        # of a layer below them
        self.shared_pools = {int(i): int(o)
                             for i, o in (shared_pools or {}).items()}
        self.no_cache = frozenset(int(i) for i in no_cache)
        if self.no_cache & (set(self.recurrent) | self.window_layers
                            | set(self.shared_pools)):
            raise ValueError('a no_cache layer is of no other kind')
        self.last_only_from = (None if last_only_from is None
                               else int(last_only_from))
        if self.last_only_from is not None and not all(
                i in self.shared_pools or i in self.no_cache
                for i in range(self.last_only_from, self.n_layer)):
            raise ValueError(
                'last_only_from=%d: a layer from there on keeps a cache or '
                'a state, which every position of a slice has to write'
                % self.last_only_from)
        for i, o in self.shared_pools.items():
            if (not 0 <= o < i < self.n_layer or o in self.shared_pools
                    or o in self.recurrent or i in self.recurrent
                    or i in self.window_layers):
                raise ValueError(
                    'shared_pools: layer %d cannot attend layer %d\'s '
                    'pools (a reader lies above its owner, keeps nothing '
                    'of its own, and the owner keeps K/V rows)' % (i, o))
        # the _quant and the verify ops attend full layers' K and V pools
        for what, asked in (("kv_cache_dtype='int8'", self.int8),
                            ('draft_k=%d' % self.draft_k, self.draft_k)):
            for kind, has in (('window layers', self.window_layers),
                              ('a latent pool (v_width)', self.v_width),
                              ('recurrent layers', self.recurrent),
                              ('a shared pool', self.shared_pools),
                              ('layers at a row\'s last position only '
                               '(last_only_from)',
                               self.last_only_from is not None)):
                if asked and has:
                    raise ValueError('%s is not built beside %s: its ops '
                                     'take full layers only' % (what, kind))
        if self.mp and self.shared_pools:
            raise ValueError('mp_shard=%d is not built beside a shared '
                             'pool: a reader\'s heads would have to follow '
                             'its owner\'s partition' % self.mp)
        self.param_shardings, self.state_shardings = {}, {}
        self.startup = fluid.Program()
        self._io = None      # the cache ops of the program being built
        self.rows = None     # what that program knows of its rows

    # -- parameters -----------------------------------------------------
    def matrix(self, name, shape):
        return fluid.layers.create_parameter(
            shape, self.weights_dtype,
            attr=fluid.ParamAttr(name=name, trainable=False),
            default_initializer=fluid.initializer.NormalInitializer(
                0.0, self.init_std))

    def linear(self, x, name, d_out, nfd):
        return fluid.layers.mul(
            x, self.matrix(name, [int(x.shape[-1]), d_out]),
            x_num_col_dims=nfd)

    def norm(self, x, name):
        return fluid.layers.rms_norm(
            x, epsilon=self.rms_eps,
            param_attr=fluid.ParamAttr(
                name=name, trainable=False,
                initializer=fluid.initializer.NormalInitializer(1.0, 0.1)))

    def embed(self, ids):
        x = fluid.layers.embedding(
            ids, size=[self.vocab, self.D], dtype=self.weights_dtype,
            param_attr=fluid.ParamAttr(
                name='embed_w', trainable=False,
                initializer=fluid.initializer.NormalInitializer(
                    0.0, self.embed_std)))
        return fluid.layers.cast(x, 'float32')

    def shard(self, var, spec):
        """With `mp_shard`, `var` partitioned as `spec` over the mesh
        (parallel/api.shard_parameter), noted for export_decode."""
        if self.mp:
            shard_parameter(var, spec)
            self.param_shardings[var.name] = tuple(spec)
        return var

    def hint(self, x, spec=()):
        """Replicate (or re-shard) an activation at a contraction
        boundary; the identity without `mp_shard`."""
        return fluid.layers.sharding_hint(x, spec) if self.mp else x

    def cache_names(self, i):
        """Layer i's pools, in the order `write` takes their rows (the
        int8 pool: then each one's scales); a recurrent layer's per-slot
        states, in the order `state` gives; none for a layer that attends
        another's (`shared_pools`)."""
        if i in self.shared_pools or i in self.no_cache:
            return []
        if i in self.recurrent:
            return ['rec_%s_%d' % (name, i) for name in self.recurrent[i]]
        if self.v_width:
            return ['kv_c_%d' % i]
        return (['kv_k_%d' % i, 'kv_v_%d' % i]
                + ['kv_ks_%d' % i, 'kv_vs_%d' % i] * self.int8)

    def _caches(self, i):
        def var(name, shape, dtype, value=0.0):
            return fluid.layers.create_parameter(
                shape, dtype, attr=fluid.ParamAttr(name=name, trainable=False),
                default_initializer=fluid.initializer.ConstantInitializer(
                    value))
        names = self.cache_names(i)
        if i in self.recurrent:
            return tuple(var(name, [self.S] + [int(n) for n in shape], dtype)
                         for name, (shape, dtype) in zip(
                             names, self.recurrent[i].values()))
        nb = self.NBW if i in self.window_layers else self.NB
        pools = names[:2] if self.int8 else names
        made = [var(name, [nb, self.BS, self.kv_width], self.kv_cache_dtype)
                for name in pools]
        if self.mp:                 # the D axis over the mesh, scales whole
            for pool in made:
                self.shard(pool, (None, None, 'mp'))
                self.state_shardings[pool.name] = (None, None, 'mp')
        return tuple(made + [var(name, [nb, self.BS], 'float32', 1.0)
                             for name in names[len(pools):]])

    # -- the cache ops of the program being built ------------------------
    def _ops(self, write, write_quant, attend, attend_quant, pos, tables,
             fresh=False):
        """The write / attend layers of one kind of program (step, chunk,
        verify) bound to its position and table feeds, as `write` and
        `attend` below call them: the float pair, or over the int8 pool
        the _quant pair (`fresh`: the chunk's, which attends the rows it
        was handed beside the pages they were rounded into)."""
        if self.int8:
            return {'write': lambda c, s, kv: write_quant(c, s, kv, pos,
                                                          tables[0]),
                    'attend': lambda q, kc, ks, vc, vs, k, v, **kw:
                        attend_quant(q, kc, ks, vc, vs,
                                     *((k, v) if fresh else ()),
                                     pos, tables[0], **kw)}
        return {'write': lambda c, kv, kind: write(c, kv, pos, tables[kind]),
                'attend': lambda q, kc, vc, kind, at=None, **kw:
                    attend(q, kc, vc, pos if at is None else at,
                           tables[kind], **kw)}

    def state(self, i):
        """Recurrent layer i's per-slot state variables ([max_slots, ...],
        cache_names' order), and through `self.rows` what the program
        being built knows of its rows: {'block_tables'} in the step (row r
        is slot r, live where its table is), {'start', 'chunk_len',
        'state_slot'} in a chunk program."""
        if i not in self.recurrent:
            raise ValueError('layer %d keeps no recurrent state' % i)
        return self._caches(i)

    def pools(self, i):
        """Layer i's pools, declared before its rows are there to write:
        for a block whose pools come ahead of its own weights in the
        startup program (models/transformer.py), which draws the weights
        in the order it first met their names. A layer that attends
        another's (`shared_pools`) gets its OWNER's, which hold what the
        owner's `write` put there: the owner lies below it in every
        program."""
        return self._caches(self.shared_pools.get(i, i))

    def write(self, i, *rows):
        """Layer i's pools with this program's rows written, one tensor
        of rows a pool (cache_names' order: K and V, or the one latent
        row): the pools, a tuple as long, each as `attend` takes it (an
        int8 pool: its pages, its scales and the rows as handed in)."""
        if not self.cache_names(i):
            raise ValueError('layer %d keeps no pool' % i)
        caches = self._caches(i)
        n = len(caches) // 2 if self.int8 else len(caches)
        if len(rows) != n:
            raise ValueError('layer %d keeps %d pool(s) %r, got %d tensors '
                             'of rows' % (i, n, self.cache_names(i)[:n],
                                          len(rows)))
        write = self._io['write']
        if self.int8:
            return tuple(tuple(write(c, s, r)) + (r,)
                         for c, s, r in zip(caches[:n], caches[n:], rows))
        kind = i in self.window_layers
        return tuple(write(c, r, kind) for c, r in zip(caches, rows))

    def attend(self, i, q, kcache, vcache, n_head, n_kv_head=None,
               scale=None):
        """Layer i's attention over its pools (inside its window, if it
        is a window layer); over a latent pool — `kcache` and `vcache`
        the same pool — the op is told where in the row the value
        lies. A layer of `shared_pools` attends through its owner's table
        and window; a layer a chunk program runs at its rows' last
        position only (`last_only_from`), at that position."""
        i = self.shared_pools.get(i, i)
        kw = {'n_head': n_head, 'scale': scale}
        if self.int8:
            (kc, ks, k), (vc, vs, v) = kcache, vcache
            return self._io['attend'](q, kc, ks, vc, vs, k, v, **kw)
        # what the step's and the chunks' float ops alone take: said
        # where it is not their default
        kind = i in self.window_layers
        if n_kv_head is not None:
            kw['n_kv_head'] = n_kv_head
        if kind:
            kw['window'] = self.window
        if self.v_width:
            kw['v_width'] = self.v_width
        if self.rows.get('last_pos') is not None:
            kw['at'] = self.rows['last_pos']
        return self._io['attend'](q, kcache, vcache, kind, **kw)

    def at_last(self, x):
        """`x` [1, C, W], something a layer below left for the layers from
        `last_only_from` on, where the program being built runs them: as
        it is in the step, and in a chunk program at its row's last valid
        position, [1, 1, W] (chunk_len - 1; a pad row's, chunk_len 0, is
        position 0: unread)."""
        if self.rows.get('last_pos') is None:
            return x
        L = fluid.layers
        _, C, W = (int(n) for n in x.shape)
        at = L.clip(L.elementwise_sub(
            L.reshape(self.rows['chunk_len'], shape=[1]),
            L.fill_constant([1], 'int32', 1)), 0, C - 1)
        return L.reshape(L.gather(L.reshape(x, shape=[C, W]), at),
                         shape=[1, 1, W])

    # -- the programs ----------------------------------------------------
    def build(self, block, logits, embed=None):
        """The spec of a model whose layer i is `block(b, x, i, nfd, pos)`
        and whose head is `logits(b, x)`, over `embed(b, ids)` (`b.embed`
        unless the model has its own). Programs are opened step, chunks
        ascending, verify, row program, and each creates its parameters
        in the model's order: the startup program draws the weights in
        the order it first met them."""
        S, MAXB, D = self.S, self.MAXB, self.D
        L = fluid.layers
        windowed = bool(self.window_layers)
        embed = embed or DecodeSpecBuilder.embed

        def data(name, shape, dtype='int32'):
            return L.data(name=name, shape=shape, append_batch_size=False,
                          dtype=dtype)

        def table_feeds(name, rows):
            return [data(name % kind, [rows, MAXB])
                    for kind in ['block'] + ['window'] * windowed]

        def step_program(R, write, write_quant, attend, attend_quant):
            """[S] slots advance R tokens through the pool: the decode
            step (R = 1, x [S, D]) and the verify program (R = draft_k +
            1, x [S, R, D]), whose pad rows carry pos = MAXB * BS, the
            span guard's trash route: a pad row can never land in a
            SHARED full prefix block the way pos = max_cache_len could
            when that is not block-aligned."""
            pad_pos = MAXB * self.BS if R > 1 else 0
            sp = fluid.Program()
            with fluid.program_guard(sp, self.startup):
                tokens = data('tokens', [S, R], 'int64')
                pos = data('pos', [S, R])
                tables = table_feeds('%s_tables', S)
                self.rows = {'block_tables': tables[0]}
                self._io = self._ops(write, write_quant, attend,
                                     attend_quant, pos, tables)
                x = embed(self, tokens)
                # a pad row's pos lies past every table of max_cache_len
                # rows a block may gather from by position: an
                # out-of-range gather is NaN-filled, the pad rows' NaN
                # k / v would land in the TRASH BLOCK, and 0 * NaN in a
                # real row's masked attention would poison the batch
                at = L.clip(pos, 0, self.T - 1) if R > 1 else pos
                for i in range(self.n_layer):
                    x = block(self, x, i, 2 if R > 1 else 1, at)
                out = logits(self, x)                 # [S, V] / [S, R, V]
            samples = {'tokens': np.zeros((S, R), np.int64),
                       'pos': np.full((S, R), pad_pos, np.int32),
                       'block_tables': np.zeros((S, MAXB), np.int32)}
            if windowed:
                samples['window_tables'] = np.zeros((S, MAXB), np.int32)
            return {'program': sp,
                    'feeds': (['tokens', 'pos', 'block_tables']
                              + ['window_tables'] * windowed),
                    'samples': samples, 'fetches': [out.name]}

        # ---- chunked prefill: one CHUNK of one prompt a row; every
        # chunk size at ONE row and, where the shapes give one, the
        # largest once more at R rows
        def chunk_program(C, R=1):
            cp = fluid.Program()
            with fluid.program_guard(cp, self.startup):
                chunk_ids = data('chunk_ids', [R, C], 'int64')
                start = data('start', [R, 1])
                clen = data('chunk_len', [R, 1])
                btabs = table_feeds('%s_table', R)
                self.rows = {'start': start, 'chunk_len': clen}
                if self.recurrent:
                    self.rows['state_slot'] = data('state_slot', [R, 1])
                self._io = self._ops(
                    L.kv_block_chunk_write, L.kv_block_chunk_write_quant,
                    L.kv_block_chunk_attention,
                    L.kv_block_chunk_attention_quant, start, btabs,
                    fresh=True)
                x = embed(self, chunk_ids)                      # [R, C, D]
                posv = chunk_positions(start, C, R)          # [C] / [R, C]
                for i in range(self.n_layer):
                    if i == self.last_only_from:
                        # the rest at the row's last valid position
                        if R > 1:
                            raise NotImplementedError(
                                'last_only_from beside a row program: each '
                                'row owes its logits the one-row expression')
                        posv = L.clip(L.elementwise_sub(
                            L.elementwise_add(start, clen),
                            L.fill_constant([1], 'int32', 1)), 0, self.T - 1)
                        self.rows['last_pos'] = posv            # [1, 1]
                        x = self.at_last(x)                  # [1, 1, D]
                    x = block(self, x, i, 2, posv)
                if self.rows.get('last_pos') is not None:
                    chunk_logits = logits(self, L.reshape(x, shape=[1, D]))
                else:
                    chunk_logits = last_logits(
                        x, clen, C, R, D, lambda row: logits(self, row))
            samples = {'chunk_ids': np.zeros((R, C), np.int64),
                       'start': np.zeros((R, 1), np.int32),
                       'chunk_len': np.ones((R, 1), np.int32),
                       'block_table': np.zeros((R, MAXB), np.int32)}
            if windowed:
                samples['window_table'] = np.zeros((R, MAXB), np.int32)
            if self.recurrent:      # nobody's slot: nothing is written
                samples['state_slot'] = np.full((R, 1), S, np.int32)
            return {
                'program': cp,
                'feeds': (['chunk_ids', 'start', 'chunk_len', 'block_table']
                          + ['window_table'] * windowed
                          + ['state_slot'] * bool(self.recurrent)),
                'samples': samples,
                'fetches': [chunk_logits.name]}

        step = step_program(1, L.kv_block_write, L.kv_block_write_quant,
                            L.kv_block_attention,
                            L.kv_block_attention_quant)
        chunk_progs = {C: chunk_program(C) for C in self.chunks}
        verify = self.draft_k and step_program(
            self.draft_k + 1, L.kv_block_verify_write,
            L.kv_block_verify_write_quant, L.kv_block_verify_attention,
            L.kv_block_verify_attention_quant)
        # the row program, after everything else: (C, R) by
        # ops/decode_ops.chunk_row_program's rule, given what the largest
        # chunk program's attention ops ARE (type, heads, window) and the
        # positions a slot's table spans. A spec with recurrent layers has
        # none (ROADMAP.md Reach 3: its pad rows and its riders' states
        # are not built)
        rows = None if self.recurrent else chunk_row_program(
            list(chunk_progs),
            [(op.type, op.attr('n_head'), op.attr('n_kv_head'),
              op.attr('window'))
             for op in chunk_progs[self.chunks[-1]]['program']
             .global_block().ops
             if re.fullmatch(r'kv_\w*attention\w*', op.type)],
            MAXB * self.BS)
        chunk_rows = (dict(chunk_program(*rows), size=rows[0], rows=rows[1])
                      if rows is not None else None)
        self._io = self.rows = None

        spec = {'startup': self.startup,
                'block_size': self.BS, 'num_blocks': self.NB,
                'max_blocks_per_slot': MAXB,
                'step': step, 'chunk': chunk_progs,
                'cache_vars': [n for i in range(self.n_layer)
                               for n in self.cache_names(i)],
                'max_slots': S, 'max_cache_len': self.T,
                'eos_id': self.eos_id, 'vocab': self.vocab,
                'kv_cache_dtype': self.kv_cache_dtype}
        if self.v_width:
            spec['cache_kind'] = 'latent'
        if chunk_rows is not None:
            spec['chunk_rows'] = chunk_rows
        if verify:
            spec['verify'], spec['draft_k'] = verify, self.draft_k
        if self.mp:
            spec['mesh_axes'] = {'mp': self.mp}
            spec['param_shardings'] = dict(self.param_shardings)
            spec['state_shardings'] = dict(self.state_shardings)
        if self.shared_pools:
            spec['shared_pools'] = dict(self.shared_pools)
        if self.recurrent:
            spec['recurrent'] = {
                'cache_vars': [n for i in sorted(self.recurrent)
                               for n in self.cache_names(i)]}
        if windowed:
            spec['window'] = {
                'length': self.window, 'num_blocks': self.NBW,
                'cache_vars': [n for i in sorted(self.window_layers)
                               for n in self.cache_names(i)]}
        return spec
