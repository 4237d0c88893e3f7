"""What the pre-norm-era decoder builders (models/olmoe.py,
models/exaone_moe.py) share when they build a decode spec for
`inference.export_decode`: the step and chunked-prefill programs' feeds
and samples, the block pools, the matrix / linear / norm / embedding
helpers, the cache ops each program uses, and the spec dict. A model
gives `DecodeSpecBuilder.build` its own `block(b, x, i, nfd, pos)` — one
decoder layer over x ([S, D] with nfd 1, [1, C, D] with 2), writing and
attending through `b.write` / `b.attend` — and `logits(b, x)`.

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
"""
from __future__ import annotations

import re

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.inference.kv_blocks import window_blocks_per_slot
from paddle_tpu.ops.decode_ops import chunk_row_program


def chunk_row_shape(chunk_progs, view_len):
    """(C, R) of the ONE row program a spec with these chunked-prefill
    programs ({size: entry}) holds beside them — its largest chunk built
    once more at [R, C], slices of R different prompts in one dispatch —
    or None: ops/decode_ops.chunk_row_program's rule, given what the
    largest chunk program's attention ops ARE (type, heads, window) and
    the `view_len` positions a slot's table spans. Every decode spec
    builder asks here; nothing is asked of a caller."""
    ops = chunk_progs[max(chunk_progs)]['program'].global_block().ops
    return chunk_row_program(
        list(chunk_progs),
        [(op.type, op.attr('n_head'), op.attr('n_kv_head'),
          op.attr('window')) for op in ops
         if re.fullmatch(r'kv_\w*attention\w*', op.type)],
        view_len)


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
                 kv_cache_dtype, weights_dtype, rms_eps, init_std,
                 window_layers=(), window=0, v_width=0):
        if kv_cache_dtype not in ('float32', 'bfloat16'):
            raise ValueError("kv_cache_dtype must be 'float32' or "
                             "'bfloat16', got %r" % (kv_cache_dtype,))
        self.S, self.T, self.D, self.BS = (int(max_slots),
                                           int(max_cache_len), int(d_model),
                                           int(block_size))
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
        self.rms_eps, self.init_std = float(rms_eps), float(init_std)
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
        self.startup = fluid.Program()
        self._io = None      # the cache ops of the program being built

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
                    0.0, self.init_std)))
        return fluid.layers.cast(x, 'float32')

    def cache_names(self, i):
        """Layer i's pools, in the order `write` takes their rows."""
        if self.v_width:
            return ['kv_c_%d' % i]
        return ['kv_k_%d' % i, 'kv_v_%d' % i]

    def _caches(self, i):
        zero = fluid.initializer.ConstantInitializer(0.0)
        nb = self.NBW if i in self.window_layers else self.NB
        return tuple(fluid.layers.create_parameter(
            [nb, self.BS, self.kv_width], self.kv_cache_dtype,
            attr=fluid.ParamAttr(name=name, trainable=False),
            default_initializer=zero) for name in self.cache_names(i))

    # -- the cache ops of the program being built ------------------------
    def write(self, i, *rows):
        """Layer i's pools with this program's rows written, one tensor
        of rows a pool (cache_names' order: K and V, or the one latent
        row): the pools, a tuple as long."""
        caches = self._caches(i)
        if len(rows) != len(caches):
            raise ValueError('layer %d keeps %d pool(s) %r, got %d tensors '
                             'of rows' % (i, len(caches),
                                          self.cache_names(i), len(rows)))
        write = self._io['write']
        kind = i in self.window_layers
        return tuple(write(c, r, kind) for c, r in zip(caches, rows))

    def attend(self, i, q, kcache, vcache, n_head, n_kv_head=None,
               scale=None):
        """Layer i's attention over its pools (inside its window, if it
        is a window layer); over a latent pool — `kcache` and `vcache`
        the same pool — the op is told where in the row the value
        lies."""
        kind = i in self.window_layers
        latent = {'v_width': self.v_width} if self.v_width else {}
        return self._io['attend'](
            q, kcache, vcache, kind, n_head=n_head, n_kv_head=n_kv_head,
            window=self.window if kind else 0, scale=scale, **latent)

    # -- the programs ----------------------------------------------------
    def build(self, block, logits):
        S, MAXB, D = self.S, self.MAXB, self.D
        L = fluid.layers
        windowed = bool(self.window_layers)

        def table_feed(name, rows):
            return L.data(name=name, shape=[rows, MAXB],
                          append_batch_size=False, dtype='int32')

        # ---- decode step: [S] slots advance one token through the pool
        step_p = fluid.Program()
        with fluid.program_guard(step_p, self.startup):
            tokens = L.data(name='tokens', shape=[S, 1],
                            append_batch_size=False, dtype='int64')
            pos = L.data(name='pos', shape=[S, 1],
                         append_batch_size=False, dtype='int32')
            tables = [table_feed('block_tables', S)]
            if windowed:
                tables.append(table_feed('window_tables', S))
            self._io = {
                'write': lambda c, kv, kind: L.kv_block_write(
                    c, kv, pos, tables[kind]),
                'attend': lambda q, kc, vc, kind, **kw:
                    L.kv_block_attention(q, kc, vc, pos, tables[kind],
                                         **kw)}
            x = self.embed(tokens)                               # [S, D]
            for i in range(self.n_layer):
                x = block(self, x, i, 1, pos)
            step_logits = logits(self, x)                        # [S, V]
        step_feeds = (['tokens', 'pos', 'block_tables']
                      + ['window_tables'] * windowed)

        # ---- chunked prefill: one CHUNK of one prompt a row; every
        # chunk size at ONE row and, where the shapes give one
        # (chunk_row_shape), the largest once more at R rows
        def chunk_program(C, R=1):
            cp = fluid.Program()
            with fluid.program_guard(cp, self.startup):
                chunk_ids = L.data(name='chunk_ids', shape=[R, C],
                                   append_batch_size=False, dtype='int64')
                start = L.data(name='start', shape=[R, 1],
                               append_batch_size=False, dtype='int32')
                clen = L.data(name='chunk_len', shape=[R, 1],
                              append_batch_size=False, dtype='int32')
                btabs = [table_feed('block_table', R)]
                if windowed:
                    btabs.append(table_feed('window_table', R))
                self._io = {
                    'write': lambda c, kv, kind: L.kv_block_chunk_write(
                        c, kv, start, btabs[kind]),
                    'attend': lambda q, kc, vc, kind, **kw:
                        L.kv_block_chunk_attention(
                            q, kc, vc, start, btabs[kind], **kw)}
                x = self.embed(chunk_ids)                       # [R, C, D]
                posv = chunk_positions(start, C, R)          # [C] / [R, C]
                for i in range(self.n_layer):
                    x = block(self, x, i, 2, posv)
                chunk_logits = last_logits(
                    x, clen, C, R, D, lambda row: logits(self, row))
            samples = {'chunk_ids': np.zeros((R, C), np.int64),
                       'start': np.zeros((R, 1), np.int32),
                       'chunk_len': np.ones((R, 1), np.int32),
                       'block_table': np.zeros((R, MAXB), np.int32)}
            if windowed:
                samples['window_table'] = np.zeros((R, MAXB), np.int32)
            return {
                'program': cp,
                'feeds': (['chunk_ids', 'start', 'chunk_len', 'block_table']
                          + ['window_table'] * windowed),
                'samples': samples,
                'fetches': [chunk_logits.name]}

        chunk_progs = {C: chunk_program(C) for C in self.chunks}
        rows = chunk_row_shape(chunk_progs, MAXB * self.BS)
        chunk_rows = (dict(chunk_program(*rows), size=rows[0], rows=rows[1])
                      if rows is not None else None)
        self._io = None

        samples = {'tokens': np.zeros((S, 1), np.int64),
                   'pos': np.zeros((S, 1), np.int32),
                   'block_tables': np.zeros((S, MAXB), np.int32)}
        if windowed:
            samples['window_tables'] = np.zeros((S, MAXB), np.int32)
        spec = {'startup': self.startup,
                'block_size': self.BS, 'num_blocks': self.NB,
                'max_blocks_per_slot': MAXB,
                'step': {'program': step_p, 'feeds': step_feeds,
                         'samples': samples,
                         'fetches': [step_logits.name]},
                'chunk': chunk_progs,
                'cache_vars': [n for i in range(self.n_layer)
                               for n in self.cache_names(i)],
                'max_slots': S, 'max_cache_len': self.T,
                'eos_id': self.eos_id, 'vocab': self.vocab,
                'kv_cache_dtype': self.kv_cache_dtype}
        if self.v_width:
            spec['cache_kind'] = 'latent'
        if chunk_rows is not None:
            spec['chunk_rows'] = chunk_rows
        if windowed:
            spec['window'] = {
                'length': self.window, 'num_blocks': self.NBW,
                'cache_vars': [n for i in sorted(self.window_layers)
                               for n in self.cache_names(i)]}
        return spec
