"""Transformer-base NMT built on paddle_tpu layers.

Model math follows the reference benchmark's Transformer
(benchmark/fluid/models/transformer.py -> its transformer_model: 6+6
encoder/decoder layers, d_model 512, 8 heads, ffn 2048, post-LN residual
blocks, sinusoid position encoding), expressed through this framework's
fc/matmul/softmax/layer_norm layers. Attention is the nets-style
scaled-dot-product composed from reshape/transpose/matmul — XLA fuses the
whole block onto the MXU; bf16 AMP applies via contrib.mixed_precision.
"""
from __future__ import annotations

import paddle_tpu as fluid


def _split_heads(x, n_head, d_model, seq):
    # [B, S, D] -> [B, H, S, D/H]
    x = fluid.layers.reshape(x, shape=[-1, seq, n_head, d_model // n_head])
    return fluid.layers.transpose(x, perm=[0, 2, 1, 3])


def _merge_heads(x, n_head, d_model, seq):
    x = fluid.layers.transpose(x, perm=[0, 2, 1, 3])
    return fluid.layers.reshape(x, shape=[-1, seq, d_model])


def multi_head_attention(q_in, kv_in, n_head, d_model, q_len, kv_len,
                         mask=None, dropout=0.0, causal=False):
    q = fluid.layers.fc(q_in, size=d_model, num_flatten_dims=2,
                        bias_attr=False)
    k = fluid.layers.fc(kv_in, size=d_model, num_flatten_dims=2,
                        bias_attr=False)
    v = fluid.layers.fc(kv_in, size=d_model, num_flatten_dims=2,
                        bias_attr=False)
    q = _split_heads(q, n_head, d_model, q_len)
    k = _split_heads(k, n_head, d_model, kv_len)
    v = _split_heads(v, n_head, d_model, kv_len)
    scale = (d_model // n_head) ** -0.5
    if dropout == 0.0 and (mask is None or causal):
        # fused attention op: the lowering auto-selects the tuned Pallas
        # flash kernel where measured to win on this chip or where O(S^2)
        # score materialization can't fit, else the XLA composition
        # (ops/nn_ops.py _flash_policy; PERF_NOTES.md has the sweep).
        # Attention-weight dropout has no fused kernel, so training with
        # dropout>0 stays on the composition below.
        ctxv = fluid.layers.fused_multihead_attention(q, k, v,
                                                      causal=causal,
                                                      scale=scale)
    else:
        scores = fluid.layers.matmul(q, k, transpose_y=True, alpha=scale)
        if mask is not None:
            scores = scores + mask  # [S, S] broadcast over [B, H, S, S]
        elif causal:
            # causal must mean the same thing on BOTH paths
            pos = fluid.layers.range(0, q_len, 1, 'int32')
            row = fluid.layers.reshape(pos, shape=[q_len, 1])
            col = fluid.layers.reshape(pos, shape=[1, q_len])
            above = fluid.layers.cast(
                fluid.layers.greater_than(col, row), 'float32')
            scores = scores + above * -1e9
        weights = fluid.layers.softmax(scores)
        if dropout:
            weights = fluid.layers.dropout(
                weights, dropout_prob=dropout,
                dropout_implementation='upscale_in_train')
        ctxv = fluid.layers.matmul(weights, v)
    out = _merge_heads(ctxv, n_head, d_model, q_len)
    return fluid.layers.fc(out, size=d_model, num_flatten_dims=2,
                           bias_attr=False)


def _residual_ln(x, sub_out, dropout=0.0):
    if dropout:
        sub_out = fluid.layers.dropout(
            sub_out, dropout_prob=dropout,
            dropout_implementation='upscale_in_train')
    return fluid.layers.layer_norm(x + sub_out, begin_norm_axis=2)


def ffn(x, d_model, d_ff):
    h = fluid.layers.fc(x, size=d_ff, num_flatten_dims=2, act='relu')
    return fluid.layers.fc(h, size=d_model, num_flatten_dims=2)


def encoder_layer(x, n_head, d_model, d_ff, seq, dropout,
                  attn_dropout=None):
    ad = dropout if attn_dropout is None else attn_dropout
    x = _residual_ln(x, multi_head_attention(x, x, n_head, d_model, seq, seq,
                                             dropout=ad), dropout)
    return _residual_ln(x, ffn(x, d_model, d_ff), dropout)


def decoder_layer(x, enc_out, n_head, d_model, d_ff, trg_len, src_len,
                  causal_mask, dropout, attn_dropout=None):
    ad = dropout if attn_dropout is None else attn_dropout
    x = _residual_ln(x, multi_head_attention(x, x, n_head, d_model, trg_len,
                                             trg_len, mask=causal_mask,
                                             dropout=ad, causal=True),
                     dropout)
    x = _residual_ln(x, multi_head_attention(x, enc_out, n_head, d_model,
                                             trg_len, src_len,
                                             dropout=ad), dropout)
    return _residual_ln(x, ffn(x, d_model, d_ff), dropout)


def _embed(ids, vocab, d_model, seq, name):
    emb = fluid.layers.embedding(
        ids, size=[vocab, d_model],
        param_attr=fluid.ParamAttr(
            name=name, initializer=fluid.initializer.Normal(
                0., d_model ** -0.5)))
    emb = fluid.layers.reshape(emb, shape=[-1, seq, d_model])
    emb = emb * (d_model ** 0.5)
    return fluid.layers.add_position_encoding(emb, alpha=1.0, beta=1.0)


def build_transformer_train(src_vocab=32000, trg_vocab=32000, max_len=256,
                            d_model=512, d_ff=2048, n_head=8, n_layer=6,
                            dropout=0.1, attn_dropout=None, lr=None,
                            checkpoints=None):
    """Returns (feeds, avg_loss, train_flops_per_token).

    feeds = [(name, per-sample shape, dtype)]; sequences arrive padded to
    max_len (the bench feeds full-length synthetic batches — variable-length
    data rides the bucketing reader instead).

    checkpoints: activation rematerialization (ISSUE 18). True wraps
    each encoder/decoder layer's output as a recompute boundary, 'auto'
    lets the pass pick √N segments, None trains without recompute.
    """
    S = max_len
    src = fluid.layers.data(name='src_ids', shape=[S], dtype='int64')
    trg = fluid.layers.data(name='trg_ids', shape=[S], dtype='int64')
    lbl = fluid.layers.data(name='lbl_ids', shape=[S], dtype='int64')

    # causal mask [S, S] built in-graph: -1e9 strictly above the diagonal
    pos = fluid.layers.range(0, S, 1, 'int32')
    row = fluid.layers.reshape(pos, shape=[S, 1])
    col = fluid.layers.reshape(pos, shape=[1, S])
    above = fluid.layers.cast(fluid.layers.greater_than(col, row), 'float32')
    causal_mask = above * -1e9

    enc = _embed(src, src_vocab, d_model, S, 'src_emb')
    if dropout:
        enc = fluid.layers.dropout(enc, dropout_prob=dropout,
                                   dropout_implementation='upscale_in_train')
    layer_outs = []
    for _ in range(n_layer):
        enc = encoder_layer(enc, n_head, d_model, d_ff, S, dropout,
                            attn_dropout=attn_dropout)
        layer_outs.append(enc)

    dec = _embed(trg, trg_vocab, d_model, S, 'trg_emb')
    if dropout:
        dec = fluid.layers.dropout(dec, dropout_prob=dropout,
                                   dropout_implementation='upscale_in_train')
    for _ in range(n_layer):
        dec = decoder_layer(dec, enc, n_head, d_model, d_ff, S, S,
                            causal_mask, dropout,
                            attn_dropout=attn_dropout)
        layer_outs.append(dec)

    logits = fluid.layers.fc(dec, size=trg_vocab, num_flatten_dims=2,
                             bias_attr=False)
    logits2d = fluid.layers.reshape(logits, shape=[-1, trg_vocab])
    lbl2d = fluid.layers.reshape(lbl, shape=[-1, 1])
    loss = fluid.layers.softmax_with_cross_entropy(logits=logits2d,
                                                   label=lbl2d)
    avg_loss = fluid.layers.mean(loss)

    if lr is None:
        # reference schedule: learning_rate(2.0) x noam(d_model, warmup)
        lr = fluid.layers.noam_decay(d_model, 4000) * 2.0
    opt = fluid.optimizer.Adam(learning_rate=lr, beta1=0.9, beta2=0.997,
                               epsilon=1e-9)
    cps = None
    if checkpoints == 'auto':
        cps = 'auto'
    elif checkpoints:
        cps = checkpoints if isinstance(checkpoints, (list, tuple)) \
            else layer_outs
    opt.minimize(avg_loss, checkpoints=cps)

    # analytic training FLOPs per TARGET token (fwd 2*MACs, train = 3x):
    # enc layer 4d^2+2*d*dff, dec layer 8d^2+2*d*dff, attention scores
    # 2*S*d per token per attention (12 self + 6 cross at n_layer=6),
    # logits d*V once
    enc_macs = n_layer * (4 * d_model ** 2 + 2 * d_model * d_ff)
    dec_macs = n_layer * (8 * d_model ** 2 + 2 * d_model * d_ff)
    attn_macs = (3 * n_layer) * 2 * S * d_model
    logit_macs = d_model * trg_vocab
    flops_per_tok = 3 * 2 * (enc_macs + dec_macs + attn_macs + logit_macs)

    feeds = [('src_ids', (S,), 'int64'), ('trg_ids', (S,), 'int64'),
             ('lbl_ids', (S,), 'int64')]
    return feeds, avg_loss, flops_per_tok


# ---------------------------------------------------------------------------
# Continuous-decode serving programs (ISSUE 8): a decoder-only LM expressed
# as the fixed-shape programs the decode-serving tier compiles once and
# reuses forever — a chunked-PREFILL program per chunk size (one slice of
# one request's prompt, K/V rows written through its block table into the
# paged pool) and a DECODE-STEP program (max_slots requests, one token per
# slot per step), both attending the cache through ops/decode_ops.py. All
# parameters are shared by name across every program, the reference's
# train-program/infer-program pattern (tests/test_book.py NMT).
# ---------------------------------------------------------------------------

def _pe_table(max_len, d_model):
    """Sinusoid position-encoding table [max_len, d_model] precomputed in
    float32 host numpy: chunked prefill and decode step gather from the
    SAME table by position, so positional values agree bit-for-bit
    across the programs."""
    import numpy as np
    half = d_model // 2
    pos = np.arange(max_len, dtype=np.float32)[:, None]
    div = np.power(np.float32(10000.0),
                   np.arange(half, dtype=np.float32) / np.float32(half))
    return np.concatenate([np.sin(pos / div), np.cos(pos / div)],
                          axis=1).astype(np.float32)


def build_decode_spec(vocab=67, d_model=32, n_head=4, n_layer=2, d_ff=64,
                      max_slots=8, max_cache_len=48, chunk_sizes=(8, 16),
                      eos_id=1, kv_cache_dtype='float32', block_size=16,
                      num_blocks=None, mp_shard=0, draft_k=0):
    """Build the decode-serving program set for a decoder-only transformer
    LM. Returns the spec dict `inference.export_decode` consumes:

      {'startup': Program,           # run ONCE to init shared params
       'step':    {'program', 'feeds', 'samples', 'fetches'},
       'chunk':   {chunk_size: {'program', 'feeds', 'samples', 'fetches'}},
       'chunk_rows': {..., 'size': C, 'rows': R},   # where the shapes
                                     # give one (decode_spec.chunk_row_shape)
       'cache_vars': [names],        # the KV pool [NB, block_size, d_model]
       'block_size', 'num_blocks', 'max_blocks_per_slot',
       'max_slots', 'max_cache_len', 'eos_id', 'vocab'}

    The KV cache is per-layer persistable state shared by name between the
    programs: a BLOCK POOL [num_blocks, block_size, D] addressed through
    per-slot block tables the serving tier feeds each dispatch
    (inference/kv_blocks.py owns refcounts/CoW/prefix sharing);
    export_decode threads it as donated input->output state and passes
    every other parameter as an argument. Prefill is CHUNKED: one chunk
    program per size in `chunk_sizes` admits a prompt in fixed slices
    interleaved with decode steps (attending earlier chunks / shared
    prefix blocks through the table). num_blocks defaults to full
    capacity (max_slots * ceil(max_cache_len / block_size) + 1 trash
    block); size it SMALLER to oversubscribe on prefix sharing.

    kv_cache_dtype='bfloat16': the float pool holds bfloat16 rows (K and
    V round once, at the write; attention reads them as they are) —
    half the bytes of the float32 pool, and what the TPU's paged kernel
    reads with block_size % 16 == 0.

    kv_cache_dtype='int8' (ISSUE 11): the pool stores int8 rows with one
    f32 scale per cache position (kv_ks_<i>/kv_vs_<i> [num_blocks,
    block_size] ride the cache_vars state next to the int8 pages) and
    the programs use the quantized write/attention ops
    (ops/decode_ops.py) — ~(1+4/D)/2 the cache bytes of the f32 form,
    so the same cache-HBM budget holds ~2x the slots.

    mp_shard=k (ISSUE 13): annotate every weight (and the D axis of the
    KV block pool) for k-way tensor-model sharding over the 'mp' mesh
    axis (parallel/api.shard_parameter) and insert sharding_hint
    replicate points at contraction boundaries so every reduction stays
    full-width — export_decode traces the programs over the mesh and
    the sharded artifact's transcripts are BIT-IDENTICAL to the
    single-chip one. Requires k | n_head, k | d_ff.

    draft_k=K (ISSUE 17): add a third, VERIFY program for speculative
    decoding — [S, K+1] token/position rows score in ONE dispatch over
    the same paged cache (KV written speculatively for every fed row,
    row i attending j <= pos[s, i], so row i's logits match the plain
    step's at the same accepted prefix). The verify program is built
    LAST and shares every weight by name, so the step/chunk programs
    (and the weights the per-op rng streams draw) are byte-for-byte
    what a draft_k=0 build produces. The serving tier drafts host-side
    and rolls rejected rows back (inference/decoding.py).
    """
    import numpy as np
    from paddle_tpu.parallel import shard_parameter
    from .decode_spec import (chunk_positions, chunk_row_shape,
                              last_logits)
    PA = fluid.ParamAttr
    if kv_cache_dtype not in ('float32', 'bfloat16', 'int8'):
        raise ValueError("kv_cache_dtype must be 'float32', 'bfloat16' "
                         "or 'int8', got %r" % (kv_cache_dtype,))
    if not 0 <= int(draft_k) <= int(max_cache_len) - 2:
        raise ValueError('draft_k must be in [0, max_cache_len - 2], '
                         'got %r' % (draft_k,))
    kv_int8 = kv_cache_dtype == 'int8'
    S, T, D = int(max_slots), int(max_cache_len), int(d_model)
    BS = int(block_size)
    if D % n_head or D % 2:
        raise ValueError("d_model must be even and divisible by n_head")
    if not 1 <= BS <= T:
        raise ValueError("block_size must be in [1, max_cache_len]")
    MAXB = -(-T // BS)                     # logical blocks per slot
    NB = int(num_blocks) if num_blocks is not None else S * MAXB + 1
    if NB < 2:
        raise ValueError("num_blocks must be >= 2 (block 0 is the "
                         "reserved trash block)")
    chunks = sorted({int(c) for c in chunk_sizes})
    if not chunks or chunks[0] < 1 or chunks[-1] > T:
        raise ValueError("chunk_sizes must be in [1, max_cache_len]")
    mp = int(mp_shard or 0)
    if mp:
        if n_head % mp or d_ff % mp:
            raise ValueError(
                'mp_shard=%d must divide n_head=%d and d_ff=%d (the D '
                'axis shards by whole head groups)' % (mp, n_head, d_ff))
    startup = fluid.Program()
    pe = _pe_table(T, D)
    cache_vars = []
    for i in range(n_layer):
        cache_vars += ['kv_k_%d' % i, 'kv_v_%d' % i]
        if kv_int8:
            cache_vars += ['kv_ks_%d' % i, 'kv_vs_%d' % i]

    # name -> partition spec for export_decode (collected from the
    # shard_parameter annotations as each program is built)
    param_shardings = {}
    state_shardings = {}

    def _shard(var, spec):
        if mp:
            shard_parameter(var, spec)
            param_shardings[var.name] = tuple(spec)
        return var

    def _hint(x, spec=()):
        """Replicate (or re-shard) an activation at a contraction
        boundary; identity when unsharded."""
        return fluid.layers.sharding_hint(x, spec) if mp else x

    def const_param(name, shape, init, dtype='float32', spec=None):
        p = fluid.layers.create_parameter(
            shape, dtype, attr=PA(name=name, trainable=False),
            default_initializer=init)
        if spec is not None:
            _shard(p, spec)
        return p

    def caches(i):
        zero = fluid.initializer.ConstantInitializer(0.0)
        dt = 'int8' if kv_int8 else kv_cache_dtype
        cspec = (None, None, 'mp') if mp else None
        k = const_param('kv_k_%d' % i, [NB, BS, D], zero, dt, spec=cspec)
        v = const_param('kv_v_%d' % i, [NB, BS, D], zero, dt, spec=cspec)
        if mp:
            state_shardings['kv_k_%d' % i] = (None, None, 'mp')
            state_shardings['kv_v_%d' % i] = (None, None, 'mp')
        if not kv_int8:
            return k, v
        one = fluid.initializer.ConstantInitializer(1.0)
        return (k, v, const_param('kv_ks_%d' % i, [NB, BS], one),
                const_param('kv_vs_%d' % i, [NB, BS], one))

    def pe_param():
        return const_param(
            'pos_enc_w', [T, D], fluid.initializer.NumpyArrayInitializer(pe))

    def qkv(x, i, nfd):
        def proj(tag):
            w_attr = PA(name='l%d_%s_w' % (i, tag))
            out = fluid.layers.fc(x, D, num_flatten_dims=nfd,
                                  param_attr=w_attr, bias_attr=False)
            return out
        q, k, v = proj('q'), proj('k'), proj('v')
        if mp:
            gb = x.block.program.global_block()
            for tag in ('q', 'k', 'v'):
                _shard(gb.var('l%d_%s_w' % (i, tag)), (None, 'mp'))
        return q, k, v

    def block_tail(x, a, i, nfd):
        """Shared residual+LN+FFN tail; `nfd` = 1 (step, [S, D]) or 2
        (chunk / verify, [1, C, D] / [S, R, D]) — same [D]-shaped params
        either way. The mp replicate hints: attention context gathers
        before the o projection, h before f2, and each projection output
        before its LN — every contraction stays full-width."""
        a = _hint(a)
        o = fluid.layers.fc(a, D, num_flatten_dims=nfd,
                            param_attr=PA(name='l%d_o_w' % i),
                            bias_attr=False)
        if mp:
            _shard(a.block.program.global_block().var('l%d_o_w' % i),
                   (None, 'mp'))
        o = _hint(o)
        x = fluid.layers.layer_norm(
            x + o, begin_norm_axis=nfd, param_attr=PA(name='l%d_ln1_s' % i),
            bias_attr=PA(name='l%d_ln1_b' % i))
        # pin the LN output replicated too: left unconstrained, GSPMD may
        # shard it over 'mp' and the next projection's contraction turns
        # into a partial-sum all-reduce — reordered accumulation, bit
        # drift vs the single-chip artifact
        x = _hint(x)
        h = fluid.layers.fc(x, d_ff, num_flatten_dims=nfd, act='relu',
                            param_attr=PA(name='l%d_f1_w' % i),
                            bias_attr=PA(name='l%d_f1_b' % i))
        if mp:
            gb = x.block.program.global_block()
            _shard(gb.var('l%d_f1_w' % i), (None, 'mp'))
            _shard(gb.var('l%d_f1_b' % i), ('mp',))
        h = _hint(h)
        f = fluid.layers.fc(h, D, num_flatten_dims=nfd,
                            param_attr=PA(name='l%d_f2_w' % i),
                            bias_attr=PA(name='l%d_f2_b' % i))
        if mp:
            gb = h.block.program.global_block()
            _shard(gb.var('l%d_f2_w' % i), (None, 'mp'))
        f = _hint(f)
        return _hint(fluid.layers.layer_norm(
            x + f, begin_norm_axis=nfd, param_attr=PA(name='l%d_ln2_s' % i),
            bias_attr=PA(name='l%d_ln2_b' % i)))

    def embed(ids):
        x = fluid.layers.embedding(ids, size=[vocab, D],
                                   param_attr=PA(name='dec_emb_w'))
        if mp:
            _shard(x.block.program.global_block().var('dec_emb_w'),
                   (None, 'mp'))
        return fluid.layers.scale(x, scale=float(D ** 0.5))

    def out_logits(x, nfd=1):
        lg = fluid.layers.fc(x, vocab, num_flatten_dims=nfd,
                             param_attr=PA(name='out_w'), bias_attr=False)
        if mp:
            _shard(x.block.program.global_block().var('out_w'),
                   (None, 'mp'))
        return _hint(lg)

    # ---- decode-step program: [S] slots advance one token through the
    # block pool (tables fed from the host scheduler) ----------------------
    step_p = fluid.Program()
    with fluid.program_guard(step_p, startup):
        tokens = fluid.layers.data(name='tokens', shape=[S, 1],
                                   append_batch_size=False, dtype='int64')
        pos = fluid.layers.data(name='pos', shape=[S, 1],
                                append_batch_size=False, dtype='int32')
        tables = fluid.layers.data(name='block_tables', shape=[S, MAXB],
                                   append_batch_size=False, dtype='int32')
        table = pe_param()
        x = embed(tokens)                                       # [S, D]
        x = fluid.layers.elementwise_add(x,
                                         fluid.layers.gather(table, pos))
        x = _hint(x)
        for i in range(n_layer):
            if kv_int8:
                kcache, vcache, kscale, vscale = caches(i)
                q, k, v = qkv(x, i, 1)
                kcache, kscale = fluid.layers.kv_block_write_quant(
                    kcache, kscale, k, pos, tables)
                vcache, vscale = fluid.layers.kv_block_write_quant(
                    vcache, vscale, v, pos, tables)
                a = fluid.layers.kv_block_attention_quant(
                    q, kcache, kscale, vcache, vscale, pos, tables,
                    n_head)
            else:
                kcache, vcache = caches(i)
                q, k, v = qkv(x, i, 1)
                kcache = fluid.layers.kv_block_write(kcache, k, pos,
                                                     tables)
                vcache = fluid.layers.kv_block_write(vcache, v, pos,
                                                     tables)
                a = fluid.layers.kv_block_attention(q, kcache, vcache,
                                                    pos, tables, n_head)
            x = block_tail(x, a, i, 1)
        step_logits = out_logits(x)                             # [S, V]

    # ---- chunked-prefill programs: one CHUNK of one prompt a row; every
    # chunk size at ONE row, and where the shapes allow it (chunk_rows,
    # below) the largest once more at R rows: slices of R different
    # prompts in one dispatch ------------------------------------------
    def chunk_program(C, R=1):
        cp = fluid.Program()
        with fluid.program_guard(cp, startup):
            chunk_ids = fluid.layers.data(name='chunk_ids', shape=[R, C],
                                          append_batch_size=False,
                                          dtype='int64')
            start = fluid.layers.data(name='start', shape=[R, 1],
                                      append_batch_size=False,
                                      dtype='int32')
            clen = fluid.layers.data(name='chunk_len', shape=[R, 1],
                                     append_batch_size=False,
                                     dtype='int32')
            btab = fluid.layers.data(name='block_table', shape=[R, MAXB],
                                     append_batch_size=False,
                                     dtype='int32')
            table = pe_param()
            x = embed(chunk_ids)                               # [R, C, D]
            posv = chunk_positions(start, C, R)              # [C] / [R, C]
            pe_c = fluid.layers.gather(table, posv)            # [R*C, D]
            x = fluid.layers.elementwise_add(
                x, fluid.layers.reshape(pe_c, shape=[R, C, D]))
            x = _hint(x)
            for i in range(n_layer):
                if kv_int8:
                    kcache, vcache, kscale, vscale = caches(i)
                    q, k, v = qkv(x, i, 2)
                    kcache, kscale = \
                        fluid.layers.kv_block_chunk_write_quant(
                            kcache, kscale, k, start, btab)
                    vcache, vscale = \
                        fluid.layers.kv_block_chunk_write_quant(
                            vcache, vscale, v, start, btab)
                    a = fluid.layers.kv_block_chunk_attention_quant(
                        q, kcache, kscale, vcache, vscale, k, v, start,
                        btab, n_head)
                else:
                    kcache, vcache = caches(i)
                    q, k, v = qkv(x, i, 2)
                    kcache = fluid.layers.kv_block_chunk_write(
                        kcache, k, start, btab)
                    vcache = fluid.layers.kv_block_chunk_write(
                        vcache, v, start, btab)
                    a = fluid.layers.kv_block_chunk_attention(
                        q, kcache, vcache, start, btab, n_head)
                x = block_tail(x, a, i, 2)
            chunk_logits = last_logits(x, clen, C, R, D,
                                       out_logits)             # [R, V]
        return {
            'program': cp,
            'feeds': ['chunk_ids', 'start', 'chunk_len', 'block_table'],
            'samples': {'chunk_ids': np.zeros((R, C), np.int64),
                        'start': np.zeros((R, 1), np.int32),
                        'chunk_len': np.ones((R, 1), np.int32),
                        'block_table': np.zeros((R, MAXB), np.int32)},
            'fetches': [chunk_logits.name]}

    chunk_progs = {C: chunk_program(C) for C in chunks}

    # ---- verify program (ISSUE 17, built LAST so the op-creation rng
    # order of step/chunk — and thus the weights — is untouched):
    # [S, R] rows (R = draft_k + 1) score in one dispatch; pad rows
    # carry pos = MAXB * BS, the span guard's trash route, so a pad row
    # can never land in a SHARED full prefix block the way pos = T
    # could when T is not block-aligned --------------------------------
    verify = None
    if draft_k:
        R = int(draft_k) + 1
        vp = fluid.Program()
        with fluid.program_guard(vp, startup):
            vtok = fluid.layers.data(name='tokens', shape=[S, R],
                                     append_batch_size=False,
                                     dtype='int64')
            vpos = fluid.layers.data(name='pos', shape=[S, R],
                                     append_batch_size=False,
                                     dtype='int32')
            vtab = fluid.layers.data(name='block_tables',
                                     shape=[S, MAXB],
                                     append_batch_size=False,
                                     dtype='int32')
            table = pe_param()
            x = embed(vtok)                                 # [S, R, D]
            # clamp the PE GATHER index only (pad rows carry
            # pos = MAXB * BS, past the PE table): an unclamped OOB
            # gather is NaN-filled under jnp.take, the pad rows' NaN
            # k/v would land in the TRASH BLOCK, and 0 * NaN in every
            # real row's masked attention would poison the whole batch
            pe_idx = fluid.layers.clip(vpos, 0, T - 1)
            pe_r = fluid.layers.gather(table, pe_idx)       # [S*R, D]
            x = fluid.layers.elementwise_add(
                x, fluid.layers.reshape(pe_r, shape=[S, R, D]))
            x = _hint(x)
            for i in range(n_layer):
                if kv_int8:
                    kcache, vcache, kscale, vscale = caches(i)
                    q, k, v = qkv(x, i, 2)
                    kcache, kscale = \
                        fluid.layers.kv_block_verify_write_quant(
                            kcache, kscale, k, vpos, vtab)
                    vcache, vscale = \
                        fluid.layers.kv_block_verify_write_quant(
                            vcache, vscale, v, vpos, vtab)
                    a = fluid.layers.kv_block_verify_attention_quant(
                        q, kcache, kscale, vcache, vscale, vpos, vtab,
                        n_head)
                else:
                    kcache, vcache = caches(i)
                    q, k, v = qkv(x, i, 2)
                    kcache = fluid.layers.kv_block_verify_write(
                        kcache, k, vpos, vtab)
                    vcache = fluid.layers.kv_block_verify_write(
                        vcache, v, vpos, vtab)
                    a = fluid.layers.kv_block_verify_attention(
                        q, kcache, vcache, vpos, vtab, n_head)
                x = block_tail(x, a, i, 2)
            verify_logits = out_logits(x, nfd=2)            # [S, R, V]
        verify = {'program': vp,
                  'feeds': ['tokens', 'pos', 'block_tables'],
                  'samples': {'tokens': np.zeros((S, R), np.int64),
                              'pos': np.full((S, R), MAXB * BS,
                                             np.int32),
                              'block_tables': np.zeros((S, MAXB),
                                                       np.int32)},
                  'fetches': [verify_logits.name]}

    # ---- the row program, after everything else for the same reason: the
    # largest chunk once more at [R, C], where the shapes give one
    # (decode_spec.chunk_row_shape: chunks (32, 128) -> 128 x 4) ----------
    rows = chunk_row_shape(chunk_progs, MAXB * BS)

    spec = {'startup': startup,
            'block_size': BS, 'num_blocks': NB,
            'max_blocks_per_slot': MAXB,
            'step': {'program': step_p,
                     'feeds': ['tokens', 'pos', 'block_tables'],
                     'samples': {'tokens': np.zeros((S, 1), np.int64),
                                 'pos': np.zeros((S, 1), np.int32),
                                 'block_tables': np.zeros((S, MAXB),
                                                          np.int32)},
                     'fetches': [step_logits.name]},
            'chunk': chunk_progs,
            'cache_vars': list(cache_vars),
            'max_slots': S, 'max_cache_len': T,
            'eos_id': int(eos_id), 'vocab': int(vocab),
            'kv_cache_dtype': kv_cache_dtype}
    if rows is not None:
        spec['chunk_rows'] = dict(chunk_program(*rows), size=rows[0],
                                  rows=rows[1])
    if verify is not None:
        spec['verify'] = verify
        spec['draft_k'] = int(draft_k)
    if mp:
        spec['mesh_axes'] = {'mp': mp}
        spec['param_shardings'] = dict(param_shardings)
        spec['state_shardings'] = dict(state_shardings)
    return spec
