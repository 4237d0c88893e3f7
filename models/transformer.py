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
                                     # give one (DecodeSpecBuilder.build)
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
    after the step and the chunk programs and shares every weight by
    name, so those programs (and the weights the per-op rng streams
    draw) are byte-for-byte what a draft_k=0 build produces. The serving
    tier drafts host-side and rolls rejected rows back
    (inference/decoding.py).

    The feeds, the samples, the programs' order, the pools and the spec
    dict are models/decode_spec.py DecodeSpecBuilder's, as for every
    other decoder of models/; this file gives it the post-LN block, the
    scaled embedding under the sinusoid position table, and the head.
    """
    from .decode_spec import DecodeSpecBuilder
    PA = fluid.ParamAttr
    L = fluid.layers
    T, D = int(max_cache_len), int(d_model)
    if D % n_head or D % 2:
        raise ValueError("d_model must be even and divisible by n_head")
    mp = int(mp_shard or 0)
    if mp and (n_head % mp or d_ff % mp):
        raise ValueError(
            'mp_shard=%d must divide n_head=%d and d_ff=%d (the D '
            'axis shards by whole head groups)' % (mp, n_head, d_ff))
    pe = _pe_table(T, D)

    def param(x, name):
        return x.block.program.global_block().var(name)

    def fc(b, x, size, nfd, name, bias=None, act=None):
        """x through the matrix `name` (and bias `bias`), its columns
        over the mesh where the spec is sharded."""
        out = L.fc(x, size, num_flatten_dims=nfd, act=act,
                   param_attr=PA(name=name),
                   bias_attr=PA(name=bias) if bias else False)
        b.shard(param(x, name), (None, 'mp'))
        return out

    def embed(b, ids):
        # the position table first: the startup program's order
        L.create_parameter(
            [T, D], 'float32', attr=PA(name='pos_enc_w', trainable=False),
            default_initializer=fluid.initializer.NumpyArrayInitializer(pe))
        x = L.embedding(ids, size=[vocab, D], param_attr=PA(name='dec_emb_w'))
        b.shard(param(x, 'dec_emb_w'), (None, 'mp'))
        return L.scale(x, scale=float(D ** 0.5))

    def block(b, x, i, nfd, pos):
        """One post-LN decoder layer over x ([S, D] with nfd 1; [R, C, D]
        of a chunk, [S, R, D] of the verify program with 2) — the same
        [D]-shaped parameters either way. The first adds the position
        table's rows at `pos` to the scaled embedding: the SAME table in
        every program, so positional values agree bit for bit. The mp
        replicate hints: attention context gathers before the o
        projection, h before f2, and each projection output before its LN
        — every contraction stays full-width."""
        if i == 0:
            rows = L.gather(param(x, 'pos_enc_w'), pos)
            if nfd == 2:
                rows = L.reshape(rows, shape=list(x.shape))
            x = b.hint(L.elementwise_add(x, rows))
        b.pools(i)      # before q, k, v: the order the startup program draws in
        p = 'l%d_' % i
        q, k, v = (fc(b, x, D, nfd, p + tag + '_w') for tag in 'qkv')
        kcache, vcache = b.write(i, k, v)
        a = b.hint(b.attend(i, q, kcache, vcache, n_head))
        o = b.hint(fc(b, a, D, nfd, p + 'o_w'))
        x = L.layer_norm(x + o, begin_norm_axis=nfd,
                         param_attr=PA(name=p + 'ln1_s'),
                         bias_attr=PA(name=p + 'ln1_b'))
        # pin the LN output replicated too: left unconstrained, GSPMD may
        # shard it over 'mp' and the next projection's contraction turns
        # into a partial-sum all-reduce — reordered accumulation, bit
        # drift vs the single-chip artifact
        x = b.hint(x)
        h = fc(b, x, d_ff, nfd, p + 'f1_w', bias=p + 'f1_b', act='relu')
        b.shard(param(x, p + 'f1_b'), ('mp',))
        f = b.hint(fc(b, b.hint(h), D, nfd, p + 'f2_w', bias=p + 'f2_b'))
        return b.hint(L.layer_norm(x + f, begin_norm_axis=nfd,
                                   param_attr=PA(name=p + 'ln2_s'),
                                   bias_attr=PA(name=p + 'ln2_b')))

    def logits(b, x):       # [S, D] / one chunk row [1, D] / [S, R, D]
        return b.hint(fc(b, x, vocab, len(x.shape) - 1, 'out_w'))

    return DecodeSpecBuilder(
        vocab=vocab, d_model=D, kv_width=D, n_layer=n_layer,
        max_slots=max_slots, max_cache_len=T, block_size=block_size,
        chunk_sizes=chunk_sizes, num_blocks=num_blocks, eos_id=eos_id,
        kv_cache_dtype=kv_cache_dtype, draft_k=draft_k,
        mp_shard=mp).build(block, logits, embed)
