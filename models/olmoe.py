"""OLMoE decode serving (allenai/OLMoE-1B-7B family): a pre-norm decoder
whose every feed-forward is a dropless top-k mixture of SwiGLU experts,
with RMSNorm, QK-norm over the whole projection, and rotate-half RoPE.

Layer equations, as `transformers`' modeling_olmoe.py computes them (the
plain reference, benchmark/reference/olmoe.py, writes them out):

    h = x + W_o Attn(RoPE(RMSNorm_q(W_q n)), RoPE(RMSNorm_k(W_k n)), W_v n)
                                                      with n = RMSNorm(x)
    y = h + MoE(RMSNorm(h)),   logits = W_head RMSNorm(y_L)

`build_decode_spec` composes them from the ops the IR has — rms_norm,
rotary_embedding, moe_topk_ffn (layers/nn.py) — and the block-paged cache
ops models/transformer.py serves with (kv_block_write / kv_block_attention /
kv_block_chunk_write / kv_block_chunk_attention), and returns the spec
`inference.export_decode` consumes: a decode-step program and one chunked-
prefill program per chunk size over one block pool.

Precision: matrices are STORED in `weights_dtype` (bfloat16 as served) and
multiply as bf16 x bf16 with float32 accumulation (ops/math_ops.py mul);
the residual stream, the norms, RoPE, the router and the attention query
are float32; K (after RoPE) and V are cached in `kv_cache_dtype`.
"""
from __future__ import annotations

import numpy as np

import paddle_tpu as fluid


def build_decode_spec(vocab=128, d_model=64, n_head=4, n_layer=2,
                      n_expert=8, d_expert=32, top_k=2, max_slots=4,
                      max_cache_len=64, block_size=8, chunk_sizes=(8, 16),
                      num_blocks=None, eos_id=1, kv_cache_dtype='bfloat16',
                      weights_dtype='bfloat16', rms_eps=1e-5,
                      rope_theta=10000.0, norm_topk_prob=False,
                      init_std=0.02):
    """The block-paged decode program set of an OLMoE model (defaults: a
    toy for the cpu tests). The spec has models/transformer.py's keys:
    'startup', 'step', 'chunk' {size: entry}, 'cache_vars',
    'block_size', 'num_blocks' (default: full capacity plus the trash
    block), 'max_blocks_per_slot', 'max_slots',
    'max_cache_len', 'eos_id', 'vocab', 'kv_cache_dtype'.

    Weights draw from N(0, init_std) (the family's initializer_range);
    norm weights from N(1, 0.1), so that a norm whose weight were dropped
    would not pass for the real one. Names: embed_w, l<i>_{in_norm_w, q_w,
    k_w, v_w, o_w, q_norm_w, k_norm_w, post_norm_w, moe_router, moe_gate,
    moe_up, moe_down}, final_norm_w, lm_head_w; the pool kv_k_<i> /
    kv_v_<i> [num_blocks, block_size, d_model]."""
    if kv_cache_dtype not in ('float32', 'bfloat16'):
        raise ValueError("kv_cache_dtype must be 'float32' or 'bfloat16', "
                         "got %r" % (kv_cache_dtype,))
    S, T, D, BS = (int(max_slots), int(max_cache_len), int(d_model),
                   int(block_size))
    if D % n_head or (D // n_head) % 2:
        raise ValueError('d_model must split into n_head even-sized heads')
    if not 1 <= BS <= T:
        raise ValueError('block_size must be in [1, max_cache_len]')
    if not 1 <= top_k <= n_expert:
        raise ValueError('top_k must be in [1, n_expert]')
    MAXB = -(-T // BS)
    NB = int(num_blocks) if num_blocks is not None else S * MAXB + 1
    if NB < 2:
        raise ValueError('num_blocks must be >= 2 (block 0 is the reserved '
                         'trash block)')
    chunks = sorted({int(c) for c in chunk_sizes})
    if not chunks or chunks[0] < 1 or chunks[-1] > T:
        raise ValueError('chunk_sizes must be in [1, max_cache_len]')
    PA = fluid.ParamAttr
    Normal = fluid.initializer.NormalInitializer
    startup = fluid.Program()
    cache_vars = []
    for i in range(n_layer):
        cache_vars += ['kv_k_%d' % i, 'kv_v_%d' % i]

    def matrix(name, shape):
        return fluid.layers.create_parameter(
            shape, weights_dtype, attr=PA(name=name, trainable=False),
            default_initializer=Normal(0.0, init_std))

    def linear(x, name, d_out, nfd):
        return fluid.layers.mul(x, matrix(name, [int(x.shape[-1]), d_out]),
                                x_num_col_dims=nfd)

    def norm(x, name):
        return fluid.layers.rms_norm(
            x, epsilon=rms_eps,
            param_attr=PA(name=name, trainable=False,
                          initializer=Normal(1.0, 0.1)))

    def caches(i):
        zero = fluid.initializer.ConstantInitializer(0.0)
        return tuple(fluid.layers.create_parameter(
            [NB, BS, D], kv_cache_dtype,
            attr=PA(name='kv_%s_%d' % (kv, i), trainable=False),
            default_initializer=zero) for kv in 'kv')

    def embed(ids):
        x = fluid.layers.embedding(
            ids, size=[vocab, D], dtype=weights_dtype,
            param_attr=PA(name='embed_w', trainable=False,
                          initializer=Normal(0.0, init_std)))
        return fluid.layers.cast(x, 'float32')

    def block(x, i, nfd, pos, write, attend):
        """One decoder layer over x ([S, D] with nfd 1, [1, C, D] with 2);
        `write(cache, kv)` and `attend(q, kcache, vcache)` are the
        program's own cache ops."""
        p = 'l%d_' % i
        h = norm(x, p + 'in_norm_w')
        q = norm(linear(h, p + 'q_w', D, nfd), p + 'q_norm_w')
        k = norm(linear(h, p + 'k_w', D, nfd), p + 'k_norm_w')
        v = linear(h, p + 'v_w', D, nfd)
        q = fluid.layers.rotary_embedding(q, pos, n_head, rope_theta)
        k = fluid.layers.rotary_embedding(k, pos, n_head, rope_theta)
        kcache, vcache = caches(i)
        kcache, vcache = write(kcache, k), write(vcache, v)
        a = attend(q, kcache, vcache)
        x = fluid.layers.elementwise_add(x, linear(a, p + 'o_w', D, nfd))
        m = fluid.layers.moe_topk_ffn(
            norm(x, p + 'post_norm_w'), n_expert, d_expert, top_k,
            norm_topk_prob=norm_topk_prob, dtype=weights_dtype,
            param_attr=PA(name=p + 'moe', trainable=False,
                          initializer=Normal(0.0, init_std)))
        return fluid.layers.elementwise_add(x, m)

    def out_logits(x):
        return linear(norm(x, 'final_norm_w'), 'lm_head_w', vocab, 1)

    # ---- decode step: [S] slots advance one token through the pool ------
    step_p = fluid.Program()
    with fluid.program_guard(step_p, startup):
        tokens = fluid.layers.data(name='tokens', shape=[S, 1],
                                   append_batch_size=False, dtype='int64')
        pos = fluid.layers.data(name='pos', shape=[S, 1],
                                append_batch_size=False, dtype='int32')
        tables = fluid.layers.data(name='block_tables', shape=[S, MAXB],
                                   append_batch_size=False, dtype='int32')
        x = embed(tokens)                                        # [S, D]
        for i in range(n_layer):
            x = block(
                x, i, 1, pos,
                lambda c, kv: fluid.layers.kv_block_write(c, kv, pos,
                                                          tables),
                lambda q, kc, vc: fluid.layers.kv_block_attention(
                    q, kc, vc, pos, tables, n_head))
        step_logits = out_logits(x)                              # [S, V]

    # ---- chunked prefill: one CHUNK of one prompt ------------------------
    chunk_progs = {}
    for C in chunks:
        cp = fluid.Program()
        with fluid.program_guard(cp, startup):
            chunk_ids = fluid.layers.data(name='chunk_ids', shape=[1, C],
                                          append_batch_size=False,
                                          dtype='int64')
            start = fluid.layers.data(name='start', shape=[1, 1],
                                      append_batch_size=False,
                                      dtype='int32')
            clen = fluid.layers.data(name='chunk_len', shape=[1, 1],
                                     append_batch_size=False,
                                     dtype='int32')
            btab = fluid.layers.data(name='block_table', shape=[1, MAXB],
                                     append_batch_size=False,
                                     dtype='int32')
            x = embed(chunk_ids)                                # [1, C, D]
            posv = fluid.layers.elementwise_add(
                fluid.layers.range(0, C, 1, 'int32'),
                fluid.layers.reshape(start, shape=[1]))          # [C]
            for i in range(n_layer):
                x = block(
                    x, i, 2, posv,
                    lambda c, kv: fluid.layers.kv_block_chunk_write(
                        c, kv, start, btab),
                    lambda q, kc, vc: fluid.layers.kv_block_chunk_attention(
                        q, kc, vc, start, btab, n_head))
            # logits at the chunk's LAST VALID row (the scheduler reads
            # them only from a prompt's final chunk)
            last = fluid.layers.gather(
                fluid.layers.reshape(x, shape=[C, D]),
                fluid.layers.elementwise_sub(
                    clen, fluid.layers.fill_constant([1], 'int32', 1)))
            chunk_logits = out_logits(last)                      # [1, V]
        chunk_progs[C] = {
            'program': cp,
            'feeds': ['chunk_ids', 'start', 'chunk_len', 'block_table'],
            'samples': {'chunk_ids': np.zeros((1, C), np.int64),
                        'start': np.zeros((1, 1), np.int32),
                        'chunk_len': np.ones((1, 1), np.int32),
                        'block_table': np.zeros((1, MAXB), np.int32)},
            'fetches': [chunk_logits.name]}

    return {'startup': startup,
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
