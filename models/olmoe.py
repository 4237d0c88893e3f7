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

import paddle_tpu as fluid

from .decode_spec import DecodeSpecBuilder


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
    D = int(d_model)
    if D % n_head or (D // n_head) % 2:
        raise ValueError('d_model must split into n_head even-sized heads')
    if not 1 <= top_k <= n_expert:
        raise ValueError('top_k must be in [1, n_expert]')
    PA = fluid.ParamAttr
    Normal = fluid.initializer.NormalInitializer

    def block(b, x, i, nfd, pos):
        """One decoder layer over x ([S, D] with nfd 1, [1, C, D] with 2);
        the program's own cache ops are the builder's write / attend."""
        p = 'l%d_' % i
        h = b.norm(x, p + 'in_norm_w')
        q = b.norm(b.linear(h, p + 'q_w', D, nfd), p + 'q_norm_w')
        k = b.norm(b.linear(h, p + 'k_w', D, nfd), p + 'k_norm_w')
        v = b.linear(h, p + 'v_w', D, nfd)
        q = fluid.layers.rotary_embedding(q, pos, n_head, rope_theta)
        k = fluid.layers.rotary_embedding(k, pos, n_head, rope_theta)
        kcache, vcache = b.write(i, k, v)
        a = b.attend(i, q, kcache, vcache, n_head)
        x = fluid.layers.elementwise_add(x, b.linear(a, p + 'o_w', D, nfd))
        m = fluid.layers.moe_topk_ffn(
            b.norm(x, p + 'post_norm_w'), n_expert, d_expert, top_k,
            norm_topk_prob=norm_topk_prob, dtype=weights_dtype,
            param_attr=PA(name=p + 'moe', trainable=False,
                          initializer=Normal(0.0, init_std)))
        return fluid.layers.elementwise_add(x, m)

    def logits(b, x):
        return b.linear(b.norm(x, 'final_norm_w'), 'lm_head_w', vocab, 1)

    return DecodeSpecBuilder(
        vocab=vocab, d_model=D, kv_width=D, n_layer=n_layer,
        max_slots=max_slots, max_cache_len=max_cache_len,
        block_size=block_size, chunk_sizes=chunk_sizes,
        num_blocks=num_blocks, eos_id=eos_id,
        kv_cache_dtype=kv_cache_dtype, weights_dtype=weights_dtype,
        rms_eps=rms_eps, init_std=init_std).build(block, logits)
