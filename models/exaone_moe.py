"""K-EXAONE decode serving (LGAI-EXAONE/K-EXAONE-236B-A23B, model_type
exaone_moe): a post-norm decoder with grouped K/V heads, per-head QK-norm,
a sliding-window / full attention mix (rotary on the sliding layers only),
a dense SwiGLU in the leading layer(s) and, after them, a sigmoid top-k
router with a selection bias over routed experts plus a shared expert.

Layer equations (benchmark/reference/exaone_moe.py writes them out; N is
RMSNorm, x a row of the residual stream at position pos):

    q = W_q x (n_head x d_head), k = W_k x, v = W_v x (n_kv_head x d_head)
    q_h <- N(q_h; w_qn), k_g <- N(k_g; w_kn)          per head, one [d_head]
    sliding layers only: q, k <- RoPE(., pos)         rotate-half, theta
    a = W_o Attn(q, k, v)   head h reads K/V head h // (n_head / n_kv_head),
                            causal; sliding layers: 0 <= pos - j < window
    h = x + N(a; w_post_attn)
    dense layers:  m = W_d(silu(W_g h) * W_u h)
    routed layers: s = sigmoid(W_r h); T = top-k of s + b;
                   g_e = scaling * s_e / (sum_T s + 1e-20)
                   m = sum_{e in T, held} g_e E_e(h) + E_shared(h)
    y = h + N(m; w_post_ff);   logits = W_head N(y_L; w_final)

One chip's SHARE of an expert-parallel deployment: the router scores all
`n_expert` experts, this program holds `n_held` of them from
`expert_offset` on (moe_topk_ffn's attributes) and adds nothing for the
pairs routed elsewhere; `vocab` is the slice of the vocabulary held here
(embedding rows, head columns, the argmax).

`build_decode_spec` composes this from the ops the IR has — mul, rms_norm,
rotary_embedding, swiglu, moe_topk_ffn, kv_block_* with n_kv_head / window —
on models/decode_spec.py's scaffolding: sliding layers' K/V live in the
window pool, full layers' in the pool that keeps every position.

Precision as models/olmoe.py: matrices stored in `weights_dtype`, bf16 x
bf16 products with float32 accumulation; residual stream, norms, RoPE,
router and queries float32; K and V cached in `kv_cache_dtype`.
"""
from __future__ import annotations

import paddle_tpu as fluid

from .decode_spec import DecodeSpecBuilder

SLIDING, FULL = 'sliding_attention', 'full_attention'


def layer_types(n_layer, period=4):
    """The family's pattern: every `period`-th layer full, the rest
    sliding (LLLG...)."""
    return [FULL if i % period == period - 1 else SLIDING
            for i in range(n_layer)]


def build_decode_spec(vocab=128, d_model=64, n_head=4, n_kv_head=2,
                      d_head=16, n_layer=5, types=None, window=16,
                      d_dense=96, first_dense=1, n_expert=16, n_held=None,
                      expert_offset=0, d_expert=32, top_k=4, n_shared=1,
                      routed_scaling_factor=2.5, norm_topk_prob=True,
                      max_slots=4, max_cache_len=96, block_size=8,
                      chunk_sizes=(8, 16), num_blocks=None,
                      eos_id=1, kv_cache_dtype='bfloat16',
                      weights_dtype='bfloat16', rms_eps=1e-5,
                      rope_theta=1e6, init_std=0.02, bias_std=0.01):
    """The block-paged decode program set (defaults: a toy for the cpu
    tests); the spec has models/olmoe.py's keys plus 'window' (the
    sliding layers' pool: inference/export.py export_decode).

    `types[i]` is layer i's attention kind (default layer_types(n_layer));
    layers below `first_dense` have a dense SwiGLU of width d_dense, the
    rest `n_held` (default all) of `n_expert` routed experts of width
    d_expert from `expert_offset` on and a shared expert of width
    n_shared * d_expert.

    Weights draw from N(0, init_std), norm weights from N(1, 0.1), the
    router's selection bias from N(0, bias_std). Names: embed_w,
    l<i>_{q_w, k_w, v_w, o_w, q_norm_w, k_norm_w, post_attn_norm_w,
    post_ff_norm_w}, dense l<i>_ff_{gate,up,down}_w, routed
    l<i>_moe_{router, router_bias, gate, up, down} and
    l<i>_shared_{gate,up,down}_w, final_norm_w, lm_head_w; pools
    kv_k_<i> / kv_v_<i> [blocks, block_size, n_kv_head * d_head]."""
    D, H, KV, DH = int(d_model), int(n_head), int(n_kv_head), int(d_head)
    if H % KV or DH % 2:
        raise ValueError('n_head must be a multiple of n_kv_head and '
                         'd_head even')
    types = list(types) if types is not None else layer_types(n_layer)
    if len(types) != n_layer or set(types) - {SLIDING, FULL}:
        raise ValueError('types must name %d layers %r or %r'
                         % (n_layer, SLIDING, FULL))
    held = int(n_expert if n_held is None else n_held)
    if not 1 <= top_k <= n_expert:
        raise ValueError('top_k must be in [1, n_expert]')
    L = fluid.layers
    PA = fluid.ParamAttr
    Normal = fluid.initializer.NormalInitializer

    def head_norm(b, x, name, heads):
        """RMSNorm over each head's d_head channels, one [d_head] weight."""
        lead = [int(n) for n in x.shape[:-1]]
        return L.reshape(b.norm(L.reshape(x, shape=lead + [heads, DH]), name),
                         shape=lead + [heads * DH])

    def ffn(b, x, prefix, width, nfd):
        return b.linear(L.swiglu(b.linear(x, prefix + 'gate_w', width, nfd),
                                 b.linear(x, prefix + 'up_w', width, nfd)),
                        prefix + 'down_w', D, nfd)

    def block(b, x, i, nfd, pos):
        p = 'l%d_' % i
        q = head_norm(b, b.linear(x, p + 'q_w', H * DH, nfd),
                      p + 'q_norm_w', H)
        k = head_norm(b, b.linear(x, p + 'k_w', KV * DH, nfd),
                      p + 'k_norm_w', KV)
        v = b.linear(x, p + 'v_w', KV * DH, nfd)
        if types[i] == SLIDING:       # the full layers carry no position
            q = L.rotary_embedding(q, pos, H, rope_theta)
            k = L.rotary_embedding(k, pos, KV, rope_theta)
        kcache, vcache = b.write(i, k, v)
        a = b.attend(i, q, kcache, vcache, H, n_kv_head=KV)
        h = L.elementwise_add(
            x, b.norm(b.linear(a, p + 'o_w', D, nfd),
                      p + 'post_attn_norm_w'))
        if i < first_dense:
            m = ffn(b, h, p + 'ff_', int(d_dense), nfd)
        else:
            m = L.moe_topk_ffn(
                h, n_expert, d_expert, top_k,
                norm_topk_prob=norm_topk_prob, dtype=weights_dtype,
                param_attr=PA(name=p + 'moe', trainable=False,
                              initializer=Normal(0.0, init_std)),
                scoring='sigmoid',
                router_bias=PA(name=p + 'moe_router_bias', trainable=False,
                               initializer=Normal(0.0, bias_std)),
                routed_scaling_factor=routed_scaling_factor,
                num_held=held, expert_offset=expert_offset)
            if n_shared:
                with fluid.name_scope('shared_expert'):
                    m = L.elementwise_add(
                        m, ffn(b, h, p + 'shared_',
                               int(n_shared) * int(d_expert), nfd))
        return L.elementwise_add(h, b.norm(m, p + 'post_ff_norm_w'))

    def logits(b, x):
        return b.linear(b.norm(x, 'final_norm_w'), 'lm_head_w', vocab, 1)

    return DecodeSpecBuilder(
        vocab=vocab, d_model=D, kv_width=KV * DH, n_layer=n_layer,
        max_slots=max_slots, max_cache_len=max_cache_len,
        block_size=block_size, chunk_sizes=chunk_sizes,
        num_blocks=num_blocks, eos_id=eos_id,
        kv_cache_dtype=kv_cache_dtype, weights_dtype=weights_dtype,
        rms_eps=rms_eps, init_std=init_std,
        window_layers=[i for i, t in enumerate(types) if t == SLIDING],
        window=window).build(block, logits)
