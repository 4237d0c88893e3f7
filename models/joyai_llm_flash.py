"""JoyAI-LLM-Flash decode serving (jdopensource/JoyAI-LLM-Flash, model_type
joyai_llm_flash; its keys are DeepSeek-V3's): a pre-norm decoder with
LATENT attention (MLA) — a low-rank query, and a key/value state compressed
to one `kv_lora_rank`-wide latent and ONE rotary key a position, shared by
every head — a dense SwiGLU in the leading layer(s) and, after them, a
sigmoid top-k router with a selection bias over routed experts plus a
shared expert (models/exaone_moe.py's routed layer, op for op).

Layer equations (benchmark/reference/joyai_llm_flash.py writes them out in
the published, EXPANDED form; N is RMSNorm, x a row of the residual stream
at position pos, H heads, r = kv_lora_rank, dn / dr / dv the no-position,
rotary and value widths of a head):

    xn = N(x; w_in)
    cq = N(W_dq xn; w_qa);  q = W_uq cq -> H x [q_nope dn | q_rope dr]
    [c | k_r] = W_dkv xn (r + dr);  c <- N(c; w_kva)
    q_rope_i, k_r <- RoPE(., pos)      neighbouring pairs (2j, 2j+1), theta
    THE CACHE ROW is [c | RoPE(k_r)], r + dr values (+ zeros up to a whole
    number of 128-lane tiles: see below)
    [k_nope_i | v_i] = W_ukv,i c            (H x (dn + dv), the published
                                             kv_b_proj)
    score_i,s = (q_nope_i . k_nope_i,s + q_rope_i . k_r,s) / sqrt(dn + dr)
    o_i = sum_s softmax_s(score_i) v_i,s;   a = W_o [o_1 .. o_H]
    h = x + a;   y = h + FFN(N(h; w_post))  FFN: dense SwiGLU, or
        s = sigmoid(W_r hn); T = top-k of s + b; g_e = scaling * s_e /
        (sum_T s + 1e-20); sum_{e in T, held} g_e E_e(hn) + E_shared(hn)
    logits = W_head N(y_L; w_final)

WHAT THE PROGRAMS COMPUTE is the ABSORBED form of the same numbers, in the
decode step and in the prefill slices alike: W_ukv never meets the cache.

    q~_i = W_uk,i^T q_nope_i (r)                         scope q_absorb
    score_i,s = ([q~_i | q_rope_i] . [c_s | k_r,s]) / sqrt(dn + dr)
    o~_i = sum_s p_i,s c_s (r)     kv_block_attention / _chunk_attention
                                   with n_kv_head 1 and v_width r over
                                   the ONE pool: K and V the same pages
    o_i = W_uv,i o~_i (dv)                               scope v_expand

so a cached position costs r + dr values a layer instead of H x (dn + dr +
dv), and every head reads the same row. Prefill and decode attention differ
in the BODY, not in the mathematics: the step's is the latent Pallas kernel
on a TPU (ops/pallas_paged_attention.latent_paged_attention: one copy of a
page, two products), a slice's the paged jnp body a block of 512 positions
at a time (ops/decode_ops._chunk_attention_blocked). Expanding the latent
to per-head K/V for a slice's view (fewer operations per cached row at 512
queries a row) was weighed and left out: a slice here reads every weight
once (3.9 GB) and its absorbed attention is a fraction of that time.

THE ROW IS STORED A WHOLE NUMBER OF 128-LANE TILES WIDE (576 -> 640, the
tail zeros, the query's tail zeros too): the chip's layout holds a 576-wide
bfloat16 row in five tiles whatever the program says, and a page cannot be
copied out of a row that ends inside a tile (pallas_paged_attention.
refuses_latent). The zeros cost a ninth more bytes and score products than
the 576 the algorithm needs; the roofline counts the 576.

One chip's SHARE of an expert-parallel deployment, as models/exaone_moe.py:
the router scores all `n_expert` experts, this program holds `n_held` of
them from `expert_offset` on; `vocab` is the slice of the vocabulary held.

Precision as models/olmoe.py: matrices stored in `weights_dtype`, bf16 x
bf16 products with float32 accumulation; residual stream, norms, RoPE,
router and queries float32; the latent row cached in `kv_cache_dtype`, and
both attention products over it take that dtype as operands (float32 sums).
"""
from __future__ import annotations

import math

import paddle_tpu as fluid

from .decode_spec import DecodeSpecBuilder

_LANES = 128


def row_width(kv_lora_rank, d_rope):
    """The stored width of a latent row: latent + rotary key, up to whole
    128-lane tiles."""
    return -(-(int(kv_lora_rank) + int(d_rope)) // _LANES) * _LANES


def _ffn(b, x, prefix, width, nfd):
    return b.linear(
        fluid.layers.swiglu(b.linear(x, prefix + 'gate_w', width, nfd),
                            b.linear(x, prefix + 'up_w', width, nfd)),
        prefix + 'down_w', int(b.D), nfd)


def _cols(x, lo, hi):
    return fluid.layers.slice(x, axes=[len(x.shape) - 1], starts=[lo],
                              ends=[hi])


def _per_head(x, w):
    """x [N, H, a] times w [H, a, b], head by head: [N, H, b]."""
    L = fluid.layers
    return L.transpose(L.matmul(L.transpose(x, perm=[1, 0, 2]), w),
                       perm=[1, 0, 2])


def latent_attention(b, xn, i, nfd, pos, n_head, q_lora_rank, kv_lora_rank,
                     d_nope, d_rope, d_v, rope_theta):
    """a = W_o Attn of the normed rows xn ([S, D] with nfd 1, [1, C, D]
    with 2), absorbed, through layer i's latent pool (`b` a
    DecodeSpecBuilder with v_width = kv_lora_rank and rows row_width(...)
    wide). SHARED by the models that serve latent attention (this one and
    models/kimi_linear.py), which differ in two arguments: `q_lora_rank`
    None is ONE full-rank query projection l<i>_q_w in place of q_a, its
    norm and q_b — still under the scope q_lora, so the scope holds the
    query projection whatever its rank and latent_proj_device_share stays
    whole — and `rope_theta` None leaves the d_rope channels of the query
    and of the key UNROTATED (no position term: mla_use_nope)."""
    L = fluid.layers
    H, R, DN, DR, DV = (int(n_head), int(kv_lora_rank), int(d_nope),
                        int(d_rope), int(d_v))
    W = row_width(R, DR)
    pad = W - R - DR
    scale = float(DN + DR) ** -0.5
    p = 'l%d_' % i
    lead = [int(m) for m in xn.shape[:-1]]
    n = math.prod(lead)

    def rotate(x, heads):
        if rope_theta is None:
            return x
        return L.rotary_embedding(x, pos, heads, rope_theta,
                                  interleave=True)

    with fluid.name_scope('q_lora'):
        if q_lora_rank is None:
            q = b.linear(xn, p + 'q_w', H * (DN + DR), nfd)
        else:
            cq = b.norm(b.linear(xn, p + 'q_a_w', int(q_lora_rank), nfd),
                        p + 'q_a_norm_w')
            q = b.linear(cq, p + 'q_b_w', H * (DN + DR), nfd)
        q = L.reshape(q, shape=[n, H, DN + DR])
        q_rope = L.reshape(rotate(
            L.reshape(_cols(q, DN, DN + DR), shape=[n, H * DR]), H),
            shape=[n, H, DR])
    with fluid.name_scope('kv_down'):
        ckr = b.linear(xn, p + 'kv_a_w', R + DR, nfd)
        row = [b.norm(_cols(ckr, 0, R), p + 'kv_a_norm_w'),
               rotate(_cols(ckr, R, R + DR), 1)]
        if pad:
            row.append(L.fill_constant(lead + [pad], 'float32', 0.0))
        (pool,) = b.write(i, L.concat(row, axis=len(lead)))
    # the published kv_b_proj, [r, H x (dn + dv)], taken apart per head
    w_ukv = L.reshape(b.matrix(p + 'kv_b_w', [R, H * (DN + DV)]),
                      shape=[R, H, DN + DV])
    with fluid.name_scope('q_absorb'):
        q_lat = _per_head(_cols(q, 0, DN), L.transpose(
            _cols(w_ukv, 0, DN), perm=[1, 2, 0]))        # [n, H, r]
        parts = [q_lat, q_rope]
        if pad:
            parts.append(L.fill_constant([n, H, pad], 'float32', 0.0))
        q_abs = L.reshape(L.concat(parts, axis=2),
                          shape=lead + [H * W])
    o_lat = b.attend(i, q_abs, pool, pool, H, n_kv_head=1, scale=scale)
    with fluid.name_scope('v_expand'):
        o = _per_head(L.reshape(o_lat, shape=[n, H, R]), L.transpose(
            _cols(w_ukv, DN, DN + DV), perm=[1, 0, 2]))  # [n, H, dv]
        o = L.reshape(o, shape=lead + [H * DV])
    return b.linear(o, p + 'o_w', int(b.D), nfd)


def sigmoid_routed_ffn(b, hn, i, nfd, d_dense, first_dense, n_expert,
                       d_expert, top_k, n_shared, bias_std, **routed):
    """FFN of layer i over the normed rows hn: a dense SwiGLU of width
    d_dense below `first_dense`, else models/exaone_moe.py's routed layer
    op for op — a sigmoid router with a selection bias (seeded N(0,
    bias_std)) over `n_expert` experts of width d_expert, `routed` being
    moe_topk_ffn's own norm_topk_prob, routed_scaling_factor, num_held and
    expert_offset — plus an unweighted shared expert of width n_shared *
    d_expert. Shared as latent_attention is."""
    L = fluid.layers
    PA = fluid.ParamAttr
    Normal = fluid.initializer.NormalInitializer
    p = 'l%d_' % i
    if i < first_dense:
        return _ffn(b, hn, p + 'ff_', int(d_dense), nfd)
    m = L.moe_topk_ffn(
        hn, n_expert, d_expert, top_k, dtype=b.weights_dtype,
        param_attr=PA(name=p + 'moe', trainable=False,
                      initializer=Normal(0.0, b.init_std)),
        scoring='sigmoid',
        router_bias=PA(name=p + 'moe_router_bias', trainable=False,
                       initializer=Normal(0.0, bias_std)), **routed)
    if n_shared:
        with fluid.name_scope('shared_expert'):
            m = L.elementwise_add(
                m, _ffn(b, hn, p + 'shared_',
                        int(n_shared) * int(d_expert), nfd))
    return m


def build_decode_spec(vocab=128, d_model=64, n_head=4, q_lora_rank=24,
                      kv_lora_rank=32, d_nope=16, d_rope=8, d_v=16,
                      n_layer=3, d_dense=96, first_dense=1, n_expert=16,
                      n_held=None, expert_offset=0, d_expert=32, top_k=4,
                      n_shared=1, routed_scaling_factor=2.5,
                      norm_topk_prob=True, max_slots=4, max_cache_len=96,
                      block_size=8, chunk_sizes=(8, 16), num_blocks=None,
                      eos_id=1, kv_cache_dtype='bfloat16',
                      weights_dtype='bfloat16', rms_eps=1e-6,
                      rope_theta=32e6, init_std=0.02, bias_std=0.01):
    """The block-paged decode program set (defaults: a toy for the cpu
    tests); the spec has models/olmoe.py's keys, with ONE cache var a
    layer: kv_c_<i> [blocks, block_size, row_width(kv_lora_rank, d_rope)].

    Layers below `first_dense` have a dense SwiGLU of width d_dense, the
    rest `n_held` (default all) of `n_expert` routed experts of width
    d_expert from `expert_offset` on and a shared expert of width n_shared
    * d_expert.

    Weights draw from N(0, init_std), norm weights from N(1, 0.1), the
    router's selection bias from N(0, bias_std). Names: embed_w,
    l<i>_{input_norm_w, q_a_w, q_a_norm_w, q_b_w, kv_a_w, kv_a_norm_w,
    kv_b_w, o_w, post_attn_norm_w}, dense l<i>_ff_{gate,up,down}_w, routed
    l<i>_moe_{router, router_bias, gate, up, down} and
    l<i>_shared_{gate,up,down}_w, final_norm_w, lm_head_w."""
    D, H = int(d_model), int(n_head)
    R, DN, DR, DV = (int(kv_lora_rank), int(d_nope), int(d_rope), int(d_v))
    if DR % 2:
        raise ValueError('d_rope must be even')
    held = int(n_expert if n_held is None else n_held)
    if not 1 <= top_k <= n_expert:
        raise ValueError('top_k must be in [1, n_expert]')
    L = fluid.layers

    def block(b, x, i, nfd, pos):
        p = 'l%d_' % i
        with fluid.name_scope('latent_attention'):
            a = latent_attention(
                b, b.norm(x, p + 'input_norm_w'), i, nfd, pos, n_head=H,
                q_lora_rank=q_lora_rank, kv_lora_rank=R, d_nope=DN,
                d_rope=DR, d_v=DV, rope_theta=rope_theta)
        h = L.elementwise_add(x, a)
        hn = b.norm(h, p + 'post_attn_norm_w')
        return L.elementwise_add(h, sigmoid_routed_ffn(
            b, hn, i, nfd, d_dense, first_dense, n_expert, d_expert, top_k,
            n_shared, bias_std, norm_topk_prob=norm_topk_prob,
            routed_scaling_factor=routed_scaling_factor, num_held=held,
            expert_offset=expert_offset))

    def logits(b, x):
        return b.linear(b.norm(x, 'final_norm_w'), 'lm_head_w', vocab, 1)

    return DecodeSpecBuilder(
        vocab=vocab, d_model=D, kv_width=row_width(R, DR), n_layer=n_layer,
        max_slots=max_slots, max_cache_len=max_cache_len,
        block_size=block_size, chunk_sizes=chunk_sizes,
        num_blocks=num_blocks, eos_id=eos_id,
        kv_cache_dtype=kv_cache_dtype, weights_dtype=weights_dtype,
        rms_eps=rms_eps, init_std=init_std, v_width=R).build(block, logits)
