"""Granite 4.0-H decode serving (ibm-granite/granite-4.0-h-micro, model_type
granitemoehybrid, here without routed experts: num_local_experts 0): a
pre-norm decoder WITHOUT positional encoding whose token mixer is a MAMBA-2
(SSD) layer — a state of fixed size per request, no cache — in nine layers
of every ten and grouped full attention in the tenth (`layer_types`), every
layer followed by one SwiGLU MLP, under four scalar multipliers.

Layer equations (benchmark/reference/granite_hybrid.py writes them out with
their departures from transformers' modeling_granitemoehybrid.py; N is
RMSNorm with a weight, x a row of the residual stream):

    h_0 = e_mult E[ids]
    h = x + r_mult Mixer_i(N(x));  y = h + r_mult W_out(silu(a) * b),
        [a | b] = W_in N(h)
    logits = E N(y_L) / logits_scaling          (E the embedding table: tied)
    attention: q, k, v = W_q xn, W_k xn, W_v xn (no bias, NO rotary: `nope`)
        a = Attn(q, k, v) causal softmax AT SCALE a_mult (not d_head^-1/2),
        query head h over K/V head h // (H / KV);  out = W_o a
    mamba:  [z | xBC | dt] = W_in xn            (widths HP | HP + 2 N | H)
        xBC <- silu(conv_K(xBC) + b_c)          depthwise, causal: a carried
        [x | B | C] = xBC                       tail of K - 1 inputs
        delta = softplus(dt + dt_bias);  a = exp(-delta exp(A_log))   a head
        S <- a S + (delta x_h) B^T;  m_h = S C + D_h x_h  (S [P, N] a head,
                                     B and C shared by all heads: one group)
        out = W_out N(m * silu(z))              the norm over all H P AFTER
                                                the gate, with a weight

HEADS OF 64 IN THE PAGED KERNEL. The K/V paged kernel
(ops/pallas_paged_attention.py) lays a group's query heads side by side at
128-lane tile boundaries and refuses "grouped heads whose width is no
multiple of 128"; this family's heads are 64 wide over 8 K/V heads. A K (or
V) row as published, [k_0 | k_1 | .. | k_7], IS four tiles [k_2t | k_2t+1]
of 128: the attention op is handed the H query heads each padded to 128 —
[q | 0] for a head whose K/V head is even, [0 | q] for odd (`pad_heads`,
models/phi4_flash.py's idiom without its permutation: no column moves) —
over KV / 2 grouped heads of 128 at scale a_mult: a head's scores are its
own K/V head's, its output is [A v_2t | A v_2t+1], of which `unpad_heads`
keeps its own half. Every cached row is read once a layer a step; the zero
halves double two products the step is not bound by.

BOTH ARE A PRODUCT WITH ONE CONSTANT, `own` [side, 1, half, 1] = 1 where a
head's half of the tile is its K/V head's: the pad an outer product ([.., 1,
DH] x own), the unpad the same product summed over the halves. NOT slices
and a concat: the TPU compiler of this installation MISCOMPILES the unpad
written as reshape -> slice a side -> slice lanes 64..128 -> concat ->
reshape (values from other heads, 5.5 off in randn data, in a jitted
function of four lines; right on the cpu; PERF.md 6, PR 55: found on the
chip as served logits 0.118 from the reference where the cpu read 0.010) —
tests/test_granite_hybrid.py holds the scopes free of `slice` and `concat`.

WHAT A LAYER KEEPS (models/decode_spec.py): an attention layer its K and V
rows in the block pool; a Mamba layer, PER SLOT and unpaged, `rec_ssm_<i>`
[max_slots, H, P, N] (`state_dtype`; d_state on the lanes, 128 of them one
whole tile: ops/state_space_ops.py) and the convolution's tail
`rec_conv_<i>` [max_slots, K - 1, H P + 2 N] (float32). Nothing here asks
the builder, the scheduler or the block manager for anything the other two
recurrent families did not: `recurrent`, `b.state`, `b.rows`.

THE EMBEDDING'S SCALE, AND THE FINAL NORM'S (models/phi4_flash.py's
argument, which holds here with the multipliers counted in): under seeded
weights a mixer or MLP writes an update of rms O(1) whatever it read (the
norm in front makes it so), times r_mult; the table at N(0, embed_std)
times e_mult is what those 2 L updates are added to, and `embed_std` says
how large it starts. The head is the table: `final_norm_std` seeds the
final norm's weight N(0, final_norm_std) — zero mean, so the input token's
own logit gets no offset — small enough for logits of standard deviation
~1. The defaults (None) are N(0, init_std) and the plain N(1, 0.1).

Precision as models/olmoe.py: matrices stored in `weights_dtype`, bf16 x
bf16 products with float32 accumulation; residual stream, norms,
convolution, the recurrence (both forms: float32 products at HIGHEST) and
the queries float32; K and V cached in `kv_cache_dtype`.
"""
from __future__ import annotations

import math

import numpy as np

import paddle_tpu as fluid

from .decode_spec import DecodeSpecBuilder

MAMBA, ATTENTION = 'mamba', 'attention'
# the published order of a period (config.json layer_types: layers 5, 15,
# 25, 35 of 40 attend)
PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4


def layer_types(n_layer):
    """The mixer of each layer: the published period of ten, repeated (and
    cut where n_layer is no whole number of periods)."""
    return [PERIOD[i % len(PERIOD)] for i in range(int(n_layer))]


def build_decode_spec(vocab=128, d_model=256, n_head=4, n_kv_head=2,
                      d_ff=256, n_layer=20, types=None, ssm_heads=8,
                      ssm_head_dim=64, d_state=8, d_conv=4, sub_chunk=256,
                      embedding_multiplier=12.0, attention_multiplier=None,
                      residual_multiplier=0.22, logits_scaling=8.0,
                      max_slots=4, max_cache_len=96, block_size=8,
                      chunk_sizes=(8, 16), num_blocks=None, eos_id=1,
                      kv_cache_dtype='bfloat16', weights_dtype='bfloat16',
                      state_dtype='float32', norm_eps=1e-5, init_std=0.02,
                      conv_std=0.3, embed_std=None, final_norm_std=None,
                      dt_range=(1e-3, 1e-1), a_range=(1.0, 16.0)):
    """The decode program set (defaults: a toy for the cpu tests, two
    periods); the spec has models/qwen3_next.py's keys.

    Matrices draw from N(0, init_std), the convolution's from N(0,
    conv_std) and its bias from N(0, 0.1), norm weights and D from N(1,
    0.1), A_log uniformly over log(a_range), dt_bias uniformly over the
    inverse softplus of `dt_range` (delta at a zero projection log-uniform
    over it, as mamba_ssm initialises it), the embedding from N(0,
    embed_std) (init_std unless given) and the final norm as
    `final_norm_std` says (THE EMBEDDING'S SCALE above). Names: embed_w,
    l<i>_{ln1,ln2}_w, l<i>_ff_{in,out}_w, Mamba l<i>_ssm_{in_w, conv_w,
    conv_b, a_log, dt_b, d, norm_w, out_w}, attention l<i>_{q,k,v,o}_w,
    final_ln_w; pools kv_k_<i> / kv_v_<i>, states rec_ssm_<i> /
    rec_conv_<i>."""
    D, H, KV = int(d_model), int(n_head), int(n_kv_head)
    if D % H or H % KV or KV % 2:
        raise ValueError('n_head must divide d_model and be a multiple of '
                         'n_kv_head, which fills whole pairs of 128-lane '
                         'tiles')
    DH = D // H
    MH, P, N, K = (int(ssm_heads), int(ssm_head_dim), int(d_state),
                   int(d_conv))
    DI, XBC = MH * P, MH * P + 2 * N
    types = layer_types(n_layer) if types is None else list(types)
    if len(types) != int(n_layer) or set(types) - {MAMBA, ATTENTION}:
        raise ValueError('types names a mixer, %r or %r, for each of the %d '
                         'layers' % (MAMBA, ATTENTION, n_layer))
    a_mult = (DH ** -0.5 if attention_multiplier is None
              else float(attention_multiplier))
    r_mult = float(residual_multiplier)
    L = fluid.layers
    PA = fluid.ParamAttr
    Normal = fluid.initializer.NormalInitializer
    Uniform = fluid.initializer.UniformInitializer

    def vector(name, shape, init):
        return L.create_parameter(shape, 'float32',
                                  attr=PA(name=name, trainable=False),
                                  default_initializer=init)

    def cols(x, lo, hi):
        return L.slice(x, axes=[len(x.shape) - 1], starts=[lo], ends=[hi])

    def norm(x, name, weight=Normal(1.0, 0.1)):
        return L.rms_norm(x, epsilon=norm_eps,
                          param_attr=PA(name=name, trainable=False,
                                        initializer=weight))

    def mamba(b, xn, i, nfd):
        p = 'l%d_ssm_' % i
        state, tail = b.state(i)
        with fluid.name_scope('in_proj'):
            zxd = b.linear(xn, p + 'in_w', DI + XBC + MH, nfd)
            z, xbc, dt = (cols(zxd, 0, DI), cols(zxd, DI, DI + XBC),
                          cols(zxd, DI + XBC, DI + XBC + MH))
        with fluid.name_scope('conv'):
            w = vector(p + 'conv_w', [K, XBC], Normal(0.0, conv_std))
            bias = vector(p + 'conv_b', [XBC], Normal(0.0, 0.1))
            if nfd == 1:
                xbc, tail = L.causal_conv_step(
                    xbc, w, tail, b.rows['block_tables'], bias=bias)
            else:
                xbc, tail = L.causal_conv_chunk(
                    xbc, w, tail, b.rows['start'], b.rows['chunk_len'],
                    b.rows['state_slot'], bias=bias)
        with fluid.name_scope('selective_scan'):
            lo, hi = (math.log(math.expm1(t)) for t in dt_range)
            args = (cols(xbc, 0, DI), dt, cols(xbc, DI, DI + N),
                    cols(xbc, DI + N, XBC),
                    vector(p + 'a_log', [MH],
                           Uniform(*(math.log(a) for a in a_range))),
                    vector(p + 'dt_b', [MH], Uniform(lo, hi)),
                    vector(p + 'd', [MH], Normal(1.0, 0.1)), state)
            if nfd == 1:
                m, state = L.ssd_step(*args, b.rows['block_tables'],
                                      n_head=MH)
            else:
                m, state = L.ssd_chunk(
                    *args, b.rows['start'], b.rows['chunk_len'],
                    b.rows['state_slot'], n_head=MH, sub_chunk=sub_chunk)
        with fluid.name_scope('gated_norm'):
            m = norm(L.swiglu(z, m), p + 'norm_w')
        with fluid.name_scope('out_proj'):
            return b.linear(m, p + 'out_w', D, nfd)

    def attention(b, xn, i, nfd):
        p = 'l%d_' % i
        q = b.linear(xn, p + 'q_w', D, nfd)
        pools = b.write(i, b.linear(xn, p + 'k_w', KV * DH, nfd),
                        b.linear(xn, p + 'v_w', KV * DH, nfd))
        lead = [int(n) for n in q.shape[:-1]]
        # [tile, its even | odd K/V head, that head's queries, half, DH]:
        # `own` is 1 where a head's half of the tile is its K/V head's
        own = L.assign(np.eye(2, dtype=np.float32).reshape(2, 1, 2, 1))
        with fluid.name_scope('pad_heads'):
            q = L.reshape(
                L.elementwise_mul(
                    L.reshape(q, shape=lead + [KV // 2, 2, H // KV, 1, DH]),
                    own, axis=len(lead) + 1),
                shape=lead + [H * 2 * DH])
        a = b.attend(i, q, *pools, n_head=H, n_kv_head=KV // 2,
                     scale=a_mult)
        with fluid.name_scope('unpad_heads'):
            a = L.reshape(
                L.reduce_sum(L.elementwise_mul(
                    L.reshape(a, shape=lead + [KV // 2, 2, H // KV, 2, DH]),
                    own, axis=len(lead) + 1), dim=len(lead) + 3),
                shape=lead + [D])
        return b.linear(a, p + 'o_w', D, nfd)

    def block(b, x, i, nfd, pos):
        p = 'l%d_' % i
        xn = norm(x, p + 'ln1_w')
        if types[i] == MAMBA:
            with fluid.name_scope('state_space'):
                a = mamba(b, xn, i, nfd)
        else:
            with fluid.name_scope('full_attention'):
                a = attention(b, xn, i, nfd)
        h = L.elementwise_add(x, L.scale(a, scale=r_mult))
        ab = b.linear(norm(h, p + 'ln2_w'), p + 'ff_in_w', 2 * int(d_ff),
                      nfd)
        m = b.linear(L.swiglu(cols(ab, 0, int(d_ff)),
                              cols(ab, int(d_ff), 2 * int(d_ff))),
                     p + 'ff_out_w', D, nfd)
        return L.elementwise_add(h, L.scale(m, scale=r_mult))

    def embed(b, ids):
        return L.scale(DecodeSpecBuilder.embed(b, ids),
                       scale=float(embedding_multiplier))

    def logits(b, x):
        seeds = {} if final_norm_std is None else dict(
            weight=Normal(0.0, final_norm_std))
        return L.scale(L.matmul(norm(x, 'final_ln_w', **seeds),
                                b.matrix('embed_w', [vocab, D]),
                                transpose_y=True),
                       scale=1.0 / float(logits_scaling))

    return DecodeSpecBuilder(
        vocab=vocab, d_model=D, kv_width=KV * DH, n_layer=n_layer,
        max_slots=max_slots, max_cache_len=max_cache_len,
        block_size=block_size, chunk_sizes=chunk_sizes,
        num_blocks=num_blocks, eos_id=eos_id,
        kv_cache_dtype=kv_cache_dtype, weights_dtype=weights_dtype,
        init_std=init_std, embed_std=embed_std,
        recurrent={i: {'ssm': ([MH, P, N], state_dtype),
                       'conv': ([K - 1, XBC], 'float32')}
                   for i, t in enumerate(types) if t == MAMBA},
    ).build(block, logits, embed=embed)
