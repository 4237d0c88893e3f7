"""Phi-4-mini-flash-reasoning decode serving (microsoft/Phi-4-mini-flash-
reasoning, model_type phi4flash; the SambaY decoder-hybrid-decoder of Ren et
al., arXiv:2507.06607): a pre-norm decoder without positional encoding whose
SELF-DECODER alternates Mamba-1 layers (a state of fixed size per request)
with differential attention inside a sliding window, ends in one Mamba layer
and one FULL differential-attention layer, and whose CROSS-DECODER alternates
Gated Memory Units — which read the last Mamba layer's scan output at the
same position — with cross attention over the full layer's K/V cache: seven
layers that keep nothing and read ONE cache.

Layer equations (benchmark/reference/phi4_flash.py writes them out; LN is
LayerNorm with bias, x a row of the residual stream; `n_self` = 18 at the
published depth, the first layer of the cross-decoder):

    h = x + Mixer(LN(x));  y = h + W_d(silu(W_g LN(h)) * W_u LN(h))
    logits = E LN(y_L)                       (E the embedding table: tied)
    even i < n_self   Mamba: [u z] = W_in xn;  u <- silu(conv_4(u) + b_c)
        [dt B C] = W_x u;  delta = softplus(W_dt dt + b_dt);  A = -exp(A_log)
        s <- exp(delta A) * s + (delta u) B^T;  m = s C + D u
        out = W_out (m * silu(z))       (layer n_self - 2 hands m on: GMU)
    odd i < n_self    DiffAttn(q, k, v = W xn + b), window on all but the last
    even i >= n_self  GMU:  W_2 (m * silu(W_1 xn)),  m the hand-over AT THE
                      SAME POSITION
    odd i >= n_self   DiffAttn(q = W_q xn + b, the K and V rows layer
                      n_self - 1 cached)

Differential attention (Ye et al., arXiv:2410.05258): the H query heads and
the KV key and KV value heads are split into two halves (`half_heads`); pair
j = 0 .. H/2 - 1 has a query head of each half, which score the group's key
head of their own half (pair j reads group j // (H / KV)) at d_head^-1/2;
each softmax A_1, A_2 is applied to BOTH halves' value heads of the group,
laid side by side (a head of 2 d_head);

    out_j = (1 - l_init) RMSNorm_{2 d_head}(A_1 [v_1 | v_2] - l A_2 [v_1 | v_2])
    l = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + l_init,
    l_init = 0.8 - 0.6 exp(-0.3 i)

and the H/2 outputs of 2 d_head are read as H d_head channels into W_o.

ONE PASS OVER THE CACHE. The four-product form of public code (A_1 v_1, A_1
v_2, A_2 v_1, A_2 v_2 as four attention calls) reads every cached row twice.
Here a K row is stored as KV/2 tiles [k_1g | k_2g] and a V row as KV/2 tiles
[v_1g | v_2g], each 2 d_head wide, and the attention op is handed H query
heads of 2 d_head — a first-half head as [q | 0], a second-half head as [0 |
q] — over KV/2 grouped K/V heads at scale d_head^-1/2: a head's scores are
its own half's, its output is [A v_1g | A v_2g], every cached row is read
once a layer a step, and at d_head 64 the grouped head is 128 wide: whole
lane tiles, which is what the paged kernel (ops/pallas_paged_attention.py)
asks of grouped heads. The zero halves double a product the step is not
bound by. The subtraction, the norm and (1 - l_init) follow outside the op.

WHICH HEADS FORM A HALF is `half_heads` and nothing else: the first half of
the heads is half 1 (stripes), as public implementations chunk them; the
catalog's config does not say. The programs' W_q, W_k and W_v columns are
laid out as the op wants them (`program_heads`); with seeded weights that
is a relabelling of columns, which the reference undoes.

WHAT A LAYER KEEPS (models/decode_spec.py): a window layer its K and V rows
in the window pool; layer n_self - 1 its K and V rows in the pool every
position stays in, which the cross-decoder's attention layers READ
(`shared_pools`) — they and the GMU layers (`no_cache`) keep nothing; a
Mamba layer, PER SLOT and unpaged, `rec_ssm_<i>` [max_slots, d_state,
channels] (`state_dtype`; ops/state_space_ops.py says why it lies so) and the
convolution's tail `rec_conv_<i>` [max_slots, 3, channels] (float32).

THE CROSS-DECODER AT A ROW'S LAST POSITION (`last_only_from`): its layers
keep no state and write no cache, so a chunk program owes them only to the
position whose logits it returns: it runs the self-decoder over the slice
and the cross-decoder over one position a row — the architecture's own
prefill. The step runs all layers.

THE EMBEDDING'S SCALE, AND THE FINAL NORM'S. models/qwen3_next.py's
argument holds here, 32 layers deep: under seeded weights every layer writes
an update of rms 0.4-1.1 whatever it read (the LayerNorm in front makes it
so), an embedding at N(0, init_std) is 1/50 of one, so the first update IS
the stream, layer i's is 1/sqrt(i) of it, and a perturbation — the bfloat16
rounding of every product's operands — grows polynomially with depth (~10 x
over 32 layers; a trained model's updates are a fraction of its stream). The
configuration seeds the table apart (`embed_std`) so that the stream starts
from something EXACT that outweighs the updates. But the head is the table:
it would read a large embedding straight back (the input token's own logit
tens of standard deviations up), and its scale is the logits'. So
`final_norm_std` seeds the final LayerNorm's weight N(0, final_norm_std) —
zero mean: the input token's own logit gets no offset — and small enough for
logits of standard deviation ~1 (final_norm_std x embed_std x sqrt(d_model)),
its bias at init_std of that. The defaults (None) are the plain N(1, 0.1).

Precision as models/olmoe.py: matrices stored in `weights_dtype`, bf16 x
bf16 products with float32 accumulation; residual stream, norms, biases,
convolution, the scan and the queries float32; K and V cached in
`kv_cache_dtype`.
"""
from __future__ import annotations

import math

import numpy as np

import paddle_tpu as fluid

from .decode_spec import DecodeSpecBuilder

MAMBA, WINDOW, FULL, GMU, CROSS = ('mamba', 'window_attention',
                                   'full_attention', 'gated_memory',
                                   'cross_attention')


def layer_types(n_layer, n_self=None):
    """The mixer of each layer: below `n_self` (default n_layer // 2 + 2:
    18 of 32) Mamba on the even and windowed attention on the odd layers,
    the last of them full; from there on Gated Memory Units on the even
    and cross attention on the odd layers."""
    n_self = n_layer // 2 + 2 if n_self is None else int(n_self)
    if not 2 <= n_self <= n_layer or n_self % 2:
        raise ValueError('the self-decoder is a whole number of (Mamba, '
                         'attention) periods inside the model')
    return [(MAMBA if i % 2 == 0 else FULL if i == n_self - 1 else WINDOW)
            if i < n_self else (GMU if i % 2 == 0 else CROSS)
            for i in range(n_layer)]


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def half_heads(n):
    """(the heads of half 1, the heads of half 2) among `n` published
    heads: stripes — the first n / 2, the rest."""
    return list(range(n // 2)), list(range(n // 2, n))


def program_heads(n):
    """The published head each of the programs' `n` head slots holds:
    slot 2 j + s is pair j's head of half s + 1, so that a K or V row is
    tiles [half 1 | half 2] and a query pair lies in one tile's group."""
    first, second = half_heads(n)
    return [h for pair in zip(first, second) for h in pair]


def published_columns(n, d_head):
    """Index array: column c of a PUBLISHED q, k or v projection (n heads
    of d_head) is column published_columns(n, d_head)[c] of the
    programs'."""
    slot = np.argsort(program_heads(n))
    return (slot[:, None] * d_head + np.arange(d_head)[None, :]).reshape(-1)


def build_decode_spec(vocab=128, d_model=64, n_head=4, n_kv_head=2,
                      d_ff=128, n_layer=8, n_self=None, window=16,
                      d_state=4, d_conv=4, expand=2, dt_rank=8,
                      max_slots=4, max_cache_len=96, block_size=8,
                      chunk_sizes=(8, 16), num_blocks=None, eos_id=1,
                      kv_cache_dtype='bfloat16', weights_dtype='bfloat16',
                      state_dtype='float32', norm_eps=1e-5, init_std=0.02,
                      conv_std=0.3, embed_std=None, dt_range=(1e-3, 1e-1),
                      a_range=(1.0, 16.0), final_norm_std=None):
    """The decode program set (defaults: a toy for the cpu tests); the
    spec has models/qwen3_next.py's keys plus 'window' and 'shared_pools'.

    Matrices draw from N(0, init_std) (W_dt from N(0, dt_rank^-1/2), the
    convolution's from N(0, conv_std)), norm weights and D from N(1, 0.1),
    biases and the four lambda vectors from N(0, init_std) / N(0, 0.1),
    A_log uniformly over log(a_range), b_dt uniformly over the inverse
    softplus of `dt_range` (so delta at a zero projection is log-uniform
    over it, as Mamba initialises it), the final LayerNorm as
    `final_norm_std` says (THE EMBEDDING'S SCALE above), the embedding from
    N(0, embed_std)
    (init_std unless given). Names: embed_w, l<i>_{ln1,ln2}_{w,b},
    l<i>_ff_{gate,up,down}_w, Mamba l<i>_ssm_{in_w, conv_w, conv_b, x_w,
    dt_w, dt_b, a_log, d, out_w}, attention l<i>_{q,k,v,o}_{w,b} (cross: q
    and o), l<i>_lambda_{q1,k1,q2,k2}, l<i>_subln_w, GMU l<i>_gmu_{in,out}_w,
    final_ln_{w,b}; pools kv_k_<i> / kv_v_<i>, states rec_ssm_<i> /
    rec_conv_<i>."""
    D, H, KV = int(d_model), int(n_head), int(n_kv_head)
    if D % H or H % KV or KV % 2:
        raise ValueError('n_head must divide d_model and be a multiple of '
                         'n_kv_head, which the two halves share evenly')
    DH = D // H
    DI, N, K, RANK = (int(expand) * D, int(d_state), int(d_conv),
                      int(dt_rank))
    types = layer_types(n_layer, n_self)
    n_self = types.index(GMU) if GMU in types else n_layer
    owner = n_self - 1              # the full layer the cross-decoder reads
    handover = n_self - 2           # the Mamba layer the GMUs read
    L = fluid.layers
    PA = fluid.ParamAttr
    Normal = fluid.initializer.NormalInitializer
    Uniform = fluid.initializer.UniformInitializer

    # what layer `handover` leaves for the GMUs of the program being built:
    # every program meets that layer before its first GMU
    memory = {}

    def vector(name, shape, init):
        return L.create_parameter(shape, 'float32',
                                  attr=PA(name=name, trainable=False),
                                  default_initializer=init)

    def cols(x, lo, hi):
        return L.slice(x, axes=[len(x.shape) - 1], starts=[lo], ends=[hi])

    def ln(x, name, nfd, weight=Normal(1.0, 0.1), bias_std=init_std):
        return L.layer_norm(
            x, begin_norm_axis=nfd, epsilon=norm_eps,
            param_attr=PA(name=name + '_w', trainable=False,
                          initializer=weight),
            bias_attr=PA(name=name + '_b', trainable=False,
                         initializer=Normal(0.0, bias_std)))

    def affine(b, x, name, d_out, nfd):
        return L.elementwise_add(
            b.linear(x, name + '_w', d_out, nfd),
            vector(name + '_b', [d_out], Normal(0.0, init_std)))

    def mamba(b, xn, i, nfd):
        p = 'l%d_ssm_' % i
        state, tail = b.state(i)
        with fluid.name_scope('in_proj'):
            uz = b.linear(xn, p + 'in_w', 2 * DI, nfd)
            u, z = cols(uz, 0, DI), cols(uz, DI, 2 * DI)
        with fluid.name_scope('conv'):
            w = vector(p + 'conv_w', [K, DI], Normal(0.0, conv_std))
            bias = vector(p + 'conv_b', [DI], Normal(0.0, 0.1))
            if nfd == 1:
                u, tail = L.causal_conv_step(
                    u, w, tail, b.rows['block_tables'], bias=bias)
            else:
                u, tail = L.causal_conv_chunk(
                    u, w, tail, b.rows['start'], b.rows['chunk_len'],
                    b.rows['state_slot'], bias=bias)
        with fluid.name_scope('x_proj'):
            dbc = b.linear(u, p + 'x_w', RANK + 2 * N, nfd)
            dt = L.mul(cols(dbc, 0, RANK), L.create_parameter(
                [RANK, DI], weights_dtype,
                attr=PA(name=p + 'dt_w', trainable=False),
                default_initializer=Normal(0.0, RANK ** -0.5)),
                x_num_col_dims=nfd)
            bb, cc = cols(dbc, RANK, RANK + N), cols(dbc, RANK + N,
                                                     RANK + 2 * N)
        with fluid.name_scope('selective_scan'):
            lo, hi = (math.log(math.expm1(t)) for t in dt_range)
            args = (u, dt, bb, cc,
                    vector(p + 'a_log', [N, DI],
                           Uniform(*(math.log(a) for a in a_range))),
                    vector(p + 'dt_b', [DI], Uniform(lo, hi)),
                    vector(p + 'd', [DI], Normal(1.0, 0.1)), state)
            if nfd == 1:
                m, state = L.selective_scan_step(
                    *args, b.rows['block_tables'])
            else:
                m, state = L.selective_scan_chunk(
                    *args, b.rows['start'], b.rows['chunk_len'],
                    b.rows['state_slot'])
        if i == handover:
            memory['m'] = m
        with fluid.name_scope('out_proj'):
            return b.linear(L.swiglu(z, m), p + 'out_w', D, nfd)

    def differential(b, q, i, nfd):
        """The attention of layer i for its queries q [..., H * DH]
        (program_heads' order) over the pools layer i attends."""
        p = 'l%d_' % i
        lead = [int(n) for n in q.shape[:-1]]
        with fluid.name_scope('pad_heads'):
            q = L.reshape(q, shape=lead + [H // 2, 2, DH])
            zero = L.fill_constant(lead + [H // 2, 1, DH], 'float32', 0.0)
            sides = [L.slice(q, axes=[len(lead) + 1], starts=[s],
                             ends=[s + 1]) for s in (0, 1)]
            q = L.reshape(
                L.concat([L.concat([sides[0], zero], axis=len(lead) + 2),
                          L.concat([zero, sides[1]], axis=len(lead) + 2)],
                         axis=len(lead) + 1),
                shape=lead + [H * 2 * DH])
        a = b.attend(i, q, *b.pools(i), n_head=H, n_kv_head=KV // 2,
                     scale=DH ** -0.5)
        with fluid.name_scope('differential'):
            lam = [L.exp(L.reduce_sum(L.elementwise_mul(
                vector(p + 'lambda_q%d' % s, [DH], Normal(0.0, 0.1)),
                vector(p + 'lambda_k%d' % s, [DH], Normal(0.0, 0.1)))))
                for s in (1, 2)]
            lam = L.scale(L.elementwise_sub(lam[0], lam[1]), scale=1.0,
                          bias=lambda_init(i))
            a = L.reshape(a, shape=lead + [H // 2, 2, 2 * DH])
            a1, a2 = (L.slice(a, axes=[len(lead) + 1], starts=[s],
                              ends=[s + 1]) for s in (0, 1))
            a = L.rms_norm(
                L.elementwise_sub(a1, L.elementwise_mul(a2, lam)),
                epsilon=norm_eps,
                param_attr=PA(name=p + 'subln_w', trainable=False,
                              initializer=Normal(1.0, 0.1)))
            a = L.scale(L.reshape(a, shape=lead + [D]),
                        scale=1.0 - lambda_init(i))
        return affine(b, a, p + 'o', D, nfd)

    def block(b, x, i, nfd, pos):
        p = 'l%d_' % i
        xn = ln(x, p + 'ln1', nfd)
        kind = types[i]
        if kind == MAMBA:
            with fluid.name_scope('state_space'):
                a = mamba(b, xn, i, nfd)
        elif kind == GMU:
            with fluid.name_scope('gated_memory'):
                a = b.linear(
                    L.swiglu(b.linear(xn, p + 'gmu_in_w', DI, nfd),
                             b.at_last(memory['m'])),
                    p + 'gmu_out_w', D, nfd)
        elif kind == CROSS:
            with fluid.name_scope('cross_decoder'):
                a = differential(b, affine(b, xn, p + 'q', D, nfd), i, nfd)
        else:
            q = affine(b, xn, p + 'q', D, nfd)
            b.write(i, affine(b, xn, p + 'k', KV * DH, nfd),
                    affine(b, xn, p + 'v', KV * DH, nfd))
            a = differential(b, q, i, nfd)
        h = L.elementwise_add(x, a)
        hn = ln(h, p + 'ln2', nfd)
        m = b.linear(L.swiglu(b.linear(hn, p + 'ff_gate_w', int(d_ff), nfd),
                              b.linear(hn, p + 'ff_up_w', int(d_ff), nfd)),
                     p + 'ff_down_w', D, nfd)
        return L.elementwise_add(h, m)

    def logits(b, x):
        seeds = {} if final_norm_std is None else dict(
            weight=Normal(0.0, final_norm_std),
            bias_std=init_std * final_norm_std)
        return L.matmul(ln(x, 'final_ln', 1, **seeds),
                        b.matrix('embed_w', [vocab, D]), transpose_y=True)

    return DecodeSpecBuilder(
        vocab=vocab, d_model=D, kv_width=KV * DH, n_layer=n_layer,
        max_slots=max_slots, max_cache_len=max_cache_len,
        block_size=block_size, chunk_sizes=chunk_sizes,
        num_blocks=num_blocks, eos_id=eos_id,
        kv_cache_dtype=kv_cache_dtype, weights_dtype=weights_dtype,
        init_std=init_std, embed_std=embed_std,
        window_layers=[i for i, t in enumerate(types) if t == WINDOW],
        window=window,
        recurrent={i: {'ssm': ([N, DI], state_dtype),
                       'conv': ([K - 1, DI], 'float32')}
                   for i, t in enumerate(types) if t == MAMBA},
        shared_pools={i: owner for i, t in enumerate(types) if t == CROSS},
        no_cache=[i for i, t in enumerate(types) if t == GMU],
        last_only_from=n_self if n_self < n_layer else None,
    ).build(block, logits)
