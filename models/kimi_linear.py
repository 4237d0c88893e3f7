"""Kimi Linear decode serving (moonshotai/Kimi-Linear-48B-A3B-Instruct,
model_type kimi_linear; Kimi Linear, arXiv:2510.26692): a pre-norm decoder
whose token mixer is KIMI DELTA ATTENTION (KDA: a gated delta rule whose
decay is a VECTOR over a head's key channels — a state of fixed size per
request, no cache) in three layers of every four and LATENT attention with
NO position term (MLA, `mla_use_nope`, a full-rank query) in the fourth; the
leading layer's FFN is a dense SwiGLU, every other a sigmoid top-k router
with a selection bias over routed experts plus a shared expert. The first
artifact that holds a recurrent state and a latent pool together
(models/decode_spec.py: `recurrent` and `v_width` in one builder).

Layer equations (benchmark/reference/kimi_linear.py writes them out with
each departure; N is RMSNorm, x a row of the residual stream, layer i
1-based):

    h = x + Mixer(N(x));  y = h + FFN(N(h));  logits = W_head N(y_L)
    KDA (i not in full_attn_layers), xn = N(x), 32 heads, dk = dv = 128:
        q = silu(conv4(W_q xn)); k = silu(conv4(W_k xn)); v = silu(conv4(W_v xn))
            three causal depthwise convolutions; ONE carried tail holds the
            last 3 inputs of all 3 x 4,096 channels
        q_h <- q_h / |q_h| / sqrt(dk);  k_h <- k_h / |k_h|
        g_h = -exp(A_log_h) softplus((W_fb W_fa xn)_h + dt_bias_h)  in R^dk
        beta_h = sigmoid(w_b,h . xn)
        S_h <- Diag(e^{g_h}) S_h;  S_h <- S_h + beta_h k_h (v_h - S_h^T k_h)^T
        o_h = S_h^T q_h                     (ops/linear_attention_ops.py:
                                             gated_delta_step / _chunk with
                                             a PER-CHANNEL decay)
        y_h = N(o_h; w[dv]) * sigmoid((W_gb W_ga xn)_h);  out = W_o [y_h]
    MLA (i in full_attn_layers): models/joyai_llm_flash.py latent_attention
        with q_lora_rank None and rope_theta None — q = W_q xn (one
        product), the cache row [N(c) | k_p] with k_p 64 UNROTATED channels,
        served ABSORBED through the latent pool and the latent paged kernel.
    FFN: layer 1 dense SwiGLU; else models/joyai_llm_flash.py
        sigmoid_routed_ffn (models/exaone_moe.py's routed layer op for op).

SCOPES (benchmark/layer_metrics read the device trace by them):
linear_attention/{in_proj, conv, decay_proj, delta_rule, gate_proj,
gated_norm, out_proj} a KDA layer — `delta_rule` holds gated_delta_step in
the step and gated_delta_chunk in a chunk program — and
latent_attention/{q_lora, kv_down, q_absorb, v_expand} around
kv_block_attention a MLA layer. `q_lora` holds the query projection
WHATEVER ITS RANK: with q_lora_rank null it is one product and no norm, so
latent_proj_device_share stays the whole of the projections around the
pages. moe_topk_ffn/... and shared_expert as in every routed model.

WHAT A LAYER KEEPS (models/decode_spec.py): a MLA layer ONE latent pool
kv_c_<i>, block-paged, rows row_width(kv_lora_rank, 64) = 640 wide (576
values); a KDA layer, PER SLOT and unpaged, rec_state_<i> [max_slots, 32,
128, 128] (`state_dtype`, float32) and rec_conv_<i> [max_slots, 3, 12288]
float32. The step runs the recurrence from the old state (a Pallas kernel
on a TPU: ops/pallas_delta_rule.py, e^g a third column block), the chunk
programs the chunked form from a carried state to a carried state (jnp:
ops/linear_attention_ops.py delta_chunk_channels says what bounds its
exponent).

THE DECAY'S SEEDS. A_log [heads] is drawn uniformly so that a head's
per-token decay e^g at f + dt_bias = 0 spans `decay` (models/qwen3_next.py
decay_log_range); dt_bias [heads * dk] from N(0, dt_std) is what makes the
decay differ from channel to channel before the token does (W_fb W_fa xn
adds ~N(0, 0.2) at init_std 0.02). At decay (0.9, 0.999) and dt_std 0.5 a
channel's e^g lies in 0.55-0.9999 a token (softplus over +-6 sigma).

One chip's SHARE of an expert-parallel deployment, as models/exaone_moe.py;
the embedding's own scale, as models/qwen3_next.py (THE EMBEDDING'S SCALE:
a KDA mixer's update is O(1) whatever it read, its head norm makes it so).

Precision as models/olmoe.py: matrices stored in `weights_dtype`, bf16 x
bf16 products with float32 accumulation; residual stream, norms, router,
convolution, decay and the delta rule float32; the latent row cached in
`kv_cache_dtype`.
"""
from __future__ import annotations

import paddle_tpu as fluid

from .decode_spec import DecodeSpecBuilder
from .joyai_llm_flash import latent_attention, row_width, sigmoid_routed_ffn
from .qwen3_next import decay_log_range

KDA, MLA = 'linear_attention', 'latent_attention'


def layer_types(n_layer, full_attn_layers):
    """Layer i (0-based) is MLA where i + 1 is in the published, 1-based
    `full_attn_layers`, else KDA."""
    full = {int(i) for i in full_attn_layers}
    return [MLA if i + 1 in full else KDA for i in range(n_layer)]


def build_decode_spec(vocab=128, d_model=64, n_layer=4,
                      full_attn_layers=(4,), kda_heads=2, kda_head_dim=8,
                      conv_width=4, n_head=4, kv_lora_rank=32,
                      d_nope=16, d_rope=8, d_v=16, d_dense=96,
                      first_dense=1, n_expert=16, n_held=None,
                      expert_offset=0, d_expert=32, top_k=4, n_shared=1,
                      routed_scaling_factor=2.446, norm_topk_prob=True,
                      max_slots=4, max_cache_len=96, block_size=8,
                      chunk_sizes=(8, 16), num_blocks=None, eos_id=1,
                      kv_cache_dtype='bfloat16', weights_dtype='bfloat16',
                      state_dtype='float32', rms_eps=1e-5, init_std=0.02,
                      bias_std=0.01, conv_std=0.3, decay=(0.9, 0.999),
                      dt_std=0.5, embed_std=None):
    """The decode program set (defaults: a toy for the cpu tests); the
    spec has models/joyai_llm_flash.py's keys ('cache_kind' latent) plus
    'recurrent' (the KDA layers' per-slot states).

    The two low-rank side projections (decay and output gate) are as
    wide as a head, as published (128). Weights draw from N(0, init_std), norm weights from N(1, 0.1), the
    convolutions' from N(0, conv_std), A_log and dt_bias as THE DECAY'S
    SEEDS says, the router's selection bias from N(0, bias_std), the
    embedding from N(0, embed_std) (init_std unless given). Names: embed_w,
    l<i>_{input_norm_w, post_attn_norm_w}, KDA layers l<i>_kda_{q_w, k_w,
    v_w, b_w, q_conv_w, k_conv_w, v_conv_w, f_a_w, f_b_w, a_log, dt_bias,
    g_a_w, g_b_w, norm_w, o_w}, MLA layers l<i>_{q_w, kv_a_w, kv_a_norm_w,
    kv_b_w, o_w}, dense l<i>_ff_{gate,up,down}_w, routed l<i>_moe_{router,
    router_bias, gate, up, down} and l<i>_shared_{gate,up,down}_w,
    final_norm_w, lm_head_w; pools kv_c_<i> (MLA layers), states
    rec_state_<i> / rec_conv_<i> (KDA layers)."""
    D, HK, DK, K = int(d_model), int(kda_heads), int(kda_head_dim), \
        int(conv_width)
    R, DR = int(kv_lora_rank), int(d_rope)
    held = int(n_expert if n_held is None else n_held)
    if not 1 <= top_k <= n_expert:
        raise ValueError('top_k must be in [1, n_expert]')
    types = layer_types(n_layer, full_attn_layers)
    W = HK * DK                       # the width of q, of k and of v
    L = fluid.layers
    PA = fluid.ParamAttr
    Normal = fluid.initializer.NormalInitializer

    def vector(name, shape, init):
        return L.create_parameter(shape, 'float32',
                                  attr=PA(name=name, trainable=False),
                                  default_initializer=init)

    def cols(x, lo, hi):
        return L.slice(x, axes=[len(x.shape) - 1], starts=[lo], ends=[hi])

    def low_rank(b, xn, prefix, nfd):
        return b.linear(b.linear(xn, prefix + 'a_w', DK, nfd),
                        prefix + 'b_w', W, nfd)

    def kda(b, xn, i, nfd):
        p = 'l%d_kda_' % i
        lead = [int(n) for n in xn.shape[:-1]]
        state, tail = b.state(i)
        with fluid.name_scope('in_proj'):
            u = L.concat([b.linear(xn, p + n, W, nfd)
                          for n in ('q_w', 'k_w', 'v_w')], axis=len(lead))
            beta_in = b.linear(xn, p + 'b_w', HK, nfd)
        with fluid.name_scope('conv'):
            # three depthwise convolutions side by side are one over their
            # channels: one op, one carried tail
            w = L.concat([vector(p + n, [K, W], Normal(0.0, conv_std))
                          for n in ('q_conv_w', 'k_conv_w', 'v_conv_w')],
                         axis=1)
            if nfd == 1:
                u, tail = L.causal_conv_step(u, w, tail,
                                             b.rows['block_tables'])
            else:
                u, tail = L.causal_conv_chunk(
                    u, w, tail, b.rows['start'], b.rows['chunk_len'],
                    b.rows['state_slot'])
            q, k, v = cols(u, 0, W), cols(u, W, 2 * W), cols(u, 2 * W, 3 * W)
        with fluid.name_scope('decay_proj'):
            f = low_rank(b, xn, p + 'f_', nfd)
        with fluid.name_scope('delta_rule'):
            a_log = vector(p + 'a_log', [HK], fluid.initializer.
                           UniformInitializer(*decay_log_range(decay)))
            dt_bias = vector(p + 'dt_bias', [W], Normal(0.0, dt_std))
            if nfd == 1:
                o, state = L.gated_delta_step(
                    q, k, v, f, beta_in, a_log, dt_bias, state,
                    b.rows['block_tables'], HK, HK)
            else:
                o, state = L.gated_delta_chunk(
                    q, k, v, f, beta_in, a_log, dt_bias, state,
                    b.rows['start'], b.rows['chunk_len'],
                    b.rows['state_slot'], HK, HK)
        with fluid.name_scope('gate_proj'):
            gate = L.sigmoid(low_rank(b, xn, p + 'g_', nfd))
        with fluid.name_scope('gated_norm'):
            y = L.reshape(b.norm(L.reshape(o, shape=lead + [HK, DK]),
                                 p + 'norm_w'), shape=lead + [W])
            y = L.elementwise_mul(y, gate)
        with fluid.name_scope('out_proj'):
            return b.linear(y, p + 'o_w', D, nfd)

    def block(b, x, i, nfd, pos):
        p = 'l%d_' % i
        xn = b.norm(x, p + 'input_norm_w')
        if types[i] == MLA:
            with fluid.name_scope('latent_attention'):
                a = latent_attention(
                    b, xn, i, nfd, pos, n_head=n_head, q_lora_rank=None,
                    kv_lora_rank=R, d_nope=d_nope, d_rope=DR, d_v=d_v,
                    rope_theta=None)
        else:
            with fluid.name_scope('linear_attention'):
                a = kda(b, xn, i, nfd)
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
        rms_eps=rms_eps, init_std=init_std, embed_std=embed_std, v_width=R,
        recurrent={i: {'state': ([HK, DK, DK], state_dtype),
                       'conv': ([K - 1, 3 * W], 'float32')}
                   for i, t in enumerate(types) if t == KDA}
    ).build(block, logits)
