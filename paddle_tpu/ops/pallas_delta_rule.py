"""The gated delta rule's decode STEP as a Pallas TPU kernel (ISSUE 42).

`gated_delta_step`'s TPU lowering: one token a slot against the per-slot
recurrent state [S, H, dk, dv] float32 (ops/linear_attention_ops.py has
the mathematics and delta_step, the other body and the reference). XLA
makes two fusions of that expression and passes over the state three
times — once to take S^T k and S^T q, and, because delta depends on
S^T k, once more to read the state it then writes (PERF.md 6, PR 42).
Here a program holds ONE slot's whole state in fast memory (all heads:
2 MiB at 32 x 128 x 128), takes both products from it, and writes the new
state from the copy it holds: the state crosses the memory bus twice, which
is what the algorithm needs and what the benchmark's floor counts
(`linear_state_bytes`).

Layout. A head's state has d_k on sublanes and d_v on lanes, so both
products are sums over SUBLANES of the state times the key (the query) as
a COLUMN, and the update is that column times delta as a row. The keys and
queries come in HEADS-ON-LANES, [S, dk, 2 H] (the keys' heads, then the
queries'), so that a head's key is a static lane of the block; v, the decay
e^g and beta come as rows [S, H, dv] (the two per-head scalars broadcast
over dv by the caller: 16 KiB a slot beside a 2 MiB state).

A PER-CHANNEL decay (g [S, H, dk]: Kimi Delta Attention, models/
kimi_linear.py) scales the state's ROWS, and d_k lies on sublanes: e^g comes
as a third COLUMN block beside the keys and the queries, [S, dk, 3 H], and
multiplies the state once as it is read — both products and the new state
take the decayed copy — instead of the [1, dv] row that multiplied the
sums. The same two crossings of the bus.

An idle row (live == 0) copies its state through and computes nothing: the
block is written back whatever the program does.

Everything is float32 on the vector unit — no matrix product — so the
kernel computes the jnp body's own sums in another order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import mlir

_LANES, _SUBLANES = 128, 8
# one slot's state in and out, each held twice by the pipeline, and room
_VMEM_LIMIT_BYTES = 32 << 20
_STATE_BLOCK_BYTES = 4 << 20


def refuses(state, per_channel=False):
    """None where the kernel takes a state of this shape and dtype, else
    why not (the op then lowers the jnp body on every platform).
    `per_channel`: the decay comes as a third column block."""
    _, n_head, dk, dv = state.shape
    if state.dtype != jnp.float32:
        return 'the state is %s, not float32' % state.dtype
    if dk % _SUBLANES or dv % _LANES:
        return ('a head\'s state [%d, %d] is no whole number of (%d, %d) '
                'tiles' % (dk, dv, _SUBLANES, _LANES))
    if (2 + bool(per_channel)) * n_head > _LANES:
        return '%d heads\' keys and queries%s do not fit %d lanes' % (
            n_head, ' and decays' * bool(per_channel), _LANES)
    if n_head * dk * dv * 4 > _STATE_BLOCK_BYTES:
        return 'a slot\'s state is over %d bytes' % _STATE_BLOCK_BYTES
    return None


def _kernel(live_ref, kq_ref, v_ref, decay_ref, beta_ref, s_ref, o_ref,
            so_ref, *, n_head):
    slot = pl.program_id(0)

    @pl.when(live_ref[slot] == 0)
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[slot] != 0)
    def _():
        kq = kq_ref[0]                                   # [dk, 2 H]
        for h in range(n_head):
            state = s_ref[0, h]                          # [dk, dv]
            k = kq[:, h:h + 1]                           # [dk, 1]
            q = kq[:, n_head + h:n_head + h + 1]
            decay = decay_ref[0, h:h + 1, :]             # [1, dv]
            m = jnp.sum(state * k, axis=0, keepdims=True) * decay
            sq = jnp.sum(state * q, axis=0, keepdims=True) * decay
            delta = beta_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - m)
            o_ref[0, h:h + 1, :] = sq + jnp.sum(k * q, axis=0,
                                                keepdims=True) * delta
            so_ref[0, h] = state * decay + k * delta


def _channel_kernel(live_ref, kqd_ref, v_ref, beta_ref, s_ref, o_ref,
                    so_ref, *, n_head):
    """_kernel under a per-channel decay: the third column block of
    `kqd_ref` [dk, 3 H] is e^g, and scales the state's rows as they are
    read."""
    slot = pl.program_id(0)

    @pl.when(live_ref[slot] == 0)
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live_ref[slot] != 0)
    def _():
        kqd = kqd_ref[0]                                 # [dk, 3 H]
        for h in range(n_head):
            k = kqd[:, h:h + 1]                          # [dk, 1]
            q = kqd[:, n_head + h:n_head + h + 1]
            state = s_ref[0, h] * kqd[:, 2 * n_head + h:2 * n_head + h + 1]
            m = jnp.sum(state * k, axis=0, keepdims=True)
            sq = jnp.sum(state * q, axis=0, keepdims=True)
            delta = beta_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - m)
            o_ref[0, h:h + 1, :] = sq + jnp.sum(k * q, axis=0,
                                                keepdims=True) * delta
            so_ref[0, h] = state + k * delta


def delta_step(q, k, v, g, beta, state, live, *, interpret=False):
    """linear_attention_ops.delta_step with the idle rows' states left as
    they are: q, k [S, H, dk], v [S, H, dv], g, beta [S, H] (g [S, H, dk]:
    a per-channel decay), state [S, H, dk, dv] float32, live [S] bool ->
    (o [S, H, dv], new state). `refuses` must give None."""
    n_slot, n_head, dk, dv = state.shape
    per_channel = g.ndim == k.ndim
    rows = (n_slot, n_head, dv)
    as_rows = lambda x: jnp.broadcast_to(x[..., None], rows)
    # heads on lanes, [S, dk, 2 H]: the keys' heads then the queries' (a
    # per-channel decay: [S, dk, 3 H], e^g's behind them); the per-head
    # scalars as rows: the decay (where it is one) and beta
    if per_channel:
        columns, scalars = [k, q, jnp.exp(g)], [as_rows(beta)]
    else:
        columns, scalars = [k, q], [as_rows(jnp.exp(g)), as_rows(beta)]
    kq = jnp.concatenate(columns, axis=1).transpose(0, 2, 1)
    row_spec = pl.BlockSpec((1, n_head, dv), lambda s, live: (s, 0, 0))
    state_spec = pl.BlockSpec((1, n_head, dk, dv),
                              lambda s, live: (s, 0, 0, 0))
    o, new = pl.pallas_call(
        functools.partial(_channel_kernel if per_channel else _kernel,
                          n_head=n_head),
        out_shape=(jax.ShapeDtypeStruct(rows, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_slot,),
            in_specs=[
                pl.BlockSpec((1, dk, len(columns) * n_head),
                             lambda s, live: (s, 0, 0)),
                row_spec] + [row_spec] * len(scalars) + [state_spec],
            out_specs=(row_spec, state_spec)),
        # operands count the prefetched scalar: the state is the last
        input_output_aliases={3 + len(scalars): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name='gated_delta_step',
        interpret=interpret,
    )(live.astype(jnp.int32), kq, v, *scalars, state)
    return o, new


def jnp_step(q, k, v, g, beta, state, live):
    """The other body, and the reference: the expression XLA fuses."""
    from .linear_attention_ops import _where, delta_step as body
    o, new = body(q, k, v, g, beta, state.astype(jnp.float32))
    return o, _where(live[:, None, None, None], new.astype(state.dtype),
                     state)


# The platform switch, pallas_grouped_matmul.py's idiom: a primitive whose
# TPU rule lowers the kernel and whose default rule lowers the jnp body, so
# a cpu+tpu jax.export holds both and a program lowered for one platform
# holds that platform's alone.
_step_p = Primitive('gated_delta_step')
_step_p.multiple_results = True
_step_p.def_abstract_eval(
    lambda q, k, v, g, beta, state, live: (
        jax.core.ShapedArray(v.shape, jnp.float32),
        jax.core.ShapedArray(state.shape, state.dtype)))
_step_p.def_impl(lambda *args: jax.jit(_step_p.bind)(*args))
mlir.register_lowering(
    _step_p, mlir.lower_fun(delta_step, multiple_results=True),
    platform='tpu')
mlir.register_lowering(
    _step_p, mlir.lower_fun(jnp_step, multiple_results=True))


def kernel_or_jnp(q, k, v, g, beta, state, live):
    """`delta_step` where the program runs on a TPU, the jnp body anywhere
    else."""
    return _step_p.bind(q, k, v, g, beta, state, live)
