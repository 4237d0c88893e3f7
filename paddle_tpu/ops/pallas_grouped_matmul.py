"""The routed FFN's grouped matmul as a Pallas TPU kernel (ISSUE 41).

`moe_topk_ffn`'s TPU lowering of its three grouped products: rows [M, K]
sorted by expert, weights [E, K, N], sizes [E] -> [M, N] float32, group e
multiplying its own contiguous rows — `lax.ragged_dot`'s contract as the
op uses it. XLA's TPU rewrite of `ragged_dot` pays ~7 us a group beyond
its bytes (PERF.md, PR 41); here a group boundary is a scalar read.

Weights are STREAMED, groups are not launched. The grid walks the
VISITS — (group, row tile) pairs in row order, made from `cumsum(sizes)`
once a call and handed over by scalar prefetch — and each visit's
[K, tn] weight tile comes through VMEM by the pipeline's double buffer:
group e + 1's tile is in flight while group e's multiplies. A group that
spans several row tiles visits them back to back on one copy of its
tile. Row tiles behind `sum(sizes)` (the pairs of experts held
elsewhere, sorted last) belong to no visit: they are neither read nor
written.

EVERY held expert's weights pass through once a call — a group that
holds no row has one visit too, whose mask holds no row. That is what
XLA's rewrite read and what the benchmark's floor counts
(`moe_expert_bytes`: the experts a step is EXPECTED to hit under uniform
routing, 15.7 of 16 in k_exaone_236b_a23b.longgen_closed). Skipping the
empty groups' visits is one `jnp.where` in `_visits`, and on the chip it
read that cell's step at 10.99 ms against 16.07 — and its
`moe_experts_roofline` at 142.9 %: with seeded weights the tokens of a
batch choose alike, a step hits ~10 of the 16, and a share of a floor
that the kernel undercuts is no reading. The skip is for the PR after a
`benchmark` PR makes the floor count the experts a traced step really
hit (PERF.md 7 iv).

An output tile is shared by the groups whose rows lie in it. Its visits
are consecutive, so it stays in VMEM: the first zeroes it, each adds its
own rows' products under a row mask (a select, so whatever the other
rows hold — NaN included — never reaches a held row), and it is written
back once.

Tile widths come from the shapes. K is never split (one product a visit,
float32 out of the MXU: no partial sums to order); a weight tile is the
widest [K, tn] — tn a multiple of 128 lanes dividing N — inside
_WEIGHT_TILE_BYTES (2,048 x 768 and 2,048 x 1,024 whole, 6,144 x 2,048 in
eight); with several column tiles the columns are the OUTER grid axis, so
that an output tile's visits stay consecutive. A row tile is 128 rows.

Contract (ragged_dot's, as the op reads it): operands bfloat16, products
summed in float32, a row's result a function of that row and its group's
weights alone; rows of a visited tile that no group holds read zero; rows
of tiles behind `sum(sizes)` are not the op's to read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.core import Primitive
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import mlir

# rows a row tile: the MXU's own height. 256 read 1-4 % slower on the
# chip at 4,096 rows in all three cells' widths (PERF.md, PR 41): a group
# of a slice holds 16-64 rows, and a taller tile multiplies more rows
# that are not the visit's
_ROW_TILE = 128
# one [K, tn] weight tile; the pipeline holds two. 4 and 8 MiB read the
# same on the chip, 2 MiB 1-10 % slower but for 6,144 x 2,048 at decode
# rows (3 % faster: a shorter first copy, which nothing hides)
_WEIGHT_TILE_BYTES = 4 << 20
# two weight tiles, two row tiles, two output tiles, and room
_VMEM_LIMIT_BYTES = 32 << 20


def ragged_dot(rows, w, sizes):
    """The other body, and the reference: lax.ragged_dot with float32
    sums, as moe_topk_ffn has always called it."""
    return lax.ragged_dot(rows, w, sizes,
                          preferred_element_type=jnp.float32)


def _column_tile(k, n, itemsize):
    """Columns a weight tile: the widest multiple of 128 that divides N
    and keeps K rows of it inside _WEIGHT_TILE_BYTES, or None where 128
    columns already pass it."""
    widths = [tn for tn in range(n, 0, -128)
              if n % tn == 0 and k * tn * itemsize <= _WEIGHT_TILE_BYTES]
    return widths[0] if widths else None


def refuses(rows, w, sizes):
    """Why `grouped_matmul` cannot take these operands — the name of the
    first rule they break — or None: a trace that is not sharded (a
    pallas_call has no partitioning rule), rows [M, K], weights
    [E, K, N] and sizes [E] int32, both operands bfloat16 (the MXU's own
    product; float32 operands would take a precision the two bodies do
    not share), K and N whole 128-lane tiles, and a K whose 128-column
    weight tile fits the kernel's VMEM budget. Anything else keeps
    lax.ragged_dot."""
    from ..parallel.mesh import current_trace_mesh
    if current_trace_mesh() is not None:
        return 'a sharded trace'
    if (rows.ndim != 2 or w.ndim != 3 or sizes.ndim != 1
            or rows.shape[1] != w.shape[1] or sizes.shape[0] != w.shape[0]):
        return 'operands that are not rows [M, K], weights [E, K, N] ' \
               'and sizes [E]'
    if rows.dtype != jnp.bfloat16 or w.dtype != jnp.bfloat16:
        return 'operands that are not bfloat16'
    if not jnp.issubdtype(sizes.dtype, jnp.integer):
        return 'sizes that are not integers'
    _, k, n = w.shape
    if k % 128 or n % 128:
        return 'a width that is no multiple of 128'
    if _column_tile(k, n, w.dtype.itemsize) is None:
        return 'rows too wide for one weight tile'
    return None


def _visits(sizes, tm, n_tile, n_visit):
    """The grid's walk, from the group sizes: (group [V], row tile [V],
    first row [E], end row [E], visits) int32. Visit v multiplies the
    rows of group[v] that lie in row tile tile[v]; groups in order, a
    group's tiles in order. A group that holds no row has ONE visit, at
    the tile its rows would start in: its mask holds no row and its
    weights pass through VMEM like any other's (the module's docstring
    says why). Entries from `visits` on repeat the last visit (the grid
    does not reach them)."""
    sizes = sizes.astype(jnp.int32)
    end = jnp.cumsum(sizes)
    start = end - sizes
    first = jnp.minimum(start // tm, n_tile - 1)
    count = jnp.where(sizes > 0, (end - 1) // tm - first + 1, 1)
    upto = jnp.cumsum(count)                 # visits of groups 0 .. e
    total = upto[-1]
    at = jnp.minimum(jnp.arange(n_visit, dtype=jnp.int32), total - 1)
    # compare-and-sum over [V, E], not searchsorted and takes: on a TPU
    # a gather is a program of its own
    group = jnp.sum(at[:, None] >= upto[None, :], axis=1, dtype=jnp.int32)
    mine = group[:, None] == jnp.arange(sizes.shape[0])[None, :]
    tile = at + jnp.sum(
        jnp.where(mine, (first - (upto - count))[None, :], 0), axis=1)
    return group, tile, start, end, total


def _kernel(group_ref, tile_ref, start_ref, end_ref, x_ref, w_ref, o_ref):
    v = pl.program_id(1)
    g = group_ref[v]
    t = tile_ref[v]
    tm = x_ref.shape[0]

    @pl.when((v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    prod = jnp.dot(x_ref[...], w_ref[0],
                   preferred_element_type=jnp.float32)          # [tm, tn]
    row = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= start_ref[g]) & (row < end_ref[g])
    o_ref[...] += jnp.where(mine, prod, 0.0)


def grouped_matmul(rows, w, sizes, *, interpret=False):
    """rows [M, K] and w [E, K, N] bfloat16, sizes [E] int32 -> [M, N]
    float32: rows sum(sizes[:e]) .. sum(sizes[:e + 1]) times w[e].
    `refuses` must give None."""
    m, k = rows.shape
    n_group, _, n = w.shape
    tm, tn = _ROW_TILE, _column_tile(k, n, w.dtype.itemsize)
    if m % tm:
        # a row count no tile divides: whole tiles, the pad behind every
        # group (no cell's program has one)
        pad = tm - m % tm
        return grouped_matmul(jnp.pad(rows, ((0, pad), (0, 0))), w, sizes,
                              interpret=interpret)[:m]
    # every group visits the tiles it owns a first row of, and at most
    # one more that it shares with the group in front (an empty group:
    # one): the length of the walk's arrays. The grid itself stops at the
    # visits there are, its bound a run-time scalar: behind them every
    # step of a column tile would hold back the first copy of the next
    # (3-5 % of 6,144 x 2,048's eight column tiles on the chip, nothing
    # where a matrix is one tile)
    n_visit = m // tm + n_group - 1
    *walk, visits = _visits(sizes, tm, m // tm, n_visit)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, visits),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, group, tile, *_: (tile[v], 0)),
                pl.BlockSpec((1, k, tn),
                             lambda j, v, group, tile, *_: (group[v], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, group, tile, *_: (tile[v], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name='moe_grouped_matmul',
        interpret=interpret,
    )(*walk, rows, w)


# The platform switch, pallas_paged_attention.py's idiom (its comment
# says why not lax.platform_dependent): a primitive whose TPU rule lowers
# the kernel and whose default rule lowers lax.ragged_dot, so a cpu+tpu
# jax.export holds both bodies and a program lowered for one platform
# holds that platform's alone.
_grouped_p = Primitive('moe_grouped_matmul')
_grouped_p.def_abstract_eval(
    lambda rows, w, sizes: jax.core.ShapedArray(
        (rows.shape[0], w.shape[2]), jnp.float32))
_grouped_p.def_impl(lambda *args: jax.jit(_grouped_p.bind)(*args))
mlir.register_lowering(
    _grouped_p, mlir.lower_fun(grouped_matmul, multiple_results=False),
    platform='tpu')
mlir.register_lowering(
    _grouped_p, mlir.lower_fun(ragged_dot, multiple_results=False))


@jax.custom_vjp
def kernel_or_ragged_dot(rows, w, sizes):
    """`grouped_matmul` where the program runs on a TPU, lax.ragged_dot
    anywhere else; the gradient is ragged_dot's on every platform."""
    return _grouped_p.bind(rows, w, sizes)


def _fwd(rows, w, sizes):
    return kernel_or_ragged_dot(rows, w, sizes), (rows, w, sizes)


def _bwd(saved, g):
    rows, w, sizes = saved
    _, pull = jax.vjp(functools.partial(ragged_dot, sizes=sizes), rows, w)
    return pull(g) + (None,)


kernel_or_ragged_dot.defvjp(_fwd, _bwd)
