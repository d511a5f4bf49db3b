"""The expert layer's grouped matmuls as Pallas kernels of ours.

``parallel/moe.py`` keeps an expert layer's rows SORTED by expert: ``lhs``
(n, K), of which the first ``sum(group_sizes)`` count, and multiplies
each group's rows by its expert's panel ``rhs[g]`` (K, N).
``grouped_matmul`` has ``jax.lax.ragged_dot``'s contract: operands as
they come, float32 accumulation, ONE rounding to the rows' dtype, and
the rows past the last group left UNWRITTEN. It is differentiated by one
``custom_vjp``: the input gradient is the same kernel reading the panel
transposed (``d_out @ rhs[g]^T``), the weight gradient a second kernel
(``lhs_g^T @ d_out_g``, a float32 accumulator a tile of one group's
panel, zeros for a group without rows), handed back in the panels'
dtype.

The walk. Rows go by in tiles of ``tm``; a VISIT is a (group, row tile)
in which the group owns rows. ``plan`` lists the visits on the device
from ``group_sizes``, group by group, so that consecutive visits of one
group keep the panel's block index (Pallas does not fetch it again) and
a tile that holds a boundary is visited once a group, the other group's
rows masked (a select: what a dead row holds is never multiplied into a
live one). The grid's extent is the number of LIVE visits, a program
value: the dead half of a held layer's prefix costs no grid step, and
neither do the dead seven eighths of its whole length. A group without
rows is visited once, everything masked: the weight gradient's kernel
writes its zeros there.

Tiles follow the shape alone (``_ROW_TILE``, ``_column_tile``); a shape
they do not divide (``divides``) is ``lax.ragged_dot``'s, which is also
what the kernels are tested against.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.jax.introspect import (
    KERNEL_MOE_GROUPED,
    KERNEL_MOE_GROUPED_DW,
)
from horovod_tpu.ops import pallas_attention
from horovod_tpu.ops.pallas_attention import _LANES, _NN, _NT
from horovod_tpu.utils import metrics as _metrics
from horovod_tpu.utils.timeline import trace_span

# Counted at trace time: the grouped matmuls one traced expert layer
# makes, by kind (``forward``, ``input_grad``, ``weight_grad``) and by
# what makes them: ``kernel`` (this module) or ``xla``
# (``lax.ragged_dot`` where the tiles do not divide the shape; jax
# derives that one's two gradients itself, so only its ``forward`` is
# counted).
M_GROUPED_MATMULS = _metrics.counter(
    "hvd_moe_grouped_matmuls_total",
    "Grouped matmuls per traced expert layer, by kind and by what makes "
    "them (counted at trace time, not per device step).", ("kind", "via"))

_TN = (((0,), (0,)), ((), ()))   # a^T . b
# What the calls may ask Mosaic for, of a v5e's 128 MiB of VMEM.
_VMEM_DEFAULT = 16 << 20
_VMEM_MOST = 100 << 20


# Rows a tile. A boundary costs a visit, a tile's worth of rows multiplied
# and masked, so small tiles waste less where boundaries fall inside
# tiles; a tile's pieces pay the MXU's fill and drain once each, so large
# tiles amortise more. Kernel alone on a v5e (PERF.md, PR 43; ms a call,
# bf16, M 2048, a routing at rest: the balanced share within 5%; forward
# up-projection | its weight gradient; ``lax.ragged_dot``, then the
# kernels at 128 and 256 rows a tile): LFM2 (n 32,768, 8 groups of 2,048
# live rows, F 1792) 1.425 | 1.532, 0.856 | 0.770, 0.795 | 0.762; GLM
# (8,192, 8 of 512, F 1536) 0.330 | 0.397, 0.240 | 0.239, 0.229 | 0.241;
# Trinity (16,384, 16 of 512, F 1024) 0.452 | 0.565, 0.338 | 0.331,
# 0.319 | 0.337; OLMoE (32,768 all live, 64 of 512, F 1024) 1.898 |
# 2.346, 1.352 | 1.323, 1.282 | 1.359. (With a visit's product in one
# piece 128 and 256 tie and 512 loses 7-25%; in pieces 256 wins.)
_ROW_TILE = 256


def _column_tile(width):
    """The widest multiple of 128 lanes, at most 2048, that divides
    ``width`` columns of a product: the cells' widths whole, so that the
    rows go by once (at most 1024 a pass reads 1-7% slower)."""
    return max(t for t in range(_LANES, min(width, 2048) + 1, _LANES)
               if width % t == 0)


def divides(lhs_shape, rhs_shape):
    """Whether the tiles divide ``lhs`` (n, K) and ``rhs`` (G, K, N):
    whole 128-lane columns both ways and whole row tiles."""
    (n, k), (_, _, width) = lhs_shape, rhs_shape
    return k % _LANES == 0 and width % _LANES == 0 and n % _ROW_TILE == 0


class Plan(NamedTuple):
    """The visits of one (n, groups): ``plan``. Two prefetched
    arrays and the grid's extent: with the two operands a call has FIVE
    (``benchmark/trace_reduce.py`` takes a Mosaic call of 3 or 6 for a
    flash kernel)."""
    offsets: jax.Array    # (groups + 1,) int32: a group's first row
    visits: jax.Array     # (2 bound,) int32: the visits' groups, then
    #                       their row tiles
    count: jax.Array      # () int32: the visits that are made


# Jitted, as the kernels below: a model traces the same plan and the same
# calls once a layer, a branch and a direction, and again inside each
# transform round them; a jitted callee is traced and lowered once a
# signature (PR 40: inline, sixteen kernels cost 2 s of a warm set-up).
@functools.partial(jax.jit, static_argnums=(1,))
def plan(group_sizes, n):
    """The visits over ``n`` rows in tiles of ``tm`` = ``_ROW_TILE``:
    comparisons and a running count over (visits x groups), no sort and
    no ``repeat``. At most ``n / tm + groups - 1`` are made: every tile
    once, one more a boundary inside a tile, and a group without rows
    once."""
    num, tm = group_sizes.shape[0], _ROW_TILE
    last_tile = n // tm - 1
    ends = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), n)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = jnp.minimum(starts // tm, last_tile)
    last = jnp.minimum(jnp.maximum(ends - 1, starts) // tm, last_tile)
    upto = jnp.cumsum(last - first + 1)          # visits through group g
    visit = jnp.arange(n // tm + num - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(upto <= visit[:, None], axis=1, dtype=jnp.int32), num - 1)
    # The visit's place inside its group, read off by a one-hot sum.
    mine = group[:, None] == jnp.arange(num, dtype=jnp.int32)
    tile = visit + jnp.sum(
        jnp.where(mine, first - (upto - (last - first + 1)), 0), axis=1)
    return Plan(jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
                jnp.concatenate([group, jnp.minimum(tile, last_tile)]),
                upto[-1])


def _group(visits, v):
    return visits[v]


def _tile(visits, v):
    return visits[visits.shape[0] // 2 + v]


def _visit(offsets, visits, v, tm):
    """(group, the (tm, 1) mask of the group's rows in the tile) of
    visit ``v``."""
    g = _group(visits, v)
    row = _tile(visits, v) * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return g, (row >= offsets[g]) & (row < offsets[g + 1])


def _chunk(width):
    """Columns a step of a kernel's inner loop: Mosaic unrolls a matmul
    whole, and a visit's product in one piece is 12k bundles of program
    (a held cell's step holds 64 such calls beside 24 of the weight
    gradient's: tens of MB of executable to read at every warm start);
    a loop over pieces of 256 columns multiplies the same tiles in the
    same order from an eighth of the code."""
    return 256 if width % 256 == 0 else _LANES


def _product_kernel(offsets, visits, lhs_ref, rhs_ref, out_ref, *,
                    transposed):
    """Grid (column tiles, visits). ``lhs_ref`` (tm, K) a row tile,
    ``rhs_ref`` the group's panel, the columns of this pass; ``out_ref``
    (tm, tn): it stays in VMEM while consecutive visits share the tile,
    each writing its own group's rows (a select: the tile's other rows
    keep what the visit before left there)."""
    _, mine = _visit(offsets, visits, pl.program_id(1), lhs_ref.shape[0])
    chunk = _chunk(out_ref.shape[1])

    def columns(c, _):
        cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        # (The tile is read where it is multiplied: a value made before
        # the loop is a copy of it, 600 bundles a visit.)
        if transposed:
            product = lax.dot_general(lhs_ref[...], rhs_ref[cols, :], _NT,
                                      preferred_element_type=jnp.float32)
        else:
            product = lax.dot_general(lhs_ref[...], rhs_ref[:, cols], _NN,
                                      preferred_element_type=jnp.float32)
        out_ref[:, cols] = jnp.where(mine, product.astype(out_ref.dtype),
                                     out_ref[:, cols])

    lax.fori_loop(0, out_ref.shape[1] // chunk, columns, None)


def _weight_kernel(offsets, visits, lhs_ref, d_out_ref, out_ref, acc):
    """Grid (K tiles, N tiles, visits). ``lhs_ref`` (tm, tk) and
    ``d_out_ref`` (tm, tn) the visit's row tile; ``acc`` (tk, tn)
    float32, one group's: zeroed when the walk enters the group, written
    to ``out_ref`` (the group's (tk, tn)) when it leaves."""
    v, final = pl.program_id(2), pl.num_programs(2) - 1
    g, mine = _visit(offsets, visits, v, lhs_ref.shape[0])

    @pl.when((v == 0) | (_group(visits, jnp.maximum(v - 1, 0)) != g))
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    # Both sides masked: a dead row may hold anything, and 0 x NaN is NaN.
    # In one piece: in a loop over pieces of columns Mosaic transposes
    # the row tile again for each and the loop's body outgrows the MXU's
    # time for it (3.7k bundles beside 2.0k cycles, a sandbox dump).
    acc[...] += lax.dot_general(
        jnp.where(mine, lhs_ref[...], 0), jnp.where(mine, d_out_ref[...], 0),
        _TN, preferred_element_type=jnp.float32)

    @pl.when((v == final) | (_group(visits, jnp.minimum(v + 1, final)) != g))
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _vmem_limit(need):
    return None if need <= _VMEM_DEFAULT else min(need, _VMEM_MOST)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _product(lhs, rhs, walk, transposed, interpret):
    """(n, N) = each group's rows times its panel; ``transposed``: (n,
    K) = each group's rows (n, N) times its panel's transpose."""
    (n, depth), tm = lhs.shape, _ROW_TILE
    width = rhs.shape[1] if transposed else rhs.shape[2]
    tn = _column_tile(width)
    if transposed:
        panel = pl.BlockSpec((None, tn, depth),
                             lambda j, v, _, visits: (_group(visits, v), j, 0))
    else:
        panel = pl.BlockSpec((None, depth, tn),
                             lambda j, v, _, visits: (_group(visits, v), 0, j))
    item = lhs.dtype.itemsize
    # Two buffers of each block, and the tile once more as a value.
    need = (2 * (tm * depth + depth * tn + tm * tn) * item
            + tm * depth * item + (4 << 20))
    with trace_span("kernel", kernel=KERNEL_MOE_GROUPED):
        return pl.pallas_call(
            functools.partial(_product_kernel, transposed=transposed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(width // tn, walk.count),
                in_specs=[
                    pl.BlockSpec(
                        (tm, depth),
                        lambda j, v, _, visits: (_tile(visits, v), 0)),
                    panel,
                ],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda j, v, _, visits: (_tile(visits, v), j))),
            out_shape=jax.ShapeDtypeStruct((n, width), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(need)),
            interpret=interpret,
            name=KERNEL_MOE_GROUPED,
        )(walk.offsets, walk.visits, lhs, rhs)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _weight_grad(lhs, d_out, walk, dtype, interpret):
    """(G, K, N) of ``dtype``: each group's ``lhs_g^T @ d_out_g``."""
    (n, k), width = lhs.shape, d_out.shape[1]
    groups, tm = walk.offsets.shape[0] - 1, _ROW_TILE
    tk, tn = _column_tile(k), _column_tile(width)
    item = lhs.dtype.itemsize
    # Two buffers of each block, the masked tiles, the accumulator and a
    # visit's product.
    need = (3 * tm * (tk + tn) * item + 2 * tk * tn * jnp.dtype(dtype).itemsize
            + 2 * tk * tn * 4 + (4 << 20))
    with trace_span("kernel", kernel=KERNEL_MOE_GROUPED_DW):
        return pl.pallas_call(
            _weight_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(k // tk, width // tn, walk.count),
                in_specs=[
                    pl.BlockSpec(
                        (tm, tk),
                        lambda i, j, v, _, visits: (_tile(visits, v), i)),
                    pl.BlockSpec(
                        (tm, tn),
                        lambda i, j, v, _, visits: (_tile(visits, v), j)),
                ],
                out_specs=pl.BlockSpec(
                    (None, tk, tn),
                    lambda i, j, v, _, visits: (_group(visits, v), i, j)),
                scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((groups, k, width), dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_vmem_limit(need)),
            interpret=interpret,
            name=KERNEL_MOE_GROUPED_DW,
        )(walk.offsets, walk.visits, lhs, d_out)


def _counted(kind):
    M_GROUPED_MATMULS.labels(kind=kind, via="kernel").inc()
    return pallas_attention._should_interpret(None)


@jax.custom_vjp
def _grouped(lhs, rhs, walk):
    return _product(lhs, rhs, walk, False, _counted("forward"))


def _grouped_fwd(lhs, rhs, walk):
    return _grouped(lhs, rhs, walk), (lhs, rhs, walk)


def _grouped_bwd(residuals, d_out):
    lhs, rhs, walk = residuals
    return (_product(d_out, rhs, walk, True, _counted("input_grad")),
            _weight_grad(lhs, d_out, walk, rhs.dtype,
                         _counted("weight_grad")), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lax.ragged_dot(lhs, rhs, group_sizes)`` for shapes the tiles
    divide: ``lhs`` (n, K) sorted by group, ``rhs`` (G, K, N),
    ``group_sizes`` (G,); float32 accumulation, rounded once to ``lhs``'
    dtype; the rows past ``sum(group_sizes)`` are left unwritten. No
    gradient reaches ``group_sizes``."""
    if not divides(lhs.shape, rhs.shape):
        raise ValueError("The tiles do not divide %s rows by %s panels"
                         % (lhs.shape, rhs.shape))
    return _grouped(lhs, rhs, plan(group_sizes, lhs.shape[0]))
