"""The expert layer's sum over a token's sorted rows as ONE Pallas kernel.

``parallel/moe.py`` keeps an expert layer's rows in SORTED order (by
expert, then by token: a stable sort), ``n`` rows of width M of which the
first ``live`` count. Each token owns up to k of them, and the layer
twice needs, per token, their float32 sum rounded once to the rows'
dtype: combine forward and dispatch backward. XLA's form gathers
``rows[inverse]`` into a (T x k, M) array, masks the dead pairs and
reduces over k: T x k rows written and read again to add k of them.

Here no such array exists. The kernel walks the output in blocks of
``tb`` tokens and the rows in aligned chunks of ``ch``; a VISIT is a
(block, chunk) in which some live row of the chunk belongs to a token
of the block. One visit reads the chunk once (Pallas's own pipeline:
one contiguous DMA of whole tiles, no copy per row: Mosaic refuses a
one-row slice of a tiled HBM array), zeroes what lies past ``live`` (a
grouped matmul leaves those rows unwritten), and adds ``pick @ chunk``
into the block's float32 accumulator, ``pick[t, r] = 1`` where sorted
row r is token t's. A product with 0 or 1 is exact and the accumulator
is float32, so a token's rows are ADDED in float32 and rounded once, as
in XLA's form, but in sorted-row order (by expert) where XLA's sum runs
over the k slots in the router's order: the two are the same bits
wherever the float32 sum is exact (bf16 rows within 16 binades of each
other: every case the tests and the benchmark's check can tell apart),
and differ by float32 roundings of the partial sums elsewhere. The
prefix and the whole length visit the same chunks in the same order:
what both compute is the same bits.

The stable sort is what bounds the visits: inside one expert's group the
rows lie in token order, so a block's rows are at most ``groups``
contiguous runs, and a run of L rows touches at most ``L / ch + 2``
chunks. ``plan`` lists the visits on the device (comparisons and a
running count over the blocks x chunks incidence), block-major, every
block at least once (so that each block of the output is written),
padded to the static bound with copies of the last one, which read
nothing again and add nothing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.jax.introspect import KERNEL_MOE_GATHER_SUM
from horovod_tpu.ops import pallas_attention
from horovod_tpu.utils.timeline import trace_span

# What a block of ``tb`` tokens may hold in VMEM, of Mosaic's default 16
# MiB of scoped VMEM on a v5e: its float32 accumulator, a visit's float32
# product and the output block in two buffers (a chunk's two buffers
# fit beside them).
_BLOCK_BYTES = 12 << 20
_MAX_BLOCK = 512
# Rows a chunk: the MXU's contraction width.
_CHUNK = 128


class _Geometry(NamedTuple):
    """Static: ``tb`` tokens a block and ``nb`` blocks, ``ch`` rows a
    chunk and ``nc`` chunks, ``visits`` the bound on (block, chunk)s."""
    tb: int
    nb: int
    ch: int
    nc: int
    visits: int


def _block_tokens(t, m, dtype):
    """``tb``: the largest block of tokens that VMEM holds. Kernel alone
    on a v5e (PERF.md, PR 40; ms a call, bf16, M 2048, (n, T, k) of the
    four expert cells, XLA's gather + mask + sum | this kernel at 128,
    256, 512 tokens a block): (32768, 16384, 4) 3.89 | 1.35, 0.94, 0.83;
    (16384, 8192, 8) 0.89 | 1.17, 0.77, 0.64; (8192, 8192, 4) 0.99 |
    0.66, 0.44, 0.37; (32768, 4096, 8), every row live, 1.38 | 2.24,
    1.49, 1.34. A visit costs about 2 us at 512 tokens (the MXU's 1.4
    and the accumulator's pass) and their number halves as the block
    doubles. Chunks of 64 rows read the same, of 256 rows 40-60% more."""
    per_token = m * (8 + 2 * jnp.dtype(dtype).itemsize)
    tb = min(_MAX_BLOCK, max(16, _BLOCK_BYTES // per_token // 16 * 16))
    return t if t <= tb else tb


def _geometry(n, t, m, dtype, groups):
    tb = _block_tokens(t, m, dtype)
    ch = n if n <= _CHUNK else _CHUNK
    nb, nc = pl.cdiv(t, tb), pl.cdiv(n, ch)
    return _Geometry(tb, nb, ch, nc,
                     min(n + nb, nc + nb * (2 * groups + 1)))


class Plan(NamedTuple):
    """What one body of the expert layer's two sums share (``plan``)."""
    blocks: jax.Array     # (visits,) int32: the block of tokens
    chunks: jax.Array     # (visits,) int32: the chunk of rows
    scalars: jax.Array    # (2,) int32: the visits that count, live rows
    tokens: jax.Array     # (nc, 1, ch) int32: a live row's token, else -1


def _whole_chunks(per_row, g):
    """``per_row`` (n,) int32 with -1 for the rows that the last chunk
    holds past n."""
    past = g.nc * g.ch - per_row.shape[0]
    if not past:
        return per_row
    return jnp.concatenate([per_row, jnp.full((past,), -1, per_row.dtype)])


def plan(order, k, live, t, m, dtype, groups):
    """The visits of ``gather_sum`` over ``n = len(order)`` sorted rows
    of ``t`` tokens, ``m`` wide, of ``dtype``: ``order[r]`` is the
    (token, slot) pair of sorted row r (``order[r] // k`` its token), in
    a STABLE order by expert over ``groups`` experts; the first ``live``
    rows count (None: all)."""
    return _plan(order, live, k, _geometry(order.shape[0], t, m, dtype,
                                           groups))


# Jitted, as ``_gather_sum`` below: a model traces the same plan and the
# same kernel once a layer, a branch and a direction, and again inside
# each transform round them (a held cell's step 16 kernels; traced and
# lowered one by one they added 2 s to its warm set-up and 2 s to its
# check's); a jitted callee is traced and lowered once a signature.
@functools.partial(jax.jit, static_argnums=(2, 3))
def _plan(order, live, k, g):
    n = order.shape[0]
    limit = jnp.int32(n) if live is None else jnp.minimum(live, n)
    # A live row's token, -1 for a dead one and past n; its block.
    tokens = _whole_chunks(
        jnp.where(jnp.arange(n, dtype=jnp.int32) < limit, order // k, -1), g)
    block = tokens // g.tb
    # (block, chunk)s that share a live row, and every block's chunk 0
    # (so that each block of the output is written): a comparison of
    # each row's block with every block, no sort (XLA's compile of a
    # 32,768-key sort for a v5e takes 12 s).
    visited = jnp.any(block.reshape(g.nc, g.ch, 1)
                      == jnp.arange(g.nb, dtype=jnp.int32), axis=1)
    visited = (visited | (jnp.arange(g.nc) == 0)[:, None]).T.reshape(-1)
    # Block-major, the i-th visit is the (block, chunk) before which i
    # are set; past the last, the last again.
    before = jnp.cumsum(visited, dtype=jnp.int32)
    count = before[-1]
    last = jnp.max(jnp.where(visited, jnp.arange(g.nb * g.nc), 0))
    key = jnp.sum(before <= jnp.arange(g.visits, dtype=jnp.int32)[:, None],
                  axis=1, dtype=jnp.int32)
    key = jnp.minimum(key, last)
    return Plan(key // g.nc, key % g.nc, jnp.stack([count, limit]),
                tokens.reshape(g.nc, 1, g.ch))


def _kernel(blocks, chunks, scalars, tokens_ref, rows_ref, out_ref, acc, *,
            tb, ch, precision):
    w, last = pl.program_id(0), pl.num_programs(0) - 1
    block, chunk = blocks[w], chunks[w]

    @pl.when((w == 0) | (blocks[jnp.maximum(w - 1, 0)] != block))
    def _():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(w < scalars[0])
    def _():
        row = chunk * ch + lax.broadcasted_iota(jnp.int32, (ch, 1), 0)
        rows = jnp.where(row < scalars[1], rows_ref[...], 0)
        token = block * tb + lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
        pick = (tokens_ref[...] == token).astype(rows.dtype)
        acc[...] += jnp.dot(pick, rows, precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when((w == last) | (blocks[jnp.minimum(w + 1, last)] != block))
    def _():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def gather_sum(rows, visits: Plan, t):
    """(t, M): each token's float32 sum over its live sorted rows in
    ``rows`` (n, M), rounded once to their dtype; ``visits`` from
    ``plan`` over the same n, t and M."""
    return _gather_sum(rows, visits, t,
                       pallas_attention._should_interpret(None))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _gather_sum(rows, visits, t, interpret):
    m = rows.shape[1]
    ch = visits.tokens.shape[2]
    tb = _block_tokens(t, m, rows.dtype)
    # A 0/1 matrix times float32 rows is exact only if the MXU is given
    # all of their mantissa.
    precision = (lax.Precision.HIGHEST if rows.dtype == jnp.float32
                 else None)
    with trace_span("kernel", kernel=KERNEL_MOE_GATHER_SUM):
        return pl.pallas_call(
            functools.partial(_kernel, tb=tb, ch=ch, precision=precision),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(visits.blocks.shape[0],),
                in_specs=[
                    pl.BlockSpec(
                        (None, 1, ch),
                        lambda w, blocks, chunks, _: (chunks[w], 0, 0)),
                    pl.BlockSpec(
                        (ch, m), lambda w, blocks, chunks, _: (chunks[w], 0)),
                ],
                out_specs=pl.BlockSpec(
                    (tb, m), lambda w, blocks, chunks, _: (blocks[w], 0)),
                scratch_shapes=[pltpu.VMEM((tb, m), jnp.float32)]),
            out_shape=jax.ShapeDtypeStruct((t, m), rows.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            name=KERNEL_MOE_GATHER_SUM,
        )(visits.blocks, visits.chunks, visits.scalars, visits.tokens, rows)
