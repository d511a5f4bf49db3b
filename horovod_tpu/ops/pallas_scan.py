"""A Mamba layer's selective scan as two Pallas kernels of ours.

    h_t = exp(Delta_t A) * h_(t-1) + (Delta_t x_t) outer B_t,   h_0 = 0
    y_t = h_t C_t + D * x_t

over E channels of N states each, the decay ``exp(Delta_t A)`` DATA: a
number for every (position, channel, state), so no matmul form exists
(Mamba-1, arXiv:2312.00752; not Mamba-2's scalar decay a head). The
plain definition by ``lax.associative_scan`` over the sequence holds a
(T, E, N) float32 array: 2.7 GB a layer at T 8,192, E 5,120, N 16.

Here the (N, channel tile) state lives in registers and VMEM. The grid
is (batch, channel tiles, chunks of ``_CHUNK`` positions), the chunks
walked in order; a chunk's eight-position groups are one ``fori_loop``,
a group's positions unrolled. States on the sublanes, channels on the
lanes: ``Delta_t`` and ``x_t`` are rows spread over the states,
``B_t`` and ``C_t`` columns spread over the channels (they arrive as
ONE (T / 8, 2 N, 8) array: a group's eight columns side by side, B's
rows above C's), the sum over the states a sublane reduction.

``hvd_ssm_scan_fwd`` writes y and the state at each chunk's START
(``T / _CHUNK`` x N x E float32: 10.5 MB at the sizes above), nothing
else of h. ``hvd_ssm_scan_bwd`` walks the chunks in reverse: it remakes
a chunk's states from its boundary into a VMEM scratch, then walks the
chunk backwards with the state's cotangent in registers, and gives the
cotangents of x, Delta, A, B, C and D (B's and C's a channel tile,
summed outside; A's and D's a batch row). Everything is float32.

``selective_scan_plain`` is the same arithmetic in XLA, a ``lax.scan``
over the chunks that carries the state and is recomputed a chunk at a
time in the backward pass: what the CPU tests hold the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.jax.introspect import (
    KERNEL_SSM_SCAN_BWD,
    KERNEL_SSM_SCAN_FWD,
    SAVED_SSM_STATES,
    SCOPE_SSM_SCAN,
)
from horovod_tpu.ops import pallas_attention
from horovod_tpu.utils.timeline import trace_span

_GROUP = 8        # positions a group: one (8, lanes) float32 tile of rows
# Positions between two kept states, and channels a tile (the widest of
# these that divides E). Kernel alone on a v5e (PERF.md, PR 45; 1 x 8192
# positions x 5120 channels x 16 states, float32; ms forward | forward
# and backward; XLA's plain chunked path 146.2 | 474.1), chunk x tile:
# 256 x 128 7.09 | 41.50; 256 x 256 3.56 | 21.04; 256 x 512 2.45 |
# 13.03; 256 x 1024 2.20 | 9.01; 128 x 1024 2.24 | 9.04; 64 x 1024 2.26
# | 9.12; 128 x 2560 2.54 | 9.48 (64 x 2560 and any x 5120: the backward
# call's scratch does not fit VMEM). The walk is bound by the chain from
# one position's state to the next, so a wider tile is more independent
# registers a step until they spill; the chunk hardly matters.
_CHUNK = 256
_TILES = (1024, 512, 256, 128)


def _channel_tile(e):
    return next((t for t in _TILES if e % t == 0), e)


def _rows(g):
    return pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)


def _fwd_kernel(x_ref, dt_ref, a_ref, bc_ref, d_ref, y_ref, hb_ref, h_ref):
    """Grid (B, E / tile, T / chunk). ``x_ref``, ``dt_ref``, ``y_ref``
    (chunk, tile); ``a_ref`` (N, tile); ``bc_ref`` (chunk / 8, 2 N, 8);
    ``d_ref`` (1, tile); ``hb_ref`` (N, tile), this chunk's entry
    of the boundary states; ``h_ref`` (N, tile) the state between
    chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros(h_ref.shape, jnp.float32)

    hb_ref[...] = h_ref[...]
    a, d = a_ref[...], d_ref[...]
    n = a.shape[0]

    def group(g, h):
        x8, dt8 = x_ref[_rows(g), :], dt_ref[_rows(g), :]
        b8, c8 = bc_ref[g, :n, :], bc_ref[g, n:, :]
        ys = []
        for i in range(_GROUP):
            x, dt = x8[i:i + 1, :], dt8[i:i + 1, :]
            h = jnp.exp(dt * a) * h + b8[:, i:i + 1] * (dt * x)
            ys.append(jnp.sum(h * c8[:, i:i + 1], axis=0, keepdims=True)
                      + d * x)
        y_ref[_rows(g), :] = jnp.concatenate(ys, axis=0)
        return h

    h_ref[...] = lax.fori_loop(0, x_ref.shape[0] // _GROUP, group,
                               h_ref[...])


def _columns(cols):
    """(N, 8) from eight (N, 1) columns, by selects: no narrow
    concatenation along the lanes."""
    lane = lax.broadcasted_iota(jnp.int32, (cols[0].shape[0], _GROUP), 1)
    out = jnp.zeros(lane.shape, jnp.float32)
    for i, col in enumerate(cols):
        out = jnp.where(lane == i, col, out)
    return out


def _bwd_kernel(x_ref, dt_ref, a_ref, bc_ref, d_ref, hb_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, dbc_ref, dd_ref, hs_ref, g_ref):
    """The same grid, the chunks LAST first (the index maps turn them).
    ``hb_ref`` (N, tile) the state this chunk started from; ``dy_ref``
    (chunk, tile). Out: ``dx_ref``, ``ddt_ref`` (chunk, tile);
    ``dbc_ref`` (chunk / 8, 2 N, 8), this tile's part;
    ``da_ref`` (N, tile) and ``dd_ref`` (1, tile), resident over the
    chunks. ``hs_ref`` (chunk + 1, N, tile): entry j the state BEFORE
    the chunk's position j; ``g_ref`` (N, tile) the state's cotangent
    between chunks."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros(g_ref.shape, jnp.float32)
        da_ref[...] = jnp.zeros(da_ref.shape, jnp.float32)
        dd_ref[...] = jnp.zeros(dd_ref.shape, jnp.float32)

    a, d = a_ref[...], d_ref[...]
    n, groups = a.shape[0], x_ref.shape[0] // _GROUP

    def remake(g, h):
        x8, dt8 = x_ref[_rows(g), :], dt_ref[_rows(g), :]
        b8 = bc_ref[g, :n, :]
        for i in range(_GROUP):
            hs_ref[g * _GROUP + i] = h
            x, dt = x8[i:i + 1, :], dt8[i:i + 1, :]
            h = jnp.exp(dt * a) * h + b8[:, i:i + 1] * (dt * x)
        return h

    hs_ref[x_ref.shape[0]] = lax.fori_loop(0, groups, remake, hb_ref[...])

    def group(k, carry):
        grad, da, dd = carry
        g = groups - 1 - k
        x8, dt8, dy8 = (x_ref[_rows(g), :], dt_ref[_rows(g), :],
                        dy_ref[_rows(g), :])
        b8, c8 = bc_ref[g, :n, :], bc_ref[g, n:, :]
        dxs, ddts, dbs, dcs = ([None] * _GROUP for _ in range(4))
        for i in reversed(range(_GROUP)):
            x, dt, dy = x8[i:i + 1, :], dt8[i:i + 1, :], dy8[i:i + 1, :]
            h_before, h = hs_ref[g * _GROUP + i], hs_ref[g * _GROUP + i + 1]
            decay = jnp.exp(dt * a)
            grad = grad + c8[:, i:i + 1] * dy
            dcs[i] = jnp.sum(h * dy, axis=1, keepdims=True)
            dbs[i] = jnp.sum(grad * (dt * x), axis=1, keepdims=True)
            du = jnp.sum(grad * b8[:, i:i + 1], axis=0, keepdims=True)
            w = grad * h_before * decay      # d / d (Delta_t A)
            ddts[i] = jnp.sum(w * a, axis=0, keepdims=True) + du * x
            dxs[i] = du * dt + d * dy
            da = da + w * dt
            dd = dd + dy * x
            grad = grad * decay
        dx_ref[_rows(g), :] = jnp.concatenate(dxs, axis=0)
        ddt_ref[_rows(g), :] = jnp.concatenate(ddts, axis=0)
        dbc_ref[g, :n, :] = _columns(dbs)
        dbc_ref[g, n:, :] = _columns(dcs)
        return grad, da, dd

    g_ref[...], da_ref[...], dd_ref[...] = lax.fori_loop(
        0, groups, group, (g_ref[...], da_ref[...], dd_ref[...]))


def _specs(n, tile, chunk, turned):
    """The block specs both kernels share, by name; ``turned`` (the
    number of chunks, or 0) walks the chunks last first."""
    def at(ci):
        return turned - 1 - ci if turned else ci

    return dict(
        rows=pl.BlockSpec((None, chunk, tile),
                          lambda bi, ei, ci: (bi, at(ci), ei)),
        cols=pl.BlockSpec((None, chunk // _GROUP, 2 * n, _GROUP),
                          lambda bi, ei, ci: (bi, at(ci), 0, 0)),
        a=pl.BlockSpec((n, tile), lambda bi, ei, ci: (0, ei)),
        d=pl.BlockSpec((1, tile), lambda bi, ei, ci: (0, ei)),
        hb=pl.BlockSpec((None, None, n, tile),
                        lambda bi, ei, ci: (bi, at(ci), 0, ei)))


def _params(scratch_bytes, chunk, tile, n):
    """Mosaic's scoped-VMEM limit: the default where the call fits;
    else what it needs (the scratch, five row blocks and two column
    blocks in two buffers each, lanes padded to 128, and room for the
    loop's temporaries)."""
    need = (scratch_bytes + 2 * 5 * chunk * tile * 4
            + 2 * 2 * (chunk // _GROUP) * 2 * n * 128 * 4 + (4 << 20))
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=None if need <= (16 << 20) else min(need, 100 << 20))


# FIVE operands forward and SEVEN backward (B and C as one array): the
# benchmark's ``trace_reduce.flash_kernel`` takes any Mosaic call of 3
# or 6 for a kernel of ops/pallas_attention.py.
@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd_call(x, dt, a, bc, d, chunk, interpret):
    bsz, t, e = x.shape
    n, tile = a.shape[0], _channel_tile(e)
    spec = _specs(n, tile, chunk, 0)
    with trace_span("kernel", kernel=KERNEL_SSM_SCAN_FWD):
        return pl.pallas_call(
            _fwd_kernel,
            grid=(bsz, e // tile, t // chunk),
            in_specs=[spec["rows"], spec["rows"], spec["a"], spec["cols"],
                      spec["d"]],
            out_specs=[spec["rows"], spec["hb"]],
            out_shape=[jax.ShapeDtypeStruct((bsz, t, e), jnp.float32),
                       jax.ShapeDtypeStruct((bsz, t // chunk, n, e),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((n, tile), jnp.float32)],
            compiler_params=_params(n * tile * 4, chunk, tile, n),
            interpret=interpret,
            name=KERNEL_SSM_SCAN_FWD,
        )(x, dt, a, bc, d)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _bwd_call(x, dt, a, bc, d, hb, dy, chunk, interpret):
    bsz, t, e = x.shape
    n, tile = a.shape[0], _channel_tile(e)
    tiles, chunks = e // tile, t // chunk
    spec = _specs(n, tile, chunk, chunks)
    part = pl.BlockSpec(
        (None, None, chunk // _GROUP, 2 * n, _GROUP),
        lambda bi, ei, ci: (ei, bi, chunks - 1 - ci, 0, 0))
    per_row = jax.ShapeDtypeStruct((bsz, t, e), jnp.float32)
    per_col = jax.ShapeDtypeStruct(
        (tiles, bsz, t // _GROUP, 2 * n, _GROUP), jnp.float32)
    with trace_span("kernel", kernel=KERNEL_SSM_SCAN_BWD):
        dx, ddt, da, dbc, dd = pl.pallas_call(
            _bwd_kernel,
            grid=(bsz, tiles, chunks),
            in_specs=[spec["rows"], spec["rows"], spec["a"], spec["cols"],
                      spec["d"], spec["hb"], spec["rows"]],
            out_specs=[
                spec["rows"], spec["rows"],
                pl.BlockSpec((None, n, tile), lambda bi, ei, ci: (bi, 0, ei)),
                part,
                pl.BlockSpec((None, 1, tile), lambda bi, ei, ci: (bi, 0, ei))],
            out_shape=[per_row, per_row,
                       jax.ShapeDtypeStruct((bsz, n, e), jnp.float32),
                       per_col,
                       jax.ShapeDtypeStruct((bsz, 1, e), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((chunk + 1, n, tile), jnp.float32),
                            pltpu.VMEM((n, tile), jnp.float32)],
            compiler_params=_params((chunk + 2) * n * tile * 4, chunk, tile,
                                    n),
            interpret=interpret,
            name=KERNEL_SSM_SCAN_BWD,
        )(x, dt, a, bc, d, hb, dy)
    return dx, ddt, da.sum(0), dbc.sum(0), dd.sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, a, bc, d, chunk, interpret):
    return _fwd_call(x, dt, a, bc, d, chunk, interpret)[0]


def _scan_fwd(x, dt, a, bc, d, chunk, interpret):
    y, hb = _fwd_call(x, dt, a, bc, d, chunk, interpret)
    # Named like the flash kernel's results: a recomputation that keeps
    # this name does not run the forward kernel a second time.
    return y, (x, dt, a, bc, d, checkpoint_name(hb, SAVED_SSM_STATES))


def _scan_bwd(chunk, interpret, res, dy):
    with jax.named_scope(SCOPE_SSM_SCAN):
        return _bwd_call(*res, dy, chunk, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _chunk_of(t, chunk):
    """The chunk for ``t`` positions: ``chunk``, or for a shorter
    sequence its length brought up to whole groups."""
    return min(chunk, -(-t // _GROUP) * _GROUP)


def _grouped(b, c):
    """Two (B, T, N) -> (B, T / 8, 2 N, 8): a group's columns side by
    side, b's rows above c's."""
    bsz, t, n = b.shape
    both = jnp.concatenate([b, c], axis=-1)
    return jnp.swapaxes(both.reshape(bsz, t // _GROUP, _GROUP, 2 * n), 2, 3)


def selective_scan(x, delta, a, b, c, d, *, chunk=_CHUNK, interpret=None):
    """y (B, T, E) float32 of the recurrence above: ``x``, ``delta``
    (B, T, E); ``a`` (E, N); ``b``, ``c`` (B, T, N); ``d`` (E,).
    Differentiable in all six. Any T: the sequence is brought up to
    whole chunks with steps that leave the state as it is (Delta 0)."""
    t = x.shape[1]
    chunk = _chunk_of(t, chunk)
    pad = -t % chunk
    with jax.named_scope(SCOPE_SSM_SCAN):
        rows = [jnp.pad(v.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
                for v in (x, delta, b, c)]
        y = _scan(rows[0], rows[1], a.astype(jnp.float32).T,
                  _grouped(rows[2], rows[3]),
                  d.astype(jnp.float32)[None, :], chunk,
                  pallas_attention._should_interpret(interpret))
        return y[:, :t]


def selective_scan_plain(x, delta, a, b, c, d, *, chunk=_CHUNK):
    """The same y in XLA: a ``lax.scan`` over chunks of positions that
    carries the (B, E, N) state; inside a chunk an associative scan over
    its positions, which the backward pass makes again (the chunk's
    (chunk, B, E, N) products are never kept)."""
    bsz, t, e = x.shape
    chunk = _chunk_of(t, chunk)
    pad = -t % chunk
    x, delta, b, c = (
        jnp.moveaxis(jnp.pad(v.astype(jnp.float32),
                             ((0, 0), (0, pad), (0, 0))), 1, 0).reshape(
            (t + pad) // chunk, chunk, bsz, -1) for v in (x, delta, b, c))
    a, d = a.astype(jnp.float32), d.astype(jnp.float32)

    @jax.checkpoint
    def of_chunk(h, rows):
        x, delta, b, c = rows                  # (chunk, B, E | N)
        decay = jnp.exp(delta[..., None] * a)  # (chunk, B, E, N)
        drive = (delta * x)[..., None] * b[:, :, None, :]
        decay, drive = lax.associative_scan(
            lambda l, r: (l[0] * r[0], r[0] * l[1] + r[1]), (decay, drive))
        hs = decay * h + drive
        return hs[-1], jnp.einsum("tben,tbn->tbe", hs, c) + d * x

    _, y = lax.scan(of_chunk, jnp.zeros((bsz, e, a.shape[1]), jnp.float32),
                    (x, delta, b, c))
    return jnp.moveaxis(y.reshape(t + pad, bsz, e), 0, 1)[:, :t]
