"""Flash attention as a Pallas TPU kernel.

The reference framework has no fused attention (it is a pure collective
library); this kernel is part of the TPU-first compute path for the
flagship transformer (``horovod_tpu.models.transformer``), keeping the
attention working set in VMEM and the matmuls on the MXU instead of
materialising the (S, S) score matrix in HBM.

Algorithm: standard streaming-softmax (flash) attention. The forward
kernel tiles queries over the grid and walks key/value blocks with a
running (max, sum, accumulator) triple; the backward pass is two kernels
(dK/dV tiled over key blocks, dQ tiled over query blocks) using the saved
log-sum-exp, wired up through ``jax.custom_vjp``. The per-(batch, head)
K/V panel is VMEM-resident (blocks are sliced from it in-kernel), and so
are the backward's whole Q/dO/lse/delta panels, which bounds single-chip
sequence length to VMEM. Largest power-of-two S for which forward AND
backward compile on a TPU v5 lite (jax 0.9.0 / libtpu 0.0.34, default
tiles, head_dim 64 and 128 alike): 16384 in bf16, 8192 in float32; the
forward alone compiles one power of two further. The first thing to run
out is the backward's two (S, 1) float32 panels, which Mosaic pads to
128 lanes. Longer sequences shard S across chips via ring/Ulysses
attention (``horovod_tpu.parallel.sequence``), keeping each chip's panel
small.

Causal masking uses the decode convention for rectangular inputs: the
end of q aligns with the end of kv (query row r has absolute position
r + kv_len - q_len).

On non-TPU backends (CPU tests, debugging) the kernels run in Pallas
interpret mode, so the same code path is exercised everywhere; the
switch is logged once per backend so a run can tell which it got.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

logger = logging.getLogger("horovod_tpu")

NEG_INF = -1e30


@functools.cache
def _log_interpret(backend: str) -> None:
    logger.warning(
        "flash_attention: backend is %r, not 'tpu' -- the Pallas kernels "
        "run in INTERPRET mode (not compiled by Mosaic)", backend)


def _should_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    _log_interpret(backend)  # runs at trace time, once per backend
    return True


# --------------------------------------------------------------- forward ---


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                block_q, block_k, causal, kv_len, q_offset, scale):
    """Grid: (B, H, S_pad // block_q). q block vs streamed k/v blocks."""
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale  # (block_q, D)

    s_pad = k_ref.shape[0]
    num_kb = s_pad // block_k

    q_start = qi * block_q

    def body(kj, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (block_q, block_k)

        col = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            # Absolute position of query row r is r + q_offset, aligning
            # the END of q with the end of kv (decode convention).
            row = q_start + q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, col <= row)
        s = jnp.where(mask, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    if causal:
        # Key blocks strictly after this query block are fully masked.
        num_kb_eff = jax.lax.clamp(
            0, pl.cdiv(q_start + block_q + q_offset, block_k), num_kb)
    else:
        num_kb_eff = num_kb

    acc = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_kb_eff, body, (acc, m, l))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(l_safe))[:, None].astype(jnp.float32)


# -------------------------------------------------------------- backward ---


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, block_q, block_k, causal, kv_len,
                    q_offset, scale):
    """Grid: (B, H, S_pad // block_k). One k/v block vs streamed q blocks."""
    kj = pl.program_id(2)
    k = k_ref[...].astype(jnp.float32)  # (block_k, D)
    v = v_ref[...].astype(jnp.float32)

    s_pad = q_ref.shape[0]
    num_qb = s_pad // block_q
    k_start = kj * block_k
    col = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(qi, carry):
        dk, dv = carry
        q_start_blk = qi * block_q
        q = q_ref[pl.ds(q_start_blk, block_q), :].astype(jnp.float32) * scale
        do = do_ref[pl.ds(q_start_blk, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(q_start_blk, block_q), :]    # (block_q, 1)
        delta = delta_ref[pl.ds(q_start_blk, block_q), :]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        mask = col < kv_len
        if causal:
            row = q_start_blk + q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, col <= row)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)

        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # Query blocks whose last absolute row precedes this key block
        # see none of it: rows r with r + q_offset >= k_start.
        qb_start = jnp.maximum(k_start - q_offset, 0) // block_q
    else:
        qb_start = 0

    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(qb_start, num_qb, body, (dk, dv))
    # q was pre-scaled at load, so dk = Σ ds^T (scale·q) is already the
    # gradient of s = scale·q·kᵀ w.r.t. k — no extra scale factor here.
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, block_q, block_k, causal, kv_len, q_offset,
                   scale):
    """Grid: (B, H, S_pad // block_q). One q block vs streamed k/v blocks."""
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale
    do = do_ref[...].astype(jnp.float32)
    lse = lse_ref[...]    # (block_q, 1)
    delta = delta_ref[...]

    s_pad = k_ref.shape[0]
    num_kb = s_pad // block_k
    q_start = qi * block_q
    row = q_start + q_offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(kj, dq):
        k = k_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        col = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col < kv_len
        if causal:
            mask = jnp.logical_and(mask, col <= row)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        num_kb_eff = jax.lax.clamp(
            0, pl.cdiv(q_start + block_q + q_offset, block_k), num_kb)
    else:
        num_kb_eff = num_kb

    dq = jnp.zeros(q.shape, jnp.float32)
    dq = jax.lax.fori_loop(0, num_kb_eff, body, dq)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


# ------------------------------------------------------------- wrappers ---


def _pad_seq(x, block):
    s = x.shape[2]
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x


def _pick_block(s: int, want: int, dtype) -> int:
    # Sequences shorter than the tile become a single block; longer
    # sequences keep the tile and are padded up to a multiple. Either
    # way the tile is rounded up to the dtype's sublane multiple (8
    # rows of 32-bit words; packed dtypes stack 2 or 4 rows per
    # sublane), which Mosaic needs to prove its in-panel slices
    # aligned. Padded keys are masked via kv_len, padded query rows
    # sliced off.
    sublane = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    return -(-min(s, want) // sublane) * sublane


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, scale, interpret):
    out, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k, scale,
                             interpret)
    return out


def _scoped(fn):
    """Trace ``fn`` under the ``hvd_flash`` scope: the pads, slices and
    row sums around a kernel as well as the kernel, so a device trace
    tells them from the rest of the attention module. (The names live
    in jax/introspect.py, whose package imports this one: hence the
    imports inside functions.)"""
    @functools.wraps(fn)
    def scoped(*args):
        from horovod_tpu.jax.introspect import SCOPE_FLASH

        with jax.named_scope(SCOPE_FLASH):
            return fn(*args)

    return scoped


@_scoped
def _flash_fwd_impl(q, k, v, causal, block_q, block_k, scale, interpret):
    from horovod_tpu.jax.introspect import KERNEL_FLASH_FWD

    # q, k, v here are (B, H, S, D).
    b, h, s, d = q.shape
    kv_len = k.shape[2]
    qp = _pad_seq(q, block_q)
    kp = _pad_seq(k, block_k)
    vp = _pad_seq(v, block_k)
    sq_pad, sk_pad = qp.shape[2], kp.shape[2]

    grid = (b, h, sq_pad // block_q)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        kv_len=kv_len, q_offset=kv_len - s, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, sk_pad, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, sk_pad, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_pad, 1), jnp.float32),
        ],
        interpret=_should_interpret(interpret),
        name=KERNEL_FLASH_FWD,
    )(qp, kp, vp)
    return out[:, :, :s], (q, k, v, out[:, :, :s], lse[:, :, :s, 0])


def _flash_fwd(q, k, v, causal, block_q, block_k, scale, interpret):
    return _flash_fwd_impl(q, k, v, causal, block_q, block_k, scale,
                           interpret)


@_scoped
def _flash_bwd(causal, block_q, block_k, scale, interpret, res, g):
    from horovod_tpu.jax.introspect import KERNEL_FLASH_DKV, KERNEL_FLASH_DQ

    q, k, v, out, lse = res
    b, h, s, d = q.shape
    kv_len = k.shape[2]
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)  # (B, H, S)

    qp = _pad_seq(q, block_q)
    kp = _pad_seq(k, block_k)
    vp = _pad_seq(v, block_k)
    dop = _pad_seq(g.astype(q.dtype), block_q)
    sq_pad, sk_pad = qp.shape[2], kp.shape[2]
    pad_q = sq_pad - s
    # Padded query rows: lse=0, delta=0 → p = exp(-0)=1 rows would pollute
    # dk/dv; guard with lse=+inf so exp(s - lse) = 0.  Shape (B, H, S, 1)
    # keeps the last-two-dims TPU tiling rule satisfied.
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)),
                   constant_values=jnp.inf)[..., None]
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q)))[..., None]

    interp = _should_interpret(interpret)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, block_q=block_q, block_k=block_k, causal=causal,
        kv_len=kv_len, q_offset=kv_len - s, scale=scale)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, sk_pad // block_k),
        in_specs=[
            pl.BlockSpec((None, None, sq_pad, d),
                         lambda bi, hi, kj: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, kj: (bi, hi, kj, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, kj: (bi, hi, kj, 0)),
            pl.BlockSpec((None, None, sq_pad, d),
                         lambda bi, hi, kj: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, sq_pad, 1),
                         lambda bi, hi, kj: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, sq_pad, 1),
                         lambda bi, hi, kj: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, kj: (bi, hi, kj, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, kj: (bi, hi, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sk_pad, d), q.dtype),
        ],
        interpret=interp,
        name=KERNEL_FLASH_DKV,
    )(qp, kp, vp, dop, lsep, deltap)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, block_q=block_q, block_k=block_k, causal=causal,
        kv_len=kv_len, q_offset=kv_len - s, scale=scale)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, sq_pad // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, sk_pad, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, sk_pad, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_pad, d), q.dtype),
        interpret=interp,
        name=KERNEL_FLASH_DQ,
    )(qp, kp, vp, dop, lsep, deltap)

    return dq[:, :, :s], dk[:, :, :kv_len], dv[:, :, :kv_len]


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """Fused streaming-softmax attention.

    Args:
      q, k, v: (batch, seq, heads, head_dim) arrays (the layout used by
        ``horovod_tpu.models.transformer``).
      causal: apply a causal (lower-triangular) mask.
      block_q / block_k: VMEM tile sizes (clamped and made to divide the
        padded sequence length). Defaults 256/512 (best of the v5e
        sweep at seq 2048, ci/flash_block_sweep.py); overridable
        per-job via HVD_FLASH_BLOCK_Q / HVD_FLASH_BLOCK_K, or
        autotuned per (seq, head_dim, dtype, causal) shape with
        HVD_FLASH_TUNE=1 (ops/block_tuner.py caches winners across
        processes; docs/mfu.md). Precedence: explicit argument >
        HVD_FLASH_BLOCK_Q/K env > tuned cache > default.
      scale: score scaling; defaults to 1/sqrt(head_dim).
      interpret: force Pallas interpret mode (defaults to True off-TPU).

    Returns:
      (batch, seq, heads, head_dim) attention output in q.dtype.
    """
    if q.ndim != 4:
        raise ValueError("expected (B, S, H, D) inputs, got %r"
                         % (q.shape,))
    d = q.shape[-1]
    if scale is None:
        scale = float(d) ** -0.5
    if block_q is None and block_k is None and \
            "HVD_FLASH_BLOCK_Q" not in os.environ and \
            "HVD_FLASH_BLOCK_K" not in os.environ:
        from horovod_tpu.ops import block_tuner

        if block_tuner.tune_mode() \
                or block_tuner.world_synced_view_active():
            # On-first-call autotuning: the sweep (or a cache hit from
            # an earlier process) picks the tiles for this live shape.
            # Runs at trace time on synthetic same-shape inputs, so a
            # jitted caller tunes exactly once per shape. The second
            # arm matters when THIS rank has HVD_FLASH_TUNE unset but
            # the world synced rank 0's tile view at init: rank 0's
            # settings are authoritative, and skipping the lookup
            # here would trace default tiles against rank 0's tuned
            # ones — the per-rank env divergence docs/mfu.md forbids.
            picked = block_tuner.best_blocks(
                q.shape[1], k.shape[1], d, q.dtype, causal,
                interpret=interpret)
            if picked is not None:
                block_q, block_k = picked
    if block_q is None:
        block_q = int(os.environ.get("HVD_FLASH_BLOCK_Q", "256"))
    if block_k is None:
        block_k = int(os.environ.get("HVD_FLASH_BLOCK_K", "512"))
    # Kernel layout is (B, H, S, D).
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    block_q = _pick_block(max(qt.shape[2], 1), block_q, q.dtype)
    block_k = _pick_block(max(kt.shape[2], 1), block_k, k.dtype)
    out = _flash(qt, kt, vt, causal, block_q, block_k, scale, interpret)
    return jnp.swapaxes(out, 1, 2)
