"""Hierarchical (two-level) collectives: ICI intra-slice x DCN cross-slice.

TPU-native rebuild of NCCLHierarchicalAllreduce
(reference: horovod/common/ops/nccl_operations.cc:233-440 — intra-node
ncclReduceScatter, cross-node MPI allreduce on the CROSS communicator,
intra-node ncclAllGather; toggled by HOROVOD_HIERARCHICAL_ALLREDUCE,
reference: horovod/common/operations.cc:514-551).

On TPU the two levels are mesh axes: ``ici`` (fast intra-slice
interconnect) and ``dcn`` (slower cross-slice links). The sequence
reduce_scatter(ici) → allreduce(dcn) → all_gather(ici) moves only 1/ici_size
of the bytes over the slow links — the same bandwidth argument as the
reference's node-hierarchy.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel import bucketing
from horovod_tpu.parallel.mesh import traced_axis_size

ICI_AXIS = "data_ici"
DCN_AXIS = "data_dcn"

# The most leaf bytes ``grouped_hierarchical_allreduce`` packs into one
# flat buffer (one ladder a buffer). A constant: one value is in use.
PACK_BYTES = 4 * 1024 * 1024


def make_hierarchical_axes(ici_size: int, dcn_size: int) -> Dict[str, int]:
    """Axis spec for ``make_mesh``: the data dimension factored into
    (dcn outer, ici inner) so ici neighbors are physically adjacent."""
    return {DCN_AXIS: dcn_size, ICI_AXIS: ici_size}


def hierarchical_allreduce(x, *, average: bool = True, ici_axis=ICI_AXIS,
                           dcn_axis=DCN_AXIS, scatter_dim: int = 0):
    """Two-level allreduce across ici x dcn axes.

    Requires ``x.shape[scatter_dim]`` divisible by the ici axis size.
    """
    ici = traced_axis_size(ici_axis)
    dcn = traced_axis_size(dcn_axis)
    # 1. reduce-scatter across the fast axis: each chip owns 1/ici of the
    #    intra-slice sum.
    shard = lax.psum_scatter(x, ici_axis, scatter_dimension=scatter_dim,
                             tiled=True)
    # 2. cross-slice allreduce of the small shard (rides DCN).
    shard = lax.psum(shard, dcn_axis)
    # 3. all-gather across the fast axis to rebuild the full tensor.
    out = lax.all_gather(shard, ici_axis, axis=scatter_dim, tiled=True)
    if average:
        out = out / jnp.asarray(ici * dcn, dtype=out.dtype)
    return out


def grouped_hierarchical_allreduce(xs, *, average: bool = True,
                                   ici_axis=ICI_AXIS, dcn_axis=DCN_AXIS):
    """Two-level allreduce of a tensor group through fused buffers.

    The per-tensor path requires dim 0 divisible by the ici size —
    gradient pytrees rarely oblige (biases, odd leading dims). Instead,
    reproduce the reference's fusion-buffer move
    (reference: horovod/common/fusion_buffer_manager.h:40 + the
    memcpy-in/collective/memcpy-out sequence in
    ops/nccl_operations.cc:233-440): flatten the tensors into 1-D
    buffers, pad each to a multiple of the ici size, run the
    reduce_scatter(ici) → psum(dcn) → all_gather(ici) ladder once per
    buffer, and slice the results back out: one collective ladder per
    buffer instead of one per tensor.

    Buffers are strictly per-dtype (a bf16 leaf in an fp32 buffer would
    be upcast and double its bytes on the wire) and close once they
    hold ``PACK_BYTES`` of leaves, in the order given and a leaf never
    split, so the ladder's temporaries stay a few MiB however large the
    tree is. ``parallel.bucketing`` does the assignment and the copies.
    """
    xs = [jnp.asarray(x) for x in xs]
    ici = traced_axis_size(ici_axis)
    out = [None] * len(xs)
    buckets = bucketing.assign_buckets(
        [x.size * jnp.dtype(x.dtype).itemsize for x in xs],
        [jnp.dtype(x.dtype).name for x in xs],
        PACK_BYTES, reverse=False)
    for bucket in buckets:
        leaves = [xs[i] for i in bucket.indices]
        flat, _ = bucketing.pack_bucket(leaves, pad_multiple=ici)
        reduced = hierarchical_allreduce(
            flat, average=average, ici_axis=ici_axis, dcn_axis=dcn_axis)
        for i, o in zip(bucket.indices,
                        bucketing.unpack_bucket(reduced, leaves)):
            out[i] = o
    return out


def hierarchical_allgather(x, *, ici_axis=ICI_AXIS, dcn_axis=DCN_AXIS):
    """Two-level allgather (reference analog: MPIHierarchicalAllgather,
    horovod/common/ops/mpi_operations.cc): gather across ici, then across
    dcn, preserving rank order (dcn outer, ici inner)."""
    intra = lax.all_gather(x, ici_axis, tiled=True)
    return lax.all_gather(intra, dcn_axis, tiled=True)
