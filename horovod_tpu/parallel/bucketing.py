"""Per-dtype, byte-capped gradient bucketing for grouped collectives.

The reference earns its overlap from the fusion buffer: gradients are
packed into large same-dtype buffers and reduced while later gradients
are still being computed (reference: horovod/common/
fusion_buffer_manager.h:40, docs/tensor-fusion.rst). In-graph, on the
v5e, that overlap does not exist to be earned: XLA combines whatever
buckets it is handed into a few ``all-reduce``s (11 for GPT-2-medium's
1.42 GB), they are synchronous on libtpu 0.0.34, and nothing runs
beside them (PERF.md, PR 22 and PR 27). So a bucket here is a GROUP: the
leaves one grouped collective takes together, and one name in the trace.

This module owns the bucket *math*, shared by
``horovod_tpu.jax.optimizer`` (byte-capped buckets, reverse-gradient
order; on the flat route the leaves of a bucket go to one ``lax.psum``
as they lie and nothing here copies them) and
``parallel.hierarchical.grouped_hierarchical_allreduce`` (one uncapped
bucket per dtype, packed into ONE flat buffer because a ``psum_scatter``
needs an array divisible by the ici size), so the two paths can never
drift on dtype handling. Buckets are always per-dtype: a bf16 leaf in
an fp32 buffer would be upcast and double its bytes on the wire, and
one all-reduce takes operands of one element type.

The assignment functions are pure Python over ``(nbytes, dtype_key)``
descriptors, unit-testable without tracing anything;
``pack_bucket``/``unpack_bucket`` do the jnp ravel/concat/slice work for
the hierarchical route alone.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Sequence, Tuple


class Bucket(NamedTuple):
    """One grouped collective's worth of leaves.

    ``indices`` are positions into the caller's leaf list, in issue
    order (reverse-gradient order when ``reverse=True``); ``nbytes`` is
    the summed payload of the bucket.
    """

    dtype_key: Any
    indices: Tuple[int, ...]
    nbytes: int


def assign_buckets(
    nbytes_per_leaf: Sequence[int],
    dtype_keys: Sequence[Any],
    bucket_bytes: int,
    *,
    reverse: bool = True,
) -> List[Bucket]:
    """Assign leaves to per-dtype buckets capped at ``bucket_bytes``.

    Walks the leaves in reverse order by default: backprop finishes the
    *last* layers' gradients first, so reverse-flatten order names the
    groups in the order their inputs become ready (the reference's
    coordinator negotiates tensors as they become ready). A bucket closes once its payload reaches ``bucket_bytes``;
    a single leaf larger than the cap still gets its own bucket (the
    cap bounds *batching*, it never splits a tensor).

    ``bucket_bytes <= 0`` means "no cap": exactly one bucket per dtype,
    in first-seen (reverse) order — the fusion behavior
    ``grouped_hierarchical_allreduce`` always had.
    """
    if len(nbytes_per_leaf) != len(dtype_keys):
        raise ValueError("leaf size/dtype lists disagree: %d vs %d"
                         % (len(nbytes_per_leaf), len(dtype_keys)))
    order = range(len(dtype_keys))
    if reverse:
        order = reversed(order)

    buckets: List[Bucket] = []
    open_by_dtype = {}  # dtype_key -> index into buckets
    for i in order:
        key = dtype_keys[i]
        nbytes = int(nbytes_per_leaf[i])
        slot = open_by_dtype.get(key)
        if slot is None:
            buckets.append(Bucket(key, (i,), nbytes))
            open_by_dtype[key] = len(buckets) - 1
        else:
            b = buckets[slot]
            buckets[slot] = Bucket(key, b.indices + (i,),
                                   b.nbytes + nbytes)
        if bucket_bytes > 0 and buckets[open_by_dtype[key]].nbytes >= \
                bucket_bytes:
            del open_by_dtype[key]
    return buckets


def pack_bucket(leaves, *, pad_multiple: int = 1):
    """Ravel+concat a bucket's leaves into one 1-D fused buffer.

    ``pad_multiple`` zero-pads the buffer length up to a multiple (the
    hierarchical ladder needs dim 0 divisible by the ici axis size).
    Returns ``(flat, padded)`` where ``padded`` is the pad element
    count (slice it back off after the collective).
    """
    import jax.numpy as jnp

    flat = jnp.concatenate([jnp.ravel(jnp.asarray(l)) for l in leaves]) \
        if len(leaves) > 1 else jnp.ravel(jnp.asarray(leaves[0]))
    pad = (-flat.size) % max(pad_multiple, 1)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def unpack_bucket(flat, leaves):
    """Slice a reduced fused buffer back into the bucket's leaf shapes
    (templates come from the original ``leaves``; trailing padding is
    ignored)."""
    outs = []
    offset = 0
    for l in leaves:
        n = l.size
        outs.append(flat[offset:offset + n].reshape(l.shape))
        offset += n
    return outs
