"""Per-dtype, byte-capped packing of a tensor group into flat buffers.

The reference's fusion buffer: tensors are packed into large same-dtype
buffers and each buffer is reduced by one collective (reference:
horovod/common/fusion_buffer_manager.h:40, docs/tensor-fusion.rst).

ONE caller is left: ``parallel.hierarchical.
grouped_hierarchical_allreduce``, whose ``psum_scatter`` needs one
array divisible by the ici size and so cannot take the leaves where
they lie. The flat route of the gradient sync (``lax.psum`` over the
tuple of leaves) copies nothing and does not come here. Buckets are
always per-dtype: a bf16 leaf in an fp32 buffer would be upcast and
double its bytes on the wire, and one all-reduce takes operands of one
element type.

``assign_buckets`` is pure Python over ``(nbytes, dtype_key)``
descriptors, unit-testable without tracing anything;
``pack_bucket``/``unpack_bucket`` do the jnp ravel/concat/slice work.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Sequence, Tuple


class Bucket(NamedTuple):
    """One flat buffer's worth of leaves.

    ``indices`` are positions into the caller's leaf list, in packing
    order (reverse order when ``reverse=True``); ``nbytes`` is the
    summed payload of the bucket.
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
    *last* layers' gradients first (the reference's coordinator
    negotiates tensors as they become ready). A bucket closes once its
    payload reaches ``bucket_bytes``; a single leaf larger than the cap
    still gets its own bucket (the cap bounds *batching*, it never
    splits a tensor).

    ``bucket_bytes <= 0`` means "no cap": exactly one bucket per dtype,
    in first-seen order.
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
