"""Bucket-assignment math units (parallel/bucketing.py).

Pure-Python contracts the hierarchical ladder's packing relies on:
per-dtype splitting (never upcast a bf16 majority into an fp32
buffer), byte caps, order, and pack/unpack round-trips. No
mesh, no sweeps — seconds-fast (docs/mfu.md).
"""

import numpy as np
import pytest

from horovod_tpu.parallel.bucketing import (
    Bucket,
    assign_buckets,
    pack_bucket,
    unpack_bucket,
)


def _buckets(sizes, dtypes, cap, **kw):
    return assign_buckets(sizes, dtypes, cap, **kw)


def test_single_dtype_no_cap_is_one_bucket():
    bs = _buckets([100, 200, 300], ["f32"] * 3, 0)
    assert len(bs) == 1
    assert bs[0].nbytes == 600
    assert bs[0].dtype_key == "f32"


def test_per_dtype_split_never_mixes():
    bs = _buckets([4, 2, 4, 2], ["f32", "bf16", "f32", "bf16"], 0)
    assert len(bs) == 2
    by_key = {b.dtype_key: b for b in bs}
    assert set(by_key) == {"f32", "bf16"}
    # indices 0/2 are f32, 1/3 bf16 — no cross-contamination.
    assert sorted(by_key["f32"].indices) == [0, 2]
    assert sorted(by_key["bf16"].indices) == [1, 3]


def test_reverse_gradient_issue_order():
    # Reverse order: the LAST leaf leads the FIRST bucket, so the
    # collectives whose gradients backprop finishes first are issued
    # first.
    bs = _buckets([8, 8, 8], ["f32"] * 3, 16)
    assert bs[0].indices == (2, 1)
    assert bs[1].indices == (0,)


def test_forward_order_when_requested():
    bs = _buckets([8, 8, 8], ["f32"] * 3, 16, reverse=False)
    assert bs[0].indices == (0, 1)
    assert bs[1].indices == (2,)


def test_byte_cap_closes_buckets():
    bs = _buckets([10, 10, 10, 10], ["f32"] * 4, 20, reverse=False)
    assert [b.indices for b in bs] == [(0, 1), (2, 3)]
    assert all(b.nbytes == 20 for b in bs)


def test_oversize_leaf_gets_own_bucket():
    bs = _buckets([100, 4, 4], ["f32"] * 3, 16, reverse=False)
    assert bs[0] == Bucket("f32", (0,), 100)
    assert bs[1].indices == (1, 2)


def test_cap_interleaved_dtypes():
    sizes = [6, 6, 6, 6, 6]
    dts = ["a", "b", "a", "b", "a"]
    bs = _buckets(sizes, dts, 12, reverse=False)
    assert [(b.dtype_key, b.indices) for b in bs] == [
        ("a", (0, 2)), ("b", (1, 3)), ("a", (4,))]


def test_every_leaf_assigned_exactly_once():
    rng = np.random.RandomState(0)
    sizes = rng.randint(1, 1000, size=50).tolist()
    dts = rng.choice(["f32", "bf16", "i32"], size=50).tolist()
    bs = _buckets(sizes, dts, 512)
    seen = sorted(i for b in bs for i in b.indices)
    assert seen == list(range(50))
    for b in bs:
        assert b.nbytes == sum(sizes[i] for i in b.indices)
        assert all(dts[i] == b.dtype_key for i in b.indices)


def test_length_mismatch_raises():
    with pytest.raises(ValueError):
        assign_buckets([1, 2], ["f32"], 0)


def test_pack_unpack_round_trip():
    import jax.numpy as jnp

    leaves = [jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
              jnp.full((5,), 7.0, jnp.float32)]
    flat, pad = pack_bucket(leaves, pad_multiple=4)
    assert pad == 1 and flat.size == 12
    outs = unpack_bucket(flat, leaves)
    for orig, out in zip(leaves, outs):
        assert out.shape == orig.shape
        np.testing.assert_array_equal(np.asarray(out), np.asarray(orig))


def test_pack_preserves_dtype():
    import jax.numpy as jnp

    leaves = [jnp.ones((3,), jnp.bfloat16), jnp.ones((2, 2), jnp.bfloat16)]
    flat, _ = pack_bucket(leaves)
    # The fused buffer must stay bf16 — upcasting would double the
    # bytes on the wire for the bf16 majority.
    assert flat.dtype == jnp.bfloat16


@pytest.mark.parametrize("hierarchical", [False, True])
def test_only_the_hierarchical_route_packs(hierarchical, monkeypatch):
    """The optimizer's flat route hands the tree's leaves to the
    collective as they lie: ``pack_bucket`` / ``unpack_bucket`` are the
    hierarchical ladder's alone (one call each per buffer there)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.jax.optimizer import allreduce_gradients
    from horovod_tpu.parallel import bucketing, hierarchical as hier
    from horovod_tpu.parallel.mesh import shard_map_compat

    calls = {"pack": 0, "unpack": 0}
    pack, unpack = bucketing.pack_bucket, bucketing.unpack_bucket

    def counting_pack(leaves, **kw):
        calls["pack"] += 1
        return pack(leaves, **kw)

    def counting_unpack(flat, leaves):
        calls["unpack"] += 1
        return unpack(flat, leaves)

    monkeypatch.setattr(bucketing, "pack_bucket", counting_pack)
    monkeypatch.setattr(bucketing, "unpack_bucket", counting_unpack)
    monkeypatch.setattr(hier, "PACK_BYTES", 64)
    if hierarchical:
        monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data_dcn", "data_ici"))
    grads = {"a": jnp.ones((3, 5), jnp.float32),
             "b": jnp.ones((7,), jnp.bfloat16),
             "c": jnp.ones((33,), jnp.float32)}
    jax.make_jaxpr(shard_map_compat(
        lambda g: allreduce_gradients(g, axis=("data_dcn", "data_ici")),
        mesh=mesh, in_specs=P(), out_specs=P()))(grads)
    # 64-byte buffers: a (60 B) and c (132 B) share one, b (bf16) has
    # its own.
    assert calls == ({"pack": 2, "unpack": 2} if hierarchical
                     else {"pack": 0, "unpack": 0})
