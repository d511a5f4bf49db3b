"""In-graph collective op correctness on an 8-device mesh.

Pattern follows the reference's parallel tests: every rank contributes a
deterministic rank-dependent tensor, the collective runs, and the result is
checked against a locally computed expectation
(reference: test/parallel/test_torch.py:154-400).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
# The one sanctioned spelling of shard_map (the jaxcompat checker
# enforces it).
from horovod_tpu.parallel.mesh import shard_map_compat as shard_map

import horovod_tpu as hvd
from horovod_tpu.ops import collective_ops as C


def _per_rank(mesh, fn, x, out_specs=P("data"), check_vma=True):
    """Run fn under shard_map over the data axis with per-rank input rows."""
    sm = shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=out_specs,
                   check_vma=check_vma)
    return jax.jit(sm)(x)


@pytest.fixture(autouse=True)
def _init():
    hvd.init()


def test_allreduce_average_and_sum(mesh8):
    # x[r] = r * ones(3); per-rank shard is one row.
    x = np.arange(8, dtype=np.float32)[:, None] * np.ones((8, 3), np.float32)

    out = _per_rank(mesh8, lambda s: C.allreduce(s, op=C.Average), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(3.5, (8, 3)))

    out = _per_rank(mesh8, lambda s: C.allreduce(s, op=C.Sum), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(28.0, (8, 3)))


def test_allreduce_min_max_product(mesh8):
    x = (np.arange(8, dtype=np.float32) + 1.0)[:, None] * np.ones((8, 2), np.float32)
    out = _per_rank(mesh8, lambda s: C.allreduce(s, op=C.Min), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(1.0, (8, 2)))
    out = _per_rank(mesh8, lambda s: C.allreduce(s, op=C.Max), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(8.0, (8, 2)))
    out = _per_rank(mesh8, lambda s: C.allreduce(s, op=C.Product), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(np.prod(np.arange(1, 9.0)), (8, 2)))


def test_allreduce_prescale_postscale(mesh8):
    x = np.ones((8, 4), np.float32)
    out = _per_rank(
        mesh8,
        lambda s: C.allreduce(s, op=C.Sum, prescale_factor=0.5, postscale_factor=3.0),
        x,
    )
    np.testing.assert_allclose(np.asarray(out), np.tile(0.5 * 8 * 3.0, (8, 4)))


def test_allreduce_process_set(mesh8):
    ps = hvd.ProcessSet([0, 2, 4, 6])
    ps.process_set_id = 99  # mark as non-global without registering
    x = np.arange(8, dtype=np.float32)[:, None] * np.ones((8, 1), np.float32)
    out = _per_rank(mesh8, lambda s: C.allreduce(s, op=C.Sum, process_set=ps), x,
                    check_vma=False)
    out = np.asarray(out)
    # Ranks 0,2,4,6 see 0+2+4+6=12; complement group ranks see 1+3+5+7=16.
    for r in range(8):
        expect = 12.0 if r % 2 == 0 else 16.0
        np.testing.assert_allclose(out[r], expect)


def test_alltoall_process_set(mesh8):
    """In-graph alltoall restricted to a set: exchange stays inside the
    group (lowered to axis_index_groups; complement ranks run their own
    well-formed exchange that callers ignore)."""
    ps = hvd.ProcessSet([0, 2, 4, 6])
    ps.process_set_id = 98  # mark as non-global without registering
    # Each rank holds 4 rows valued 10*rank + row.
    x = (10.0 * np.arange(8)[:, None]
         + np.arange(4)[None, :]).astype(np.float32).reshape(8, 4, 1)

    out = _per_rank(
        mesh8,
        lambda s: C.alltoall(s[0], process_set=ps)[None], x,
        check_vma=False)
    out = np.asarray(out)
    members = [0, 2, 4, 6]
    for gi, r in enumerate(members):
        # Row j of member gi = member j's slice gi (set-rank order).
        expect = np.array([10.0 * members[j] + gi for j in range(4)])
        np.testing.assert_allclose(out[r].ravel(), expect)

    # A set whose size does not divide the axis raises loudly.
    bad = hvd.ProcessSet([0, 1, 2])
    bad.process_set_id = 97
    with pytest.raises(ValueError, match="divide"):
        _per_rank(mesh8,
                  lambda s: C.alltoall(s[0], process_set=bad)[None], x,
                  check_vma=False)


def test_grouped_allreduce(mesh8):
    xs = [np.ones((8, 2), np.float32), 2.0 * np.ones((8, 3), np.float32)]

    def fn(a, b):
        outs = C.grouped_allreduce([a, b], op=C.Sum)
        return tuple(outs)

    sm = shard_map(fn, mesh=mesh8, in_specs=(P("data"), P("data")),
                   out_specs=(P("data"), P("data")))
    o1, o2 = jax.jit(sm)(*xs)
    np.testing.assert_allclose(np.asarray(o1), np.tile(8.0, (8, 2)))
    np.testing.assert_allclose(np.asarray(o2), np.tile(16.0, (8, 3)))


def test_allgather(mesh8):
    x = np.arange(8, dtype=np.float32)[:, None] * np.ones((8, 2), np.float32)
    out = _per_rank(mesh8, lambda s: C.allgather(s), x,
                    out_specs=P("data"))
    # Each rank receives the full 8x2 stack; tiled output across 8 ranks
    # gives global shape (64, 2).
    out = np.asarray(out)
    assert out.shape == (64, 2)
    for r in range(8):
        np.testing.assert_allclose(out[r * 8:(r + 1) * 8, 0], np.arange(8.0))


def test_broadcast(mesh8):
    x = np.arange(8, dtype=np.float32)[:, None] * np.ones((8, 3), np.float32)
    out = _per_rank(mesh8, lambda s: C.broadcast(s, root_rank=5), x)
    np.testing.assert_allclose(np.asarray(out), np.tile(5.0, (8, 3)))


def test_broadcast_int_and_bool(mesh8):
    xi = np.arange(8, dtype=np.int32)[:, None] * np.ones((8, 2), np.int32)
    out = _per_rank(mesh8, lambda s: C.broadcast(s, root_rank=3), xi)
    assert np.asarray(out).dtype == np.int32
    np.testing.assert_array_equal(np.asarray(out), np.tile(3, (8, 2)))

    xb = (np.arange(8)[:, None] % 2 == 0) * np.ones((8, 2), bool)
    out = _per_rank(mesh8, lambda s: C.broadcast(s, root_rank=1), xb)
    assert np.asarray(out).dtype == bool
    np.testing.assert_array_equal(np.asarray(out), np.zeros((8, 2), bool))


def test_alltoall(mesh8):
    # Each rank r holds rows [r*8, r*8+8); after alltoall rank r holds
    # column slice j==r from every sender.
    x = np.arange(64, dtype=np.float32).reshape(64, 1)
    out = _per_rank(mesh8, lambda s: C.alltoall(s), x)
    out = np.asarray(out).reshape(8, 8)
    expect = np.arange(64).reshape(8, 8).T
    np.testing.assert_allclose(out, expect)


def test_reducescatter(mesh8):
    x = np.ones((8, 8, 2), np.float32)  # per rank: (8, 2) → scatter dim0

    def fn(s):
        return C.reducescatter(s[0], op=C.Sum)

    sm = shard_map(fn, mesh=mesh8, in_specs=P("data"), out_specs=P("data"))
    out = jax.jit(sm)(x)
    out = np.asarray(out)
    assert out.shape == (8, 2)
    np.testing.assert_allclose(out, 8.0)


def test_reducescatter_average(mesh8):
    x = np.full((8, 8, 2), 4.0, np.float32)

    def fn(s):
        return C.reducescatter(s[0], op=C.Average)

    sm = shard_map(fn, mesh=mesh8, in_specs=P("data"), out_specs=P("data"))
    out = np.asarray(jax.jit(sm)(x))
    np.testing.assert_allclose(out, 4.0)


def test_allreduce_differentiable(mesh8):
    x = np.ones((8, 2), np.float32)

    def loss(s):
        r = C.allreduce(s, op=C.Average)
        return jnp.sum(r * r)

    def per_rank(s):
        return jax.grad(loss)(s)

    out = _per_rank(mesh8, per_rank, x)
    # shard_map's psum transpose: each rank sees the partial of its
    # OWN loss, 2*mean/8 = 0.25 (not the total derivative of the
    # global summed loss, 2.0, that transpose(psum) = psum would give).
    expected = 0.25
    np.testing.assert_allclose(np.asarray(out), np.tile(expected, (8, 2)),
                               rtol=1e-6)


def test_mesh_factory():
    from horovod_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "model": -1})
    assert mesh.shape["data"] == 2
    assert mesh.shape["model"] == 4
    with pytest.raises(ValueError):
        make_mesh({"data": 3})
    with pytest.raises(ValueError):
        make_mesh({"data": -1, "model": -1})


def test_ingraph_fuzz(mesh8):
    """Seeded random op x dtype x shape sweep on the in-graph plane:
    12 cells through shard_map over the virtual 8-device mesh, exact
    expectations computed in numpy (the enumerated tests above cover
    the named cells; this samples the cross-product corners)."""
    rng = np.random.RandomState(31072026)
    for i in range(12):
        kind = rng.choice(["allreduce", "allgather", "reducescatter",
                           "broadcast"])
        dt = [np.float32, np.bfloat16 if hasattr(np, "bfloat16")
              else np.float32, np.int32][rng.randint(3)]
        inner = (int(rng.randint(1, 4)),)
        rows_per_rank = int(rng.randint(1, 3))
        # x[r] block = (r+1) * seeded values, one block per rank.
        base = rng.rand(8 * rows_per_rank, *inner)
        if np.issubdtype(dt, np.integer):
            base = (base * 10).astype(dt)
        else:
            base = base.astype(dt)
        scale = np.repeat(np.arange(1, 9, dtype=np.float64),
                          rows_per_rank)[:, None]
        x = (base.astype(np.float64) * scale).astype(dt)
        blocks = [x[r * rows_per_rank:(r + 1) * rows_per_rank]
                  for r in range(8)]

        if kind == "allreduce":
            out = _per_rank(mesh8, lambda s: C.allreduce(s, op=C.Sum), x)
            # Per-device shard: sum over ranks of each rank's block.
            expect = np.tile(
                sum(b.astype(np.float64) for b in blocks), (8, 1))
            np.testing.assert_allclose(
                np.asarray(out, np.float64), expect,
                rtol=2e-2 if dt not in (np.float32, np.int32) else 1e-5)
        elif kind == "allgather":
            out = _per_rank(mesh8, lambda s: C.allgather(s), x,
                            check_vma=False)
            expect = np.tile(x.astype(np.float64), (8, 1))
            np.testing.assert_allclose(
                np.asarray(out, np.float64), expect, rtol=1e-6)
        elif kind == "reducescatter":
            # scatter_dim rows must divide the axis: rebuild this
            # cell's input with 8 rows per device.
            base8 = rng.rand(64, *inner)
            base8 = ((base8 * 10).astype(dt)
                     if np.issubdtype(dt, np.integer)
                     else base8.astype(dt))
            scale8 = np.repeat(np.arange(1, 9, dtype=np.float64),
                               8)[:, None]
            x8 = (base8.astype(np.float64) * scale8).astype(dt)
            blocks8 = [x8[q * 8:(q + 1) * 8] for q in range(8)]
            out = _per_rank(
                mesh8, lambda s: C.reducescatter(s, op=C.Sum), x8,
                check_vma=False)
            total = sum(b.astype(np.float64) for b in blocks8)
            # Device q's shard is row q of the reduced block; stacked
            # over devices that is exactly `total`.
            np.testing.assert_allclose(
                np.asarray(out, np.float64), total,
                rtol=2e-2 if dt not in (np.float32, np.int32) else 1e-5)
        else:
            root = int(rng.randint(8))
            out = _per_rank(
                mesh8, lambda s: C.broadcast(s, root_rank=root), x,
                check_vma=False)
            expect = np.tile(blocks[root].astype(np.float64), (8, 1))
            np.testing.assert_allclose(
                np.asarray(out, np.float64), expect, rtol=1e-6)
