"""In-graph gradient sync: equality, traced shape, donation.

- the sync IS ``lax.psum(tree)`` times the scales, bit for bit, with
  the leaves reduced where they lie (no pack, no unpack, one ``psum``
  and at most one division a leaf, all directly under ``hvd_sync``);
- nothing in the environment changes what is traced (the bucket
  option left with PR 28);
- the hierarchical ``(dcn, ici)`` ladder owns its own packing;
- donated buffers survive lowering (``tf.aliasing_output`` in the
  StableHLO).

Runs on the 8-device virtual CPU mesh via shard_map (compat import:
this jax predates ``jax.shard_map``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.mesh import shard_map_compat


def shard_map(f, mesh, in_specs, out_specs):
    return shard_map_compat(f, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs)

import horovod_tpu.jax as hvd_jax
from horovod_tpu.jax import introspect
from horovod_tpu.jax.optimizer import allreduce_gradients
from horovod_tpu.ops import collective_ops as C
from horovod_tpu.parallel import hierarchical


@pytest.fixture
def mesh2():
    assert jax.device_count() >= 2
    return Mesh(np.asarray(jax.devices()[:2]), ("data",))


@pytest.fixture
def mesh4_hier():
    assert jax.device_count() >= 4
    return Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data_dcn", "data_ici"))


def _grads():
    rng = np.random.RandomState(0)
    return {
        "w1": jnp.asarray(rng.randn(10, 30), jnp.float32),
        "b1": jnp.asarray(rng.randn(7), jnp.bfloat16),
        "w2": jnp.asarray(rng.randn(501), jnp.float32),
        "w3": jnp.asarray(rng.randn(64, 64), jnp.bfloat16),
    }


def _reduce_on(mesh, grads, axis="data"):
    def red(g):
        return allreduce_gradients(g, axis=axis)

    return jax.jit(shard_map(red, mesh, P(), P()))(grads)


def _primitive_counts(fn, *args):
    """{primitive name: equations} of the traced ``fn``, the enclosing
    ``shard_map`` equation itself left out."""
    counts = {}
    for eqn in introspect.equations(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name != "shard_map":
            counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return counts


def _psums(fn, *args):
    """The ``psum`` equations of the traced ``fn``, in order."""
    return [eqn for eqn in
            introspect.equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "psum"]


def _leaves_by_route():
    from horovod_tpu.utils import metrics

    fam = metrics.REGISTRY.snapshot().get("hvd_grad_leaves_total", {})
    return {v["labels"]["route"]: v["value"]
            for v in fam.get("values", [])}


def test_bucketed_values_correct_np2(mesh2):
    # Average over 2 identical replicas == the input, bit for bit.
    grads = _grads()
    out = _reduce_on(mesh2, grads)
    for k in grads:
        np.testing.assert_allclose(
            np.asarray(out[k], np.float32),
            np.asarray(grads[k], np.float32), rtol=1e-6)


def test_hierarchical_bucket_routing(mesh4_hier, monkeypatch):
    # (dcn, ici) axis tuple + env toggle: the tree rides the
    # reduce_scatter -> psum -> all_gather ladder and comes back whole.
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    grads = _grads()
    axis = ("data_dcn", "data_ici")
    out = jax.jit(shard_map(
        lambda g: allreduce_gradients(g, axis=axis),
        mesh4_hier, P(), P()))(grads)
    for k in grads:
        assert out[k].dtype == grads[k].dtype
        np.testing.assert_allclose(
            np.asarray(out[k], np.float32),
            np.asarray(grads[k], np.float32), rtol=1e-5)


@pytest.mark.parametrize("pack_bytes,ladders", [(1024, 3), (None, 2)])
def test_hierarchical_ladder_owns_its_pack_size(mesh4_hier, monkeypatch,
                                                pack_bytes, ladders):
    # The ladder packs its own buffers, closed at ``PACK_BYTES`` of
    # leaves: at 1 KiB w1 and w2 (fp32, 1.2 and 2 KB) are a buffer each
    # and b1 waits for w3 (bf16); the default (4 MiB) is one buffer a
    # dtype. One ladder a buffer.
    assert hierarchical.PACK_BYTES == 4 * 1024 * 1024
    if pack_bytes is not None:
        monkeypatch.setattr(hierarchical, "PACK_BYTES", pack_bytes)
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    axis = ("data_dcn", "data_ici")
    counts = introspect.collective_counts(
        shard_map(lambda g: allreduce_gradients(g, axis=axis),
                  mesh4_hier, P(), P()), _grads())
    assert counts == {"reduce_scatter": ladders, "psum": ladders,
                      "all_gather": ladders}


def test_full_train_step_buckets_and_donates(mesh2):
    """End-to-end shape of the acceptance criterion: a jitted
    DistributedOptimizer train step traces the framework's ``psum`` of
    both gradient leaves AND lowers with donated weight/optimizer
    buffers."""
    import optax

    tx = hvd_jax.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.asarray(np.random.RandomState(1).randn(64, 17),
                               jnp.float32),
              "b": jnp.zeros((17,), jnp.float32)}
    opt_state = tx.init(params)
    x = jnp.asarray(np.random.RandomState(2).randn(8, 64), jnp.float32)

    def loss(params, x):
        return jnp.mean(jnp.square(x @ params["w"] + params["b"]))

    def step(params, opt_state, x):
        grads = jax.grad(loss)(params, x)
        updates, opt_state = tx.update(grads, opt_state, params)
        return jax.tree_util.tree_map(lambda p, u: p + u, params,
                                      updates), opt_state

    sm = shard_map(step, mesh2, (P(), P(), P("data")), (P(), P()))
    assert introspect.collective_counts(
        sm, params, opt_state, x)["psum"] >= 2
    donated = introspect.assert_donation_survives_lowering(
        sm, (0, 1), params, opt_state, x, min_donated=2)
    # params has 2 leaves; sgd momentum-less state may be empty, so
    # require at least the params buffers to alias outputs.
    assert len(donated) >= 2


def test_donation_detected_with_sharded_args(mesh2):
    """Sharded args carry mhlo.sharding = "{...}" attributes whose
    quoted braces sit in the same attribute dict as tf.aliasing_output;
    the detector must still credit the donation (regression: a
    brace-bounded regex missed every sharded donated arg — exactly the
    real-mesh train steps the tripwire guards)."""
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh2, P("data"))

    def step(a, b):
        return a + b

    a = jax.device_put(jnp.ones((8, 4)), sharding)
    b = jax.device_put(jnp.ones((8, 4)), sharding)
    donated = introspect.donated_input_indices(step, (0,), a, b)
    assert donated == [0]


def test_grouped_hierarchical_preserves_dtypes(mesh4_hier):
    """Direct satellite check: a bf16+fp32 mix through the fused
    hierarchical path yields one buffer per dtype — the bf16 majority
    never rides (and pays the bytes of) an fp32 buffer."""
    from horovod_tpu.parallel.hierarchical import (
        grouped_hierarchical_allreduce,
    )

    xs = [jnp.ones((6,), jnp.bfloat16),
          jnp.full((4, 4), 2.0, jnp.float32),
          jnp.full((10,), 3.0, jnp.bfloat16)]

    def fused(*xs):
        return tuple(grouped_hierarchical_allreduce(list(xs)))

    sm = shard_map(fused, mesh4_hier, (P(),) * 3, (P(),) * 3)
    outs = jax.jit(sm)(*xs)
    for x, o in zip(xs, outs):
        assert o.dtype == x.dtype
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(x, np.float32), rtol=1e-6)
    # Two dtypes -> exactly two ladders (2 reduce_scatter eqns), never
    # one merged (upcast) buffer.
    counts = introspect.collective_counts(sm, *xs)
    assert counts["reduce_scatter"] == 2


def test_donation_negative_case():
    def step(a, b):
        return a + b

    assert introspect.donated_input_indices(
        step, (), jnp.ones(3), jnp.ones(3)) == []
    with pytest.raises(AssertionError, match="donation"):
        introspect.assert_donation_survives_lowering(
            step, (), jnp.ones(3), jnp.ones(3))


def test_min_max_ops_keep_legacy_path(mesh2):
    # Non-fusable reductions must not be concatenated across leaves.
    grads = {"a": jnp.ones((4,), jnp.float32),
             "b": jnp.full((4,), 2.0, jnp.float32)}
    counts = introspect.collective_counts(
        shard_map(lambda g: allreduce_gradients(g, op=C.Max, axis="data"),
                  mesh2, P(), P()), grads)
    assert counts.get("psum", 0) == 0
    assert counts.get("pmax", 0) == 2


# ---- the flat route: the tree's leaves are reduced where they lie -----

def _odd_grads(n):
    """Per-replica DIFFERENT gradients, stacked over a leading axis of
    ``n``: both dtypes, odd shapes, a scalar, a 3-D leaf."""
    rng = np.random.RandomState(27)

    def leaf(shape, dtype):
        return jnp.asarray(rng.randn(n, *shape) * 3.0, dtype)

    return {
        "a_3d": leaf((3, 5, 7), jnp.float32),
        "b_bias": leaf((13,), jnp.bfloat16),
        "c_scalar": leaf((), jnp.float32),
        "d_wide": leaf((1, 129), jnp.bfloat16),
        "e_mat": leaf((17, 31), jnp.float32),
        "f_col": leaf((257, 1), jnp.float32),
    }


@pytest.mark.parametrize("scale", [(1.0, 1.0), (0.5, 4.0)])
@pytest.mark.parametrize("op", ["Average", "Sum"])
@pytest.mark.parametrize("n", [2, 4])
def test_sync_equals_whole_tree_psum_bit_for_bit(n, op, scale):
    """The sync IS the whole-tree ``psum`` (``/ n`` for Average) with
    the scales before and behind it: the same float sums of the same n
    values, element for element, and every replica holds the same
    bits."""
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("data",))
    grads = _odd_grads(n)
    pre, post = scale

    def squeeze(g):
        return jax.tree_util.tree_map(lambda x: x[0], g)

    def synced(g):
        out = allreduce_gradients(
            squeeze(g), op=getattr(C, op), axis="data",
            prescale_factor=pre, postscale_factor=post)
        return jax.tree_util.tree_map(lambda x: x[None], out)

    def times(x, factor):
        return x if factor == 1.0 else x * jnp.asarray(factor, x.dtype)

    def whole_tree(g):
        summed = jax.lax.psum(jax.tree_util.tree_map(
            lambda x: times(x, pre), squeeze(g)), "data")
        if op == "Average":
            summed = jax.tree_util.tree_map(
                lambda x: x / jnp.asarray(n, x.dtype), summed)
        return jax.tree_util.tree_map(
            lambda x: times(x, post)[None], summed)

    got = jax.jit(shard_map(synced, mesh, P("data"), P("data")))(grads)
    want = jax.jit(shard_map(whole_tree, mesh, P("data"), P("data")))(grads)
    for k in grads:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype == np.asarray(grads[k]).dtype
        assert g.shape == grads[k].shape
        assert np.array_equal(g, w), k
        for r in range(1, n):
            assert np.array_equal(g[0], g[r]), "replica %d leaf %s" % (r, k)
        # And it is the mean (or the sum) of the n values, scaled:
        # float64 of them, to the dtype.
        ref = np.asarray(grads[k], np.float64).sum(axis=0) * pre * post
        if op == "Average":
            ref = ref / n
        np.testing.assert_allclose(
            np.asarray(g[0], np.float64), ref,
            rtol=4e-2 if g.dtype != np.float32 else 1e-5, atol=1e-5)


def _trees():
    rng = np.random.RandomState(28)
    return {
        "mixed-dtype": _grads(),
        "one-leaf": {"w": jnp.asarray(rng.randn(33, 5), jnp.float32)},
        "40-leaf": {"l%02d" % i: jnp.asarray(
            rng.randn(1 + i % 7, 3 + i % 5),
            jnp.bfloat16 if i % 3 == 0 else jnp.float32)
            for i in range(40)},
    }


@pytest.mark.parametrize("tree", ["mixed-dtype", "one-leaf", "40-leaf"])
def test_flat_route_is_one_psum_a_leaf(mesh2, tree):
    """No leaf is packed, unpacked or converted on the flat route: the
    traced program is one ``psum`` a leaf, one division a leaf for
    Average and none for Sum, every ``psum`` directly under
    ``hvd_sync``, and nothing else."""
    grads = _trees()[tree]

    def fn(op):
        return shard_map(
            lambda g: allreduce_gradients(g, op=op, axis="data"),
            mesh2, P(), P())

    assert _primitive_counts(fn(C.Average), grads) == {
        "psum": len(grads), "div": len(grads)}
    assert _primitive_counts(fn(C.Sum), grads) == {"psum": len(grads)}
    psums = _psums(fn(C.Average), grads)
    for eqn in psums:
        assert str(eqn.source_info.name_stack) == "hvd_sync"
    # Each psum takes a gradient leaf as it came and gives its shape back.
    shapes = sorted((e.invars[0].aval.shape, str(e.invars[0].aval.dtype))
                    for e in psums)
    assert shapes == sorted((v.shape, str(v.dtype)) for v in grads.values())


def test_sync_issues_leaves_in_flatten_order(mesh2):
    """The group is handed over in the tree's own order, whatever the
    dtypes (nothing regroups the leaves by dtype or size any more), and
    the tree comes back in that order."""
    grads = {"l%d" % i: jnp.full((i + 1,), float(i),
                                 jnp.bfloat16 if i % 2 else jnp.float32)
             for i in range(6)}
    fn = shard_map(lambda g: allreduce_gradients(g, axis="data"),
                   mesh2, P(), P())
    assert [e.invars[0].aval.shape for e in _psums(fn, grads)] == \
        [(i + 1,) for i in range(6)]
    out = jax.jit(fn)(grads)
    for k in grads:
        assert out[k].dtype == grads[k].dtype
        assert np.array_equal(np.asarray(out[k]), np.asarray(grads[k])), k


def test_env_cannot_change_the_traced_sync(mesh2, monkeypatch):
    """The bucket option is gone: its name in the environment changes
    nothing that is traced, and the registry does not know it."""
    from horovod_tpu.common import knobs

    fn = shard_map(lambda g: allreduce_gradients(g, axis="data"),
                   mesh2, P(), P())
    monkeypatch.delenv("HVD_GRAD_BUCKET_BYTES", raising=False)
    texts = {str(jax.make_jaxpr(fn)(_grads()))}
    for value in ("0", "64"):
        monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", value)
        texts.add(str(jax.make_jaxpr(fn)(_grads())))
    assert len(texts) == 1
    assert "HVD_GRAD_BUCKET_BYTES" not in knobs.REGISTRY
    assert "grad_bucket_bytes" not in knobs.TUNABLE


@pytest.mark.parametrize("scale,expected", [
    ((1.0, 1.0), {}),
    ((0.5, 4.0), {"mul": 4}),
])
@pytest.mark.parametrize("op", ["Average", "Sum"])
def test_one_chip_axis_traces_nothing(op, scale, expected):
    """Axis size 1: the leaves come back as they came (times prescale x
    postscale when that is not 1.0): no collective, no copy, no scope."""
    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    grads = _grads()
    fn = shard_map(
        lambda g: allreduce_gradients(
            g, op=getattr(C, op), axis="data",
            prescale_factor=scale[0], postscale_factor=scale[1]),
        mesh1, P(), P())
    assert introspect.collective_counts(fn, grads) == {}
    assert _primitive_counts(fn, grads) == expected
    out = jax.jit(fn)(grads)
    for k in grads:
        assert out[k].dtype == grads[k].dtype
        want = grads[k] * jnp.asarray(scale[0] * scale[1], grads[k].dtype)
        assert np.array_equal(np.asarray(out[k]), np.asarray(want)), k


def test_one_chip_optimizer_step_holds_no_sync():
    """A DistributedOptimizer step over a one-device mesh lowers with no
    ``hvd_sync`` location and no all-reduce at all."""
    import optax

    mesh1 = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    tx = hvd_jax.DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((64, 17), jnp.float32),
              "b": jnp.zeros((17,), jnp.float32)}
    opt_state = tx.init(params)
    x = jnp.ones((8, 64), jnp.float32)

    def step(params, opt_state, x):
        grads = jax.grad(lambda p: jnp.mean(
            jnp.square(x @ p["w"] + p["b"])))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return jax.tree_util.tree_map(lambda p, u: p + u, params,
                                      updates), opt_state

    sm = shard_map(step, mesh1, (P(), P(), P("data")), (P(), P()))
    text = jax.jit(sm).lower(params, opt_state, x).as_text(debug_info=True)
    assert "hvd_update" in text
    assert "hvd_sync" not in text
    assert "all_reduce" not in text


def test_hierarchical_route_still_packs_and_pads(mesh4_hier, monkeypatch):
    """The (dcn, ici) ladder keeps its flat buffer: a ``psum_scatter``
    needs ONE array divisible by the ici size."""
    monkeypatch.setattr(hierarchical, "PACK_BYTES", 1024)
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    grads = _grads()  # b1 + w3 hold 4103 elements, w2 501: both padded
    axis = ("data_dcn", "data_ici")
    fn = shard_map(lambda g: allreduce_gradients(g, axis=axis),
                   mesh4_hier, P(), P())
    prims = _primitive_counts(fn, grads)
    assert prims["pad"] == 2
    assert prims["reshape"] >= len(grads)
    assert prims["reduce_scatter"] == prims["all_gather"] == 3


@pytest.mark.parametrize("route", ["in_place", "packed", "skipped"])
def test_leaf_counter_counts_each_leaf_once(route, mesh2, mesh4_hier,
                                            monkeypatch):
    mesh, axis = mesh2, "data"
    if route == "packed":
        monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
        mesh, axis = mesh4_hier, ("data_dcn", "data_ici")
    elif route == "skipped":
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    grads = _grads()
    before = _leaves_by_route()
    introspect.collective_counts(
        shard_map(lambda g: allreduce_gradients(g, axis=axis),
                  mesh, P(), P()), grads)
    after = _leaves_by_route()
    rose = {r: after.get(r, 0) - before.get(r, 0)
            for r in ("in_place", "packed", "skipped")}
    assert rose == {r: (len(grads) if r == route else 0) for r in rose}
