"""Bucketed gradient allreduce: equality, overlap structure, donation.

The ISSUE 7 acceptance tests (docs/mfu.md):

- ``HVD_GRAD_BUCKET_BYTES=0`` restores the legacy single-psum path
  bit-exactly (equality at np=2 on the virtual mesh);
- the lowered train step contains >= N *independent* bucket
  collectives, not one whole-pytree psum (introspect-based);
- donated buffers survive lowering (``tf.aliasing_output`` in the
  StableHLO).

Runs on the 8-device virtual CPU mesh via shard_map (compat import:
this jax predates ``jax.shard_map``).
"""

import os

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
from horovod_tpu.jax.optimizer import (
    DEFAULT_GRAD_BUCKET_BYTES,
    allreduce_gradients,
    grad_bucket_bytes,
)


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


def _bucket_scopes(fn, *args):
    """{bucket scope: psum equations traced under it}."""
    scopes = {}
    for eqn in introspect.equations(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "psum":
            stack = str(eqn.source_info.name_stack)
            assert stack.startswith("hvd_sync/bucket_"), stack
            scope = stack.split("/")[1]
            scopes[scope] = scopes.get(scope, 0) + 1
    return scopes


def _leaves_by_route():
    from horovod_tpu.utils import metrics

    fam = metrics.REGISTRY.snapshot().get("hvd_grad_leaves_total", {})
    return {v["labels"]["route"]: v["value"]
            for v in fam.get("values", [])}


def test_default_bucket_bytes():
    assert DEFAULT_GRAD_BUCKET_BYTES == 4 * 1024 * 1024
    assert grad_bucket_bytes() in (DEFAULT_GRAD_BUCKET_BYTES,
                                   int(os.environ.get(
                                       "HVD_GRAD_BUCKET_BYTES", -1)))


def test_zero_restores_legacy_bit_exactly_np2(mesh2, monkeypatch):
    grads = _grads()
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "0")
    legacy = _reduce_on(mesh2, grads)
    for cap in ("1024", str(DEFAULT_GRAD_BUCKET_BYTES), "1073741824"):
        monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", cap)
        bucketed = _reduce_on(mesh2, grads)
        for k in grads:
            assert bucketed[k].dtype == grads[k].dtype
            assert np.array_equal(np.asarray(legacy[k]),
                                  np.asarray(bucketed[k])), \
                "cap=%s leaf=%s" % (cap, k)


def test_legacy_is_single_psum(mesh2, monkeypatch):
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "0")
    counts = introspect.collective_counts(
        shard_map(lambda g: allreduce_gradients(g, axis="data"),
                  mesh2, P(), P()), _grads())
    assert counts == {"psum": 1}


def test_bucketed_issues_independent_collectives(mesh2, monkeypatch):
    # 1 KiB cap over ~6 KiB of leaves: fp32 splits into 2 buckets and
    # bf16 into 2 -> 4 independent psums for XLA to overlap.
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
    counts = introspect.assert_bucketed_gradient_sync(
        shard_map(lambda g: allreduce_gradients(g, axis="data"),
                  mesh2, P(), P()), _grads(), min_buckets=4)
    assert counts["psum"] == 4


def test_per_dtype_buckets_at_large_cap(mesh2, monkeypatch):
    # A cap bigger than the whole tree still yields one bucket PER
    # DTYPE (bf16 never rides an fp32 group). jax 0.9.0 binds one
    # ``psum`` equation per leaf of a group, so the buckets are read
    # from their scopes and the psums counted per leaf.
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1073741824")
    fn = shard_map(lambda g: allreduce_gradients(g, axis="data"),
                   mesh2, P(), P())
    assert _bucket_scopes(fn, _grads()) == {
        "bucket_0_bfloat16": 2, "bucket_1_float32": 2}
    assert introspect.collective_counts(fn, _grads())["psum"] == 4
    assert "convert_element_type" not in _primitive_counts(fn, _grads())


def test_assert_bucketed_rejects_monolith(mesh2, monkeypatch):
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "0")
    with pytest.raises(AssertionError, match="monolithic"):
        introspect.assert_bucketed_gradient_sync(
            shard_map(lambda g: allreduce_gradients(g, axis="data"),
                      mesh2, P(), P()), _grads(), min_buckets=2)


def test_bucketed_values_correct_np2(mesh2, monkeypatch):
    # Average over 2 identical replicas == the input, bit for bit.
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
    grads = _grads()
    out = _reduce_on(mesh2, grads)
    for k in grads:
        np.testing.assert_allclose(
            np.asarray(out[k], np.float32),
            np.asarray(grads[k], np.float32), rtol=1e-6)


def test_hierarchical_bucket_routing(mesh4_hier, monkeypatch):
    # (dcn, ici) axis tuple + env toggle: every bucket rides the
    # reduce_scatter -> psum -> all_gather ladder.
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    grads = _grads()
    axis = ("data_dcn", "data_ici")
    counts = introspect.collective_counts(
        shard_map(lambda g: allreduce_gradients(g, axis=axis),
                  mesh4_hier, P(), P()), grads)
    assert counts["reduce_scatter"] == 4
    assert counts["all_gather"] == 4
    assert counts["psum"] == 4  # dcn hop per bucket
    out = jax.jit(shard_map(
        lambda g: allreduce_gradients(g, axis=axis),
        mesh4_hier, P(), P()))(grads)
    for k in grads:
        np.testing.assert_allclose(
            np.asarray(out[k], np.float32),
            np.asarray(grads[k], np.float32), rtol=1e-5)


def test_assert_bucketed_rejects_hierarchical_monolith(mesh4_hier,
                                                       monkeypatch):
    # One whole-pytree hierarchical ladder traces as 1 reduce_scatter
    # + 1 dcn psum; summing those would fake 2 "buckets" (review
    # catch) — the max-based count must still call it a monolith.
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "0")
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    grads = {"a": jnp.ones((8,), jnp.float32),
             "b": jnp.ones((8,), jnp.float32)}
    axis = ("data_dcn", "data_ici")
    with pytest.raises(AssertionError, match="monolithic"):
        introspect.assert_bucketed_gradient_sync(
            shard_map(lambda g: allreduce_gradients(g, axis=axis),
                      mesh4_hier, P(), P()), grads, min_buckets=2)


def test_bucket_counter_increments_at_trace(mesh2, monkeypatch):
    from horovod_tpu.utils import metrics

    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")

    def total():
        fam = metrics.REGISTRY.snapshot().get("hvd_grad_buckets_total", {})
        return sum(v["value"] for v in fam.get("values", []))

    before = total()
    introspect.collective_counts(
        shard_map(lambda g: allreduce_gradients(g, axis="data"),
                  mesh2, P(), P()), _grads())
    assert total() - before == 4


def test_full_train_step_buckets_and_donates(mesh2, monkeypatch):
    """End-to-end shape of the acceptance criterion: a jitted
    DistributedOptimizer train step lowers with >= N independent bucket
    collectives AND donated weight/optimizer buffers."""
    import optax

    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
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
    introspect.assert_bucketed_gradient_sync(
        sm, params, opt_state, x, min_buckets=2)
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


def test_min_max_ops_keep_legacy_path(mesh2, monkeypatch):
    # Non-fusable reductions must not be concatenated across leaves.
    from horovod_tpu.ops import collective_ops as C

    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
    grads = {"a": jnp.ones((4,), jnp.float32),
             "b": jnp.full((4,), 2.0, jnp.float32)}
    counts = introspect.collective_counts(
        shard_map(lambda g: allreduce_gradients(g, op=C.Max, axis="data"),
                  mesh2, P(), P()), grads)
    assert counts.get("psum", 0) == 0
    assert counts.get("pmax", 0) == 2


# ---- ISSUE 27: a bucket's leaves are reduced where they lie ------------

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


@pytest.mark.parametrize("cap", ["64", "1024", str(DEFAULT_GRAD_BUCKET_BYTES)])
@pytest.mark.parametrize("n", [2, 4])
def test_in_place_equals_whole_tree_psum_bit_for_bit(n, cap, monkeypatch):
    """The bucketed result IS the whole-tree ``psum / n``: the same
    float sums of the same n values, element for element, and every
    replica holds the same bits."""
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", cap)
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("data",))
    grads = _odd_grads(n)

    def squeeze(g):
        return jax.tree_util.tree_map(lambda x: x[0], g)

    def bucketed(g):
        out = allreduce_gradients(squeeze(g), axis="data")
        return jax.tree_util.tree_map(lambda x: x[None], out)

    def whole_tree(g):
        out = jax.tree_util.tree_map(
            lambda x: x / jnp.asarray(n, x.dtype),
            jax.lax.psum(squeeze(g), "data"))
        return jax.tree_util.tree_map(lambda x: x[None], out)

    got = jax.jit(shard_map(bucketed, mesh, P("data"), P("data")))(grads)
    want = jax.jit(shard_map(whole_tree, mesh, P("data"), P("data")))(grads)
    for k in grads:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert g.dtype == w.dtype == np.asarray(grads[k]).dtype
        assert g.shape == grads[k].shape
        assert np.array_equal(g, w), k
        for r in range(1, n):
            assert np.array_equal(g[0], g[r]), "replica %d leaf %s" % (r, k)
        # And it is the average: float64 of the n values, to the dtype.
        mean = np.asarray(grads[k], np.float64).mean(axis=0)
        np.testing.assert_allclose(
            np.asarray(g[0], np.float64), mean,
            rtol=2e-2 if g.dtype != np.float32 else 1e-6, atol=1e-6)


@pytest.mark.parametrize("cap,buckets", [("1024", 4), ("1073741824", 2)])
def test_flat_route_copies_no_leaf(mesh2, monkeypatch, cap, buckets):
    """No leaf is packed or unpacked on the flat route: the traced
    program is one ``psum`` and one division a leaf, each under its
    bucket's scope, and nothing else."""
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", cap)
    grads = _grads()
    fn = shard_map(lambda g: allreduce_gradients(g, axis="data"),
                   mesh2, P(), P())
    assert _primitive_counts(fn, grads) == {"psum": len(grads),
                                            "div": len(grads)}
    scopes = _bucket_scopes(fn, grads)
    assert len(scopes) == buckets
    assert sum(scopes.values()) == len(grads)
    # Each psum takes a gradient leaf as it came and gives its shape back.
    shapes = sorted((e.invars[0].aval.shape, str(e.invars[0].aval.dtype))
                    for e in introspect.equations(jax.make_jaxpr(fn)(grads).jaxpr)
                    if e.primitive.name == "psum")
    assert shapes == sorted((v.shape, str(v.dtype)) for v in grads.values())


@pytest.mark.parametrize("scale,expected", [
    ((1.0, 1.0), {}),
    ((0.5, 4.0), {"mul": 4}),
])
@pytest.mark.parametrize("op", ["Average", "Sum"])
def test_one_chip_axis_traces_nothing(monkeypatch, op, scale, expected):
    """Axis size 1: the leaves come back as they came (times prescale x
    postscale when that is not 1.0): no collective, no copy, no scope."""
    from horovod_tpu.ops import collective_ops as C

    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
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


def test_one_chip_optimizer_step_holds_no_sync(monkeypatch):
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
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    grads = _grads()  # b1 has 7 elements, w2 501: both need padding
    axis = ("data_dcn", "data_ici")
    fn = shard_map(lambda g: allreduce_gradients(g, axis=axis),
                   mesh4_hier, P(), P())
    prims = _primitive_counts(fn, grads)
    assert prims["pad"] == 2
    assert prims["reshape"] >= len(grads)
    assert prims["reduce_scatter"] == prims["all_gather"] == 4


@pytest.mark.parametrize("route", ["in_place", "packed", "skipped"])
def test_leaf_counter_counts_each_leaf_once(route, mesh2, mesh4_hier,
                                            monkeypatch):
    monkeypatch.setenv("HVD_GRAD_BUCKET_BYTES", "1024")
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
