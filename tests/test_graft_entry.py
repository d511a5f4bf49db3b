"""The multichip flagship must sync gradients through hvd's OWN data plane.

Round-2 verdict: under plain pjit the DistributedOptimizer takes the
identity path and XLA auto-sharding does the gradient sync — so "hvd
trains multi-chip" was only proven in unit tests. These tests enforce
the shard_map composition used by ``__graft_entry__.dryrun_multichip``:
the traced train step must contain the framework's collectives
(``jax.introspect``), and the plain-pjit regression must fail the
assertion loudly.

Trace-only (``jax.make_jaxpr``): no XLA compilation, so this stays
tier-1 cheap while covering the same program construction the driver's
dryrun compiles.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
# The one sanctioned spelling of shard_map (the jaxcompat checker
# enforces it).
from horovod_tpu.parallel.mesh import shard_map_compat as shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd_jax
from horovod_tpu.jax import introspect
from horovod_tpu.ops import collective_ops as C
from horovod_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def flagship():
    import __graft_entry__ as g
    from horovod_tpu.models import Transformer

    cfg = g._flagship_config(tiny=True)
    model = Transformer(cfg)
    tokens = jnp.zeros((4, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens))

    def loss_fn(p, t):
        logits = model.apply(p, t)
        targets = jnp.roll(t, -1, axis=1)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.sum(jax.nn.one_hot(
            targets, logits.shape[-1], dtype=logits.dtype) * logits,
            axis=-1)
        return (lse - ll).mean()

    return model, loss_fn, params, tokens


def _make_step(tx, loss_fn):
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def test_flagship_shard_map_step_contains_framework_psum(flagship):
    model, loss_fn, params, tokens = flagship
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2},
                     devices=jax.devices()[:8])
    tx = hvd_jax.DistributedOptimizer(optax.sgd(1e-3))
    opt_state = jax.eval_shape(tx.init, params)
    fn = shard_map(
        _make_step(tx, loss_fn), mesh=mesh,
        in_specs=(P(), P(), P("data", None)),
        out_specs=(P(), P(), P()),
        axis_names={"data"}, check_vma=False)
    counts = introspect.assert_in_graph_gradient_sync(
        fn, params, opt_state, tokens, required=("psum",))
    assert counts["psum"] >= 1


def test_plain_pjit_regression_fails_loudly(flagship):
    """The tripwire discriminates: under plain jit (no bound axis) the
    optimizer takes the identity path and the assertion must raise."""
    model, loss_fn, params, tokens = flagship
    tx = hvd_jax.DistributedOptimizer(optax.sgd(1e-3))
    opt_state = jax.eval_shape(tx.init, params)
    step = _make_step(tx, loss_fn)
    counts = introspect.collective_counts(step, params, opt_state, tokens)
    assert counts.get("psum", 0) == 0
    with pytest.raises(AssertionError, match="NOT going through"):
        introspect.assert_in_graph_gradient_sync(
            step, params, opt_state, tokens, required=("psum",))


def test_flagship_hierarchical_step_contains_ladder(flagship, monkeypatch):
    """dcn x ici factored mesh: the traced step must contain the
    reduce_scatter -> psum -> all_gather ladder from
    parallel.hierarchical.grouped_hierarchical_allreduce (reference:
    NCCLHierarchicalAllreduce, nccl_operations.cc:233-440)."""
    model, loss_fn, params, tokens = flagship
    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    mesh = make_mesh({"data_dcn": 2, "data_ici": 2, "model": 2},
                     devices=jax.devices()[:8])
    dp = ("data_dcn", "data_ici")
    tx = hvd_jax.DistributedOptimizer(optax.sgd(1e-3), axis=dp)
    opt_state = jax.eval_shape(tx.init, params)
    fn = shard_map(
        _make_step(tx, loss_fn), mesh=mesh,
        in_specs=(P(), P(), P(dp, None)),
        out_specs=(P(), P(), P()),
        axis_names=set(dp), check_vma=False)
    counts = introspect.assert_in_graph_gradient_sync(
        fn, params, opt_state, tokens,
        required=("reduce_scatter", "psum", "all_gather"))
    assert counts["reduce_scatter"] >= 1


def test_flagship_adasum_step_contains_gather_tree(flagship):
    model, loss_fn, params, tokens = flagship
    mesh = make_mesh({"data": 2, "seq": 2, "model": 2},
                     devices=jax.devices()[:8])
    tx = hvd_jax.DistributedOptimizer(optax.sgd(1e-3), op=C.Adasum)
    opt_state = jax.eval_shape(tx.init, params)
    fn = shard_map(
        _make_step(tx, loss_fn), mesh=mesh,
        in_specs=(P(), P(), P("data", None)),
        out_specs=(P(), P(), P()),
        axis_names={"data"}, check_vma=False)
    counts = introspect.assert_in_graph_gradient_sync(
        fn, params, opt_state, tokens, required=("all_gather",))
    assert counts["all_gather"] >= 1


def test_dryrun_with_too_few_real_devices_raises(monkeypatch):
    """Asked for more devices than jax offers, and not for virtual
    ones: the dry run raises; it does not switch platform to get
    them."""
    import __graft_entry__ as g

    monkeypatch.setenv("XLA_FLAGS", "")
    want = jax.device_count() + 1
    with pytest.raises(RuntimeError, match="jax offers %d cpu"
                       % jax.device_count()):
        g.dryrun_multichip(want)
