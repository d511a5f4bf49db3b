"""DistributedOptimizer (optax) correctness on the 8-device mesh.

Verifies the key invariant of the reference's DistributedOptimizer
(reference: horovod/torch/optimizer.py:128-247): after one step, parameters
on every replica equal a single-process step taken with the mean gradient.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
# The one sanctioned spelling of shard_map (the jaxcompat checker
# enforces it).
from horovod_tpu.parallel.mesh import shard_map_compat as shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
import horovod_tpu.jax as hvd_jax
from horovod_tpu.jax.compression import Compression


@pytest.fixture(autouse=True)
def _init():
    hvd.init()


def _loss(params, x):
    pred = x @ params["w"] + params["b"]
    return jnp.mean(jnp.square(pred))


def test_distributed_optimizer_matches_mean_gradient(mesh8):
    key = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(key, (4, 2), jnp.float32),
        "b": jnp.zeros((2,), jnp.float32),
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 4), jnp.float32)

    tx = hvd_jax.DistributedOptimizer(optax.sgd(0.1))
    opt_state = tx.init(params)

    def step(params, opt_state, batch):
        grads = jax.grad(_loss)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    sm = shard_map(
        step, mesh=mesh8,
        in_specs=(P(), P(), P("data")),
        out_specs=(P(), P()),
        check_vma=False,
    )
    new_params, _ = jax.jit(sm)(params, opt_state, x)

    # Expectation: one SGD step with the mean of per-shard gradients.
    shard_grads = [
        jax.grad(_loss)(params, x[i * 2:(i + 1) * 2]) for i in range(8)
    ]
    mean_grads = jax.tree.map(
        lambda *gs: sum(gs) / len(gs), *shard_grads)
    expect = jax.tree.map(lambda p, g: p - 0.1 * g, params, mean_grads)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(new_params[k]), np.asarray(expect[k]),
            rtol=1e-5, atol=1e-6)


def test_a_leaf_whose_gradient_is_zero_goes_through_like_any_other(mesh8):
    """A parameter the loss does not move (a sparse-attention indexer
    under a language-model loss): its exactly-zero gradient is reduced
    and counted with the others, AdamW keeps moments for it (zeros) and
    its update is the weight decay alone."""
    from horovod_tpu.utils import metrics

    def in_place():
        fam = metrics.REGISTRY.snapshot().get("hvd_grad_leaves_total", {})
        return sum(v["value"] for v in fam.get("values", [])
                   if v["labels"]["route"] == "in_place")

    params = {"w": jnp.ones((4, 2), jnp.float32),
              "dead": jnp.full((3,), 2.0, jnp.float32)}
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 4), jnp.float32)
    tx = hvd_jax.DistributedOptimizer(
        optax.adamw(1e-2, b1=0.9, b2=0.95, weight_decay=0.1))

    def step(params, opt_state, batch):
        grads = jax.grad(lambda p, b: jnp.mean(jnp.square(
            b @ p["w"] * (jax.lax.stop_gradient(p["dead"]).sum() > 0))))(
                params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return grads, updates, opt_state

    before = in_place()
    grads, updates, opt_state = jax.jit(shard_map(
        step, mesh=mesh8, in_specs=(P(), P(), P("data")),
        out_specs=(P(), P(), P()), check_vma=False))(
            params, tx.init(params), x)
    assert in_place() - before == 2          # both leaves, the dead one too
    assert float(jnp.abs(grads["dead"]).max()) == 0.0
    assert float(jnp.abs(grads["w"]).max()) > 0.0
    np.testing.assert_allclose(np.asarray(updates["dead"]),
                               -1e-2 * 0.1 * 2.0 * np.ones(3), rtol=1e-6)
    mu = optax.tree_utils.tree_get(opt_state, "mu")
    nu = optax.tree_utils.tree_get(opt_state, "nu")
    assert mu["dead"].shape == (3,) and not np.asarray(mu["dead"]).any()
    assert not np.asarray(nu["dead"]).any() and np.asarray(nu["w"]).any()


def test_distributed_optimizer_compression(mesh8):
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    grads = {"w": jnp.full((8, 8), 0.123456789, jnp.float32)}
    tx = hvd_jax.DistributedOptimizer(
        optax.sgd(1.0), compression=Compression.bf16)

    def reduce_only(g):
        out = hvd_jax.allreduce_gradients(g, compression=Compression.bf16)
        return out

    sm = shard_map(reduce_only, mesh=mesh8, in_specs=P(), out_specs=P())
    out = jax.jit(sm)(grads)
    # bf16 round-trip: ~3 decimal digits.
    np.testing.assert_allclose(np.asarray(out["w"]), 0.123456789, rtol=1e-2)
    assert out["w"].dtype == jnp.float32
    del tx


def test_backward_passes_per_step(mesh8):
    params = {"w": jnp.zeros((2,), jnp.float32)}
    tx = hvd_jax.DistributedOptimizer(optax.sgd(1.0), backward_passes_per_step=2)
    opt_state = tx.init(params)

    def apply(g, opt_state, params):
        updates, opt_state = tx.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    g1 = {"w": jnp.array([1.0, 1.0])}
    g2 = {"w": jnp.array([3.0, 3.0])}
    params, opt_state = jax.jit(apply)(g1, opt_state, params)
    # First of two passes: no update applied yet.
    np.testing.assert_allclose(np.asarray(params["w"]), 0.0)
    params, opt_state = jax.jit(apply)(g2, opt_state, params)
    # Second pass: SGD step with the average (1+3)/2 = 2.
    np.testing.assert_allclose(np.asarray(params["w"]), -2.0)


def test_eager_allreduce_gradients_size1(hvd):
    grads = {"a": np.ones(3, np.float32), "b": np.full(2, 4.0, np.float32)}
    out = hvd_jax.allreduce_gradients(grads)
    np.testing.assert_allclose(np.asarray(out["a"]), 1.0)
    np.testing.assert_allclose(np.asarray(out["b"]), 4.0)


def test_broadcast_functions_size1(hvd):
    params = {"w": jnp.ones((2, 2))}
    out = hvd_jax.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(out["w"]), 1.0)
    obj = {"step": 7, "name": "x"}
    assert hvd_jax.broadcast_object(obj) == obj
    assert hvd_jax.allgather_object(obj) == [obj]


def test_sync_batch_stats(mesh8):
    # Per-replica data with different means; global stats must match numpy.
    x = np.random.RandomState(0).randn(16, 4).astype(np.float32)

    def fn(s):
        m, v = hvd_jax.sync_batch_stats(s, reduce_axes=(0,))
        return m, v

    sm = shard_map(fn, mesh=mesh8, in_specs=P("data"),
                   out_specs=(P(), P()), check_vma=False)
    m, v = jax.jit(sm)(x)
    np.testing.assert_allclose(np.asarray(m), x.mean(0), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), x.var(0), rtol=1e-4, atol=1e-4)


def test_sync_batch_norm_module(mesh8):
    import flax.linen as nn
    x = np.random.RandomState(1).randn(16, 6).astype(np.float32)
    bn = hvd_jax.SyncBatchNorm(use_running_average=False)

    def fn(s):
        vars_ = bn.init(jax.random.PRNGKey(0), s)
        out, _ = bn.apply(vars_, s, mutable=["batch_stats"])
        return out

    sm = shard_map(fn, mesh=mesh8, in_specs=P("data"), out_specs=P("data"),
                   check_vma=False)
    out = np.asarray(jax.jit(sm)(x))
    # Globally normalized → global mean ~0, var ~1.
    np.testing.assert_allclose(out.mean(0), 0.0, atol=1e-5)
    np.testing.assert_allclose(out.var(0), 1.0, atol=1e-2)


def test_jit_step_syncs_across_processes():
    """np=2, whole train step under plain jax.jit: gradients must sync
    through the io_callback bridge (r4 regression — the identity
    branch used to swallow multi-process sync; jax_jit_worker.py
    asserts step-on-mean-gradient and cross-rank identity)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, os.path.join(repo, "tests", "jax_jit_worker.py")],
        cwd=repo, capture_output=True, text=True, timeout=300)
    assert procs.returncode == 0, procs.stdout + procs.stderr
    assert procs.stdout.count("JAX_JIT_OK") == 2, procs.stdout
