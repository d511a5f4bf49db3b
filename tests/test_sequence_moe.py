"""Sequence parallelism (ring / Ulysses attention) and MoE correctness."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
# The one sanctioned spelling of shard_map (the jaxcompat checker
# enforces it).
from horovod_tpu.parallel.mesh import shard_map_compat as shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import make_mesh
from horovod_tpu.parallel import moe as moe_mod
from horovod_tpu.parallel import sequence as seq_mod
from horovod_tpu import models


@pytest.fixture(autouse=True)
def _init():
    hvd.init()


def _dense_reference(q, k, v, causal):
    return np.asarray(seq_mod._dense_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal))


def _seq_mesh(n):
    return make_mesh({"seq": n})


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = _seq_mesh(8)
    rng = np.random.RandomState(0)
    b, s, h, d = 2, 32, 4, 8
    q = rng.randn(b, s, h, d).astype(np.float32)
    k = rng.randn(b, s, h, d).astype(np.float32)
    v = rng.randn(b, s, h, d).astype(np.float32)

    fn = shard_map(
        lambda q_, k_, v_: seq_mod.ring_attention(q_, k_, v_, axis="seq",
                                                  causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)
    out = np.asarray(jax.jit(fn)(q, k, v))
    expect = _dense_reference(q, k, v, causal)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)


def test_ulysses_attention_matches_dense():
    rng = np.random.RandomState(1)
    b, s, h, d = 2, 16, 8, 4
    q = rng.randn(b, s, h, d).astype(np.float32)
    k = rng.randn(b, s, h, d).astype(np.float32)
    v = rng.randn(b, s, h, d).astype(np.float32)

    devices = jax.devices()[:4]
    mesh = make_mesh({"seq": 4}, devices=devices)
    fn = shard_map(
        lambda q_, k_, v_: seq_mod.ulysses_attention(q_, k_, v_, axis="seq"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False)
    out = np.asarray(jax.jit(fn)(q, k, v))
    expect = _dense_reference(q, k, v, True)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)


def test_ring_attention_grad_flows():
    mesh = _seq_mesh(8)
    rng = np.random.RandomState(2)
    q = rng.randn(1, 16, 2, 4).astype(np.float32)

    def loss(q_):
        out = seq_mod.ring_attention(q_, q_, q_, axis="seq", causal=True)
        return jax.lax.psum(jnp.sum(out * out), "seq")

    fn = shard_map(jax.grad(loss), mesh=mesh, in_specs=P(None, "seq"),
                   out_specs=P(None, "seq"), check_vma=False)
    g = np.asarray(jax.jit(fn)(q))
    assert g.shape == q.shape
    assert np.isfinite(g).all()
    assert np.abs(g).sum() > 0


def test_transformer_ring_matches_dense():
    cfg = models.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=64, dtype=jnp.float32)
    from flax.core import meta

    model_dense = models.Transformer(cfg)
    tokens = np.arange(32, dtype=np.int32).reshape(1, 32) % 64
    params = meta.unbox(
        model_dense.init(jax.random.PRNGKey(0), jnp.asarray(tokens)))
    expect = np.asarray(model_dense.apply(params, jnp.asarray(tokens)))

    cfg_ring = dataclasses.replace(cfg, attention="ring", seq_axis="seq")
    model_ring = models.Transformer(cfg_ring)
    mesh = _seq_mesh(8)
    fn = shard_map(
        lambda p, t: model_ring.apply(p, t),
        mesh=mesh, in_specs=(P(), P(None, "seq")),
        out_specs=P(None, "seq"), check_vma=False)
    out = np.asarray(jax.jit(fn)(params, tokens))
    np.testing.assert_allclose(out, expect, rtol=5e-3, atol=5e-4)


def test_top1_dispatch_capacity():
    logits = jnp.asarray(np.random.RandomState(3).randn(16, 4), jnp.float32)
    dispatch, combine = moe_mod.top1_dispatch(logits, capacity=3)
    assert dispatch.shape == (16, 4, 3)
    # Each token goes to at most one (expert, slot).
    assert float(dispatch.sum(axis=(1, 2)).max()) <= 1.0
    # No expert slot double-booked.
    assert float(dispatch.sum(axis=0).max()) <= 1.0
    # Combine weights are gate-scaled dispatch.
    assert float((combine > 0).sum()) == float((dispatch > 0).sum())


def test_expert_parallel_moe_matches_dense():
    n_chips, e, m, f = 4, 8, 16, 32
    t_local = 10
    capacity = 6
    rng = np.random.RandomState(4)
    x = rng.randn(n_chips, t_local, m).astype(np.float32)
    router = rng.randn(m, e).astype(np.float32) * 0.5
    wi = rng.randn(e, m, f).astype(np.float32) * 0.1
    wo = rng.randn(e, f, m).astype(np.float32) * 0.1

    devices = jax.devices()[:n_chips]
    mesh = make_mesh({"expert": n_chips}, devices=devices)
    fn = shard_map(
        lambda x_, wi_, wo_: moe_mod.expert_parallel_moe(
            x_[0], router, wi_, wo_, capacity, axis="expert")[None],
        mesh=mesh,
        in_specs=(P("expert"), P("expert"), P("expert")),
        out_specs=P("expert"), check_vma=False)
    out = np.asarray(jax.jit(fn)(x, wi, wo))

    for c in range(n_chips):
        expect = np.asarray(moe_mod.moe_ffn(
            jnp.asarray(x[c]), jnp.asarray(router), jnp.asarray(wi),
            jnp.asarray(wo), capacity))
        np.testing.assert_allclose(out[c], expect, rtol=2e-4, atol=2e-5)


def test_moe_transformer_forward_and_grad():
    cfg = models.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_seq_len=32, dtype=jnp.float32,
        block=models.BlockSpec(num_experts=4))
    model = models.Transformer(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    out = model.apply(params, tokens)
    assert out.shape == (2, 8, 64)

    def loss(p):
        return jnp.mean(model.apply(p, tokens) ** 2)

    g = jax.grad(loss)(params)
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    # Router must receive gradient (routing is differentiable through
    # the combine weights).
    flat = jax.tree_util.tree_flatten_with_path(g)[0]
    router_grads = [v for k, v in flat if "router" in str(k)]
    assert router_grads and float(np.abs(np.asarray(router_grads[0])).sum()) > 0
