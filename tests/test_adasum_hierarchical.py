"""Adasum numerical tests (mirroring the reference's
test_adasum_pytorch.py coefficient checks) + hierarchical allreduce."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
# The one sanctioned spelling of shard_map (the jaxcompat checker
# enforces it).
from horovod_tpu.parallel.mesh import shard_map_compat as shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import adasum as ad
from horovod_tpu.parallel import hierarchical as hier
from horovod_tpu.parallel import make_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _init():
    hvd.init()


def test_adasum_pair_properties():
    v = jnp.asarray(np.random.RandomState(0).randn(32), jnp.float32)
    # Identical gradients: adasum(a, a) == a (averaging regime).
    out = ad.adasum_pair(v, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v), rtol=1e-6)
    # Orthogonal gradients: adasum == sum.
    a = jnp.zeros(4).at[0].set(3.0)
    b = jnp.zeros(4).at[1].set(2.0)
    out = ad.adasum_pair(a, b)
    np.testing.assert_allclose(np.asarray(out), [3.0, 2.0, 0.0, 0.0],
                               rtol=1e-6)


def test_adasum_ingraph_matches_reference(mesh8):
    rng = np.random.RandomState(1)
    x = rng.randn(8, 16).astype(np.float32)

    out = jax.jit(shard_map(
        lambda s: ad.adasum_allreduce(s[0])[None],
        mesh=mesh8, in_specs=P("data"), out_specs=P("data")))(x)
    out = np.asarray(out)
    expect = ad.adasum_reference([x[i] for i in range(8)])
    for r in range(8):
        np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-5)


def test_adasum_via_allreduce_op(mesh8):
    from horovod_tpu.ops import collective_ops as C

    x = np.tile(np.random.RandomState(2).randn(16).astype(np.float32),
                (8, 1))
    out = jax.jit(shard_map(
        lambda s: C.allreduce(s[0], op=C.Adasum)[None],
        mesh=mesh8, in_specs=P("data"), out_specs=P("data")))(x)
    # All replicas identical input → adasum == that input.
    np.testing.assert_allclose(np.asarray(out)[0], x[0], rtol=1e-4,
                               atol=1e-5)


def test_hierarchical_allreduce():
    mesh = make_mesh(hier.make_hierarchical_axes(ici_size=4, dcn_size=2))
    x = np.random.RandomState(3).randn(8, 4, 6).astype(np.float32)

    def fn(s):
        return hier.hierarchical_allreduce(s.reshape(4, 6), average=True)[None]

    sm = shard_map(fn, mesh=mesh,
                   in_specs=P(("data_dcn", "data_ici")),
                   out_specs=P(("data_dcn", "data_ici")))
    out = np.asarray(jax.jit(sm)(x))
    expect = x.mean(0)
    for r in range(8):
        np.testing.assert_allclose(out[r], expect, rtol=1e-5, atol=1e-6)


def test_grouped_hierarchical_allreduce_fused_buffer():
    """Mixed-dtype, ici-indivisible leaves go through the fused flat
    buffer (pad to ici multiple, one ladder per dtype) and come back
    equal to the global mean — the fusion-buffer parity case
    (reference: fusion_buffer_manager.h:40)."""
    mesh = make_mesh(hier.make_hierarchical_axes(ici_size=4, dcn_size=2))
    rng = np.random.RandomState(7)
    # Leaf sizes 3*2=6, 5, 1 — none divisible by ici=4.
    leaves = [rng.randn(8, 3, 2).astype(np.float32),
              rng.randn(8, 5).astype(np.float32),
              rng.randn(8, 1).astype(np.float16)]

    def fn(a, b, c):
        outs = hier.grouped_hierarchical_allreduce(
            [a[0], b[0], c[0]], average=True)
        return tuple(o[None] for o in outs)

    spec = P(("data_dcn", "data_ici"))
    sm = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=(spec, spec, spec))
    outs = jax.jit(sm)(*leaves)
    for leaf, out in zip(leaves, outs):
        out = np.asarray(out)
        assert out.dtype == leaf.dtype
        expect = leaf.astype(np.float64).mean(0)
        tol = 1e-5 if leaf.dtype == np.float32 else 2e-3
        for r in range(8):
            np.testing.assert_allclose(out[r], expect, rtol=tol, atol=tol)


def test_grouped_allreduce_env_routes_hierarchical(monkeypatch):
    """C.grouped_allreduce honors HOROVOD_HIERARCHICAL_ALLREDUCE for a
    2-level axis tuple (reference: operations.cc:514-551 toggle)."""
    from horovod_tpu.ops import collective_ops as C

    monkeypatch.setenv("HOROVOD_HIERARCHICAL_ALLREDUCE", "1")
    mesh = make_mesh(hier.make_hierarchical_axes(ici_size=2, dcn_size=2),
                     devices=jax.devices()[:4])
    x = np.random.RandomState(11).randn(4, 5).astype(np.float32)

    def fn(s):
        (out,) = C.grouped_allreduce(
            [s[0]], op=C.Average, axis=("data_dcn", "data_ici"))
        return out[None]

    spec = P(("data_dcn", "data_ici"))
    sm = shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec)
    from horovod_tpu.jax import introspect

    counts = introspect.collective_counts(jax.jit(sm), x)
    assert counts.get("reduce_scatter", 0) >= 1, counts
    out = np.asarray(jax.jit(sm)(x))
    for r in range(4):
        np.testing.assert_allclose(out[r], x.mean(0), rtol=1e-5, atol=1e-6)


def test_hierarchical_allgather():
    mesh = make_mesh(hier.make_hierarchical_axes(ici_size=2, dcn_size=4))
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def fn(s):
        return hier.hierarchical_allgather(s)

    sm = shard_map(fn, mesh=mesh,
                   in_specs=P(("data_dcn", "data_ici")),
                   out_specs=P(("data_dcn", "data_ici")))
    out = np.asarray(jax.jit(sm)(x)).reshape(8, 8)
    # Order: dcn outer, ici inner == global rank order for this layout.
    for r in range(8):
        np.testing.assert_allclose(out[r], np.arange(8.0))


@pytest.mark.parametrize(
    "np_", [2, pytest.param(3, marks=pytest.mark.tier2)])
def test_adasum_native_multiproc(np_):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
         sys.executable, os.path.join(_REPO, "tests", "adasum_worker.py")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.count("ADASUM_OK") == np_
