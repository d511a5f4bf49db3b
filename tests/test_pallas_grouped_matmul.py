"""The expert layer's grouped matmuls as Pallas kernels
(``ops/pallas_grouped_matmul.py``), in interpret mode on the CPU, against
``jax.lax.ragged_dot`` and its ``vjp``: the forward kernel, the same
kernel reading the panel transposed and the weight gradient's, then
``grouped_ffn`` and one held ``MoeMlp`` layer through them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import models
from horovod_tpu.ops import pallas_grouped_matmul as gmm
from horovod_tpu.parallel import moe as moe_mod
from horovod_tpu.utils import metrics

# (n, K, N, group sizes); a tile is 256 rows.
_CASES = {
    "balanced": (512, 128, 256, [128, 128, 128, 128]),
    "uneven": (512, 256, 128, [40, 300, 7, 165]),
    "empty-group": (512, 128, 256, [200, 0, 312, 0]),
    "boundary-inside-a-tile": (256, 128, 128, [100, 156]),
    "dead-rows": (512, 128, 256, [90, 0, 130, 50]),
    "dead-tiles": (1024, 128, 128, [1, 2, 3, 4, 5, 6, 7, 8]),
    "all-rows-live-last-groups-empty": (512, 256, 128, [512, 0, 0]),
    "no-row-live": (256, 128, 128, [0, 0]),
    "many-dead-tiles": (4096, 128, 128, [300, 212, 0, 100]),
}


def _operands(case, dtype):
    """Eighths below 8 and panels of quarters: every float32 partial sum
    of a product is exact, so the kernel's order of additions and XLA's
    give the same bits. The dead rows of ``lhs`` hold NaN."""
    n, k, width, sizes = _CASES[case]
    rng = np.random.default_rng(43)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    alive = (jnp.arange(n) < live)[:, None]
    lhs = jnp.asarray(rng.integers(-16, 16, (n, k)) / 8.0, dtype)
    rhs = jnp.asarray(rng.integers(-4, 4, (len(sizes), k, width)) / 4.0,
                      dtype)
    d_out = jnp.asarray(rng.integers(-16, 16, (n, width)) / 8.0, dtype)
    return lhs, rhs, d_out, sizes, alive


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernels_multiply_what_ragged_dot_multiplies(case, dtype):
    """Forward, input gradient (the panel read transposed) and weight
    gradient, bit for bit on the live rows; a group without rows
    receives a ZERO weight gradient; NaN in the dead rows of ``lhs`` and
    of the cotangent reaches no live row and no weight gradient."""
    lhs, rhs, d_out, sizes, alive = _operands(case, dtype)
    assert gmm.divides(lhs.shape, rhs.shape)
    poisoned = jnp.where(alive, lhs, jnp.nan)
    d_poisoned = jnp.where(alive, d_out, jnp.nan)

    def kernel(lhs, rhs, d_out):
        out, vjp = jax.vjp(lambda l, r: gmm.grouped_matmul(l, r, sizes),
                           lhs, rhs)
        return (out,) + vjp(d_out)

    def xla(lhs, rhs, d_out):
        out, vjp = jax.vjp(lambda l, r: lax.ragged_dot(l, r, sizes),
                           lhs, rhs)
        return (out,) + vjp(d_out)

    out, d_lhs, d_rhs = jax.jit(kernel)(poisoned, rhs, d_poisoned)
    want, want_lhs, want_rhs = jax.jit(xla)(
        jnp.where(alive, lhs, 0), rhs, jnp.where(alive, d_out, 0))
    assert out.dtype == d_lhs.dtype == lhs.dtype and d_rhs.dtype == rhs.dtype
    for name, got, ref in (("out", out, want), ("d_lhs", d_lhs, want_lhs)):
        got, ref = jnp.where(alive, got, 0), jnp.where(alive, ref, 0)
        assert bool(jnp.all(jnp.isfinite(got))), name
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32), name)
    assert bool(jnp.all(jnp.isfinite(d_rhs)))
    np.testing.assert_array_equal(np.asarray(d_rhs, np.float32),
                                  np.asarray(want_rhs, np.float32))
    for g, size in enumerate(np.asarray(sizes)):
        if size == 0:
            assert not np.asarray(d_rhs[g], np.float32).any(), g


@pytest.mark.parametrize("case", sorted(_CASES))
def test_the_plan_walks_the_live_tiles_group_by_group(case):
    """Every (group, tile) in which the group owns rows, in order; a
    group without rows once; nothing past the live rows; at most
    ``n / tm + groups - 1`` visits."""
    n, _, _, sizes = _CASES[case]
    tm = gmm._ROW_TILE
    walk = gmm.plan(jnp.asarray(sizes, jnp.int32), n)
    bound = n // tm + len(sizes) - 1
    count = int(walk.count)
    groups = np.asarray(walk.visits[:bound])[:count]
    tiles = np.asarray(walk.visits[bound:])[:count]
    want, start = [], 0
    for g, size in enumerate(sizes):
        first = min(start // tm, n // tm - 1)
        last = first if size == 0 else (start + size - 1) // tm
        want += [(g, tile) for tile in range(first, last + 1)]
        start += size
    assert list(zip(groups, tiles)) == want
    assert count <= bound
    np.testing.assert_array_equal(
        np.asarray(walk.offsets), np.concatenate([[0], np.cumsum(sizes)]))


def _count(kind, via):
    return metrics.REGISTRY.value("hvd_moe_grouped_matmuls_total",
                                  kind=kind, via=via) or 0


def _ffn_operands(n, m, f, sizes, dtype, seed=7):
    rng = np.random.default_rng(seed)
    e = len(sizes)
    rows = jnp.asarray(rng.normal(size=(n, m)), dtype)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (n,)), jnp.float32)
    wi, wg = (jnp.asarray(rng.normal(size=(e, m, f)) * 0.1, dtype)
              for _ in range(2))
    wo = jnp.asarray(rng.normal(size=(e, f, m)) * 0.1, dtype)
    return rows, gates, wi, wo, wg


def _with_ragged_dot(monkeypatch):
    monkeypatch.setattr(gmm, "divides", lambda *shapes: False)


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "gelu"])
@pytest.mark.parametrize("live", [None, 300], ids=["all-live", "live-300"])
def test_grouped_ffn_through_the_kernels(monkeypatch, gated, live):
    """``grouped_ffn`` and every gradient through the kernels equal the
    ``ragged_dot`` path's to bf16's rounding (the float32 sums run in
    another order), and the counter says which made them."""
    n, m, f = 512, 128, 256
    sizes = [120, 0, 100, 80] if live else [128, 200, 0, 184]
    rows, gates, wi, wo, wg = _ffn_operands(n, m, f, sizes, jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = None if live is None else jnp.int32(live)
    alive = (jnp.arange(n) < (n if live is None else live))[:, None]

    def loss(rows, gates, wi, wo, wg):
        out = moe_mod.grouped_ffn(rows, gates, sizes, wi, wo,
                                  wg if gated else None, live,
                                  ffn="swiglu" if gated else "gelu")
        return jnp.sum(jnp.where(alive, out, 0).astype(jnp.float32) ** 2)

    grad = lambda: jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))(
        rows, gates, wi, wo, wg)
    before = {kv: _count(*kv) for kv in (
        ("forward", "kernel"), ("input_grad", "kernel"),
        ("weight_grad", "kernel"), ("forward", "xla"))}
    got, got_grads = grad()
    assert _count("forward", "xla") == before["forward", "xla"]
    for kind in ("forward", "input_grad", "weight_grad"):
        assert _count(kind, "kernel") >= before[kind, "kernel"] + (
            3 if gated else 2), kind
    _with_ragged_dot(monkeypatch)
    kernel_counts = {kind: _count(kind, "kernel")
                     for kind in ("forward", "input_grad", "weight_grad")}
    want, want_grads = grad()
    assert _count("forward", "xla") >= before["forward", "xla"] + (
        3 if gated else 2)
    assert kernel_counts == {kind: _count(kind, "kernel")
                             for kind in kernel_counts}
    np.testing.assert_allclose(float(got), float(want), rtol=2e-3)
    for name, a, b in zip(("rows", "gates", "wi", "wo", "wg"), got_grads,
                          want_grads):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        if name == "rows":      # a dead row's gradient is the caller's to mask
            a, b = (np.where(np.asarray(alive), x, 0) for x in (a, b))
        assert np.isfinite(a).all(), name
        assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(b) + 1e-6, name


def test_a_shape_the_tiles_do_not_divide_is_ragged_dots():
    """An odd width or a few rows: ``lax.ragged_dot``, counted ``xla``."""
    assert not gmm.divides((512, 96), (4, 96, 128))
    assert not gmm.divides((512, 128), (4, 128, 200))
    assert not gmm.divides((384, 128), (4, 128, 128))
    with pytest.raises(ValueError, match="do not divide"):
        gmm.grouped_matmul(jnp.zeros((384, 128)), jnp.zeros((4, 128, 128)),
                           jnp.zeros((4,), jnp.int32))
    rows, gates, wi, wo, wg = _ffn_operands(96, 40, 72, [30, 66],
                                            jnp.float32)
    before = _count("forward", "xla"), _count("forward", "kernel")
    out = moe_mod.grouped_ffn(rows, gates, jnp.asarray([30, 66], jnp.int32),
                              wi, wo, wg, ffn="swiglu")
    assert out.shape == (96, 40)
    assert _count("forward", "xla") == before[0] + 3
    assert _count("forward", "kernel") == before[1]


@pytest.mark.parametrize("rows", ["prefix", "overflow"])
def test_a_held_layer_through_the_kernels(monkeypatch, rows):
    """One ``MoeMlp`` that holds 2 of 8 experts at widths the tiles
    divide, the prefix's branch and (every token sent to a held expert)
    the whole length's: loss and every gradient leaf equal the
    ``ragged_dot`` path's to bf16's rounding."""
    t, k, m, f, e, held = 512, 2, 128, 128, 8, 2
    c = moe_mod.prefix_rows(t, k, held, e)
    assert c == 512 < t * k
    assert gmm.divides((c, m), (held, m, f))
    assert gmm.divides((t * k, m), (held, m, f))
    cfg = models.TransformerConfig(
        d_model=m, n_heads=2, d_ff=f, dtype=jnp.bfloat16,
        block=models.BlockSpec(ffn="swiglu", num_experts=e,
                               experts_per_token=k, experts_held=held))
    layer = moe_mod.MoeMlp(cfg)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(1, t, m)), jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(0), x)
    if rows == "overflow":      # every pair's expert is held: 1024 > C
        assignment = jnp.tile(jnp.asarray([[0, 1]], jnp.int32), (t, 1))
    else:
        assignment = jnp.asarray(
            np.stack([rng.permutation(e)[:k] for _ in range(t)]), jnp.int32)

    def loss(params):
        out, sown = layer.apply(params, x, assignment, mutable=["moe"])
        return (jnp.sum(out.astype(jnp.float32) ** 2),
                sown["moe"]["rows_overflow"][0])

    grad = lambda: jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    before = _count("weight_grad", "kernel"), _count("forward", "xla")
    (got, overflow), got_grads = grad()
    assert int(overflow) == (rows == "overflow")
    assert _count("weight_grad", "kernel") > before[0]
    assert _count("forward", "xla") == before[1]
    _with_ragged_dot(monkeypatch)
    (want, _), want_grads = grad()
    assert _count("forward", "xla") > before[1]
    np.testing.assert_allclose(float(got), float(want), rtol=2e-3)
    flat = jax.tree_util.tree_leaves_with_path(got_grads)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.isfinite(a).all(), path
        assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(b) + 1e-6, path
