"""The expert layer's sum over a token's sorted rows as a Pallas kernel
(``ops/pallas_gather_sum.py``), in interpret mode on the CPU, against the
form XLA had until PR 40: a gather of T x k rows, a mask over the dead
pairs and a float32 reduction over k."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_gather_sum
from horovod_tpu.parallel import moe as moe_mod
from horovod_tpu.utils import metrics


def _xla_sum(rows, inverse, k, live):
    """``parallel/moe.py`` ``_sum_per_token`` as the parent had it."""
    n, total = rows.shape[0], inverse.shape[0]
    pairs = rows[inverse if n == total else jnp.minimum(inverse, n - 1)]
    if live is not None:
        pairs = jnp.where((inverse < live)[:, None], pairs, 0)
    pairs = pairs.reshape(-1, k, rows.shape[-1])
    return jnp.sum(pairs, axis=1, dtype=jnp.float32).astype(rows.dtype)


def _routing(rng, t, k, e, lonely=0):
    """(T, k) distinct experts a token; the first ``lonely`` tokens
    choose among the LAST k experts alone (held by no share)."""
    experts = np.stack([rng.permutation(e)[:k] for _ in range(t)])
    experts[:lonely] = np.arange(e - k, e)
    return jnp.asarray(experts, jnp.int32)


# (t, k, e, held, m, n (None: t k), live): ``live`` 'none' = no count
# (every expert held), 'held' = the held experts' pairs, or a number.
_CASES = {
    "k1-all-held": (16, 1, 4, 4, 40, None, "none"),
    "k4-all-held": (16, 4, 8, 8, 40, None, "none"),
    "k8-all-held-two-chunks": (24, 8, 16, 16, 128, None, "none"),
    "k4-prefix-dead-pairs-clamped": (700, 4, 8, 2, 128, 1536, "held"),
    "k4-whole-length-dead-pairs": (700, 4, 8, 2, 128, None, "held"),
    "k8-prefix-three-blocks": (1100, 8, 32, 4, 40, 3072, "held"),
    "k2-live-0": (300, 2, 8, 2, 64, 512, 0),
    "k2-live-n": (300, 2, 8, 8, 64, 512, 512),
    "k4-tokens-with-no-live-pair": (64, 4, 8, 4, 40, 128, "held"),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_adds_what_xla_added_bit_for_bit(case, dtype):
    """Rows of eighths below 8: every float32 partial sum is exact, so
    the kernel's order (sorted rows, by expert) and XLA's (the k slots)
    give the same bits, and the single rounding to bf16 is the same
    rounding. Dead rows hold NaN: what a grouped matmul leaves unwritten
    past ``live`` is never added, nor multiplied by a zero."""
    t, k, e, held, m, n, live = _CASES[case]
    rng = np.random.default_rng(40)
    lonely = 5 if "no-live-pair" in case else 0
    experts = _routing(rng, t, k, e, lonely)
    order, inverse = moe_mod.sorted_by_expert(experts, 0, e)
    n = t * k if n is None else n
    if live == "none":
        assert held == e and n == t * k
        live = None
    elif live == "held":
        live = int(jnp.sum(experts < held))
        assert 0 < live <= n
    rows = jnp.asarray(rng.integers(-64, 64, (n, m)) / 8.0, dtype)
    if live is not None:
        rows = jnp.where((jnp.arange(n) < live)[:, None], rows, jnp.nan)
        live = jnp.int32(live)

    def kernel(rows, order, live):
        visits = pallas_gather_sum.plan(order[:n], k, live, t, m, dtype, held)
        return pallas_gather_sum.gather_sum(rows, visits, t), visits

    got, visits = jax.jit(kernel)(rows, order, live)
    want = jax.jit(lambda r, i, l: _xla_sum(r, i, k, l))(rows, inverse, live)
    assert got.dtype == dtype and got.shape == (t, m)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    if lonely:
        assert not np.asarray(got[:lonely], np.float32).any()
        assert np.asarray(got[lonely:], np.float32).any()
    # The visits counted fit the static bound, each block is visited,
    # block-major; the padding repeats the last one.
    count = int(visits.scalars[0])
    g = pallas_gather_sum._geometry(n, t, m, dtype, held)
    assert g.nb <= count <= g.visits == visits.blocks.shape[0]
    blocks, chunks = np.asarray(visits.blocks), np.asarray(visits.chunks)
    assert sorted(set(blocks[:count])) == list(range(g.nb))
    assert (np.diff(blocks) >= 0).all()
    assert (blocks[count:] == blocks[count - 1]).all()
    assert (chunks[count:] == chunks[count - 1]).all()


def test_kernel_adds_any_float32_rows_within_a_rounding_of_xla():
    """Rows whose float32 partial sums are NOT exact: the two orders of
    adding k = 8 of them differ by roundings of the partial sums, a few
    units in the last place."""
    t, k, e, m = 64, 8, 16, 128
    rng = np.random.default_rng(41)
    experts = _routing(rng, t, k, e)
    order, inverse = moe_mod.sorted_by_expert(experts, 0, e)
    rows = jnp.asarray(rng.standard_normal((t * k, m)), jnp.float32)
    visits = pallas_gather_sum.plan(order, k, None, t, m, rows.dtype, e)
    got = pallas_gather_sum.gather_sum(rows, visits, t)
    np.testing.assert_allclose(got, _xla_sum(rows, inverse, k, None),
                               rtol=0, atol=4e-6)


def _reference_rows(n, k, tokens, order, inverse, gates, sizes, live, wi,
                    wo, wg):
    """``_expert_rows`` as the parent had it, differentiated by jax: the
    gathers and XLA's sum."""
    rows = tokens[order[:n] // k]
    row_gates = gates.reshape(-1)[order][:n]
    out = moe_mod.grouped_ffn(rows, row_gates, sizes, wi, wo, wg, live,
                              ffn="swiglu")
    return _xla_sum(out, inverse, k, live)


@pytest.mark.parametrize("overflow", [False, True], ids=["prefix", "whole"])
def test_held_rows_gradients_equal_the_gather_forms(overflow):
    """``_held_rows`` (the choice of the row arrays' length in one
    differentiation rule) through the branch it takes: the prefix where
    the live rows fit it, the whole length where the routing overflows
    it (forced, as ``test_sequence_moe`` forces it). Output and every
    gradient against the parent's gathers and XLA's sum at that length,
    to float32 roundings (the matmuls' operands are the same; a token's
    rows are added in another order)."""
    t, k, e, held, m, f = 1024, 2, 8, 1, 16, 24
    c = moe_mod.prefix_rows(t, k, held, e)
    assert c == 512 < t * k
    rng = np.random.default_rng(42)
    experts = np.stack([rng.permutation(np.arange(held, e))[:k]
                        for _ in range(t)])
    # The held expert's pairs: a third of the prefix, or past it.
    experts[:(600 if overflow else 170), 0] = 0
    experts = jnp.asarray(experts, jnp.int32)
    order, inverse = moe_mod.sorted_by_expert(experts, 0, e)
    sizes = jnp.bincount(experts.reshape(-1), length=e)[:held].astype(
        jnp.int32)
    live = jnp.sum(sizes)
    assert (int(live) > c) == overflow
    key = jax.random.split(jax.random.PRNGKey(0), 6)
    tokens = jax.random.normal(key[0], (t, m), jnp.float32)
    gates = jax.random.uniform(key[1], (t, k), jnp.float32)
    wi, wg = (jax.random.normal(key[i], (held, m, f), jnp.float32)
              for i in (2, 3))
    wo = jax.random.normal(key[4], (held, f, m), jnp.float32)
    ct = jax.random.normal(key[5], (t, m), jnp.float32)

    def run(body):
        def f_(tokens, gates, wi, wo, wg):
            return body(tokens, order, inverse, gates, sizes, live, wi, wo, wg)
        out, vjp = jax.vjp(f_, tokens, gates, wi, wo, wg)
        return (out,) + vjp(ct)

    got = jax.jit(lambda: run(
        lambda *a: moe_mod._held_rows(c, k, *a, "swiglu")))()
    n = t * k if overflow else c
    want = jax.jit(lambda: run(
        lambda *a: _reference_rows(n, k, *a)))()
    for name, a, b in zip(("out", "tokens", "gates", "wi", "wo", "wg"),
                          got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, name
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6 * scale,
                                   err_msg=name)


def test_every_traced_sum_is_the_kernels():
    """``hvd_moe_row_sums_total``: a layer that holds 1 of 8 experts,
    traced forward and backward, counts its two sites in each of its two
    bodies under ``via="kernel"`` and nothing under ``via="xla"``."""
    from flax.core import meta
    from horovod_tpu import models

    cfg = models.TransformerConfig(
        d_model=16, n_heads=2, d_ff=8, dtype=jnp.float32,
        block=models.BlockSpec(ffn="swiglu", num_experts=8,
                               experts_per_token=2, experts_held=1))
    layer = moe_mod.MoeMlp(cfg)
    x = jnp.ones((1, 1024, 16), jnp.float32)
    params = meta.unbox(layer.init(jax.random.PRNGKey(0), x))

    def counted():
        return {(site, rows, via): metrics.REGISTRY.value(
            "hvd_moe_row_sums_total", site=site, rows=rows, via=via) or 0
            for site in ("combine_fwd", "dispatch_bwd")
            for rows in ("whole", "prefix") for via in ("kernel", "xla")}

    before = counted()
    jax.make_jaxpr(lambda p, x_: jax.vjp(layer.apply, p, x_)[1](x_))(
        params, x)
    moved = {key: n - before[key] for key, n in counted().items()}
    assert {key for key, n in moved.items() if n} == {
        (site, rows, "kernel") for site in ("combine_fwd", "dispatch_bwd")
        for rows in ("whole", "prefix")}, moved
