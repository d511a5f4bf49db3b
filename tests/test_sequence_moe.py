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


# ---------------------------------------------- MoeMlp, token-major -------

def _moe_layer(ffn, e, k, dtype=jnp.float32, d_model=16, d_ff=24):
    cfg = models.TransformerConfig(
        d_model=d_model, n_heads=2, d_ff=d_ff, dtype=dtype,
        block=models.BlockSpec(ffn=ffn, num_experts=e, experts_per_token=k))
    return moe_mod.MoeMlp(cfg)


def _dense_moe_reference(params, x, k, assignment, first=0):
    """Every token through EVERY expert whose weights ``params`` hold
    (``first`` onward of those the router scores), then the
    gate-weighted sum over those of the k it was sent to: no sort, no
    gather, no grouped matmul."""
    p = params["params"]
    tokens = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(tokens @ p["router"], axis=-1)
    experts = jax.lax.top_k(probs, k)[1] if assignment is None \
        else assignment
    up = jnp.einsum("tm,emf->tef", tokens, p["wi"])
    if "wg" in p:
        hidden = jax.nn.silu(
            jnp.einsum("tm,emf->tef", tokens, p["wg"])) * up
    else:
        hidden = jax.nn.gelu(up)
    every = jnp.einsum("tef,efm->tem", hidden, p["wo"])
    sent = jax.nn.one_hot(experts, probs.shape[-1]).sum(1)     # (T, E)
    here = (probs * sent)[:, first:first + p["wi"].shape[0]]
    return jnp.einsum("te,tem->tm", here, every).reshape(x.shape)


def _skewed(t, e, k):
    """A routing in which expert 0 receives a row of every token (most
    of the rows, or all but a few when k is 1) and the last expert
    none."""
    rest = 1 + (np.arange(t)[:, None] + np.arange(k - 1)[None]) % (e - 2)
    table = np.concatenate([np.zeros((t, 1), np.int64), rest], axis=1)
    if k == 1:
        table[::7, 0] = 1 + np.arange(len(table[::7])) % (e - 2)
    return jnp.asarray(table[:, :k], jnp.int32)


@pytest.mark.parametrize("routing", ["free", "forced", "skewed"])
@pytest.mark.parametrize("ffn,e,k", [("swiglu", 8, 2), ("gelu", 4, 1)])
def test_moe_mlp_matches_dense_per_token_reference(ffn, e, k, routing):
    """Output and EVERY gradient leaf (the router's and the input's
    included) against the dense reference, float32, to 1e-5."""
    from flax.core import meta

    layer = _moe_layer(ffn, e, k)
    rng = jax.random.split(jax.random.PRNGKey(29), 3)
    x = jax.random.normal(rng[0], (2, 20, 16), jnp.float32)
    ct = jax.random.normal(rng[1], x.shape, jnp.float32)
    params = meta.unbox(layer.init(rng[2], x))
    # Weights large enough that the gates differ from 1/E and a wrong
    # one shows.
    params = jax.tree.map(lambda a: 10.0 * a, params)
    t = x.shape[0] * x.shape[1]
    assignment = {
        "free": None,
        "forced": ((3 * jnp.arange(t)[:, None] + 5 * jnp.arange(k)[None])
                   % e).astype(jnp.int32),
        "skewed": _skewed(t, e, k),
    }[routing]
    if routing == "skewed":
        counts = np.bincount(np.asarray(assignment).reshape(-1), minlength=e)
        assert counts[-1] == 0 and counts[0] > t * k / 2 - 1

    def program(p, x_):
        out, sown = layer.apply(p, x_, assignment, mutable=["moe"])
        return jnp.sum(out * ct), (out, sown["moe"]["tokens_per_expert"][0])

    def reference(p, x_):
        out = _dense_moe_reference(p, x_, k, assignment)
        return jnp.sum(out * ct), out

    (_, (out, counts)), grads = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(params, x)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True))(params, x)
    assert int(counts.sum()) == t * k
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == (5 if ffn == "swiglu" else 4)
    for (path, got), ref in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            got, ref, rtol=1e-5, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))


# ------------------------- a layer that holds a share of the experts -----

# 2 of 8 experts held, 512 tokens, 2 experts a token: T x k = 1024 pairs,
# the prefix C = 2 x 1024 x 2 / 8 = 512 rows, one tile.
_HELD = dict(t=512, k=2, e=8, held=2, first=2)


def _held_layer(remat=False):
    import flax.linen as nn
    from horovod_tpu.models import transformer

    cfg = models.TransformerConfig(
        d_model=16, n_heads=2, d_ff=24, dtype=jnp.float32,
        block=models.BlockSpec(
            ffn="swiglu", num_experts=_HELD["e"],
            experts_per_token=_HELD["k"], experts_held=_HELD["held"],
            first_expert_held=_HELD["first"]))
    if not remat:
        return moe_mod.MoeMlp(cfg)
    # ``cfg.remat``'s policy (PR 31): nothing of this layer is kept.
    return nn.remat(moe_mod.MoeMlp, policy=jax.checkpoint_policies
                    .save_only_these_names(transformer.SAVED_FLASH_OUT,
                                           transformer.SAVED_FLASH_LSE))(cfg)


def _held_assignment(live):
    """(T, k) distinct experts a token of which exactly ``live`` pairs
    fall to the held experts 2 and 3, three in four of them to 2; the
    rest to experts outside the share."""
    t, k = _HELD["t"], _HELD["k"]
    assert k == 2 and 0 <= live <= t * k
    token = np.arange(t)
    table = np.stack([4 + token % 4, token % 2], axis=1)      # none held
    first = token < min(live, t)
    table[first, 0] = np.where(token[first] % 4, 2, 3)
    both = token < live - t
    table[both] = [2, 3]
    assert np.isin(table, [2, 3]).sum() == live
    return jnp.asarray(table, jnp.int32)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("live", [0, 100, 512, 513, 1024])
def test_held_moe_mlp_prefix_and_whole_rows(live, remat, monkeypatch):
    """The layer that holds 2 of 8 experts, its row arrays a prefix of
    C = 512 sorted rows where the live rows fit it and the whole 1024
    where they do not, by forced assignments that send exactly ``live``
    pairs to the held experts: none, fewer than C, exactly C, one more,
    all. Output and every gradient leaf against the dense reference
    (float32, 1e-5), ``rows_overflow`` 0 or 1 as it should be, and
    against the SAME layer made to run the whole length alone: equal
    bit for bit, for the two bodies add the same numbers in the same
    order. Then the core by itself, where the gates' gradient shows."""
    from flax.core import meta
    from horovod_tpu.utils import metrics

    t, k, e = _HELD["t"], _HELD["k"], _HELD["e"]
    c = moe_mod.prefix_rows(t, k, _HELD["held"], e)
    assert c == 512 < t * k
    layer = _held_layer(remat)
    rng = jax.random.split(jax.random.PRNGKey(33), 3)
    x = jax.random.normal(rng[0], (1, t, 16), jnp.float32)
    ct = jax.random.normal(rng[1], x.shape, jnp.float32)
    params = jax.tree.map(lambda a: 10.0 * a,
                          meta.unbox(_held_layer().init(rng[2], x)))
    assignment = _held_assignment(live)

    def program(p, x_):
        out, sown = layer.apply(p, x_, assignment, mutable=["moe"])
        return jnp.sum(out * ct), (out, sown["moe"])

    def reference(p, x_):
        out = _dense_moe_reference(p, x_, k, assignment, _HELD["first"])
        return jnp.sum(out * ct), out

    def run(fn):
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))(
            params, x)

    def row_arrays():
        return {rows: metrics.REGISTRY.value(
            "hvd_moe_row_arrays_total", rows=rows) or 0
            for rows in ("prefix", "whole")}

    before = row_arrays()
    (_, (out, stats)), grads = run(program)
    traced = {rows: n - before[rows] for rows, n in row_arrays().items()}
    # Both bodies are traced, forward and backward, whatever runs.
    assert traced["prefix"] == traced["whole"] >= 2, traced
    assert int(stats["rows_held"][0]) == live
    assert int(stats["rows_overflow"][0]) == int(live > c)

    (_, want), want_grads = run(reference)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0 or not live, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            got, ref, rtol=1e-5, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))

    # The whole length alone: the layer as it is where no prefix is
    # shorter than T x k.
    monkeypatch.setattr(moe_mod, "prefix_rows", lambda t, k, held, e: t * k)
    (_, (whole_out, whole_stats)), whole_grads = run(program)
    assert int(whole_stats["rows_overflow"][0]) == 0
    np.testing.assert_array_equal(out, whole_out)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(whole_grads)):
        np.testing.assert_array_equal(
            got, ref, err_msg=jax.tree_util.keystr(path))
    if remat:
        return

    # The core: the choice against the whole-length body, on the gates
    # (T, k) of this routing as on tokens and weights.
    p = params["params"]
    tokens = x.reshape(t, 16)
    scores = jax.nn.softmax(tokens @ p["router"], axis=-1)
    gates = jnp.take_along_axis(scores, assignment, axis=1)
    order, inverse = moe_mod.sorted_by_expert(assignment, _HELD["first"], e)
    sizes = jnp.bincount(assignment.reshape(-1), length=e)[2:4]
    fixed = (order, inverse, sizes.astype(jnp.int32),
             jnp.sum(sizes).astype(jnp.int32))

    def core(body):
        def f(tokens, gates, wi, wo, wg):
            return body(k, tokens, fixed[0], fixed[1], gates, fixed[2],
                        fixed[3], wi, wo, wg)
        out, vjp = jax.vjp(f, tokens, gates, p["wi"], p["wo"], p["wg"])
        return (out,) + vjp(ct.reshape(t, 16))

    chosen = jax.jit(lambda: core(
        lambda *a: moe_mod._held_rows(c, *a, "swiglu")))()
    whole = jax.jit(lambda: core(
        lambda *a: moe_mod._expert_rows(t * k, *a, "swiglu")))()
    assert len(chosen) == 6
    for name, got, ref in zip(("out", "tokens", "gates", "wi", "wo", "wg"),
                              chosen, whole):
        assert live == 0 or float(jnp.max(jnp.abs(ref))) > 0, name
        np.testing.assert_array_equal(got, ref, err_msg=name)


@pytest.mark.parametrize("held", [None, 1], ids=["all-held", "share-held"])
def test_moe_mlp_moves_rows_by_two_gathers_and_two_kernel_sums(held):
    """Token-major dispatch and combine, read off the traced layer
    (bf16 compute over float32 parameters, forward + backward): nothing
    is broadcast to T x k rows, no row is scatter-added, the rows move
    by two gathers from the (T, M) array (dispatch forward, combine
    backward), and a token's rows are summed by the Pallas kernel of
    ``ops/pallas_gather_sum.py`` (combine forward, dispatch backward):
    NO gather reads the sorted rows, no array of T x k pairs of rows is
    made, and outside the kernels no float32 value has as many elements
    as a row array.

    The same of a layer that holds 1 of its 8 experts, in EACH of its
    two bodies (the prefix of C = 512 sorted rows and the whole 2048):
    the four sites, and the forward pair traced once more where the
    backward pass recomputes the body it takes (its combine is dead
    code there)."""
    import jax.extend
    from flax.core import meta
    from horovod_tpu.jax import introspect
    from horovod_tpu.utils import metrics

    if held is None:
        e, k, m, f, t = 4, 2, 40, 24, 16
        lengths = {"whole": t * k}
    else:
        e, k, m, f, t = 8, 2, 40, 24, 1024
        lengths = {"whole": t * k,
                   "prefix": moe_mod.prefix_rows(t, k, held, e)}
        assert lengths["prefix"] == 512
    cfg = models.TransformerConfig(
        d_model=m, n_heads=2, d_ff=f, dtype=jnp.bfloat16,
        block=models.BlockSpec(ffn="swiglu", num_experts=e,
                               experts_per_token=k, experts_held=held or 0))
    layer = moe_mod.MoeMlp(cfg)
    x = jnp.ones((1, t, m), jnp.bfloat16)
    params = meta.unbox(layer.init(jax.random.PRNGKey(0), x))

    # site: times traced in one body, forward + backward.
    gathers = {"dispatch_fwd": 1, "combine_bwd": 1}
    sums = {"combine_fwd": 1, "dispatch_bwd": 1}
    if held:
        gathers["dispatch_fwd"] = sums["combine_fwd"] = 2

    def counted():
        found = {}
        for rows in ("whole", "prefix"):
            for site in ("dispatch_fwd", "combine_fwd", "combine_bwd",
                         "dispatch_bwd"):
                for source in ("tokens", "rows"):
                    found["gather", site, source, rows] = (
                        metrics.REGISTRY.value(
                            "hvd_moe_row_gathers_total", site=site,
                            source=source, rows=rows) or 0)
                for via in ("kernel", "xla"):
                    found["sum", site, via, rows] = metrics.REGISTRY.value(
                        "hvd_moe_row_sums_total", site=site, rows=rows,
                        via=via) or 0
        return found

    before = counted()
    jaxpr = jax.make_jaxpr(lambda p, x_: jax.vjp(layer.apply, p, x_)[1](x_))(
        params, x)
    moved = {key: n - before[key] for key, n in counted().items()}
    want = {}
    for rows in lengths:
        for site, n in gathers.items():
            want["gather", site, "tokens", rows] = n
        for site, n in sums.items():
            want["sum", site, "kernel", rows] = n
    assert {key: n for key, n in moved.items() if n} == want

    eqns = list(introspect.equations(jaxpr.jaxpr, skip=("pallas_call",)))
    row_gathers, kernels = [], []
    for eqn in eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            kernels.append((eqn.invars[-1].aval.shape[0],
                            eqn.outvars[0].aval.shape))
        for out in eqn.outvars:
            shape = getattr(out.aval, "shape", ())
            if (not shape or shape[-1] != m or int(np.prod(shape))
                    not in [n * m for n in lengths.values()]):
                continue
            # Where all experts are held nothing is broadcast to rows at
            # all; a share-held body masks its dead rows by selects: a
            # select's mask and its scalar zero are no rows.
            if held and (out.aval.dtype == jnp.bool_ or (
                    name == "broadcast_in_dim"
                    and eqn.invars[0].aval.shape == ())):
                continue
            assert name != "broadcast_in_dim", eqn
            assert not name.startswith("scatter"), eqn
            assert out.aval.dtype != jnp.float32, eqn
            if name == "gather":
                row_gathers.append((shape[0], eqn.invars[0].aval.shape[0]))
    # (rows gathered, rows of the array read) and (rows summed, the
    # result's shape), each body's.
    want_gathers, want_kernels = [], []
    for n in lengths.values():
        want_gathers += [(n, t)] * (gathers["dispatch_fwd"] + 1)
        want_kernels += [(n, (t, m))] * (sums["combine_fwd"] + 1)
    assert sorted(row_gathers) == sorted(want_gathers)
    assert sorted(kernels) == sorted(want_kernels)
    assert len([eqn for eqn in eqns if eqn.primitive.name == "cond"]) == (
        2 if held else 0)
    # Nor anything else: the gates' gradient reaches the probabilities
    # by a select, the permutations are sorts.
    assert not [eqn for eqn in eqns
                if eqn.primitive.name.startswith("scatter")]
