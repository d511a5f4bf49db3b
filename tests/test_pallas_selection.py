"""``ops/pallas_selection.py``: the learned selection's choice of keys as
one Pallas call, against its plain definition (``learned_selection`` +
``pack_selection`` of ``models/transformer.py`` / ``ops/pallas_attention``),
and the rule by which a sparse layer takes one or the other.

CPU, interpret mode. The kernel and XLA add a pair's heads in their own
orders, so the inputs here are dyadic rationals small enough that every
partial sum is exact in float32: both sides then hold the same scores to
the last bit, and the planes have to be EQUAL, ties or none.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

import horovod_tpu as hvd
from horovod_tpu.jax import introspect
from horovod_tpu.models import transformer
from horovod_tpu.ops import pallas_selection
from horovod_tpu.utils import compile_cache
from horovod_tpu.ops.pallas_attention import (
    Selection,
    flash_attention,
    pack_selection,
    unpack_selection,
)

CHUNK = transformer._INDEX_CHUNK
HEADS, DIM = 4, 16
SHAPES = [(b, s, topk) for b in (1, 2) for s in (512, 1024)
          for topk in (128, 256)]


def _indexer(b, s, ties, seed=0):
    """q_i (B, S, J, D), k_i (B, S, D), w_i (B, S, J), float32: small
    integers, so a score is exact however it is added. Without ``ties``
    head 0 reads dimension 0 alone, where key s holds ``1 + s``, under
    a weight of 2 ** -11: it adds to the other heads' INTEGER sum a
    fraction in (0, 1/2] that no two keys share, so no row holds two
    equal scores. With ``ties`` integers up to 1 and weights in steps
    of a half: a few dozen values a row."""
    kq, kk, kw = jax.random.split(jax.random.PRNGKey(seed + 7 * s + b), 3)
    top, steps = (1, 2) if ties else (4, 1)
    q_i = jax.random.randint(kq, (b, s, HEADS, DIM), -top, top + 1)
    k_i = jax.random.randint(kk, (b, s, DIM), -top, top + 1)
    w_i = jax.random.randint(
        kw, (b, s, HEADS), -4 * steps, 4 * steps + 1) / steps
    if not ties:
        assert s <= 1024
        q_i = q_i.at[:, :, :, 0].set(0).at[:, :, 0, :].set(0).at[
            :, :, 0, 0].set(1)
        k_i = k_i.at[:, :, 0].set(1 + jnp.arange(s))
        w_i = w_i.at[:, :, 0].set(2.0 ** -11)
    return (q_i.astype(jnp.float32), k_i.astype(jnp.float32),
            w_i.astype(jnp.float32))


def _least_kept(b, s, topk):
    return b * sum(min(t + 1, topk) for t in range(s))


def _popcount(plane):
    return int(np.sum(np.bitwise_count(np.asarray(plane).view(np.uint32)),
                      dtype=np.int64))


@pytest.mark.parametrize("ties", [False, True], ids=["no-ties", "ties"])
@pytest.mark.parametrize("b,s,topk", SHAPES)
def test_both_planes_are_the_plain_definitions(b, s, topk, ties):
    """Word for word ``pack_selection(learned_selection(...))``: where
    no score ties at a row's threshold (asserted: the mask keeps exactly
    ``min(t + 1, topk)`` a row), and where many do (ties kept on both
    sides: more than that)."""
    q_i, k_i, w_i = _indexer(b, s, ties)
    mask = transformer.learned_selection(q_i, k_i, w_i, topk)
    kept = int(jnp.sum(mask))
    if ties:
        assert kept > _least_kept(b, s, topk)
    else:
        assert kept == _least_kept(b, s, topk)
    want = pack_selection(mask)
    got = pallas_selection.choose(q_i, k_i, w_i, topk, CHUNK)
    assert isinstance(got, Selection)
    for plane in ("by_query", "by_key"):
        np.testing.assert_array_equal(getattr(got, plane),
                                      getattr(want, plane), plane)
    # What the layer sows as ``dsa_kept``: either plane's set bits.
    assert _popcount(got.by_query) == _popcount(got.by_key) == kept


@pytest.mark.parametrize("b,s,topk", SHAPES)
def test_a_row_keeps_its_past_and_never_its_future(b, s, topk):
    """A query with no more than ``topk`` keys keeps every one of them;
    no query keeps a key after itself; every choosing query keeps at
    least ``topk``. Read off the kernel's own plane."""
    q_i, k_i, w_i = _indexer(b, s, ties=False, seed=3)
    got = pallas_selection.choose(q_i, k_i, w_i, topk, CHUNK)
    mask = np.asarray(unpack_selection(got, s))
    causal = np.tril(np.ones((s, s), bool))
    assert not (mask & ~causal).any()
    assert (mask[:, :topk] == causal[:topk]).all()
    assert (mask.sum(-1)[:, topk:] >= topk).all()
    # The other plane is the same mask, transposed.
    turned = Selection(got.by_key, got.by_query)
    np.testing.assert_array_equal(
        np.asarray(unpack_selection(turned, s)), mask.swapaxes(1, 2))


@pytest.mark.parametrize("shape", [(1, 128, 128), (2, 100, 100),
                                   (1, 512, 4224), (2, 1024, 1024)])
def test_unpack_selection_undoes_pack_selection(shape):
    mask = jax.random.bernoulli(jax.random.PRNGKey(sum(shape)), 0.4, shape)
    np.testing.assert_array_equal(
        unpack_selection(pack_selection(mask), shape[2]), mask)


@pytest.mark.parametrize("b,s,topk,d_v", [
    (1, 512, 128, 32), (2, 1024, 256, 32), (1, 512, 128, 64)])
def test_the_flash_kernels_under_the_kernels_planes(b, s, topk, d_v):
    """``flash_attention(select=)`` over the planes ``choose`` made,
    against dense attention under ``learned_selection``'s mask: output
    and the three gradients; q.k 32 wide, v ``d_v`` (the masked kernels
    go through the wrappers of the static ones: two widths there too)."""
    q_i, k_i, w_i = _indexer(b, s, ties=False, seed=5)
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(s), 4)
    q = jax.random.normal(kq, (b, s, 4, 32))
    k = jax.random.normal(kk, (b, s, 2, 32))
    v = jax.random.normal(kv, (b, s, 2, d_v))
    g = jax.random.normal(kg, (b, s, 4, d_v))
    planes = pallas_selection.choose(q_i, k_i, w_i, topk, CHUNK)
    mask = transformer.learned_selection(q_i, k_i, w_i, topk)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, select=planes)

    def dense(q, k, v):
        return transformer._dense_causal_attention(
            q, k, v, jnp.float32, select=mask)

    got, got_vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, e in zip(got_vjp(g), want_vjp(g)):
        np.testing.assert_allclose(a, e, atol=2e-4)


def test_queries_that_do_not_divide_into_blocks_are_refused():
    q_i, k_i, w_i = _indexer(1, 384, ties=False)
    with pytest.raises(ValueError, match="do not divide"):
        pallas_selection.choose(q_i, k_i, w_i, 64, CHUNK)


# ------------------------------------------------ the rule, the program ---

SPEC = transformer.BlockSpec(
    norm="rmsnorm", ffn="swiglu", positions="rope", tied_head=False,
    head_dim=16, n_kv_heads=2, qk_norm_per_head=True, index_heads=HEADS,
    index_head_dim=DIM, index_topk=64)
LAYERS = 2


def _model(attention="flash", remat=False):
    return transformer.Transformer(transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=LAYERS, d_ff=64,
        max_seq_len=512, dtype=jnp.float32, attention=attention,
        remat=remat, block=SPEC))


def _tokens(s, b=1):
    return jax.random.randint(jax.random.PRNGKey(s), (b, s), 0, 64)


@pytest.fixture(scope="module")
def params():
    variables = _model().init(jax.random.PRNGKey(1), _tokens(128))
    return {"params": meta.unbox(variables)["params"]}


def _equations(jaxpr, inside=False):
    """(equation, inside a ``checkpoint``?) of ``jaxpr`` and of every
    jaxpr its equations hold, a kernel's own body left out."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        if eqn.primitive.name == "pallas_call":
            continue
        within = inside or eqn.primitive.name == "remat2"
        for value in eqn.params.values():
            for cand in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, within)


def _calls(jaxpr, name):
    return [(eqn, inside) for eqn, inside in _equations(jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.params["name"] == name]


def _loops(jaxpr):
    return [eqn for eqn, _ in _equations(jaxpr)
            if eqn.primitive.name in ("while", "scan")]


def _selections():
    """The launch log's newest span, with the kernel's callee forgotten
    by jax's tracing cache: what is filed after it is the next trace's."""
    compile_cache.install_compile_listeners()
    pallas_selection._choose.clear_cache()
    return max((s["id"] for s in hvd.launch_spans()), default=0)


def _moved(before):
    """The sparse layers traced since ``before`` (a ``trace/block`` of
    kind ``sparse_attention`` each) and the kernel bodies that chose (a
    ``trace/kernel`` span named ``hvd_dsa_choose``; the callee is
    jitted, so the layers of one signature trace ONE)."""
    added = [s for s in hvd.launch_spans() if s["id"] > before]
    return {
        "layers": sum(s["name"] == "trace/block" and s["args"]["kind"]
                      == transformer.SPARSE_ATTENTION for s in added),
        "chosen": sum(s["name"] == "trace/kernel" and s["args"]["kernel"]
                      == introspect.KERNEL_DSA_CHOOSE for s in added)}


def test_a_flash_layer_of_whole_passes_calls_the_kernel(params):
    """S 512 under 'flash': one ``hvd_dsa_choose`` a layer, of FOUR
    operands, under ``attn/hvd_dsa_select``; two named planes a layer;
    no loop; and no value of S x S elements anywhere outside a kernel."""
    s, model = 512, _model()
    before = _selections()
    jaxpr = jax.make_jaxpr(lambda p: model.apply(p, _tokens(s)))(params)
    assert _moved(before) == {"layers": LAYERS, "chosen": 1}
    calls = _calls(jaxpr.jaxpr, introspect.KERNEL_DSA_CHOOSE)
    assert len(calls) == LAYERS
    for eqn, _ in calls:
        assert len(eqn.invars) == 4        # neither 3 nor 6: no flash kernel
    assert not _loops(jaxpr.jaxpr)
    assert str(jaxpr).count("name=hvd_flash_select]") == 2 * LAYERS
    largest = max(int(np.prod(v.aval.shape))
                  for eqn, _ in _equations(jaxpr.jaxpr)
                  if eqn.primitive.name != "pallas_call"
                  for v in eqn.outvars if hasattr(v.aval, "shape"))
    assert largest < s * s
    # Where the call stands: the jitted callee's own equation carries
    # the layer's scopes (the compiled step: ``tests/
    # test_flash_tpu_compile.py``).
    stands = [str(eqn.source_info.name_stack)
              for eqn, _ in _equations(jaxpr.jaxpr)
              if eqn.params.get("name") == "_choose"]
    assert stands == ["Transformer/layer_%d/attn/hvd_dsa_select" % i
                      for i in range(LAYERS)]
    norms = [str(eqn.source_info.name_stack)
             for eqn, _ in _equations(jaxpr.jaxpr)
             if "index_k_norm" in str(eqn.source_info.name_stack)]
    assert norms and all("attn/hvd_dsa_index/index_k_norm" in name
                         for name in norms)


def test_a_recomputed_block_does_not_choose_again(params):
    """Under ``remat`` the call runs in the first forward alone: its
    planes are kept (``_REMAT_KEEPS``), so no ``checkpoint`` equation
    holds it."""
    model = _model(remat=True)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(model.apply(p, _tokens(512)))))(params)
    calls = _calls(jaxpr.jaxpr, introspect.KERNEL_DSA_CHOOSE)
    assert [inside for _, inside in calls] == [False] * LAYERS
    # The masked kernels do stand inside: the backward pass is there.
    assert any(inside for _, inside in _calls(
        jaxpr.jaxpr, introspect.KERNEL_DSA_BWD))
    assert not _calls(jaxpr.jaxpr, introspect.KERNEL_DSA_DKV)


@pytest.mark.parametrize("attention,s,forced,via", [
    ("flash", 128, False, "plain"),     # no whole pass of queries
    ("flash", 384, False, "plain"),
    ("dense", 512, False, "plain"),     # dense attention reads the mask
    ("flash", 512, True, "forced"),     # the caller's choice
    ("dense", 512, True, "forced"),
])
def test_every_other_layer_takes_the_plain_path(params, attention, s,
                                                forced, via):
    """No kernel; a free choice scores and bisects in its loops as
    before, a forced one runs no indexer at all."""
    model = _model(attention)
    selections = None
    if forced:
        selections = [jnp.tril(jnp.ones((1, s, s), bool))] * LAYERS
    before = _selections()
    jaxpr = jax.make_jaxpr(lambda p: model.apply(
        p, _tokens(s), selections=selections))(params)
    assert _moved(before) == {"layers": LAYERS, "chosen": 0}
    assert not _calls(jaxpr.jaxpr, introspect.KERNEL_DSA_CHOOSE)
    assert len(_loops(jaxpr.jaxpr)) == (0 if forced else 2 * LAYERS)


@pytest.mark.parametrize("b", [1, 2])
def test_the_two_paths_are_one_model(params, b):
    """The kernel's layer ('flash', S 512) against the plain one
    ('dense'): the same logits, the same ``dsa_kept``, the same mask in
    ``dsa_mask`` (unpacked from the plane only because it is asked for),
    and zeros for the indexer's leaves on both."""
    tokens = _tokens(512, b)

    def run(attention):
        model = _model(attention)

        def loss(p):
            logits, sown = model.apply(p, tokens, mutable=["dsa", "dsa_mask"])
            return jnp.mean(jax.nn.logsumexp(logits, -1)), sown

        (value, sown), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return value, sown, grads

    got, got_sown, got_grads = run("flash")
    want, want_sown, want_grads = run("dense")
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for i in range(LAYERS):
        attn = "layer_%d" % i
        np.testing.assert_array_equal(
            got_sown["dsa"][attn]["attn"]["dsa_kept"][0],
            want_sown["dsa"][attn]["attn"]["dsa_kept"][0])
        mask = got_sown["dsa_mask"][attn]["attn"]["select"][0]
        assert mask.shape == (b, 512, 512) and mask.dtype == bool
        np.testing.assert_array_equal(
            mask, want_sown["dsa_mask"][attn]["attn"]["select"][0])
        leaves = got_grads["params"][attn]["attn"]
        for name in ("index_wq", "index_wk", "index_ww"):
            assert not np.asarray(leaves[name]).any(), name
    for a, e in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, e, atol=1e-6)


def test_the_mask_is_unpacked_only_where_asked_for(params):
    """``dsa_mask`` not mutable: no (B, S, S) value in the program."""
    model = _model()
    jaxpr = jax.make_jaxpr(lambda p: model.apply(
        p, _tokens(512), mutable=["dsa"]))(params)
    shapes = {tuple(v.aval.shape) for eqn, _ in _equations(jaxpr.jaxpr)
              for v in eqn.outvars if hasattr(v.aval, "shape")}
    assert (1, 512, 512) not in shapes
    asked = jax.make_jaxpr(lambda p: model.apply(
        p, _tokens(512), mutable=["dsa", "dsa_mask"]))(params)
    assert (1, 512, 512) in {
        tuple(v.aval.shape) for eqn, _ in _equations(asked.jaxpr)
        for v in eqn.outvars if hasattr(v.aval, "shape")}


def test_the_package_does_not_import_the_kernel():
    """``import horovod_tpu`` (and its ``ops``, and the model's module)
    leaves the kernel's module alone: it is imported where a sparse
    layer is first built."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, horovod_tpu, horovod_tpu.ops, "
         "horovod_tpu.models.transformer; "
         "print('horovod_tpu.ops.pallas_selection' in sys.modules)"],
        capture_output=True, text=True, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip().splitlines()[-1] == "False"
