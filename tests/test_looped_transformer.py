"""``TransformerConfig.passes``: ONE stack of blocks applied several
times a step (models/transformer.py ``_LoopedStack``, ``looped_loss``).

What the library holds whatever model uses it: a model of ONE pass
traces the program it traced before the field existed (recorded from
the parent commit); the stack's matrices and the head are cast to the
compute dtype once for all passes; the readout's hand-written backward
is the gradient of the plain cross entropy; the planner prices a looped
model as an untied model of as many block applications; what a looped
stack cannot run is refused; under recomputation a block of EVERY pass
keeps the one list a block of one pass keeps (``_REMAT_KEEPS``), so no
pass's recomputed forward multiplies anything."""

import collections
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.jax import introspect
from horovod_tpu.models import (
    BlockSpec,
    Transformer,
    TransformerConfig,
    looped_loss,
    record_loop_stats,
)
from horovod_tpu.models import transformer as transformer_module
from horovod_tpu.utils import metrics

LOOPED = BlockSpec(norm="rmsnorm", ffn="swiglu", positions="rope",
                   rope_theta=1e6, tied_head=False, head_dim=16,
                   post_norms=True)


def _config(**changes):
    return TransformerConfig(**dict(dict(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=96,
        max_seq_len=64, dtype=jnp.bfloat16, attention="dense", remat=True,
        block=LOOPED, passes=3), **changes))


def _model(**changes):
    from flax.core import meta

    model = Transformer(_config(**changes))
    params = meta.unbox(model.init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 32), jnp.int32)))
    return model, params


def _loss(model, params, tokens, beta=0.1):
    hidden = model.apply(params, tokens[:, :-1])
    p = params["params"]
    return looped_loss(hidden, p["lm_head"], p["exit_gate"], tokens[:, 1:],
                       beta)[0]


TOKENS = jax.random.randint(jax.random.PRNGKey(3), (2, 33), 0, 256)

# sha256 of ``str(jax.make_jaxpr(grad of the builder's loss))`` (addresses
# blanked) of three benchmark builders' tiny models with dense attention,
# remat off and on, RECORDED ON THE PARENT COMMIT of the PR that added
# ``passes`` (72ea92d): a model of one pass takes the code path it took
# before. A PR that means to change what these models trace records its
# own (``_traced`` below prints what it hashes).
RECORDED = {
    ("gpt2m-s1024-c1", False): (
        58008, "f539c00e28a5be6cf08ff2f7319b740423d57f0fe34cb04234f5a62433b8"
        "06cf"),
    ("gpt2m-s1024-c1", True): (
        78488, "90b6573a77677ae1905c4d26a729f1a04a0c9fae5084b677330d6d25097e"
        "2c3e"),
    ("trinity-s8192-ep8-c1", False): (
        190517, "c090b1f5960f1bb3ad080cc6d4374aedc39f3084d8a7d469eb0fc4ccc6e"
        "33104"),
    ("trinity-s8192-ep8-c1", True): (
        272636, "cc814ceec6cd95c644386e9a9338cdf9d37d21757b815b2dabf7a16c093"
        "c7c05"),
    ("phi4flash-s8192-yoco-c1", False): (
        364246, "4fd01acc4ac016dcd17bc48b0fb6d8daa4942d7866b0596ff234a407739"
        "e5bef"),
    ("phi4flash-s8192-yoco-c1", True): (
        466768, "e1c6266cb909cf6b604d0cedaa2f8a1177cc0113dfb34e1cb6a25a9e125"
        "e8dfe"),
}


def _traced(name, remat):
    from benchmark import cell as cells

    cell = cells.load(name, tiny=True)
    cell.config.update(attention="dense")
    cell.traffic["remat"] = remat
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, int(cell.traffic["seq_len"]) + 1), jnp.int32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p, s: model.loss(p, s, tokens)[0]))(params, state)
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


@pytest.mark.parametrize("name,remat", sorted(RECORDED))
def test_one_pass_traces_the_program_it_traced_before(name, remat):
    text = _traced(name, remat)
    assert "hvd_loop" not in text
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) \
        == RECORDED[name, remat]


def test_the_default_is_one_pass_and_hands_back_logits():
    assert TransformerConfig().passes == 1
    assert TransformerConfig().loop_norm is True
    model, params = _model(passes=1)
    out = model.apply(params, TOKENS[:, :-1])
    assert out.shape == (2, 32, 256) and out.dtype == jnp.float32
    assert "stack" not in params["params"]
    assert "exit_gate" not in params["params"]
    looped, looped_params = _model()
    hidden = looped.apply(looped_params, TOKENS[:, :-1])
    assert hidden.shape == (3, 2, 32, 64) and hidden.dtype == jnp.bfloat16
    assert sorted(looped_params["params"]) == ["embed", "exit_gate",
                                               "lm_head", "stack"]
    assert sorted(looped_params["params"]["stack"]) == ["layer_0", "layer_1",
                                                        "ln_f"]
    # The same count of parameters as one pass of the same blocks, but
    # for the gate: the passes share every weight.
    count = lambda p: sum(a.size for a in jax.tree.leaves(p))  # noqa: E731
    assert count(looped_params) == count(params) + 65
    assert all(a.dtype == jnp.float32
               for a in jax.tree.leaves(looped_params))


@pytest.mark.parametrize("remat", [False, True])
def test_the_weights_are_cast_once_for_all_passes(remat):
    """In the traced step a block's (64, 96) feed-forward matrices (two
    a block, two blocks) and the head are converted from float32 to the
    compute dtype ONCE each: not once a pass, and not again in a
    recomputed pass."""
    model, params = _model(remat=remat)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: _loss(model, p, TOKENS)))(params)

    def casts(shape):
        return sum(
            1 for eqn in introspect.equations(jaxpr.jaxpr)
            if eqn.primitive.name == "convert_element_type"
            and eqn.invars[0].aval.shape == shape
            and eqn.invars[0].aval.dtype == jnp.float32
            and eqn.outvars[0].aval.dtype == jnp.bfloat16)

    assert casts((64, 96)) == 4         # wi and wg of two blocks
    assert casts((96, 64)) == 2         # wo
    assert casts((3, 64, 4, 16)) == 2   # wqkv
    assert casts((256, 64)) == 2        # the embedding's lookup, the head


def test_every_pass_runs_and_the_passes_share_their_weights():
    model, params = _model(dtype=jnp.float32, remat=False)
    hidden = model.apply(params, TOKENS[:, :-1])
    once, _ = _model(dtype=jnp.float32, remat=False, passes=2)
    # Two passes are the first two of three: the same weights again.
    np.testing.assert_allclose(once.apply(params, TOKENS[:, :-1]),
                               hidden[:2], rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(hidden[2] - hidden[1]).max()) > 1e-3
    # ``loop_norm`` False: the first pass is the same, the second reads
    # the un-normed state.
    loose, _ = _model(dtype=jnp.float32, remat=False, loop_norm=False)
    other = loose.apply(params, TOKENS[:, :-1])
    np.testing.assert_allclose(other[0], hidden[0], rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(other[1] - hidden[1]).max()) > 1e-3


def test_the_looped_loss_is_the_plain_one_and_so_are_its_gradients():
    """``_readout_loss`` writes its own backward (the logits made
    again); against ``optax``'s cross entropy over whole logits and a
    plain product for the exit distribution, in float32."""
    key = jax.random.PRNGKey(5)
    hidden = jax.random.normal(key, (3, 2, 16, 64))
    head = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (256, 64))
    gate = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (65,))
    targets = TOKENS[:, :16]

    def plain(hidden, head, gate):
        logits = jnp.einsum("tbsm,vm->tbsv", hidden, head)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.broadcast_to(targets, (3,) + targets.shape))
        g = jax.nn.sigmoid(hidden @ gate[:-1] + gate[-1])
        p = jnp.stack([g[0], g[1] * (1 - g[0]), (1 - g[0]) * (1 - g[1])])
        return jnp.mean(jnp.sum(p * ce, 0) + 0.1 * jnp.sum(p * jnp.log(p), 0))

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(plain, (0, 1, 2))(
            hidden, head, gate)
        (got, stats), grads = jax.value_and_grad(
            lambda h, w, g: looped_loss(h, w, g, targets, 0.1), (0, 1, 2),
            has_aux=True)(hidden, head, gate)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)
    assert float(stats["exit_share"].sum()) == pytest.approx(1.0, abs=1e-6)
    assert stats["cross_entropy"].shape == (3,)
    # The last pass's own gate is read by nothing: no gradient through
    # its score but through the state it shares with the readout.
    record_loop_stats(jax.device_get(stats))
    shares = [metrics.value("hvd_loop_exit_share", **{"pass": str(t)})
              for t in range(3)]
    np.testing.assert_allclose(shares, stats["exit_share"], rtol=1e-6)


def test_a_looped_stack_refuses_what_it_cannot_run():
    for spec in (dict(num_experts=4, experts_per_token=2),
                 dict(index_heads=2, index_head_dim=8, index_topk=4),
                 dict(layer_types=("mamba", "memory_unit"), scan_from=0,
                      ssm_state=4, conv_taps=2, positions="none")):
        block = dataclasses.replace(LOOPED, **spec)
        with pytest.raises(ValueError, match="looped stack"):
            Transformer(_config(block=block)).init(
                jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    model, params = _model()
    with pytest.raises(ValueError, match="looped stack"):
        model.apply(params, TOKENS[:, :-1], assignments=[None, None])


def test_the_counter_of_block_applications():
    model, params = _model()
    before = metrics.value("hvd_loop_passes_total") or 0
    jax.eval_shape(lambda p: model.apply(p, TOKENS[:, :-1]), params)
    assert (metrics.value("hvd_loop_passes_total") or 0) - before == 2 * 3


def _matmuls(jaxpr):
    """Every ``dot_general`` of ``jaxpr`` outside a kernel's own body,
    by (operand shapes, dimension numbers): x W has another signature
    than the two products of its backward."""
    return collections.Counter(
        (tuple(v.aval.shape for v in eqn.invars),
         str(eqn.params["dimension_numbers"]))
        for eqn in introspect.equations(jaxpr, skip=("pallas_call",))
        if eqn.primitive.name == "dot_general")


def _made_again_by_pass(passes):
    """{pass: (the FORWARD matmuls of a block that stand inside the
    pass's ``checkpoint`` equations of the gradient, every matmul that
    stands there)}: the first is what the recomputed forward multiplies
    a second time, the second says the equations were found."""
    model, params = _model(attention="flash", passes=passes)
    plain, _ = _model(attention="flash", passes=passes, remat=False)
    forward = set(_matmuls(jax.make_jaxpr(
        lambda p: plain.apply(p, TOKENS[:, :-1]))(params).jaxpr))
    gradient = jax.make_jaxpr(jax.grad(
        lambda p: _loss(model, p, TOKENS)))(params)
    found = {t: [0, 0] for t in range(passes)}
    for eqn in gradient.jaxpr.eqns:
        if eqn.primitive.name != "remat2":
            continue
        scope = re.search(r"%s_(\d+)" % introspect.SCOPE_LOOP_PASS,
                          str(eqn.source_info.name_stack))
        counts = found[int(scope.group(1))]
        for matmul, n in _matmuls(eqn.params["jaxpr"]).items():
            counts[0] += n * (matmul in forward)
            counts[1] += n
    return {t: tuple(counts) for t, counts in found.items()}


# A block: q, k, v, the attention output's projection and the three of
# a gated feed-forward; the backward pass multiplies twice for each.
BLOCK_MATMULS = 7


@pytest.mark.parametrize("passes", [2, 4])
def test_no_pass_multiplies_a_block_again(passes):
    """Under ``remat`` the recomputed forward of EVERY pass holds no
    ``dot_general`` of a block: what a matmul made is kept, in the
    first pass as in the last. What stands inside a pass's
    ``checkpoint`` equations is the backward pass's two products a
    matmul, for each of the two blocks."""
    assert _made_again_by_pass(passes) == {
        t: (0, 2 * 2 * BLOCK_MATMULS) for t in range(passes)}


def test_the_kernels_names_alone_would_multiply_four_again(monkeypatch):
    """The control, and the rule the early passes had: with the flash
    kernel's five names alone a recomputed block multiplies the
    feed-forward's three products and the attention output's again."""
    monkeypatch.setattr(transformer_module, "_REMAT_KEEPS", (
        introspect.SAVED_FLASH_OUT, introspect.SAVED_FLASH_LSE,
        introspect.SAVED_FLASH_Q, introspect.SAVED_FLASH_K,
        introspect.SAVED_FLASH_V))
    assert _made_again_by_pass(2) == {
        t: (2 * 4, 2 * (2 * BLOCK_MATMULS + 4)) for t in range(2)}


@pytest.mark.parametrize("passes", [2, 4])
def test_every_pass_counts_its_blocks_under_the_full_list(passes):
    """``hvd_remat_blocks_total``: blocks x passes a trace under
    ``flash+products``, the list of a block of one pass, and nothing
    under ``flash``, the label of a pass that kept the kernel's names
    alone; nothing at all without ``remat``."""
    def read():
        return {keeps: metrics.value("hvd_remat_blocks_total", keeps=keeps)
                or 0 for keeps in ("flash+products", "flash", "products")}

    for remat, moved in ((True, 2 * passes), (False, 0)):
        model, params = _model(attention="flash", passes=passes, remat=remat)
        before = read()
        jax.eval_shape(lambda p: model.apply(p, TOKENS[:, :-1]), params)
        after = read()
        assert {k: after[k] - before[k] for k in after} == {
            "flash+products": moved, "flash": 0, "products": 0}


def test_a_looped_models_plan_is_an_untied_models_of_as_many_blocks():
    """``n_layers`` is block APPLICATIONS a step: a looped model of L
    blocks and T passes plans like an untied model of L x T blocks with
    the same bytes of parameters: the same mesh, the same per-layer
    collectives, the same price."""
    hvd.init()
    blocks, passes = 2, 3
    model, params = _model()
    untied, _ = _model(passes=1, n_layers=blocks * passes)
    bytes_ = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    common = dict(batch=16, chips=8, seq_len=32, d_model=64,
                  require_axes={"model": 2})
    looped_plan = hvd.plan(params, n_layers=blocks * passes, **common)
    untied_plan = hvd.plan(param_bytes=bytes_, n_layers=blocks * passes,
                           dtype_bytes=4, **common)
    assert looped_plan.mesh_axes == untied_plan.mesh_axes
    assert looped_plan.chosen.cost == untied_plan.chosen.cost
    assert looped_plan.workload.n_layers == 6
    # Counting the blocks ONCE under-prices the per-layer collectives.
    short = hvd.plan(params, n_layers=blocks, **common)
    assert short.chosen.cost.ici_bytes < looped_plan.chosen.cost.ici_bytes
    # ``live_layers``: the activations alive at once are one pass's.
    by_pass = hvd.plan(params, n_layers=blocks * passes, live_layers=blocks,
                       **common)
    assert by_pass.chosen.cost.mem_bytes < looped_plan.chosen.cost.mem_bytes
    assert by_pass.chosen.cost.mem_bytes == short.chosen.cost.mem_bytes
    assert by_pass.chosen.cost.ici_bytes == looped_plan.chosen.cost.ici_bytes
