"""``TransformerConfig.remat`` recomputes a block in the backward pass
EXCEPT what its flash kernel or a matmul made and the backward pass
reads: the kernel's operands, output and log-sum-exp and the narrow
projections' products carry ``checkpoint_name``s (``introspect.SAVED_*``)
and the recomputation's policy saves exactly those
(``models/transformer.py`` ``_REMAT_KEEPS``), so the forward kernel is
traced once a layer and the recomputed forward multiplies nothing but
a router's logits (and q and k where a norm per head stands on them).
Everything here is the CPU, Pallas in interpret mode, at tiny sizes."""

import collections
import logging
import re

import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.core import meta

from horovod_tpu.jax import introspect
from horovod_tpu.models import BlockSpec, Transformer, TransformerConfig
from horovod_tpu.models import transformer as transformer_module

LAYERS = 3
# A static mask's kernels: the forward and the one-pass backward.
KERNELS = (introspect.KERNEL_FLASH_FWD, introspect.KERNEL_FLASH_BWD)

PLAIN = BlockSpec()
LATENT = BlockSpec(
    norm="rmsnorm", ffn="swiglu", positions="rope", tied_head=False,
    attention_kind="latent", q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16)
# GLM-4.7-Flash's whole block pattern at tiny widths: a leading dense
# block, then expert blocks that hold 2 of the 8 experts they route
# over, a shared expert beside them.
LATENT_HELD_EXPERTS = BlockSpec(
    norm="rmsnorm", ffn="swiglu", positions="rope", tied_head=False,
    attention_kind="latent", q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    first_dense_layers=1, dense_ff=96, num_experts=8, experts_per_token=2,
    router="sigmoid_bias", norm_topk=True, routed_scale=1.8,
    shared_experts=1, experts_held=2)
# Trinity-Mini's at tiny widths: 4 query heads over 2 key/value heads,
# sliding and full layers, a norm on q and k per head, rotary positions
# in the sliding kind only, the output gate, a norm on each branch's
# output, a leading dense block, held experts with a shared one.
WINDOWED_GATED_HELD_EXPERTS = BlockSpec(
    norm="rmsnorm", ffn="swiglu", positions="rope", tied_head=False,
    head_dim=16, n_kv_heads=2, sliding_window=8,
    layer_types=("sliding_attention", "sliding_attention",
                 "full_attention"),
    rope_layers=("sliding_attention",), qk_norm_per_head=True,
    attn_gate=True, post_norms=True, first_dense_layers=1, dense_ff=96,
    num_experts=8, experts_per_token=2, router="sigmoid_bias",
    norm_topk=True, routed_scale=2.8, shared_experts=1, experts_held=2)
# LFM2-8B-A1B's at tiny widths: gated short-convolution layers (no
# kernel to keep) round one grouped-query attention layer with a norm
# per head, a tied head, a leading dense block, held experts with no
# shared one.
CONV_HELD_EXPERTS = BlockSpec(
    norm="rmsnorm", ffn="swiglu", positions="rope", head_dim=16,
    n_kv_heads=2, layer_types=("conv", "full_attention", "conv"),
    conv_taps=3, qk_norm_per_head=True, first_dense_layers=1, dense_ff=96,
    num_experts=8, experts_per_token=2, router="sigmoid_bias",
    norm_topk=True, experts_held=2)
# The three names PR 31 and PR 33 kept, before the products joined them.
KERNEL_RESULTS_ONLY = (introspect.SAVED_FLASH_OUT, introspect.SAVED_FLASH_LSE,
                       introspect.SAVED_MOE_OUT)
SPECS = {"plain": PLAIN, "latent": LATENT,
         "latent_held_experts": LATENT_HELD_EXPERTS,
         "windowed_gated_held_experts": WINDOWED_GATED_HELD_EXPERTS,
         "conv_held_experts": CONV_HELD_EXPERTS}


def _model(remat, attention="flash", block=PLAIN, dtype=jnp.float32):
    return Transformer(TransformerConfig(
        vocab_size=64, d_model=64, n_heads=4, n_layers=LAYERS, d_ff=32,
        max_seq_len=32, dtype=dtype, attention=attention, remat=remat,
        block=block))


def _tokens():
    return jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0, 64)


def _variables(block=PLAIN):
    # The weights do not depend on ``remat``: one tree for both models.
    return meta.unbox(_model(False, block=block).init(
        jax.random.PRNGKey(0), _tokens()))


def _loss(model, variables):
    """params -> the next-token loss; what is not a parameter (the
    routers' correction biases) rides along."""
    tokens = _tokens()
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        logits = model.apply({"params": params, **rest}, tokens[:, :-1])
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits),
                                     tokens[:, 1:, None], -1)
        return -picked.mean()

    return loss


def _gradient_jaxpr(model, variables):
    return str(jax.make_jaxpr(jax.grad(_loss(model, variables)))(
        variables["params"]))


def _kernel_calls(jaxpr_text):
    return {name: len(re.findall(r"name=%s\b" % name, jaxpr_text))
            for name in KERNELS}


def _matmuls(jaxpr, recomputation=None, inside=False):
    """Every ``dot_general`` of ``jaxpr`` by (operand shapes, dimension
    numbers), a kernel's own left out: all of them, or
    (``recomputation`` True / False) those inside / outside a
    ``checkpoint`` equation."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "dot_general" and recomputation in (
                None, inside):
            yield (tuple(v.aval.shape for v in eqn.invars),
                   str(eqn.params["dimension_numbers"]))
        within = inside or eqn.primitive.name == "remat2"
        for value in eqn.params.values():
            for cand in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from _matmuls(inner, recomputation, within)


def _recomputed_forward_matmuls(block, attention="flash"):
    """The matmuls of the model's FORWARD pass (by operand shapes and
    dimension numbers, which tell x W from the two matmuls of its
    backward) that stand inside the gradient's ``checkpoint``
    equations: what a recomputed block multiplies a second time."""
    variables = _variables(block)
    forward = set(_matmuls(jax.make_jaxpr(
        lambda v: _model(False, attention, block).apply(
            v, _tokens()[:, :-1]))(variables).jaxpr))
    # The output head (x E^T, vocabulary = width = 64 here) stands
    # outside every block and is never made again; with its shapes and
    # dimension numbers a square projection's INPUT GRADIENT (a conv
    # block's out-projection) would be taken for it.
    forward.discard((((2, 31, 64), (64, 64)), "(((2,), (1,)), ((), ()))"))
    gradient = jax.make_jaxpr(jax.grad(_loss(
        _model(True, attention, block), variables)))(variables["params"])
    if attention == "flash":
        # Each kernel once a layer that HAS one: a conv layer calls none.
        kernel_layers = LAYERS - block.layer_types.count("conv")
        assert _kernel_calls(str(gradient)) == dict.fromkeys(KERNELS,
                                                             kernel_layers)
    return collections.Counter(
        m for m in _matmuls(gradient.jaxpr, recomputation=True)
        if m in forward)


# A block's forward matmuls that the backward pass needs again, one
# entry a layer. q / k / v or the four latent projections, the gate,
# the output projection (its product is the feed-forward's input's
# input); the feed-forward's up and gate (its down-projection only
# where a norm reads the output); the router's logits. The last column:
# the q and k projections that stand before a norm per head, which the
# list leaves to be multiplied again (the norm's backward reads them;
# kept BESIDE the kernel's operands they cost more than they spared).
@pytest.mark.parametrize("name,made_twice_before,routers,q_k_under_norm", [
    ("plain", 3 * (3 + 1 + 1), 0, 0),
    ("latent", 3 * (4 + 1 + 2), 0, 0),
    ("latent_held_experts", 3 * (4 + 1 + 2) + 2, 2, 0),
    ("windowed_gated_held_experts", (5 + 3) + 2 * (5 + 3 + 1), 2, 2 * 3),
    # A conv block: its two projections; the attention block: q, k, v
    # and the output projection; the dense feed-forward's up and gate.
    ("conv_held_experts", (2 + 2) + (4 + 1) + (2 + 1), 2, 2 * 1),
])
def test_a_recomputed_block_multiplies_only_its_router(
        name, made_twice_before, routers, q_k_under_norm, monkeypatch):
    """Read off the gradient's jaxpr: with the three names of the
    kernel's and the expert layer's results alone, every projection of
    a block stands a second time inside its ``checkpoint`` equation;
    with ``_REMAT_KEEPS`` only the expert layers' router logits do
    (float32, T x E: parallel/moe.py's own, not on the list) and, in a
    block with a norm on q and k, those two projections (4 query and 2
    key heads of 16: the value's, of the key's shape, is not among
    them). Each kernel is traced once a layer either way."""
    after = _recomputed_forward_matmuls(SPECS[name])
    by_weight = collections.Counter()
    for (shapes, _), n in after.items():
        by_weight[shapes[1]] += n
    expected = {(64, 8): routers, (64, 4, 16): q_k_under_norm // 2,
                (64, 2, 16): q_k_under_norm // 2}
    assert by_weight == {w: n for w, n in expected.items() if n}, after
    monkeypatch.setattr(transformer_module, "_REMAT_KEEPS",
                        KERNEL_RESULTS_ONLY)
    before = _recomputed_forward_matmuls(SPECS[name])
    assert sum(before.values()) == made_twice_before, before


@pytest.mark.parametrize("remat", [True, False])
@pytest.mark.parametrize("block", [PLAIN, LATENT],
                         ids=["plain", "latent"])
def test_each_kernel_is_traced_once_a_layer(block, remat):
    """The gradient of a flash model holds each layer's forward kernel
    ONCE with the blocks recomputed (plain recomputation made it twice)
    and once without; dK/dV and dQ once either way."""
    variables = _variables(block)
    text = _gradient_jaxpr(_model(remat, block=block), variables)
    assert _kernel_calls(text) == dict.fromkeys(KERNELS, LAYERS)
    # The rest of the block IS recomputed: the recomputation is there
    # and the two names are what it was told to keep.
    assert ("remat2[" in text) == remat
    for name in (introspect.SAVED_FLASH_OUT, introspect.SAVED_FLASH_LSE):
        assert len(re.findall(r"name\[name=%s\]" % name, text)) >= LAYERS


def test_plain_recomputation_would_run_the_forward_kernel_twice(monkeypatch):
    """The control: with ``nn.remat(Block)`` and no policy the same
    model's gradient holds 2 forward kernels a layer. What the policy
    spares is that second run and nothing else."""
    monkeypatch.setattr(transformer_module, "_remat_block",
                        lambda cfg: nn.remat(transformer_module.Block))
    calls = _kernel_calls(_gradient_jaxpr(_model(True), _variables()))
    assert calls == {introspect.KERNEL_FLASH_FWD: 2 * LAYERS,
                     introspect.KERNEL_FLASH_BWD: LAYERS}


@pytest.mark.parametrize("block", list(SPECS.values()), ids=list(SPECS))
@pytest.mark.parametrize("dtype,loss_rtol,leaf_rel_l2", [
    (jnp.float32, 1e-6, 1e-5),
    # XLA:CPU keeps float32 inside a fusion where the program says
    # bf16, and the recomputed forward fuses otherwise than the first:
    # bf16's own rounding, with or without the policy.
    (jnp.bfloat16, 1e-6, 2e-2),
], ids=["float32", "bfloat16"])
def test_recomputed_gradients_are_the_plain_ones(block, dtype, loss_rtol,
                                                 leaf_rel_l2):
    """Loss and every gradient leaf of ``remat=True`` against
    ``remat=False`` on the same weights: each kept array is the one a
    second run of its kernel or matmul would have made."""
    variables = _variables(block)
    got, want = (
        jax.jit(jax.value_and_grad(_loss(
            _model(remat, block=block, dtype=dtype), variables)))(
                variables["params"])
        for remat in (True, False))
    np.testing.assert_allclose(got[0], want[0], rtol=loss_rtol)
    leaves = jax.tree_util.tree_leaves_with_path(got[1])
    assert len(leaves) == len(jax.tree_util.tree_leaves(want[1])) > 4
    for (path, a), b in zip(leaves, jax.tree_util.tree_leaves(want[1])):
        name = jax.tree_util.keystr(path)
        assert jnp.isfinite(a).all() and float(jnp.abs(b).max()) > 0, name
        rel = float(jnp.linalg.norm((a - b).ravel())
                    / jnp.linalg.norm(b.ravel()))
        assert rel <= leaf_rel_l2, (name, rel)


def test_dense_attention_under_remat_keeps_no_kernel_operand(monkeypatch):
    """A dense-attention block has no kernel and nothing in it carries
    the five ``hvd_flash_*`` names. What the model's own names keep
    there: the attention branch's output and the feed-forward's up
    product (``PLAIN`` has no norm on q or k, no gate, and nothing reads
    its feed-forward's output again). So of the 5 matmuls a layer that
    plain recomputation makes twice beside the attention's own two
    (q / k / v, the output projection, the feed-forward's up), the
    three that make q, k and v are left, and the softmax's ``exp`` is
    still made twice a layer (forward, recomputed) where the model
    without ``remat`` makes it once."""
    variables = _variables()
    text = _gradient_jaxpr(_model(True, "dense"), variables)
    for name in (introspect.SAVED_FLASH_Q, introspect.SAVED_FLASH_K,
                 introspect.SAVED_FLASH_V, introspect.SAVED_FLASH_OUT,
                 introspect.SAVED_FLASH_LSE):
        assert name not in text
    for name in (introspect.SAVED_ATTN_OUT, introspect.SAVED_MLP_UP):
        assert len(re.findall(r"name\[name=%s\]" % name, text)) >= LAYERS

    def exps(text):
        return len(re.findall(r"= exp ", text))

    # (The loss's own log-softmax holds one more in both.)
    assert exps(text) - LAYERS == exps(
        _gradient_jaxpr(_model(False, "dense"), variables)) == LAYERS + 1

    def projections_made_twice():
        # q k^T and p v carry batch dimensions; the projections are
        # the rest of the forward's matmuls.
        return sum(n for (_, dims), n in _recomputed_forward_matmuls(
            PLAIN, "dense").items() if dims.endswith("((), ()))"))

    assert projections_made_twice() == 3 * LAYERS
    monkeypatch.setattr(transformer_module, "_remat_block",
                        lambda cfg: nn.remat(transformer_module.Block))
    assert projections_made_twice() == 5 * LAYERS


@pytest.mark.parametrize("attention,remat,moved", [
    ("flash", True, {"flash+products": LAYERS, "products": 0}),
    ("dense", True, {"flash+products": 0, "products": LAYERS}),
    ("flash", False, {"flash+products": 0, "products": 0}),
    ("dense", False, {"flash+products": 0, "products": 0}),
])
def test_remat_counter_at_trace_time(attention, remat, moved):
    """hvd_remat_blocks_total{keeps} moves by the model's blocks each
    time a model with ``remat`` is traced, under what they keep;
    nothing runs."""
    def read():
        return {keeps: transformer_module._M_REMAT_BLOCKS.labels(
            keeps=keeps).get() for keeps in moved}

    model, variables = _model(remat, attention), _variables()
    before = read()
    jax.eval_shape(lambda v: model.apply(v, _tokens()), variables)
    after = read()
    assert {k: after[k] - before[k] for k in moved} == moved


def test_remat_is_logged_once_a_model(caplog):
    """One line a model, however often it is traced."""
    cfg = TransformerConfig(
        vocab_size=48, d_model=32, n_heads=2, n_layers=2, d_ff=32,
        max_seq_len=16, dtype=jnp.float32, attention="flash", remat=True)
    model, tokens = Transformer(cfg), jnp.zeros((1, 16), jnp.int32)
    transformer_module._log_remat.cache_clear()
    with caplog.at_level(logging.INFO, logger="horovod_tpu"):
        variables = model.init(jax.random.PRNGKey(0), tokens)
        jax.eval_shape(lambda v: model.apply(v, tokens), variables)
    lines = [r.getMessage() for r in caplog.records
             if "Transformer remat" in r.getMessage()]
    assert len(lines) == 1 and "flash+products" in lines[0], lines


def test_the_names_cost_nothing_outside_a_recomputation():
    """Outside a ``remat`` the kernel's five names are identities: the
    lowered forward and gradient of ``flash_attention`` hold no trace of
    them (StableHLO has no op for a name)."""
    from horovod_tpu.ops.pallas_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, 32, 2, 16), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x))
    lowered = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).as_text()
    for name in (introspect.SAVED_FLASH_Q, introspect.SAVED_FLASH_K,
                 introspect.SAVED_FLASH_V, introspect.SAVED_FLASH_OUT,
                 introspect.SAVED_FLASH_LSE):
        assert name in jaxpr and name not in lowered, name


@pytest.mark.parametrize("name", list(SPECS))
def test_a_step_without_remat_lowers_the_same_without_the_names(
        name, monkeypatch):
    """Every name of ``_REMAT_KEEPS`` is traced by a model that does
    not recompute, too. There each lowers to nothing: the lowered
    gradient of ``remat=False`` is, operation for operation, the one
    traced with ``checkpoint_name`` taken out of the three modules that
    call it (but for the counter jax puts behind the names of the
    private functions it lowers: one more distinct equation traced,
    one more used up)."""
    from horovod_tpu.ops import pallas_attention
    from horovod_tpu.parallel import moe

    variables = _variables(SPECS[name])

    def lowered():
        text = jax.jit(jax.grad(_loss(_model(False, block=SPECS[name]),
                                      variables))).lower(
            variables["params"]).as_text()
        return re.sub(r"(@[A-Za-z_][\w.]*?)_\d+\b", r"\1", text)

    with_names = lowered()
    traced = str(jax.make_jaxpr(jax.grad(_loss(
        _model(False, block=SPECS[name]), variables)))(variables["params"]))
    listed = [n for n in transformer_module._REMAT_KEEPS
              if "name=%s]" % n in traced]
    assert len(listed) >= 7, listed     # the kernel's five, two products
    assert not [n for n in listed if n in with_names]
    for module in (transformer_module, pallas_attention, moe):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert lowered() == with_names


@pytest.mark.parametrize("post_norms,kept,choices", [
    (True, True, 2),     # the output is kept: forward and backward rule
    (True, False, 3),    # not kept: the recomputed forward chooses again
    (False, True, 2),    # nothing reads the output again: dead either way
])
def test_a_recomputed_block_keeps_the_held_expert_layers_output(
        post_norms, kept, choices, monkeypatch):
    """An expert layer that holds a share of the experts chooses its row
    arrays' length in a ``lax.cond`` whose backward rule recomputes from
    the layer's INPUTS (parallel/moe.py ``_held_rows``), so a recomputed
    block runs the layer's forward again only where it reads the OUTPUT
    once more: the norm on it (``post_norms``). The output carries
    ``introspect.SAVED_MOE_OUT`` and the recomputation keeps it: one
    expert layer's compiled gradient holds two ``conditional``s, not
    three (512 tokens x 2 slots, 1 of 8 experts held: a prefix of 512
    rows)."""
    if not kept:    # the policy's list without the name the layer gives
        monkeypatch.setattr(
            transformer_module, "_REMAT_KEEPS",
            tuple(n for n in transformer_module._REMAT_KEEPS
                  if n != introspect.SAVED_MOE_OUT))
    model = Transformer(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=16,
        max_seq_len=512, attention="dense", remat=True,
        block=BlockSpec(norm="rmsnorm", ffn="swiglu", num_experts=8,
                        experts_per_token=2, experts_held=1,
                        post_norms=post_norms)))
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, 512), 0, 64)
    variables = meta.unbox(model.init(jax.random.PRNGKey(0), tokens))
    rest = {k: v for k, v in variables.items() if k != "params"}

    def loss(params):
        return jnp.mean(model.apply({"params": params, **rest}, tokens) ** 2)

    text = jax.jit(jax.grad(loss)).lower(
        variables["params"]).compile().as_text()
    # (Off the chip the sums' Pallas kernel runs in interpret mode, a
    # loop whose ``pl.when``s are conditionals under the kernel's name.)
    assert len([line for line in text.splitlines()
                if " conditional(" in line
                and introspect.KERNEL_MOE_GATHER_SUM not in line]) == choices


# Keye-VL-2.0's at tiny widths: every layer full attention over a
# learned selection (an indexer of 4 heads of 12 that keeps 8 keys of 31),
# 4 query heads over 2 key/value heads, a norm per head, softmax-routed
# held experts with no shared one.
SPARSE_HELD_EXPERTS = BlockSpec(
    norm="rmsnorm", ffn="swiglu", positions="rope", tied_head=False,
    head_dim=16, n_kv_heads=2, qk_norm_per_head=True, index_heads=4,
    index_head_dim=12, index_topk=8, num_experts=8, experts_per_token=2,
    norm_topk=True, experts_held=2)
MASKED_KERNELS = (introspect.KERNEL_DSA_FWD, introspect.KERNEL_DSA_BWD)
# What runs the backward where the one pass's panels pass the VMEM cap.
MASKED_PAIR = (introspect.KERNEL_DSA_DKV, introspect.KERNEL_DSA_DQ)


def _loops_inside(jaxpr, inside=False):
    """The ``while`` and ``scan`` equations inside a ``checkpoint``
    equation of ``jaxpr``, a kernel's own left out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if inside and eqn.primitive.name in ("while", "scan"):
            yield eqn.primitive.name
        within = inside or eqn.primitive.name == "remat2"
        for value in eqn.params.values():
            for cand in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from _loops_inside(inner, within)


@pytest.mark.parametrize("keeps_the_planes", [True, False])
def test_a_recomputed_sparse_block_neither_scores_nor_selects(
        keeps_the_planes, monkeypatch):
    """A block whose queries choose their keys: the two bit planes of
    the choice carry ``SAVED_FLASH_SELECT`` and both masked kernels read
    theirs, so the recomputed forward holds no indexer matmul (its
    three projections, its dot products), no loop (the passes over
    blocks of queries, the bisection) and no ``hvd_dsa_select`` work;
    what it multiplies again is each router's logits and q and k before
    their norms. Without the name on the list the indexer runs twice a
    layer. Each masked kernel is traced once a layer either way, the
    static ones and the masked pair never."""
    if not keeps_the_planes:
        monkeypatch.setattr(
            transformer_module, "_REMAT_KEEPS", tuple(
                name for name in transformer_module._REMAT_KEEPS
                if name != introspect.SAVED_FLASH_SELECT))
    block = SPARSE_HELD_EXPERTS
    variables = _variables(block)
    gradient = jax.make_jaxpr(jax.grad(_loss(
        _model(True, "flash", block), variables)))(variables["params"])
    text = str(gradient)
    assert {name: len(re.findall(r"name=%s\b" % name, text))
            for name in MASKED_KERNELS + MASKED_PAIR + KERNELS} == dict(
        dict.fromkeys(MASKED_KERNELS, LAYERS),
        **dict.fromkeys(MASKED_PAIR + KERNELS, 0))
    by_weight = collections.Counter(
        shapes[1] for shapes, _ in _matmuls(gradient.jaxpr,
                                            recomputation=True))
    indexer = {"index_wq": (64, 4, 12), "index_wk": (64, 12),
               "index_ww": (64, 4), "dots": (2, 31, 12)}
    again = {name: by_weight[shape] for name, shape in indexer.items()}
    loops = list(_loops_inside(gradient.jaxpr))
    lowered = jax.jit(jax.grad(_loss(
        _model(True, "flash", block), variables))).lower(
        variables["params"]).as_text(debug_info=True)
    redone = re.findall(
        r"rematted_computation/layer_\d/attn/[^\"]*hvd_dsa_(?:select|index)",
        lowered)
    if keeps_the_planes:
        assert again == dict.fromkeys(indexer, 0), by_weight
        assert not loops and not redone
        # The routers, and q and k under their norms, as a Trinity block.
        assert by_weight[(64, 8)] >= LAYERS
        assert by_weight[(64, 4, 16)] >= LAYERS
    else:
        assert again == dict.fromkeys(indexer, LAYERS), by_weight
        assert loops and redone
    assert introspect.SCOPE_DSA_SELECT in lowered
