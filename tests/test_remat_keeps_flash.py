"""``TransformerConfig.remat`` recomputes a block in the backward pass
EXCEPT what its flash kernel made: the kernel's output and log-sum-exp
carry ``checkpoint_name``s (``introspect.SAVED_FLASH_OUT`` / ``_LSE``)
and the recomputation's policy saves exactly those, so the forward
kernel is traced once a layer. Everything here is the CPU, Pallas in
interpret mode, at tiny sizes."""

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
KERNELS = (introspect.KERNEL_FLASH_FWD, introspect.KERNEL_FLASH_DKV,
           introspect.KERNEL_FLASH_DQ)

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
                     introspect.KERNEL_FLASH_DKV: LAYERS,
                     introspect.KERNEL_FLASH_DQ: LAYERS}


@pytest.mark.parametrize("block", [PLAIN, LATENT, LATENT_HELD_EXPERTS],
                         ids=["plain", "latent", "latent_held_experts"])
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
    ``remat=False`` on the same weights: the kept output is the array a
    second run of the kernel would have made."""
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


def test_dense_attention_under_remat_keeps_nothing(monkeypatch):
    """Nothing in a dense-attention block carries the names, so the
    policy saves nothing: the gradient is, equation for equation, the
    one of ``nn.remat(Block)`` without a policy, and the softmax's
    ``exp`` is made twice a layer (forward, recomputed) where the model
    without ``remat`` makes it once."""
    variables = _variables()
    with_policy = _gradient_jaxpr(_model(True, "dense"), variables)
    assert introspect.SAVED_FLASH_OUT not in with_policy
    assert introspect.SAVED_FLASH_LSE not in with_policy

    def exps(text):
        return len(re.findall(r"= exp ", text))

    # (The loss's own log-softmax holds one more in both.)
    assert exps(with_policy) - LAYERS == exps(
        _gradient_jaxpr(_model(False, "dense"), variables)) == LAYERS + 1

    monkeypatch.setattr(transformer_module, "_remat_block",
                        lambda cfg: nn.remat(transformer_module.Block))
    without = _gradient_jaxpr(_model(True, "dense"), variables)

    def shape(text):
        # Equations only: the policy's own repr rides in the
        # ``checkpoint`` equation's parameters.
        return re.sub(r"policy=[^\n]*", "policy=", text)

    assert shape(with_policy) == shape(without)


@pytest.mark.parametrize("attention,remat,moved", [
    ("flash", True, {"flash_out+lse": LAYERS, "nothing": 0}),
    ("dense", True, {"flash_out+lse": 0, "nothing": LAYERS}),
    ("flash", False, {"flash_out+lse": 0, "nothing": 0}),
    ("dense", False, {"flash_out+lse": 0, "nothing": 0}),
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
    assert len(lines) == 1 and "flash_out+lse" in lines[0], lines


def test_the_names_cost_nothing_outside_a_recomputation():
    """Outside a ``remat`` the two names are identities: the lowered
    forward and gradient of ``flash_attention`` hold no trace of them
    (StableHLO has no op for a name)."""
    from horovod_tpu.ops.pallas_attention import flash_attention

    x = jax.ShapeDtypeStruct((1, 32, 2, 16), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x))
    assert introspect.SAVED_FLASH_OUT in jaxpr
    lowered = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).as_text()
    assert introspect.SAVED_FLASH_OUT not in lowered
    assert introspect.SAVED_FLASH_LSE not in lowered


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
    if not kept:    # the policy lists another name than the layer gives
        monkeypatch.setattr(transformer_module, "SAVED_MOE_OUT", "unlisted")
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
    assert len(re.findall(r" conditional\(", text)) == choices
