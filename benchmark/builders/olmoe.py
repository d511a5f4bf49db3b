"""OLMoE family: a configuration file of the published ``config.json``
keys (``model_type`` olmoe) becomes the program's ``models.Transformer``
with OLMoE's block, and its loss: next-token cross entropy plus the two
weighted router losses."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops, flops_moe
from benchmark.reference import olmoe as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "intermediate_size": 32, "num_experts": 8,
               "num_experts_per_tok": 2, "num_hidden_layers": 2},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Published keys the program has one answer to; anything else is refused
# rather than run as something it is not.
_ONLY = {"model_type": "olmoe", "hidden_act": "silu",
         "attention_bias": False, "clip_qkv": None, "rope_scaling": None,
         "norm_topk_prob": False, "tie_word_embeddings": False}


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/olmoe.py runs %s=%r only, the "
                             "configuration says %r"
                             % (key, only, config[key]))
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("builders/olmoe.py has no grouped-query attention")
    return BlockSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        positions="rope", rope_theta=float(config["rope_theta"]),
        qk_norm=True, tied_head=False, num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"])


def sizes_of(config):
    """The widths as ``flops_moe`` names them."""
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                head_dim=config["hidden_size"]
                // config["num_attention_heads"],
                n_experts=config["num_experts"],
                k=config["num_experts_per_tok"],
                expert_width=config["intermediate_size"])


def build(config, traffic):
    from horovod_tpu.models import Transformer, TransformerConfig
    from horovod_tpu.parallel import moe

    seq_len = int(traffic["seq_len"])
    sizes = sizes_of(config)
    n_layer, vocab = config["num_hidden_layers"], config["vocab_size"]
    model = Transformer(TransformerConfig(
        vocab_size=vocab, d_model=sizes["hidden"], n_heads=sizes["n_head"],
        n_layers=n_layer, d_ff=sizes["expert_width"], max_seq_len=seq_len,
        dtype=jnp.dtype(config["compute_dtype"]),
        attention=config["attention"], remat=bool(traffic["remat"]),
        block=block_spec(config)))

    def init(key):
        # Parameter shapes do not depend on the batch: a short sample
        # keeps the traced forward (dead code under jit) small.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        return meta.unbox(model.init(key, sample)), {}

    def loss_and_stats(params, tokens, assignments=None):
        """The loss, and what the expert layers sowed
        (``moe.sown_stats``); ``assignments`` forces the routing."""
        logits, sown = model.apply(params, tokens[:, :-1], assignments,
                                   mutable=["moe"])
        stats = moe.sown_stats(sown)
        cross_entropy = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean()
        return (cross_entropy
                + config["router_aux_loss_coef"]
                * jnp.mean(stats["load_balance"])
                + config["router_z_loss_coef"]
                * jnp.mean(stats["z_loss"])), stats

    def loss(params, state, tokens):
        return loss_and_stats(params, tokens)[0], state

    def attention_work(per_chip_batch):
        """What the attention of one chip's step REQUIRES, ``fwd`` and
        ``bwd`` (``flops.attention_work``), summed over the layers: the
        causal pairs of every head."""
        return flops.add_work(n_layer * [flops.attention_work(
            flops.causal_pairs(seq_len), seq_len, batch=per_chip_batch,
            n_head=sizes["n_head"], n_kv=sizes["n_head"],
            d=sizes["head_dim"], d_v=sizes["head_dim"])])

    return SimpleNamespace(
        init=init, loss=loss, loss_and_stats=loss_and_stats, module=model,
        reference_loss=functools.partial(reference.loss, config),
        batch_specs=lambda plan: plan.batch_spec(2, seq_dim=None),
        plan_kwargs=dict(seq_len=seq_len, d_model=sizes["hidden"],
                         n_layers=n_layer, num_experts=sizes["n_experts"]),
        pool_kwargs=dict(seq_len=seq_len),
        units_per_item=seq_len,
        step_ops=lambda batch: flops_moe.olmoe_step_ops(
            batch, seq_len, vocab=vocab, n_layer=n_layer, **sizes),
        attention_work=attention_work)
