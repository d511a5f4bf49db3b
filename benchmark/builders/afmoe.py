"""Trinity-Mini's family (``model_type`` afmoe): a configuration file of
the published ``config.json`` keys becomes the program's
``models.Transformer`` with grouped-query heads of their own width,
sliding-window and full attention layers in turn (rotary positions in
the sliding ones only), a norm on q and k per head, a gated attention
output, four norms a block, leading dense blocks, and expert blocks of
which this chip holds its share; its loss is the next-token cross
entropy over the vocabulary held here, and the step's carried state is
the routers' correction bias."""

from __future__ import annotations

import functools
import math
from types import SimpleNamespace

import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops, flops_afmoe
from benchmark.reference import afmoe as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else. Eight shares of two experts each; q is twice the
# hidden size wide, as published; the three layers are a dense sliding
# one, then a sliding and a full expert layer.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "sliding_window": 32,
               "intermediate_size": 96, "moe_intermediate_size": 32,
               "num_experts": 2, "experts_routed_over": 16,
               "num_experts_per_tok": 2, "num_hidden_layers": 3},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Published keys the program has one answer to; anything else is refused
# rather than run as something it is not.
_ONLY = {"model_type": "afmoe", "hidden_act": "silu", "rope_scaling": None,
         "score_func": "sigmoid", "route_norm": True, "n_group": 1,
         "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
         "mup_enabled": True, "tie_word_embeddings": False}


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/afmoe.py runs %s=%r only, the "
                             "configuration says %r"
                             % (key, only, config[key]))
    if config["first_k_dense_replace"] != config["num_dense_layers"]:
        raise ValueError("first_k_dense_replace is num_dense_layers under "
                         "the name the shared readers read")
    kinds = reference.layer_kinds(config)
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types ends before first_layer + "
                         "num_hidden_layers")
    return BlockSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        positions="rope", rope_theta=float(config["rope_theta"]),
        rope_layers=(reference.SLIDING,), qk_norm_per_head=True,
        tied_head=False, head_dim=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"], layer_types=tuple(kinds),
        sliding_window=config["sliding_window"], attn_gate=True,
        post_norms=True, embed_scale=math.sqrt(config["hidden_size"]),
        first_dense_layers=config["num_dense_layers"],
        dense_ff=config["intermediate_size"],
        num_experts=config["experts_routed_over"],
        experts_per_token=config["num_experts_per_tok"],
        router="sigmoid_bias", norm_topk=config["route_norm"],
        routed_scale=float(config["route_scale"]),
        shared_experts=config["num_shared_experts"],
        experts_held=config["num_experts"],
        first_expert_held=config["first_expert_held"])


def sizes_of(config):
    """The widths as ``flops_afmoe`` names them (``hidden``,
    ``expert_width``, ``k``, ``held`` and ``routed`` also as
    ``layer_metrics/moe.held_roofline.py`` reads them)."""
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                head_dim=config["head_dim"],
                window=config["sliding_window"],
                n_dense=config["num_dense_layers"],
                dense_width=config["intermediate_size"],
                expert_width=config["moe_intermediate_size"],
                k=config["num_experts_per_tok"],
                held=config["num_experts"],
                routed=config["experts_routed_over"],
                shared=config["num_shared_experts"])


def module_of(config, traffic, block=None):
    """The program's model for ``config``; ``block`` replaces the
    configuration's own ``BlockSpec`` (benchmark/trinity_routing.py
    spoils one to show what the check refuses)."""
    from horovod_tpu.models import Transformer, TransformerConfig

    return Transformer(TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["moe_intermediate_size"],
        max_seq_len=int(traffic["seq_len"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        attention=config["attention"], remat=bool(traffic["remat"]),
        block=block or block_spec(config)))


def build(config, traffic, block=None):
    from horovod_tpu.parallel import moe

    seq_len = int(traffic["seq_len"])
    sizes = sizes_of(config)
    kinds = reference.layer_kinds(config)
    n_layer, vocab = config["num_hidden_layers"], config["vocab_size"]
    model = module_of(config, traffic, block)

    def init(key):
        # Parameter shapes do not depend on the batch: a short sample
        # keeps the traced forward (dead code under jit) small. The
        # routers' correction biases (zeros) are the step's state.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        variables = meta.unbox(model.init(key, sample))
        params = dict(variables["params"])
        # The program starts every norm's scale at one; the two norms
        # on a branch's OUTPUT start at the configuration's value (see
        # its ``assumed``): at one, each branch adds a unit-RMS vector
        # to a stream the token's own vector leads at 0.9.
        for name in ("layer_%d" % i for i in range(n_layer)):
            layer = dict(params[name])
            for norm in ("post_attn_norm", "post_mlp_norm"):
                if norm in layer:
                    layer[norm] = {"scale": layer[norm]["scale"]
                                   * config["post_norm_scale"]}
            params[name] = layer
        return {"params": params}, variables["moe_state"]

    def loss_and_stats(params, state, tokens, assignments=None):
        """The loss, and what the expert layers sowed
        (``moe.sown_stats``); ``assignments`` forces the routing."""
        logits, sown = model.apply(
            {"params": params["params"], "moe_state": state},
            tokens[:, :-1], assignments, mutable=["moe"])
        return (optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean(), moe.sown_stats(sown))

    def loss(params, state, tokens):
        value, stats = loss_and_stats(params, state, tokens)
        return value, moe.updated_router_bias(
            state, stats["tokens_per_expert"], config["load_balance_coeff"])

    def attention_work(per_chip_batch):
        """What the attention of one chip's step REQUIRES, ``fwd`` and
        ``bwd`` (``flops.attention_work``), summed over the layers: a
        sliding layer its window's pairs, a full layer the causal pairs;
        key/value panels ``n_kv`` heads wide."""
        return flops.add_work(flops_afmoe.layer_attention_work(
            per_chip_batch, seq_len, kind, **{
                key: sizes[key] for key in ("n_head", "n_kv", "head_dim",
                                            "window")}) for kind in kinds)

    return SimpleNamespace(
        init=init, loss=loss, loss_and_stats=loss_and_stats, module=model,
        reference_loss=functools.partial(reference.loss, config),
        batch_specs=lambda plan: plan.batch_spec(2, seq_dim=None),
        # The planner tells expert leaves by their leading dimension,
        # which is the number of experts HELD.
        plan_kwargs=dict(seq_len=seq_len, d_model=sizes["hidden"],
                         n_layers=n_layer, num_experts=sizes["held"]),
        pool_kwargs=dict(seq_len=seq_len),
        units_per_item=seq_len,
        step_ops=lambda batch: flops_afmoe.afmoe_step_ops(
            batch, seq_len, vocab=vocab, kinds=kinds, **sizes),
        attention_work=attention_work)
