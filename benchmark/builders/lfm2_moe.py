"""LFM2-8B-A1B's family (``model_type`` lfm2_moe): a configuration file
of the published ``config.json`` keys becomes the program's
``models.Transformer`` with gated short-convolution layers and
grouped-query attention layers in the published pattern (a norm on q
and k per head, rotary positions on all of each head), a tied output
head, leading dense blocks, and expert blocks with no shared expert of
which this chip holds its share; its loss is the next-token cross
entropy over the vocabulary held here, and the step's carried state is
the routers' bias."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops, flops_lfm2
from benchmark.reference import lfm2_moe as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else. Four shares of two experts each; four query heads
# over two key/value heads of ``hidden / heads``; the three layers are a
# dense conv one, then an attention and a conv expert layer.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "intermediate_size": 96,
               "moe_intermediate_size": 32, "num_experts": 2,
               "experts_routed_over": 8, "num_experts_per_tok": 2,
               "num_hidden_layers": 3},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Published keys the program has one answer to; anything else is refused
# rather than run as something it is not.
_ONLY = {"model_type": "lfm2_moe", "conv_bias": False,
         "norm_topk_prob": True, "use_expert_bias": True,
         "tie_embedding": True}


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/lfm2_moe.py runs %s=%r only, the "
                             "configuration says %r"
                             % (key, only, config[key]))
    if config["first_k_dense_replace"] != config["num_dense_layers"]:
        raise ValueError("first_k_dense_replace is num_dense_layers under "
                         "the name the shared readers read")
    if config["head_dim"] * config["num_attention_heads"] \
            != config["hidden_size"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads in "
                         "this family")
    kinds = reference.layer_kinds(config)
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types ends before first_layer + "
                         "num_hidden_layers")
    return BlockSpec(
        norm="rmsnorm", norm_eps=config["norm_eps"], ffn="swiglu",
        positions="rope", rope_theta=float(config["rope_theta"]),
        qk_norm_per_head=True, tied_head=True, head_dim=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"], layer_types=tuple(kinds),
        conv_taps=config["conv_L_cache"],
        first_dense_layers=config["num_dense_layers"],
        dense_ff=config["intermediate_size"],
        num_experts=config["experts_routed_over"],
        experts_per_token=config["num_experts_per_tok"],
        router="sigmoid_bias", norm_topk=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        experts_held=config["num_experts"],
        first_expert_held=config["first_expert_held"])


def sizes_of(config):
    """The widths as ``flops_lfm2`` names them (``hidden``,
    ``expert_width``, ``k``, ``held`` and ``routed`` also as
    ``layer_metrics/moe.held_roofline.py`` reads them)."""
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                head_dim=config["head_dim"],
                n_dense=config["num_dense_layers"],
                dense_width=config["intermediate_size"],
                expert_width=config["moe_intermediate_size"],
                k=config["num_experts_per_tok"],
                held=config["num_experts"],
                routed=config["experts_routed_over"])


def module_of(config, traffic, block=None):
    """The program's model for ``config``; ``block`` replaces the
    configuration's own ``BlockSpec`` (benchmark/lfm2_routing.py spoils
    one to show what the check refuses)."""
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
        # routers' biases (zeros) are the step's state.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        variables = meta.unbox(model.init(key, sample))
        return {"params": variables["params"]}, variables["moe_state"]

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
            state, stats["tokens_per_expert"],
            config["router_bias_update_rate"])

    attention_layers = sum(kind != reference.CONV for kind in kinds)

    def attention_work(per_chip_batch):
        """What the attention of one chip's step REQUIRES, ``fwd`` and
        ``bwd`` (``flops.attention_work``), summed over the ATTENTION
        layers (a conv layer has no pairs): the causal pairs of every
        head, key/value panels ``n_kv`` heads wide."""
        return flops.add_work(attention_layers * [flops.attention_work(
            flops.causal_pairs(seq_len), seq_len, batch=per_chip_batch,
            n_head=sizes["n_head"], n_kv=sizes["n_kv"],
            d=sizes["head_dim"], d_v=sizes["head_dim"])])

    def kernels(per_chip_batch):
        """A FOSSIL that no metric reads since PR 47: the flash calls a
        step as PR 38 declared them, each kernel once an attention
        layer. The readers take ``attention_work`` and count no call.
        It stays because ``tests/test_flash_tpu_compile.py`` (outside
        the benchmark's paths, which a benchmark PR may not edit) reads
        ``kernels(1)["fwd"][0]``: the PR that edits that test deletes
        this with its line (PERF.md section 7)."""
        return dict.fromkeys(("fwd", "dkv", "dq"), (attention_layers,))

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
        step_ops=lambda batch: flops_lfm2.lfm2_step_ops(
            batch, seq_len, vocab=vocab, kinds=kinds, **sizes),
        attention_work=attention_work, kernels=kernels)
