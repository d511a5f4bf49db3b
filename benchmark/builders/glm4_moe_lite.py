"""GLM-4.7-Flash's family (``model_type`` glm4_moe_lite): a
configuration file of the published ``config.json`` keys becomes the
program's ``models.Transformer`` with latent attention, the leading
dense block, and expert blocks of which this chip holds its share; its
loss is the next-token cross entropy over the vocabulary held here, and
the step's carried state is the routers' correction bias."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops, flops_glm
from benchmark.reference import glm4_moe_lite as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else. Eight shares of two experts each.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "q_lora_rank": 24, "kv_lora_rank": 16,
               "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
               "v_head_dim": 16, "intermediate_size": 96,
               "moe_intermediate_size": 32, "n_routed_experts": 2,
               "experts_routed_over": 16, "num_experts_per_tok": 2,
               "num_hidden_layers": 3},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Published keys the program has one answer to; anything else is refused
# rather than run as something it is not.
_ONLY = {"model_type": "glm4_moe_lite", "hidden_act": "silu",
         "attention_bias": False, "rope_scaling": None,
         "partial_rotary_factor": 1, "topk_method": "noaux_tc",
         "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0}


# The width ``models/transformer.py`` draws every matrix at.
_PROGRAM_INIT_STD = 0.02


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/glm4_moe_lite.py runs %s=%r only, "
                             "the configuration says %r"
                             % (key, only, config[key]))
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has one k and v a head")
    return BlockSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        positions="rope", rope_theta=float(config["rope_theta"]),
        tied_head=False, attention_kind="latent",
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        first_dense_layers=config["first_k_dense_replace"],
        dense_ff=config["intermediate_size"],
        num_experts=config["experts_routed_over"],
        experts_per_token=config["num_experts_per_tok"],
        router="sigmoid_bias", norm_topk=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        shared_experts=config["n_shared_experts"],
        experts_held=config["n_routed_experts"],
        first_expert_held=config["first_expert_held"])


def sizes_of(config):
    """The widths as ``flops_glm`` names them."""
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
                nope=config["qk_nope_head_dim"],
                rope=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
                n_dense=config["first_k_dense_replace"],
                dense_width=config["intermediate_size"],
                expert_width=config["moe_intermediate_size"],
                k=config["num_experts_per_tok"],
                held=config["n_routed_experts"],
                routed=config["experts_routed_over"],
                shared=config["n_shared_experts"])


def module_of(config, traffic, block=None):
    """The program's model for ``config``; ``block`` replaces the
    configuration's own ``BlockSpec`` (benchmark/glm_routing.py spoils
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
    n_layer, vocab = config["num_hidden_layers"], config["vocab_size"]
    model = module_of(config, traffic, block)

    def init(key):
        # Parameter shapes do not depend on the batch: a short sample
        # keeps the traced forward (dead code under jit) small. The
        # routers' correction biases (zeros) are the step's state.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        variables = meta.unbox(model.init(key, sample))
        params = dict(variables["params"])
        # The program draws every matrix at normal(0.02); the input
        # embedding is redrawn at the configuration's width (see its
        # ``assumed``): a token's own vector then decides its experts,
        # as in a trained checkpoint, and every seed's sequences load
        # the held experts alike.
        params["embed"] = params["embed"] * (
            config["embedding_std"] / _PROGRAM_INIT_STD)
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
            state, stats["tokens_per_expert"],
            config["router_bias_update_rate"])

    def attention_work(per_chip_batch):
        """What the attention of one chip's step REQUIRES, ``fwd`` and
        ``bwd`` (``flops.attention_work``), summed over the layers: the
        causal pairs of every head, q.k ``nope + rope`` wide and v
        ``v_dim``; ONE forward a layer, recomputed or not."""
        return flops.add_work(n_layer * [flops.attention_work(
            flops.causal_pairs(seq_len), seq_len, batch=per_chip_batch,
            n_head=sizes["n_head"], n_kv=sizes["n_head"],
            d=sizes["nope"] + sizes["rope"], d_v=sizes["v_dim"])])

    def kernels(per_chip_batch):
        """A FOSSIL that no metric reads since PR 47: the flash calls a
        step as PR 30 DECLARED them, the forward twice a layer under
        ``remat`` (once runs since PR 31). The readers take
        ``attention_work`` and count no call. It stays because
        ``tests/test_flash_tpu_compile.py`` (outside the benchmark's
        paths, which a benchmark PR may not edit) holds these numbers
        "so that the repair shows": the PR that edits that test deletes
        this with its two lines (PERF.md section 7)."""
        return {"fwd": ((2 if traffic["remat"] else 1) * n_layer,),
                "dkv": (n_layer,), "dq": (n_layer,)}

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
        step_ops=lambda batch: flops_glm.glm_step_ops(
            batch, seq_len, vocab=vocab, n_layer=n_layer, **sizes),
        attention_work=attention_work, kernels=kernels)
