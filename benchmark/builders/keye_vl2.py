"""Keye-VL-2.0's language model (``model_type`` KeyeVL2), text tokens
only: a configuration file of the published ``config.json`` keys
becomes the program's ``models.Transformer`` with grouped-query heads
of their own width under a norm per head and rotary positions, an
indexer in every layer whose top ``sa_config.topk`` keys a query
attends to, and softmax-routed expert blocks with renormalised gates
and no shared expert, of which this chip holds its share; its loss is
the next-token cross entropy over the vocabulary held here plus the
weighted load-balancing term; the step carries no state."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops_keye
from benchmark.reference import keye_vl2 as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else. Eight shares of two experts each; four query heads
# over two key/value heads; an indexer of four heads that keeps 32 of a
# sequence of 128, so that three queries in four choose, as in the cell.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "moe_intermediate_size": 32,
               "num_experts": 2, "experts_routed_over": 16,
               "num_experts_per_tok": 2, "num_hidden_layers": 2,
               "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                             "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                             "q_chunk_size": 512, "topk": 32}},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Published keys the program has one answer to; anything else is refused
# rather than run as something it is not.
_ONLY = {"model_type": "KeyeVL2", "hidden_act": "silu",
         "attention_bias": False, "decoder_sparse_step": 1,
         "mlp_only_layers": [], "norm_topk_prob": True,
         "use_sliding_window": False, "sliding_window": None,
         "tie_word_embeddings": False, "first_k_dense_replace": 0}


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/keye_vl2.py runs %s=%r only, the "
                             "configuration says %r"
                             % (key, only, config[key]))
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program's indexer scores over ONE key head")
    return BlockSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        positions="rope", rope_theta=float(config["rope_theta"]),
        qk_norm_per_head=True, tied_head=False, head_dim=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        num_experts=config["experts_routed_over"],
        experts_per_token=config["num_experts_per_tok"],
        router="softmax", norm_topk=config["norm_topk_prob"],
        experts_held=config["num_experts"],
        first_expert_held=config["first_expert_held"])


def sizes_of(config):
    """The widths as ``flops_keye`` names them (``hidden``,
    ``expert_width``, ``k``, ``held`` and ``routed`` also as
    ``layer_metrics/moe.held_roofline.py`` reads them)."""
    sa = config["sa_config"]
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                head_dim=config["head_dim"], topk=sa["topk"],
                index_heads=sa["indexer_num_heads"],
                index_dim=sa["indexer_head_dim"],
                expert_width=config["moe_intermediate_size"],
                k=config["num_experts_per_tok"],
                held=config["num_experts"],
                routed=config["experts_routed_over"])


def module_of(config, traffic, block=None):
    """The program's model for ``config``; ``block`` replaces the
    configuration's own ``BlockSpec`` (benchmark/keye_routing.py spoils
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


def sown_kept(sown, n_layer):
    """(L,) the pairs each layer's mask kept, as the attention modules
    sowed them (``dsa_kept``)."""
    return jnp.stack([sown["dsa"]["layer_%d" % i]["attn"]["dsa_kept"][0]
                      for i in range(n_layer)])


def build(config, traffic, block=None):
    from horovod_tpu.parallel import moe

    seq_len = int(traffic["seq_len"])
    sizes = sizes_of(config)
    n_layer, vocab = config["num_hidden_layers"], config["vocab_size"]
    model = module_of(config, traffic, block)

    def init(key):
        # Parameter shapes do not depend on the batch: a short sample
        # keeps the traced forward (dead code under jit) small. The
        # step carries no state: a softmax router has no bias.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        params = dict(meta.unbox(model.init(key, sample))["params"])
        # The program draws every matrix at normal(0.02); the INPUT
        # embedding and each branch's OUTPUT projection start at the
        # configuration's scales (see its ``assumed``), so that the
        # routers of every layer choose by token.
        params["embed"] = params["embed"] * (config["embed_init_scale"]
                                             / 0.02)
        out = config["branch_out_init_scale"] / 0.02
        for name in ("layer_%d" % i for i in range(n_layer)):
            layer = dict(params[name])
            layer["attn"] = dict(layer["attn"], wo=layer["attn"]["wo"] * out)
            layer["moe"] = dict(layer["moe"], wo=layer["moe"]["wo"] * out)
            params[name] = layer
        return {"params": params}, {}

    def loss_and_stats(params, tokens, assignments=None, selections=None):
        """The loss, and what the layers sowed: ``moe.sown_stats`` and
        ``dsa_kept`` (L,); ``assignments`` forces the routing,
        ``selections`` the indexers' choice."""
        logits, sown = model.apply(
            {"params": params["params"]}, tokens[:, :-1], assignments,
            selections, mutable=["moe", "dsa"])
        stats = moe.sown_stats(sown)
        if "dsa" in sown:       # nothing is sown under a forced selection
            stats["dsa_kept"] = sown_kept(sown, n_layer)
        cross_entropy = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean()
        return (cross_entropy + config["router_aux_loss_coef"]
                * jnp.mean(stats["load_balance"])), stats

    def loss(params, state, tokens):
        return loss_and_stats(params, tokens)[0], state

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
        step_ops=lambda batch: flops_keye.keye_step_ops(
            batch, seq_len, vocab=vocab, n_layer=n_layer, **sizes),
        # No STATIC flash kernel runs in this step (the ``kernel.flash_*``
        # rooflines read those); what the masked kernels' layers require
        # is ``flops.attention_work`` over the kept pairs, read by
        # ``dsa_view``.
        attention_work=lambda per_chip_batch: {})
