"""SmallThinker-21BA3B's family (``model_name`` smallthinker_*): a
configuration file of the published ``config.json`` keys becomes the
program's ``models.Transformer`` with grouped-query heads of their own
width (28 over 4: groups of SEVEN), full layers without positions and
sliding-window layers with rotary positions in the published layouts,
two norms a block, and in EVERY block a softmax-routed expert layer of
ReLU-gated experts with no shared expert, whose router reads the
block's normed INPUT (``BlockSpec.router_tap`` 'mixer') and of which
this chip holds its share; its loss is the next-token cross entropy
over the vocabulary held here; the step carries no state."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops, flops_smallthinker
from benchmark.reference import smallthinker as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else. Four shares of four experts each; SEVEN query heads
# over one key/value head; one full layer and three window layers of
# 32 keys under a sequence of 128.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 7, "num_key_value_heads": 1,
               "head_dim": 16, "sliding_window_size": 32,
               "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
               "experts_routed_over": 16,
               "moe_num_active_primary_experts": 3,
               "num_hidden_layers": 4},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Published keys the program has one answer to; anything else is refused
# rather than run as something it is not.
_ONLY = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
         "rope_scaling": None, "tie_word_embeddings": False,
         "first_k_dense_replace": 0, "hidden_act": "relu"}


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/smallthinker.py runs %s=%r only, the "
                             "configuration says %r"
                             % (key, only, config[key]))
    kinds = reference.layer_kinds(config)
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("sliding_window_layout ends before first_layer + "
                         "num_hidden_layers")
    first = config["first_layer"]
    if config["layer_types"][first:first + len(kinds)] != kinds:
        raise ValueError("layer_types is sliding_window_layout under the "
                         "name the shared readers read")
    # The program rotates by KIND of layer: the two layouts have to name
    # the same layers, as the published ones do.
    flags = reference.rope_flags(config)
    rotated = {kind for kind, flag in zip(kinds, flags) if flag}
    mixed = rotated & {kind for kind, flag in zip(kinds, flags) if not flag}
    if mixed:
        raise ValueError("rope_layout rotates some %s layers and not others"
                         % sorted(mixed))
    return BlockSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="reglu",
        positions="rope", rope_theta=float(config["rope_theta"]),
        rope_layers=tuple(sorted(rotated)), tied_head=False,
        head_dim=config["head_dim"],
        n_kv_heads=config["num_key_value_heads"], layer_types=tuple(kinds),
        sliding_window=config["sliding_window_size"],
        num_experts=config["experts_routed_over"],
        experts_per_token=config["moe_num_active_primary_experts"],
        router="softmax", router_tap=config["router_tap"],
        norm_topk=config["norm_topk_prob"],
        experts_held=config["moe_num_primary_experts"],
        first_expert_held=config["first_expert_held"])


def sizes_of(config):
    """The widths as ``flops_smallthinker`` names them (``hidden``,
    ``expert_width``, ``k``, ``held`` and ``routed`` also as
    ``layer_metrics/moe.held_roofline.py`` reads them, ``n_head``,
    ``n_kv``, ``head_dim`` and ``window`` as ``swa_view`` does)."""
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                head_dim=config["head_dim"],
                window=config["sliding_window_size"],
                expert_width=config["moe_ffn_hidden_size"],
                k=config["moe_num_active_primary_experts"],
                held=config["moe_num_primary_experts"],
                routed=config["experts_routed_over"])


def module_of(config, traffic, block=None):
    """The program's model for ``config``; ``block`` replaces the
    configuration's own ``BlockSpec`` (benchmark/smallthinker_routing.py
    spoils one to show what the check refuses)."""
    from horovod_tpu.models import Transformer, TransformerConfig

    return Transformer(TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["moe_ffn_hidden_size"],
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
        # step carries no state: a softmax router has no bias.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        params = dict(meta.unbox(model.init(key, sample))["params"])
        # The program draws every matrix at normal(0.02); the INPUT
        # embedding starts at the configuration's scale (see its
        # ``assumed``), so that the routers of every layer choose by
        # token.
        params["embed"] = params["embed"] * (config["embed_init_scale"]
                                             / 0.02)
        return {"params": params}, {}

    def loss_and_stats(params, tokens, assignments=None):
        """The loss, and what the expert layers sowed
        (``moe.sown_stats``); ``assignments`` forces the routing."""
        logits, sown = model.apply(
            {"params": params["params"]}, tokens[:, :-1], assignments,
            mutable=["moe"])
        return (optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean(), moe.sown_stats(sown))

    def loss(params, state, tokens):
        return loss_and_stats(params, tokens)[0], state

    def attention_work(per_chip_batch):
        """What the attention of one chip's step REQUIRES, ``fwd`` and
        ``bwd`` (``flops.attention_work``), summed over the layers: a
        sliding layer its window's pairs, a full layer the causal pairs;
        key/value panels ``n_kv`` heads wide."""
        return flops.add_work(flops_smallthinker.layer_attention_work(
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
        step_ops=lambda batch: flops_smallthinker.smallthinker_step_ops(
            batch, seq_len, vocab=vocab, kinds=kinds, **sizes),
        attention_work=attention_work)
