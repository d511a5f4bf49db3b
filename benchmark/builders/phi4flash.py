"""Phi-4-mini-flash-reasoning's family (``model_type`` phi4flash): a
configuration file of the published ``config.json`` keys, and of what
the family's public code adds to them (``assumed``), becomes the
program's ``models.Transformer`` with the published layer kinds in the
published order among the layers kept: Mamba layers (the selective scan
a kernel of ours), differential attention at a window and full, the one
Mamba layer and the one attention layer that PUBLISH, and the gated
memory units and cross-attention layers that read them; LayerNorm, a
SwiGLU feed-forward, no positions, a tied output head. Its loss is the
next-token cross entropy over the vocabulary held here; the step
carries no state."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops, flops_afmoe, flops_phi4flash
from benchmark.reference import phi4flash as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else: four query heads over two key/value heads of 16 are
# two differential heads over one pair; 128 channels of 16 states, rank 4.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "intermediate_size": 96, "sliding_window": 32,
               "mamba_dt_rank": 4,
               # At these widths a leaf is a few thousand numbers and the
               # worst one reads 0.03-0.06 in bf16 (the cell's 0.04 is
               # over millions): the rehearsal finds wrong paths, the CPU
               # tests compare every leaf in float32.
               "check": {"via": "sgd_step", "sample_per_chip": 1,
                         "loss_rtol": 2e-4, "grad_rel_l2": 0.3}},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Keys the program has one answer to; anything else is refused rather
# than run as something it is not.
_ONLY = {"model_type": "phi4flash", "hidden_act": "silu", "mlp_bias": False,
         "lm_head_bias": False, "tie_word_embeddings": True,
         "mamba_expand": 2, "embd_pdrop": 0, "resid_pdrop": 0}


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/phi4flash.py runs %s=%r only, the "
                             "configuration says %r"
                             % (key, only, config[key]))
    kept = config["layers_kept"]
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError("layers_kept names %d layers, num_hidden_layers "
                         "is %d" % (len(kept), config["num_hidden_layers"]))
    if config["mamba_dt_rank"] != -(-config["hidden_size"] // 16):
        raise ValueError("mamba_dt_rank is ceil(hidden_size / 16) in the "
                         "program (Mamba's 'auto')")
    return BlockSpec(
        norm="layernorm", norm_eps=config["layer_norm_eps"], ffn="swiglu",
        positions="none", tied_head=True,
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        layer_types=tuple(reference.layer_kinds(config)),
        sliding_window=config["sliding_window"],
        conv_taps=config["mamba_d_conv"], ssm_state=config["mamba_d_state"],
        ssm_expand=config["mamba_expand"],
        scan_from=kept.index(config["shared_scan_layer"]),
        kv_from=kept.index(config["shared_kv_layer"]),
        diff_attention=True, layer_ids=tuple(kept))


def sizes_of(config):
    """The widths as ``flops_phi4flash`` names them."""
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                n_kv=config["num_key_value_heads"],
                head_dim=config["hidden_size"]
                // config["num_attention_heads"],
                dense_width=config["intermediate_size"],
                channels=config["mamba_expand"] * config["hidden_size"],
                states=config["mamba_d_state"],
                rank=config["mamba_dt_rank"])


def module_of(config, traffic, block=None):
    """The program's model for ``config``; ``block`` replaces the
    configuration's own ``BlockSpec`` (benchmark/phi4flash_probe.py
    spoils one to show what the check refuses)."""
    from horovod_tpu.models import Transformer, TransformerConfig

    return Transformer(TransformerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq_len=int(traffic["seq_len"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        attention=config["attention"], remat=bool(traffic["remat"]),
        block=block or block_spec(config)))


def build(config, traffic, block=None):
    seq_len = int(traffic["seq_len"])
    sizes = sizes_of(config)
    kinds = reference.layer_kinds(config)
    window, vocab = config["sliding_window"], config["vocab_size"]
    model = module_of(config, traffic, block)

    def init(key):
        # Parameter shapes do not depend on the batch: a short sample
        # keeps the traced forward (dead code under jit) small.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        return meta.unbox(model.init(key, sample)), {}

    def loss(params, state, tokens):
        logits = model.apply(params, tokens[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:]).mean(), state

    def attention_work(per_chip_batch):
        """What the attention of one chip's step REQUIRES, ``fwd`` and
        ``bwd`` (``flops.attention_work``), summed over the attention
        layers (sliding, full and cross; a Mamba layer and a memory unit
        have no pairs). Differential attention is TWO softmax maps a
        pair of heads: ``n_head / 2`` maps over ``n_kv / 2`` twice, each
        q.k ``head_dim`` wide over a V of two heads side by side,
        ``2 head_dim``: 2 x (64 + 128) a pair. The sliding layer keeps
        its window's pairs; the cross layer's keys and values are the
        published layer's, all causal pairs."""
        def layer(kind):
            pairs = flops_afmoe.window_pairs(
                seq_len, window if kind == reference.SLIDING else None)
            return flops.add_work(2 * [flops.attention_work(
                pairs, seq_len, batch=per_chip_batch,
                n_head=sizes["n_head"] // 2, n_kv=sizes["n_kv"] // 2,
                d=sizes["head_dim"], d_v=2 * sizes["head_dim"])])

        return flops.add_work(
            layer(kind) for kind in kinds
            if kind not in (reference.MAMBA, reference.MEMORY_UNIT))

    return SimpleNamespace(
        init=init, loss=loss, module=model,
        reference_loss=functools.partial(reference.loss, config),
        batch_specs=lambda plan: plan.batch_spec(2, seq_dim=None),
        plan_kwargs=dict(seq_len=seq_len, d_model=sizes["hidden"],
                         n_layers=config["num_hidden_layers"]),
        pool_kwargs=dict(seq_len=seq_len),
        units_per_item=seq_len,
        step_ops=lambda batch: flops_phi4flash.phi4flash_step_ops(
            batch, seq_len, vocab=vocab, kinds=kinds, window=window,
            **sizes),
        attention_work=attention_work)
