"""Ouro's family (``model_type`` ouro): a configuration file of the
published ``config.json`` keys, and of what the family's public code
adds to them (``assumed``), becomes the program's ``models.Transformer``
with ``passes = total_ut_steps``: ONE stack of blocks (four RMSNorms a
block, 16 heads of 128 with rotary positions, SwiGLU, an untied head)
applied that many times a step with the same weights, the final norm
after every pass. The model hands back the normed state of each pass;
the loss is the library's ``looped_loss``: every pass read out through
the one head, an exit gate that weights the cross entropies, less
``exit_entropy_beta`` times the exit distribution's entropy. The step's
carried state is the loss's statistics (the mean exit share of each
pass, the mean entropy, each pass's mean cross entropy), which a caller
fetches with the state."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax.numpy as jnp
from flax.core import meta

from benchmark import flops, flops_ouro
from benchmark.reference import ouro as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else: three blocks of four heads of 16, four passes.
TINY = {
    "config": {"vocab_size": 512, "hidden_size": 64,
               "num_attention_heads": 4, "num_key_value_heads": 4,
               "head_dim": 16, "intermediate_size": 96,
               "num_hidden_layers": 3,
               # A leaf here is a few thousand numbers: the rehearsal
               # finds wrong paths, the CPU tests compare every leaf in
               # float32.
               "check": {"via": "sgd_step", "sample_per_chip": 1,
                         "loss_rtol": 2e-4, "grad_rel_l2": 0.3}},
    "traffic": {"seq_len": 128, "per_chip_batch": 1},
}

# Keys the program has one answer to; anything else is refused rather
# than run as something it is not.
_ONLY = {"model_type": "ouro", "hidden_act": "silu",
         "tie_word_embeddings": False, "rope_scaling": None,
         "sliding_window": None, "use_sliding_window": False}


def block_spec(config):
    from horovod_tpu.models import BlockSpec

    for key, only in _ONLY.items():
        if config[key] != only:
            raise ValueError("builders/ouro.py runs %s=%r only, the "
                             "configuration says %r"
                             % (key, only, config[key]))
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the ouro family has no grouped key/value heads")
    return BlockSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], ffn="swiglu",
        positions="rope", rope_theta=float(config["rope_theta"]),
        tied_head=False, head_dim=config["head_dim"], post_norms=True)


def sizes_of(config):
    """The widths as ``flops_ouro`` names them."""
    return dict(hidden=config["hidden_size"],
                n_head=config["num_attention_heads"],
                head_dim=config["head_dim"],
                width=config["intermediate_size"],
                n_layer=config["num_hidden_layers"],
                passes=config["total_ut_steps"])


def module_of(config, traffic, **spoiled):
    """The program's model for ``config``; ``spoiled`` replaces fields
    of its ``TransformerConfig`` (benchmark/ouro_probe.py spoils one to
    show what the check refuses)."""
    from horovod_tpu.models import Transformer, TransformerConfig

    fields = dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"],
        max_seq_len=int(traffic["seq_len"]),
        dtype=jnp.dtype(config["compute_dtype"]),
        attention=config["attention"], remat=bool(traffic["remat"]),
        block=block_spec(config), passes=config["total_ut_steps"],
        loop_norm=bool(config["norm_in_loop"]))
    fields.update(spoiled)
    return Transformer(TransformerConfig(**fields))


def build(config, traffic, **spoiled):
    from horovod_tpu.models import looped_loss

    seq_len = int(traffic["seq_len"])
    sizes = sizes_of(config)
    passes, vocab = sizes["passes"], config["vocab_size"]
    model = module_of(config, traffic, **spoiled)

    def init(key):
        # Parameter shapes do not depend on the batch: a short sample
        # keeps the traced forward (dead code under jit) small. The
        # state is the loss's statistics, zeros before the first step.
        sample = jnp.zeros((1, min(seq_len, 128)), jnp.int32)
        return meta.unbox(model.init(key, sample)), {
            "exit_share": jnp.zeros((passes,), jnp.float32),
            "entropy": jnp.zeros((), jnp.float32),
            "cross_entropy": jnp.zeros((passes,), jnp.float32)}

    def loss(params, state, tokens):
        hidden = model.apply(params, tokens[:, :-1])
        p = params["params"]
        return looped_loss(hidden, p["lm_head"], p["exit_gate"],
                           tokens[:, 1:], config["exit_entropy_beta"])

    def attention_work(per_chip_batch):
        """What the attention of one chip's step REQUIRES, ``fwd`` and
        ``bwd`` (``flops.attention_work``): the causal pairs of one
        block, once an APPLICATION, ``n_layer x passes`` of them."""
        return flops.add_work(
            sizes["n_layer"] * passes * [flops.attention_work(
                flops.causal_pairs(seq_len), seq_len, batch=per_chip_batch,
                n_head=sizes["n_head"], n_kv=sizes["n_head"],
                d=sizes["head_dim"], d_v=sizes["head_dim"])])

    return SimpleNamespace(
        init=init, loss=loss, module=model,
        reference_loss=functools.partial(reference.loss, config),
        batch_specs=lambda plan: plan.batch_spec(2, seq_dim=None),
        # The planner prices activations and per-layer collectives by
        # block APPLICATIONS a step; under recomputation by pass one
        # pass's blocks hold their activations at once.
        plan_kwargs=dict(seq_len=seq_len, d_model=sizes["hidden"],
                         n_layers=sizes["n_layer"] * passes,
                         live_layers=sizes["n_layer"]
                         if traffic["remat"] else 0),
        pool_kwargs=dict(seq_len=seq_len),
        units_per_item=seq_len,
        step_ops=lambda batch: flops_ouro.ouro_step_ops(
            batch, seq_len, vocab=vocab, **sizes),
        attention_work=attention_work)
