"""GPT-2 family: a configuration file of GPT-2's published keys becomes
the program's ``models.Transformer`` and its next-token loss."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import optax
from flax.core import meta

from benchmark import flops
from benchmark.reference import gpt2 as reference

# What the CPU rehearsal and the CPU tests shrink. Widths change there
# and nowhere else.
TINY = {
    "config": {"vocab_size": 512, "n_embd": 64, "n_head": 4, "n_inner": 256,
               "n_layer": 2},
    "traffic": {"seq_len": 128, "per_chip_batch": 2},
}


def build(config, traffic):
    from horovod_tpu.models import Transformer, TransformerConfig

    seq_len = int(traffic["seq_len"])
    sizes = dict(vocab=config["vocab_size"], d_model=config["n_embd"],
                 n_head=config["n_head"], d_ff=config["n_inner"],
                 n_layer=config["n_layer"])
    model = Transformer(TransformerConfig(
        vocab_size=sizes["vocab"], d_model=sizes["d_model"],
        n_heads=sizes["n_head"], n_layers=sizes["n_layer"],
        d_ff=sizes["d_ff"], max_seq_len=seq_len,
        dtype=jnp.dtype(config["compute_dtype"]),
        attention=config["attention"], remat=bool(traffic["remat"])))

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
        ``bwd`` (``flops.attention_work``), summed over the layers: the
        causal pairs of every head."""
        head = sizes["d_model"] // sizes["n_head"]
        return flops.add_work(sizes["n_layer"] * [flops.attention_work(
            flops.causal_pairs(seq_len), seq_len, batch=per_chip_batch,
            n_head=sizes["n_head"], n_kv=sizes["n_head"], d=head, d_v=head)])

    return SimpleNamespace(
        init=init, loss=loss,
        reference_loss=functools.partial(reference.loss, config),
        batch_specs=lambda plan: plan.batch_spec(2, seq_dim=None),
        plan_kwargs=dict(seq_len=seq_len, d_model=sizes["d_model"],
                         n_layers=sizes["n_layer"]),
        pool_kwargs=dict(seq_len=seq_len),
        units_per_item=seq_len,
        step_ops=lambda batch: flops.gpt2_step_ops(batch, seq_len, **sizes),
        attention_work=attention_work)
