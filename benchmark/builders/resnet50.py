"""ResNet-50: the configuration file becomes the program's
``models.ResNet`` (bottleneck blocks) and its classification loss, with
the batch statistics carried as the step's state."""

from __future__ import annotations

import functools
from types import SimpleNamespace

import jax.numpy as jnp
import optax

from benchmark import flops
from benchmark.reference import resnet50 as reference

# At 32 px the last stage normalises 8 values a channel, which bf16 turns
# into noise the real size does not have: the rehearsal's tolerances are
# looser than the configuration file's; and 8 images at 0.1 diverge.
TINY = {
    "config": {"image_size": 32, "num_classes": 10,
               "optimizer": {"name": "sgd", "learning_rate": 0.01,
                             "momentum": 0.9},
               "check": {"via": "sgd_step", "sample_per_chip": 8,
                         "loss_rtol": 2e-2, "grad_rel_l2": 1.0}},
    "traffic": {"per_chip_batch": 8},
}


def build(config, traffic):
    from horovod_tpu.models import ResNet

    px, ch = config["image_size"], config["image_channels"]
    model = ResNet(stage_sizes=list(config["stage_sizes"]),
                   num_classes=config["num_classes"],
                   num_filters=config["num_filters"],
                   dtype=jnp.dtype(config["compute_dtype"]),
                   remat=bool(traffic["remat"]))

    def init(key):
        variables = model.init(key, jnp.zeros((1, px, px, ch)), train=True)
        return {"params": variables["params"]}, variables["batch_stats"]

    def loss(params, state, batch):
        images, labels = batch
        logits, new = model.apply(
            {"params": params["params"], "batch_stats": state}, images,
            train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean(), new["batch_stats"]

    sizes = dict(stage_sizes=config["stage_sizes"],
                 num_filters=config["num_filters"], image_size=px,
                 channels=ch, expansion=config["bottleneck_expansion"],
                 num_classes=config["num_classes"])
    return SimpleNamespace(
        init=init, loss=loss,
        reference_loss=functools.partial(reference.loss, config),
        batch_specs=lambda plan: (plan.batch_spec(4, seq_dim=None),
                                  plan.batch_spec(1, seq_dim=None)),
        plan_kwargs={}, pool_kwargs={}, units_per_item=1,
        step_ops=lambda batch: flops.resnet_step_ops(batch, **sizes),
        attention_work=lambda per_chip_batch: {})
