"""ResNet-50 (He et al., arXiv:1512.03385, Table 1) in its v1.5 form, in
plain ``jax.numpy`` and ``lax`` convolutions, float32, training mode:
batch normalisation uses the statistics of the batch it is given and
moves the running ones by the configuration's momentum. No flax.

It reads the variable tree the program's ``models.ResNet50`` makes
(``params`` and ``batch_stats``; weights come from the seed). Call it
under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_DIMS = ("NHWC", "HWIO", "NHWC")


def _conv(x, p, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), padding, dimension_numbers=_DIMS)


class _Norms:
    """Train-mode batch norm over (N, H, W) that records the new running
    statistics under the same names the program keeps them."""

    def __init__(self, config, old):
        self.eps = config["batch_norm_epsilon"]
        self.momentum = config["batch_norm_momentum"]
        self.old, self.new = old, {}

    def __call__(self, x, p, *path):
        mean = jnp.mean(x, (0, 1, 2))
        var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
        old, slot = self.old, self.new
        for name in path[:-1]:
            old, slot = old[name], slot.setdefault(name, {})
        old = old[path[-1]]
        m = self.momentum
        slot[path[-1]] = {"mean": m * old["mean"] + (1 - m) * mean,
                          "var": m * old["var"] + (1 - m) * var}
        return (x - mean) / jnp.sqrt(var + self.eps) * p["scale"] + p["bias"]


def _bottleneck(x, p, name, stride, norm):
    y = jax.nn.relu(norm(_conv(x, p["Conv_0"]), p["BatchNorm_0"],
                         name, "BatchNorm_0"))
    y = jax.nn.relu(norm(_conv(y, p["Conv_1"], stride), p["BatchNorm_1"],
                         name, "BatchNorm_1"))
    y = norm(_conv(y, p["Conv_2"]), p["BatchNorm_2"], name, "BatchNorm_2")
    if "conv_proj" in p:
        x = norm(_conv(x, p["conv_proj"], stride), p["norm_proj"],
                 name, "norm_proj")
    return jax.nn.relu(x + y)


def logits(config, variables, images):
    """(logits, new batch statistics) for images (N, H, W, C)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    norm = _Norms(config, variables["batch_stats"])
    x = _conv(images.astype(jnp.float32), p["conv_init"], 2,
              [(3, 3), (3, 3)])
    x = jax.nn.relu(norm(x, p["bn_init"], "bn_init"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    n = 0
    for stage, blocks in enumerate(config["stage_sizes"]):
        for j in range(blocks):
            name = "BottleneckBlock_%d" % n
            x = _bottleneck(x, p[name], name,
                            2 if stage > 0 and j == 0 else 1, norm)
            n += 1
    x = jnp.mean(x, (1, 2))
    return x @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"], norm.new


def loss(config, params, state, batch):
    """Mean cross entropy of (images, labels); returns (loss, new batch
    statistics)."""
    images, labels = batch
    out, stats = logits(config, {"params": params["params"],
                                 "batch_stats": state}, images)
    picked = jnp.take_along_axis(out, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(out, -1) - picked), stats
