"""The one generator of training data for every cell.

A traffic mix is a data file (``benchmark/workloads/<traffic>.json``);
its ``data`` group names one of the kinds below and gives that kind's
parameters. Everything is made on the device from the seed, in one
jitted call, as a pool of distinct global batches that the window
cycles through: the input pipeline is bypassed by design in these
mixes, and the program sees only the arrays.

``markov_tokens``: each sequence is a first-order Markov chain over the
vocabulary in which every token has ``successors`` equally likely next
tokens, fixed by the seed. The achievable loss is ln(successors), far
below ln(vocab), so a training step that works makes the loss fall.

``class_images``: each image is a pattern fixed by its label (two
spatial frequencies and a phase per channel) plus unit normal noise
scaled by ``noise``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _markov_tokens(key, n_batches, batch, seq_len, vocab, successors):
    """(n_batches, batch, seq_len + 1) int32: inputs are [..., :-1] and
    next-token targets [..., 1:]."""
    k_mul, k_add, k_first, k_pick = jax.random.split(key, 4)
    # successor j of token t is (mul[j] * t + add[j]) mod vocab; mul is
    # odd and below 2**15 so the product stays inside int32.
    mul = jax.random.randint(k_mul, (successors,), 0, 2 ** 14) * 2 + 1
    add = jax.random.randint(k_add, (successors,), 0, vocab)
    first = jax.random.randint(k_first, (n_batches, batch), 0, vocab)
    picks = jax.random.randint(
        k_pick, (seq_len, n_batches, batch), 0, successors)

    def advance(tok, pick):
        nxt = (mul[pick] * tok + add[pick]) % vocab
        return nxt, nxt

    _, rest = jax.lax.scan(advance, first, picks)
    return jnp.concatenate([first[None], rest], 0).transpose(1, 2, 0)


def _class_images(key, n_batches, batch, size, channels, classes, noise,
                  dtype):
    """((n_batches, batch, size, size, channels) images, labels)."""
    k_lab, k_noise = jax.random.split(key)
    labels = jax.random.randint(k_lab, (n_batches, batch), 0, classes)
    at = jnp.arange(size, dtype=jnp.float32) / size
    fx = (1 + labels % 10).astype(jnp.float32)
    fy = (1 + (labels // 10) % 10).astype(jnp.float32)
    phase = (labels // 100).astype(jnp.float32)[..., None] \
        + jnp.arange(channels, dtype=jnp.float32)
    wave = 2 * jnp.pi * (fx[..., None, None] * at[None, :]
                         + fy[..., None, None] * at[:, None])
    pattern = jnp.sin(wave[..., None] + phase[..., None, None, :])
    shape = (n_batches, batch, size, size, channels)
    images = pattern + noise * jax.random.normal(k_noise, shape)
    return images.astype(dtype), labels


def make_pool(key, data, *, global_batch, seq_len=None, config):
    """The pool of global batches for one cell, as one pytree whose
    leaves lead with the pool dimension. ``data`` is the traffic file's
    group; sizes the data must match come from ``config``."""
    kind, n = data["kind"], int(data["pool"])
    if kind == "markov_tokens":
        return _markov_tokens(key, n, global_batch, seq_len,
                              config["vocab_size"], int(data["successors"]))
    if kind == "class_images":
        return _class_images(
            key, n, global_batch, config["image_size"],
            config["image_channels"], config["num_classes"],
            float(data["noise"]), jnp.dtype(config["compute_dtype"]))
    raise ValueError("traffic.py knows no data kind %r" % (kind,))
