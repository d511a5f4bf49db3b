"""GPT-2's decoder as published (Radford et al. 2019; the layer equations
of huggingface ``GPT2LMHeadModel``), in plain ``jax.numpy`` and float32:
learned positions, pre-LayerNorm blocks, full multi-head causal
attention, GELU (tanh form, ``gelu_new``), tied output embedding, mean
next-token cross entropy. No kernel, no flax, no cache.

It reads the parameter tree the program's ``models.Transformer`` makes
(weights come from the seed, not from a checkpoint), and follows the
configuration file's stated departures: no biases on the linear layers,
the file's ``layer_norm_epsilon``.

Attention is computed in query blocks of ``Q_BLOCK`` under
``jax.checkpoint``, and each block of the decoder is checkpointed too,
so that the float32 backward of a long sequence fits beside the
program's own state. The layers are scanned and the query blocks
mapped (``lax.scan``, ``lax.map``): one block's code, not 24 copies. Call it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attend_block(q, k, v, q_start):
    """Queries q (B, Tq, H, D) at positions q_start.. against all keys."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    q_pos = q_start + jnp.arange(q.shape[1])
    visible = q_pos[:, None] >= jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(x, p):
    wqkv, wo = p["wqkv"], p["wo"]          # (3, d, H, D), (H, D, d)
    q, k, v = (jnp.einsum("bsm,mhd->bshd", x, wqkv[i]) for i in range(3))
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    starts = jnp.arange(0, s, block)
    q_blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    ctx = jax.lax.map(
        lambda args: jax.checkpoint(_attend_block)(args[0], k, v, args[1]),
        (q_blocks, starts))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, d)
    return jnp.einsum("bshd,hdm->bsm", ctx, wo)


def _block(x, p, eps):
    x = x + _attention(_layer_norm(x, p["ln1"], eps), p["attn"])
    h = _layer_norm(x, p["ln2"], eps) @ p["mlp"]["wi"]
    return x + _gelu_new(h) @ p["mlp"]["wo"]


def loss(config, params, state, tokens):
    """Mean next-token cross entropy of ``tokens`` (B, S + 1); returns
    (loss, state) like every reference (the decoder has no state)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    eps = config["layer_norm_epsilon"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = p["embed"][inputs] + p["pos"][:inputs.shape[1]][None]
    # One scanned block over the stacked layers: the same mathematics as
    # a loop, in a program 24 times smaller to compile and to cache.
    layers = jax.tree.map(
        lambda *leaves: jnp.stack(leaves),
        *[p["layer_%d" % i] for i in range(config["n_layer"])])
    x, _ = jax.lax.scan(
        lambda x, layer: (jax.checkpoint(_block, static_argnums=2)(
            x, layer, eps), None), x, layers)
    logits = _layer_norm(x, p["ln_f"], eps) @ p["embed"].T
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked), state
