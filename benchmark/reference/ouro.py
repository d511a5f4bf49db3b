"""Ouro's looped decoder (huggingface ``ByteDance/Ouro-2.6B``,
``model_type`` ``ouro``; "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741), in plain ``jax.numpy`` and float32. The
widths, the loop count and the rotary base are the config's keys; what
``config.json`` does not say (where the final norm stands, ``beta``, the
gate's input) is listed under ``assumed`` in
``benchmark/configs/ouro-2.6b.json``, and this file reads the same keys
as the program's builder.

With ``L`` blocks, ``T = total_ut_steps`` passes, tokens x, targets y:

    h_0 = E[x]                                  (no scale, no positions)
    block:  u = a + N2(Attn(N1(a)));  b = u + N4(SwiGLU(N3(u)))
    pass:   s_t = Stack(h_(t-1));  h_t = Nf(s_t)     (``norm_in_loop``;
            otherwise the next pass reads s_t and Nf stands on the
            readout alone)
    readout:  z_t = Nf(s_t) Wh^T,  l_t(i) = CE(z_t(i), y(i))
    gate:   g_t(i) = sigmoid(Nf(s_t)(i) . wg + bg)   (ONE gate)
    exit:   p_1 = g_1,  p_t = g_t prod_(j<t) (1 - g_j)  for t < T,
            p_T = prod_(j<T) (1 - g_j)          (the last takes the rest)
    loss = mean_i [ sum_t p_t(i) l_t(i) + beta sum_t p_t(i) log p_t(i) ]

All norms are RMSNorm with a scale each (eps ``rms_norm_eps``), the SAME
weights in every pass. ``Attn``: q, k, v, o without bias,
``num_attention_heads`` heads of ``head_dim``, rotate-half RoPE at
``rope_theta`` on q and k, causal softmax at ``1 / sqrt(head_dim)``.
``SwiGLU(z) = (silu(z Wg) * (z Wu)) Wd``.

No kernel, no flax: attention in query blocks, a plain ``for`` over the
passes and the blocks. ``jax.checkpoint`` by block and pass, and the
cross entropy by blocks of rows, change no arithmetic; they let the
float32 backward of 4 x 8 block passes at 4,096 tokens and four (4096,
49152) readouts fit beside the parameters and two gradient trees. It
reads the parameter tree the program's ``models.Transformer`` makes
(``stack/layer_<i>``, ``stack/ln_f``, ``embed``, ``lm_head``,
``exit_gate``). Call it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512      # queries a block of attention
ROW_BLOCK = 1024   # positions a block of the readout


def _operand(a):
    """Every matmul's operands pass through here (but the gate's dot,
    which is float32 whatever the compute dtype): the identity.
    ``benchmark/ouro_probe.py`` replaces it to compute this reference
    BELOW the configuration's stated precision, which the check has to
    refuse."""
    return a


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half rotary positions on x (B, S, H, D), from position 0."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend_block(q, k, v, q_start):
    """Queries q (B, Tq, H, D) at positions q_start.. over every key up
    to each query, k, v (B, S, H, D)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    q_pos = (q_start + jnp.arange(q.shape[1]))[:, None]
    visible = jnp.arange(k.shape[1])[None, :] <= q_pos
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(y, p, config):
    o, theta = _operand, config["rope_theta"]
    q, k, v = (jnp.einsum("bsm,mhd->bshd", o(y), o(p["wqkv"][i]))
               for i in range(3))
    q, k, v = o(_rope(q, theta)), o(_rope(k, theta)), o(v)
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    q_blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    ctx = jax.lax.map(
        lambda args: jax.checkpoint(_attend_block)(args[0], k, v, args[1]),
        (q_blocks, jnp.arange(0, s, block)))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, d)
    return jnp.einsum("bshd,hdm->bsm", o(ctx), o(p["wo"]))


def _swiglu(y, p):
    o = _operand
    return o(jax.nn.silu(o(y) @ o(p["wg"])) * (o(y) @ o(p["wi"]))) @ o(p["wo"])


def _block(x, p, *, config):
    eps = config["rms_norm_eps"]
    attn = _attention(_rms_norm(x, p["ln1"]["scale"], eps), p["attn"], config)
    x = x + _rms_norm(attn, p["post_attn_norm"]["scale"], eps)
    ffn = _swiglu(_rms_norm(x, p["ln2"]["scale"], eps), p["mlp"])
    return x + _rms_norm(ffn, p["post_mlp_norm"]["scale"], eps)


def hidden_states(config, params, inputs, with_raw=False):
    """(T, B, S, M): the normed state after each of the
    ``total_ut_steps`` passes over ``inputs`` (B, S); ``with_raw`` also
    the states before their norm."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    stack, eps = p["stack"], config["rms_norm_eps"]
    block = jax.checkpoint(functools.partial(_block, config=config))
    x, states, raw = p["embed"][inputs], [], []
    for _ in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            x = block(x, stack["layer_%d" % i])
        raw.append(x)
        h = _rms_norm(x, stack["ln_f"]["scale"], eps)
        states.append(h)
        if config["norm_in_loop"]:
            x = h
    states = jnp.stack(states)
    return (states, jnp.stack(raw)) if with_raw else states


def _gate_input(normed, raw):
    """What the exit gate reads: the NORMED state of each pass
    (``benchmark/ouro_probe.py`` replaces this to show that the check
    refuses a gate over the un-normed one)."""
    return normed


def logits(params, h):
    """One pass's readout (B, S, vocab) of its normed state h."""
    head = params["params"]["lm_head"].astype(jnp.float32)
    return _operand(h) @ _operand(head).T


def _rows_loss(h, head, targets):
    z = _operand(h) @ _operand(head).T
    at = jnp.take_along_axis(z, targets[:, None], axis=-1)[:, 0]
    return jax.nn.logsumexp(z, axis=-1) - at


def readout_losses(params, h, targets):
    """(B, S): the cross entropy of one pass's readout at each position,
    a block of rows at a time."""
    head = params["params"]["lm_head"].astype(jnp.float32)
    rows, y = h.reshape(-1, h.shape[-1]), targets.reshape(-1)
    block = ROW_BLOCK if rows.shape[0] % ROW_BLOCK == 0 else rows.shape[0]
    out = jax.lax.map(
        lambda args: jax.checkpoint(_rows_loss)(args[0], head, args[1]),
        (rows.reshape(-1, block, rows.shape[-1]), y.reshape(-1, block)))
    return out.reshape(targets.shape)


def exit_distribution(params, states):
    """(T, B, S): the probability that a position exits after pass t,
    from ONE gate over the normed states; sums to one over t."""
    gate = params["params"]["exit_gate"].astype(jnp.float32)
    g = jax.nn.sigmoid(jnp.sum(states * gate[:-1], -1) + gate[-1])
    p, left = [], jnp.ones_like(g[0])
    for t in range(states.shape[0] - 1):
        p.append(g[t] * left)
        left = left * (1.0 - g[t])
    return jnp.stack(p + [left])


def terms(config, params, tokens):
    """The loss of ``tokens`` (B, S + 1) and what it is made of: the
    exit distribution (T, B, S) and each pass's cross entropy (T, B,
    S)."""
    states, raw = hidden_states(config, params, tokens[:, :-1], True)
    losses = jnp.stack([readout_losses(params, h, tokens[:, 1:])
                        for h in states])
    p = exit_distribution(params, _gate_input(states, raw))
    # 0 log 0 = 0: a gate shut for good leaves a pass with no weight.
    plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    total = jnp.mean(jnp.sum(p * losses, 0)
                     + config["exit_entropy_beta"] * jnp.sum(plogp, 0))
    return total, p, losses


def loss(config, params, state, tokens):
    """The loss and the (empty) state after the step, like every
    reference."""
    return terms(config, params, tokens)[0], state
