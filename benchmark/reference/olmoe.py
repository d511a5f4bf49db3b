"""OLMoE's decoder as published (Muennighoff et al. 2024,
arXiv:2409.02060; the layer equations of huggingface
``OlmoeForCausalLM``), in plain ``jax.numpy`` and float32: pre-RMSNorm
blocks, RMSNorm on q and k over the whole width before the heads are
split, rotary positions (rotate-half), full multi-head causal
attention, a router that takes the softmax over ALL experts and keeps
the ``num_experts_per_tok`` largest probabilities without
renormalising, SwiGLU experts, an output head of its own. The loss is
the mean next-token cross entropy plus ``router_aux_loss_coef`` x the
load-balancing loss plus ``router_z_loss_coef`` x the router z-loss,
each averaged over the layers. No kernel, no flax, no sort, no gather
of rows: EVERY token goes through EVERY expert, weighted by a (T, E)
matrix that holds the router's probability at the chosen experts and
zero elsewhere.

It reads the parameter tree the program's ``models.Transformer`` makes
and follows the configuration file's stated departures.

Attention is computed in query blocks under ``jax.checkpoint`` and the
experts one at a time under ``jax.checkpoint`` (``lax.map``,
``lax.scan``), so that the float32 backward of one sequence of 4096
fits beside the parameters and two gradient trees. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def _rope(x, theta):
    """x (B, S, H, D); HF's ``apply_rotary_pos_emb``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    emb = jnp.concatenate([freqs, freqs], -1)[None, :, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _attend_block(q, k, v, q_start):
    """Queries q (B, Tq, H, D) at positions q_start.. against all keys."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    q_pos = q_start + jnp.arange(q.shape[1])
    visible = q_pos[:, None] >= jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(x, p, config):
    wqkv, wo = p["wqkv"], p["wo"]          # (3, d, H, D), (H, D, d)
    q, k, v = (jnp.einsum("bsm,mhd->bshd", x, wqkv[i]) for i in range(3))
    b, s, h, d = q.shape
    eps = config["rms_norm_eps"]
    q = _rms_norm(q.reshape(b, s, h * d), p["q_norm"]["scale"], eps)
    k = _rms_norm(k.reshape(b, s, h * d), p["k_norm"]["scale"], eps)
    q = _rope(q.reshape(b, s, h, d), config["rope_theta"])
    k = _rope(k.reshape(b, s, h, d), config["rope_theta"])
    block = min(Q_BLOCK, s)
    starts = jnp.arange(0, s, block)
    q_blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    ctx = jax.lax.map(
        lambda args: jax.checkpoint(_attend_block)(args[0], k, v, args[1]),
        (q_blocks, starts))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, d)
    return jnp.einsum("bshd,hdm->bsm", ctx, wo)


def _one_expert(y, wg, wi, wo):
    return (jax.nn.silu(y @ wg) * (y @ wi)) @ wo


def _experts(y, p, config, assignment):
    """y (T, M). Returns (the layer's output, load-balancing loss,
    z-loss, the experts chosen (T, k))."""
    e, k = config["num_experts"], config["num_experts_per_tok"]
    logits = y @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    chosen = jax.lax.top_k(probs, k)[1] if assignment is None else assignment
    mask = jnp.sum(jax.nn.one_hot(chosen, e, dtype=probs.dtype), 1)  # (T, E)
    weight = probs * mask

    def add_expert(out, expert):
        wg, wi, wo, w = expert
        return out + w[:, None] * jax.checkpoint(_one_expert)(
            y, wg, wi, wo), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(y),
                          (p["wg"], p["wi"], p["wo"], weight.T))
    share = jax.lax.stop_gradient(jnp.sum(mask, 0) / (y.shape[0] * k))
    load_balance = e * jnp.sum(share * jnp.mean(probs, 0))
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return out, load_balance, z_loss, chosen


def _block(x, p, assignment, *, config):
    eps = config["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["ln1"]["scale"], eps), p["attn"],
                       config)
    y = _rms_norm(x, p["ln2"]["scale"], eps)
    b, s, m = y.shape
    out, load_balance, z_loss, chosen = _experts(
        y.reshape(b * s, m), p["moe"], config, assignment)
    return x + out.reshape(b, s, m), load_balance, z_loss, chosen


def forward(config, params, inputs, assignments=None):
    """Logits (B, S, vocab) of ``inputs`` (B, S), and per layer the two
    auxiliary losses and the experts chosen ((T, k) indices).
    ``assignments`` (one entry a layer) forces the choice."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["embed"][inputs]
    aux = {"load_balance": [], "z_loss": [], "chosen": []}
    block = jax.checkpoint(functools.partial(_block, config=config))
    for i in range(config["num_hidden_layers"]):
        x, load_balance, z_loss, chosen = block(
            x, p["layer_%d" % i],
            None if assignments is None else assignments[i])
        aux["load_balance"].append(load_balance)
        aux["z_loss"].append(z_loss)
        aux["chosen"].append(chosen)
    x = _rms_norm(x, p["ln_f"]["scale"], config["rms_norm_eps"])
    return x @ p["lm_head"].T, {k: jnp.stack(v) for k, v in aux.items()}


def total_loss(config, logits, aux, targets):
    """Mean cross entropy plus the two weighted auxiliary losses, each
    averaged over the layers."""
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    cross_entropy = jnp.mean(jax.nn.logsumexp(logits, -1) - picked)
    return (cross_entropy
            + config["router_aux_loss_coef"] * jnp.mean(aux["load_balance"])
            + config["router_z_loss_coef"] * jnp.mean(aux["z_loss"]))


def loss(config, params, state, tokens, assignments=None):
    """``total_loss`` of ``tokens`` (B, S + 1); returns (loss, state)
    like every reference (the decoder has no state)."""
    logits, aux = forward(config, params, tokens[:, :-1], assignments)
    return total_loss(config, logits, aux, tokens[:, 1:]), state
