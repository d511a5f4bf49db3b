"""GLM-4.7-Flash's decoder (huggingface ``zai-org/GLM-4.7-Flash``,
``model_type`` ``glm4_moe_lite``; the layer equations are those of the
DeepSeek-V3 family it follows), in plain ``jax.numpy`` and float32, as
ONE chip's share of an expert-parallel group sees it.

All norms are RMSNorm, scale only. A block is ``x = x + attn(norm(x))``,
``x = x + ffn(norm(x))``.

Latent attention, per token and without biases: ``c_q = norm(y Wqa)``,
``q = c_q Wqb`` -> heads x (``qk_nope_head_dim`` | ``qk_rope_head_dim``);
``[c_kv | k_pe] = y Wkva``, ``c_kv = norm(c_kv)``, ``c_kv Wkvb`` ->
heads x (``qk_nope_head_dim`` | ``v_head_dim``) = the plain part of k
and v. Rotary positions (rotate-half) on q's rotary part and on
``k_pe``, which ALL heads share. Causal softmax of ``q.k / sqrt(nope +
rope)``, times v, the heads concatenated through ``Wo``. k and v are
computed explicitly, as in training.

The first ``first_k_dense_replace`` blocks carry a dense SwiGLU of
``intermediate_size``. The others, in float32: ``s = sigmoid(y Wr)``
over ALL ``experts_routed_over`` experts; the choice is the top
``num_experts_per_tok`` of ``s + b`` (``b`` the correction bias, state,
no gradient); the gates are ``s`` of the chosen (without ``b``), divided
by their sum + 1e-20, times ``routed_scaling_factor``. The layer's
output is the sum over the chosen experts THAT ARE HELD HERE
(``first_expert_held`` onward, ``n_routed_experts`` of them) of ``g_j
E_j(y)``, plus the shared expert's ``E_s(y)``; what the absent experts
would have added is left out. Every ``E`` is a SwiGLU of
``moe_intermediate_size``. The loss is the mean next-token cross entropy
over the vocabulary held here; no auxiliary router loss.

No kernel, no flax, no sort, no gather of rows: EVERY token goes
through EVERY held expert, weighted by a (T, held) matrix that is the
gate where the expert was chosen and zero elsewhere. ``whole_layer``
is the uncut layer (all experts, the shared one once), which the test
of the shares adds up to.

It reads the parameter tree the program's ``models.Transformer`` makes
and the state tree ``{layer_<i>: {moe: {router_bias}}}``, and follows
the configuration file's stated departures. Attention is computed in
query blocks and the experts one at a time, each under
``jax.checkpoint``, so that the float32 backward of one sequence of
8192 fits beside the parameters and two gradient trees. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The pieces the two references have in common, as OLMoE's states them:
# RMSNorm, rotate-half RoPE over the whole of the last dimension, one
# block of queries against all keys, the size of that block.
from benchmark.reference.olmoe import (Q_BLOCK, _attend_block, _rms_norm,
                                       _rope)


def _operand(a):
    """Every matmul's operands pass through here (but the router's,
    which is float32 whatever the compute dtype, and the attention
    probabilities): the identity. ``benchmark/glm_routing.py`` replaces
    it to compute this reference BELOW the configuration's stated
    precision, which the check has to refuse."""
    return a


def _attention(y, p, config):
    o = _operand
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    c_q = _rms_norm(o(y) @ o(p["q_a"]), p["q_a_norm"]["scale"], eps)
    q = jnp.einsum("bsr,rhd->bshd", o(c_q), o(p["q_b"]))
    down = o(y) @ o(p["kv_a"])
    c_kv = _rms_norm(down[..., :rank], p["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("bsr,rhd->bshd", o(c_kv), o(p["kv_b"]))
    k_pe = _rope(down[:, :, None, rank:], theta)          # one for all heads
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.tile(k_pe, (1, 1, q.shape[2], 1))], -1)
    q, k, v = o(q), o(k), o(kv[..., nope:])
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    q_blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    ctx = jax.lax.map(
        lambda args: jax.checkpoint(_attend_block)(args[0], k, v, args[1]),
        (q_blocks, jnp.arange(0, s, block)))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])
    return jnp.einsum("bshd,hdm->bsm", o(ctx), o(p["wo"]))


def _swiglu(y, wg, wi, wo):
    o = _operand
    return o(jax.nn.silu(o(y) @ o(wg)) * (o(y) @ o(wi))) @ o(wo)


def gates_over_all_experts(y, router, bias, config, assignment=None):
    """((T, E) gates: zero where an expert was not chosen; the experts
    chosen (T, k)) of tokens y (T, M)."""
    e = router.shape[-1]
    scores = jax.nn.sigmoid(y @ router)
    chosen = assignment
    if chosen is None:
        chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                               config["num_experts_per_tok"])[1]
    picked = jnp.sum(jax.nn.one_hot(chosen, e, dtype=scores.dtype), 1)
    gates = scores * picked
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * config["routed_scaling_factor"], chosen


def _weighted_experts(y, weight, wg, wi, wo):
    """sum_e weight[:, e] * E_e(y), the experts one at a time."""
    def add_expert(out, expert):
        wg_e, wi_e, wo_e, w = expert
        return out + w[:, None] * jax.checkpoint(_swiglu)(
            y, wg_e, wi_e, wo_e), None

    return jax.lax.scan(add_expert, jnp.zeros_like(y),
                        (wg, wi, wo, weight.T))[0]


def _experts(y, p, bias, config, assignment):
    """y (T, M): this chip's part of the routed sum plus the shared
    expert; also the experts chosen and the (T x slot) pairs each of
    ALL experts received."""
    gates, chosen = gates_over_all_experts(y, p["router"], bias, config,
                                           assignment)
    first, held = config["first_expert_held"], p["wi"].shape[0]
    routed = _weighted_experts(y, gates[:, first:first + held], p["wg"],
                               p["wi"], p["wo"])
    shared = _swiglu(y, p["shared"]["wg"], p["shared"]["wi"],
                     p["shared"]["wo"])
    counts = jnp.sum(jax.nn.one_hot(chosen, gates.shape[-1],
                                    dtype=jnp.int32), (0, 1))
    return routed + shared, chosen, counts


def whole_layer(y, router, bias, wg, wi, wo, shared, config):
    """The UNCUT expert layer over tokens y (T, M): every one of the
    router's experts present (``wg``/``wi``/``wo`` lead with all of
    them), the shared expert once."""
    gates, _ = gates_over_all_experts(y, router, bias, config)
    return (_weighted_experts(y, gates, wg, wi, wo)
            + _swiglu(y, shared["wg"], shared["wi"], shared["wo"]))


def _block(x, p, bias, assignment, *, config):
    eps = config["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["ln1"]["scale"], eps), p["attn"],
                       config)
    y = _rms_norm(x, p["ln2"]["scale"], eps)
    if "mlp" in p:
        return x + _swiglu(y, p["mlp"]["wg"], p["mlp"]["wi"],
                           p["mlp"]["wo"]), None, None
    b, s, m = y.shape
    out, chosen, counts = _experts(y.reshape(b * s, m), p["moe"], bias,
                                   config, assignment)
    return x + out.reshape(b, s, m), chosen, counts


def forward(config, params, state, inputs, assignments=None):
    """Logits (B, S, vocab) of ``inputs`` (B, S), and per EXPERT layer
    the experts chosen ((T, k) indices) and the pairs each expert
    received. ``assignments`` (one entry a layer; a dense layer's is
    ignored) forces the choice."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["embed"][inputs]
    aux = {"chosen": [], "tokens_per_expert": []}
    block = jax.checkpoint(functools.partial(_block, config=config))
    for i in range(config["num_hidden_layers"]):
        name = "layer_%d" % i
        dense = i < config["first_k_dense_replace"]
        x, chosen, counts = block(
            x, p[name], None if dense else state[name]["moe"]["router_bias"],
            None if assignments is None else assignments[i])
        if not dense:
            aux["chosen"].append(chosen)
            aux["tokens_per_expert"].append(counts)
    x = _rms_norm(x, p["ln_f"]["scale"], config["rms_norm_eps"])
    return (_operand(x) @ _operand(p["lm_head"]).T,
            {k: jnp.stack(v) for k, v in aux.items()})


def cross_entropy(logits, targets):
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)


def next_bias(config, state, tokens_per_expert):
    """The state after one step: ``b_e + rate * sign(mean(c) - c_e)``
    per expert layer, ``c`` that layer's row of ``tokens_per_expert``."""
    names = sorted(state, key=lambda n: int(n.rsplit("_", 1)[1]))
    out = {}
    for name, counts in zip(names, tokens_per_expert):
        load = counts.astype(jnp.float32)
        out[name] = {"moe": {"router_bias": (
            state[name]["moe"]["router_bias"]
            + config["router_bias_update_rate"]
            * jnp.sign(jnp.mean(load) - load))}}
    return out


def loss(config, params, state, tokens, assignments=None):
    """The cross entropy of ``tokens`` (B, S + 1) and the state after
    the step, like every reference."""
    logits, aux = forward(config, params, state, tokens[:, :-1], assignments)
    return (cross_entropy(logits, tokens[:, 1:]),
            next_bias(config, state, aux["tokens_per_expert"]))
