"""LFM2-8B-A1B's decoder (huggingface ``LiquidAI/LFM2-8B-A1B``,
``model_type`` ``lfm2_moe``), in plain ``jax.numpy`` and float32, as ONE
chip's share of an expert-parallel group sees it. The widths, the layer
pattern and the router's settings are the config's keys; what the config
does not carry is the family's public modeling code (``transformers``
``modeling_lfm2_moe.py``), each listed with that origin under
``assumed`` in ``benchmark/configs/lfm2-8b-a1b.json``.

All norms are RMSNorm, scale only; no bias anywhere. A block:

    h = x + mixer(ln1(x))          (the family's ``operator_norm``)
    y = h + ffn(ln2(h))            (``ffn_norm``)

The mixer by the layer's entry of ``layer_types``. ``conv``: ``[b, c,
u] = split3(y W_in)`` (three contiguous thirds of the 3M columns),
``z_t = sum_j w[:, j] * (b * u)_(t - L + 1 + j)`` per channel over the
``conv_L_cache`` = L taps, zeros before position 0, no bias, no
activation; the output is ``(c * z) W_out``. ``full_attention``: ``q = y
Wq`` (``H`` heads of ``head_dim``), ``k = y Wk``, ``v = y Wv`` (``H_kv``
heads); RMSNorm over each head's ``head_dim`` on q and on k, one scale
vector each; rotary positions (rotate-half, all of ``head_dim``) on q
and k; causal ``softmax(q.k / sqrt(head_dim)) v``, query head h reading
key/value head ``h // (H // H_kv)``; the heads concatenated through
``Wo``.

The first ``num_dense_layers`` blocks carry a dense SwiGLU of
``intermediate_size``. The others, in float32: ``s = sigmoid(y Wr)``
over ALL ``experts_routed_over`` experts; the choice is the top
``num_experts_per_tok`` of ``s + b`` (``use_expert_bias``: ``b`` the
bias, carried state, no gradient); the gates are ``s`` of the chosen
(without ``b``), divided by their sum + 1e-6 (``norm_topk_prob``), times
``routed_scaling_factor``. The layer's output is the sum over the chosen
experts THAT ARE HELD HERE (``first_expert_held`` onward,
``num_experts`` of them) of ``g_j E_j(y)``; what the absent experts
would have added is left out; there is no shared expert. Every ``E`` is
a SwiGLU of ``moe_intermediate_size``. After a step ``b_e <- b_e +
router_bias_update_rate * sign(mean(c) - c_e)``, ``c`` the pairs each
expert received. The output head is the embedding (``tie_embedding``).
The loss is the mean next-token cross entropy over the vocabulary held
here; no auxiliary loss.

No kernel, no flax, no sort, no gather of rows: the convolution is L
shifted multiplies, K and V are repeated to the query heads by
``jnp.repeat``, and EVERY token goes through EVERY held expert,
weighted by a (T, held) matrix that is the gate where the expert was
chosen and zero elsewhere. ``whole_layer`` is the uncut layer (all
experts), which the test of the shares adds up to.

It reads the parameter tree the program's ``models.Transformer`` makes
and the state tree ``{layer_<i>: {moe: {router_bias}}}``. Attention is
computed in query blocks and the experts one at a time, each under
``jax.checkpoint``, so that the float32 backward of one sequence of
16,384 (32 heads x 16,384 x 16,384 scores whole would be 34 GB) fits
beside the parameters and two gradient trees. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# The pieces every token reference shares: RMSNorm over the last
# dimension, rotate-half RoPE over the whole of it, the size of a block
# of queries (OLMoE's); the cross entropy and the bias after a step
# (GLM's, whose rate has this file's key); a block of queries against
# all keys under the causal mask (Trinity's, which uses no operand hook).
from benchmark.reference.afmoe import _attend_block
from benchmark.reference.glm4_moe_lite import cross_entropy, next_bias
from benchmark.reference.olmoe import Q_BLOCK, _rms_norm, _rope

CONV, FULL = "conv", "full_attention"


def _operand(a):
    """Every matmul's operands pass through here (but the router's,
    which is float32 whatever the compute dtype, and the attention
    probabilities): the identity. ``benchmark/lfm2_routing.py`` replaces
    it to compute this reference BELOW the configuration's stated
    precision, which the check has to refuse."""
    return a


def layer_kinds(config):
    """``layer_types`` of the layers held here: the published list from
    ``first_layer`` on."""
    first = config["first_layer"]
    return config["layer_types"][first:first + config["num_hidden_layers"]]


def _conv(y, p, config):
    o = _operand
    b, c, u = jnp.split(o(y) @ o(p["w_in"]), 3, axis=-1)
    taps, s = config["conv_L_cache"], y.shape[1]
    gated = b * u
    z = jnp.zeros_like(gated)
    for j in range(taps):
        back = taps - 1 - j         # tap j reads the token ``back`` before
        shifted = jnp.pad(gated, ((0, 0), (back, 0), (0, 0)))[:, :s]
        z = z + p["w"][:, j] * shifted
    return o(c * z) @ o(p["w_out"])


def _attention(y, p, config):
    o = _operand
    eps, theta = config["norm_eps"], config["rope_theta"]
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    q = jnp.einsum("bsm,mhd->bshd", o(y), o(p["wq"]))
    k = jnp.einsum("bsm,mhd->bshd", o(y), o(p["wkv"][0]))
    v = jnp.einsum("bsm,mhd->bshd", o(y), o(p["wkv"][1]))
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), theta)
    q = o(q)
    k, v = (jnp.repeat(o(a), group, axis=2) for a in (k, v))
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    attend = jax.checkpoint(functools.partial(_attend_block, window=None))
    q_blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    ctx = jax.lax.map(lambda args: attend(args[0], k, v, args[1]),
                      (q_blocks, jnp.arange(0, s, block)))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, d)
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
    if config["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
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
    """y (T, M): this chip's part of the routed sum; also the experts
    chosen and the (T x slot) pairs each of ALL experts received."""
    gates, chosen = gates_over_all_experts(y, p["router"], bias, config,
                                           assignment)
    first, held = config["first_expert_held"], p["wi"].shape[0]
    routed = _weighted_experts(y, gates[:, first:first + held], p["wg"],
                               p["wi"], p["wo"])
    counts = jnp.sum(jax.nn.one_hot(chosen, gates.shape[-1],
                                    dtype=jnp.int32), (0, 1))
    return routed, chosen, counts


def whole_layer(y, p, bias, config):
    """The UNCUT expert layer over tokens y (T, M): every one of the
    router's experts present (``p``'s ``wg``/``wi``/``wo`` lead with
    all of them)."""
    gates, _ = gates_over_all_experts(y, p["router"], bias, config)
    return _weighted_experts(y, gates, p["wg"], p["wi"], p["wo"])


def _block(x, p, bias, assignment, *, config, kind):
    eps = config["norm_eps"]
    y = _rms_norm(x, p["ln1"]["scale"], eps)
    x = x + (_conv(y, p["conv"], config) if kind == CONV
             else _attention(y, p["attn"], config))
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
    for i, kind in enumerate(layer_kinds(config)):
        name = "layer_%d" % i
        dense = i < config["num_dense_layers"]
        block = jax.checkpoint(functools.partial(_block, config=config,
                                                 kind=kind))
        x, chosen, counts = block(
            x, p[name], None if dense else state[name]["moe"]["router_bias"],
            None if assignments is None else assignments[i])
        if not dense:
            aux["chosen"].append(chosen)
            aux["tokens_per_expert"].append(counts)
    x = _rms_norm(x, p["ln_f"]["scale"], config["norm_eps"])
    return (_operand(x) @ _operand(p["embed"]).T,
            {k: jnp.stack(v) for k, v in aux.items()})


def loss(config, params, state, tokens, assignments=None):
    """The cross entropy of ``tokens`` (B, S + 1) and the state after
    the step, like every reference."""
    logits, aux = forward(config, params, state, tokens[:, :-1], assignments)
    return (cross_entropy(logits, tokens[:, 1:]),
            next_bias(config, state, aux["tokens_per_expert"]))
