"""Trinity-Mini's decoder (huggingface ``arcee-ai/Trinity-Mini``,
``model_type`` ``afmoe``), in plain ``jax.numpy`` and float32, as ONE
chip's share of an expert-parallel group sees it. The widths, the layer
pattern, the window and the router's settings are the config's keys;
the equations are those of the family's public modeling code
(``transformers`` ``modeling_afmoe.py``), each listed with that origin
under ``assumed`` in ``benchmark/configs/trinity-mini.json``.

All norms are RMSNorm, scale only. The embedding's output is multiplied
by ``sqrt(hidden_size)`` (``mup_enabled``). A block has FOUR norms, two
of them on a branch's OUTPUT:

    x = x + post_attn_norm(attn(ln1(x)))
    x = x + post_mlp_norm(ffn(ln2(x)))

Attention, per token and without biases: ``q = y Wq`` (``H`` heads of
``head_dim``), ``k = y Wk``, ``v = y Wv`` (``H_kv`` heads), ``g = y Wg``
(as wide as q). RMSNorm over each head's ``head_dim`` on q and on k, one
scale vector each, shared by the heads. Rotary positions (rotate-half)
on q and k IN SLIDING LAYERS ONLY: a full layer carries no positions at
all. Query i sees the keys j with ``i - sliding_window < j <= i`` in a
sliding layer, ``j <= i`` in a full one; query head h reads key/value
head ``h // (H // H_kv)``. ``softmax(q.k / sqrt(head_dim)) v``, times
``sigmoid(g)`` element by element, the heads concatenated through
``Wo``.

The first ``num_dense_layers`` blocks carry a dense SwiGLU of
``intermediate_size``. The others, in float32: ``s = sigmoid(y Wr)``
over ALL ``experts_routed_over`` experts; the choice is the top
``num_experts_per_tok`` of ``s + b`` (``b`` the correction bias, state,
no gradient); the gates are ``s`` of the chosen (without ``b``), divided
by their sum + 1e-20 (``route_norm``), times ``route_scale``. The
layer's output is the sum over the chosen experts THAT ARE HELD HERE
(``first_expert_held`` onward, ``num_experts`` of them) of ``g_j
E_j(y)``, plus the shared expert's ``E_s(y)``; what the absent experts
would have added is left out. Every ``E`` is a SwiGLU of
``moe_intermediate_size``. After a step ``b_e <- b_e +
load_balance_coeff * sign(mean(c) - c_e)``, ``c`` the pairs each expert
received. The loss is the mean next-token cross entropy over the
vocabulary held here; no auxiliary loss.

No kernel, no flax, no sort, no gather of rows: K and V are repeated to
the query heads by ``jnp.repeat``, the window is an explicit mask, and
EVERY token goes through EVERY held expert, weighted by a (T, held)
matrix that is the gate where the expert was chosen and zero elsewhere.
``whole_layer`` is the uncut layer (all experts, the shared one once),
which the test of the shares adds up to.

It reads the parameter tree the program's ``models.Transformer`` makes
and the state tree ``{layer_<i>: {moe: {router_bias}}}``. Attention is
computed in query blocks and the experts one at a time, each under
``jax.checkpoint``, so that the float32 backward of one sequence of 8192
(32 heads x 8192 x 8192 scores whole would be 8.6 GB) fits beside the
parameters and two gradient trees. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# The pieces every token reference shares, as OLMoE's states them:
# RMSNorm over the last dimension, rotate-half RoPE over the whole of
# it, the size of a block of queries; and GLM's cross entropy.
from benchmark.reference.glm4_moe_lite import cross_entropy
from benchmark.reference.olmoe import Q_BLOCK, _rms_norm, _rope

SLIDING = "sliding_attention"


def _operand(a):
    """Every matmul's operands pass through here (but the router's,
    which is float32 whatever the compute dtype, and the attention
    probabilities): the identity. ``benchmark/trinity_routing.py``
    replaces it to compute this reference BELOW the configuration's
    stated precision, which the check has to refuse."""
    return a


def layer_kinds(config):
    """``layer_types`` of the layers held here: the published list from
    ``first_layer`` on."""
    first = config["first_layer"]
    return config["layer_types"][first:first + config["num_hidden_layers"]]


def _attend_block(q, k, v, q_start, window):
    """Queries q (B, Tq, H, D) at positions q_start.. against all keys
    k, v (B, S, H, D); ``window`` None: every key up to the query."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    q_pos = (q_start + jnp.arange(q.shape[1]))[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    visible = k_pos <= q_pos
    if window is not None:
        visible &= k_pos > q_pos - window
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(y, p, config, kind):
    o = _operand
    eps, heads = config["rms_norm_eps"], config["num_attention_heads"]
    group = heads // config["num_key_value_heads"]
    sliding = kind == SLIDING
    q = jnp.einsum("bsm,mhd->bshd", o(y), o(p["wq"]))
    k = jnp.einsum("bsm,mhd->bshd", o(y), o(p["wkv"][0]))
    v = jnp.einsum("bsm,mhd->bshd", o(y), o(p["wkv"][1]))
    gate = jnp.einsum("bsm,mhd->bshd", o(y), o(p["wgate"]))
    q = _rms_norm(q, p["q_norm"]["scale"], eps)     # over head_dim
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    if sliding:
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    q = o(q)
    k, v = (jnp.repeat(o(a), group, axis=2) for a in (k, v))
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    attend = jax.checkpoint(functools.partial(
        _attend_block, window=config["sliding_window"] if sliding else None))
    q_blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    ctx = jax.lax.map(lambda args: attend(args[0], k, v, args[1]),
                      (q_blocks, jnp.arange(0, s, block)))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, d) * jax.nn.sigmoid(gate)
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
    if config["route_norm"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * config["route_scale"], chosen


def _weighted_experts(y, weight, wg, wi, wo):
    """sum_e weight[:, e] * E_e(y), the experts one at a time."""
    def add_expert(out, expert):
        wg_e, wi_e, wo_e, w = expert
        return out + w[:, None] * jax.checkpoint(_swiglu)(
            y, wg_e, wi_e, wo_e), None

    return jax.lax.scan(add_expert, jnp.zeros_like(y),
                        (wg, wi, wo, weight.T))[0]


def _shared(y, p):
    return _swiglu(y, p["shared"]["wg"], p["shared"]["wi"], p["shared"]["wo"])


def _experts(y, p, bias, config, assignment):
    """y (T, M): this chip's part of the routed sum plus the shared
    expert; also the experts chosen and the (T x slot) pairs each of
    ALL experts received."""
    gates, chosen = gates_over_all_experts(y, p["router"], bias, config,
                                           assignment)
    first, held = config["first_expert_held"], p["wi"].shape[0]
    routed = _weighted_experts(y, gates[:, first:first + held], p["wg"],
                               p["wi"], p["wo"])
    counts = jnp.sum(jax.nn.one_hot(chosen, gates.shape[-1],
                                    dtype=jnp.int32), (0, 1))
    return routed + _shared(y, p), chosen, counts


def whole_layer(y, p, bias, config):
    """The UNCUT expert layer over tokens y (T, M): every one of the
    router's experts present (``p``'s ``wg``/``wi``/``wo`` lead with
    all of them), the shared expert once."""
    gates, _ = gates_over_all_experts(y, p["router"], bias, config)
    return _weighted_experts(y, gates, p["wg"], p["wi"], p["wo"]) \
        + _shared(y, p)


def _block(x, p, bias, assignment, *, config, kind):
    eps = config["rms_norm_eps"]
    attn = _attention(_rms_norm(x, p["ln1"]["scale"], eps), p["attn"],
                      config, kind)
    x = x + _rms_norm(attn, p["post_attn_norm"]["scale"], eps)
    y = _rms_norm(x, p["ln2"]["scale"], eps)
    chosen = counts = None
    if "mlp" in p:
        out = _swiglu(y, p["mlp"]["wg"], p["mlp"]["wi"], p["mlp"]["wo"])
    else:
        b, s, m = y.shape
        out, chosen, counts = _experts(y.reshape(b * s, m), p["moe"], bias,
                                       config, assignment)
        out = out.reshape(b, s, m)
    return (x + _rms_norm(out, p["post_mlp_norm"]["scale"], eps), chosen,
            counts)


def forward(config, params, state, inputs, assignments=None):
    """Logits (B, S, vocab) of ``inputs`` (B, S), and per EXPERT layer
    the experts chosen ((T, k) indices) and the pairs each expert
    received. ``assignments`` (one entry a layer; a dense layer's is
    ignored) forces the choice."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["embed"][inputs]
    if config["mup_enabled"]:
        x = x * math.sqrt(config["hidden_size"])
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
    x = _rms_norm(x, p["ln_f"]["scale"], config["rms_norm_eps"])
    return (_operand(x) @ _operand(p["lm_head"]).T,
            {k: jnp.stack(v) for k, v in aux.items()})


def next_bias(config, state, tokens_per_expert):
    """The state after one step: ``b_e + load_balance_coeff *
    sign(mean(c) - c_e)`` per expert layer, ``c`` that layer's row of
    ``tokens_per_expert``."""
    names = sorted(state, key=lambda n: int(n.rsplit("_", 1)[1]))
    out = {}
    for name, counts in zip(names, tokens_per_expert):
        load = counts.astype(jnp.float32)
        out[name] = {"moe": {"router_bias": (
            state[name]["moe"]["router_bias"]
            + config["load_balance_coeff"]
            * jnp.sign(jnp.mean(load) - load))}}
    return out


def loss(config, params, state, tokens, assignments=None):
    """The cross entropy of ``tokens`` (B, S + 1) and the state after
    the step, like every reference."""
    logits, aux = forward(config, params, state, tokens[:, :-1], assignments)
    return (cross_entropy(logits, tokens[:, 1:]),
            next_bias(config, state, aux["tokens_per_expert"]))
