"""SmallThinker-21BA3B's decoder (huggingface
``PowerInfer/SmallThinker-21BA3B-Instruct``, ``model_type``
``smallthinker``; arXiv:2507.20984), in plain ``jax.numpy`` and float32,
as ONE chip's share of an expert-parallel group sees it. The widths, the
two layouts, the window and the router's settings are the config's keys;
what is recalled from the family's public ``modeling_smallthinker.py``
is listed under ``assumed`` in
``benchmark/configs/smallthinker-21b-a3b.json``.

All norms are RMSNorm, scale only; the embedding's output has no
multiplier. A block has TWO norms and every block is an expert block:

    n = ln1(x)
    plan = route(n)                    # BEFORE the attention
    u = x + attn(n)
    y = u + experts(ln2(u), plan)

The router reads the block's normed INPUT (``router_tap`` ``mixer``:
"router placed before attention"), not what the experts read: ``r = n
Wr`` in float32 over ALL ``experts_routed_over`` experts, the choice
the ``moe_num_active_primary_experts`` largest of ``r``, the gates
``softmax`` over the CHOSEN logits (which is the softmax over all 64
renormalised over the chosen: the same number). No bias, no auxiliary
loss. With ``router_tap`` ``ffn`` the router reads ``ln2(u)`` as every
other family's does; one key says which, for program and reference.

Attention, per token and without biases or head norms: ``q = n Wq``
(``H`` heads of ``head_dim``), ``k = n Wk``, ``v = n Wv`` (``H_kv``
heads). Rotary positions (rotate-half, theta ``rope_theta``) on q and k
IN SLIDING LAYERS ONLY (``rope_layout`` 1): a full layer
(``sliding_window_layout`` 0) carries no positions at all. Query i sees
the keys j with ``i - sliding_window_size < j <= i`` in a sliding layer,
``j <= i`` in a full one; query head h reads key/value head ``h // (H //
H_kv)`` (groups of SEVEN as published). ``softmax(q.k / sqrt(head_dim))
v``, the heads concatenated through ``Wo``.

The expert layer's output is the sum over the chosen experts THAT ARE
HELD HERE (``first_expert_held`` onward, ``moe_num_primary_experts`` of
them) of ``g_e (relu(y Wgate_e) * (y Wup_e)) Wdown_e``: a ReLU-gated
feed-forward (ReGLU) of ``moe_ffn_hidden_size``, no shared expert; what
the absent experts would have added is left out, and the gates keep
their normalisation over all chosen wherever they live. The loss is the
mean next-token cross entropy over the vocabulary held here.

No kernel, no flax, no sort, no gather of rows, nothing imported from
``horovod_tpu``: K and V are repeated to the query heads by
``jnp.repeat``, the window is an explicit mask, and EVERY token goes
through EVERY held expert, weighted by a (T, held) matrix that is the
gate where the expert was chosen and zero elsewhere (the held share is
a slice of the expert ids). ``whole_layer`` is the uncut layer (all
experts), which the test of the shares adds up to.

It reads the parameter tree the program's ``models.Transformer`` makes;
the step carries no state. Attention is computed in query blocks and
the experts one at a time, each under ``jax.checkpoint``, and so is each
block, so that the float32 backward of one sequence of 8192 (28 heads x
8192 x 8192 scores whole would be 7.5 GB) fits beside the parameters
and two gradient trees. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 512
SLIDING, FULL = "sliding_attention", "full_attention"


def _operand(a):
    """Every matmul's operands pass through here (but the router's,
    which is float32 whatever the compute dtype, and the attention
    probabilities): the identity. ``benchmark/smallthinker_routing.py``
    replaces it to compute this reference BELOW the configuration's
    stated precision, which the check has to refuse."""
    return a


def _held(config, per_layer):
    first = config["first_layer"]
    return per_layer[first:first + config["num_hidden_layers"]]


def layer_kinds(config):
    """The kinds of the layers held here, from ``first_layer`` on, as
    ``sliding_window_layout`` gives them (1: a window layer)."""
    return [SLIDING if flag else FULL
            for flag in _held(config, config["sliding_window_layout"])]


def rope_flags(config):
    """Whether each layer held here rotates q and k (``rope_layout``)."""
    return [bool(flag) for flag in _held(config, config["rope_layout"])]


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, D) rotated by its position: rotate-half, HF's
    ``apply_rotary_pos_emb``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


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


def _attention(n, p, config, sliding, rotated):
    o = _operand
    group = (config["num_attention_heads"]
             // config["num_key_value_heads"])
    q = jnp.einsum("bsm,mhd->bshd", o(n), o(p["wq"]))
    k = jnp.einsum("bsm,mhd->bshd", o(n), o(p["wkv"][0]))
    v = jnp.einsum("bsm,mhd->bshd", o(n), o(p["wkv"][1]))
    if rotated:
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    q = o(q)
    k, v = (jnp.repeat(o(a), group, axis=2) for a in (k, v))
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    attend = jax.checkpoint(functools.partial(
        _attend_block,
        window=config["sliding_window_size"] if sliding else None))
    q_blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    ctx = jax.lax.map(lambda args: attend(args[0], k, v, args[1]),
                      (q_blocks, jnp.arange(0, s, block)))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, d)
    return jnp.einsum("bshd,hdm->bsm", o(ctx), o(p["wo"]))


def _reglu(y, wg, wi, wo):
    o = _operand
    gate = o(y) @ o(wg)
    return o(jnp.where(gate > 0, gate, 0.0) * (o(y) @ o(wi))) @ o(wo)


def gates_over_all_experts(n, router, config, assignment=None):
    """((T, E) gates: zero where an expert was not chosen; the experts
    chosen (T, k)) of the router's input n (T, M): the softmax over the
    CHOSEN logits."""
    logits = n @ router
    chosen = assignment
    if chosen is None:
        chosen = jax.lax.top_k(
            logits, config["moe_num_active_primary_experts"])[1]
    picked = jnp.sum(jax.nn.one_hot(chosen, logits.shape[-1],
                                    dtype=logits.dtype), 1)
    weights = jnp.exp(logits - jax.lax.stop_gradient(
        jnp.max(logits, -1, keepdims=True))) * picked
    return weights / jnp.sum(weights, -1, keepdims=True), chosen


def _weighted_experts(y, weight, wg, wi, wo):
    """sum_e weight[:, e] * E_e(y), the experts one at a time."""
    def add_expert(out, expert):
        wg_e, wi_e, wo_e, w = expert
        return out + w[:, None] * jax.checkpoint(_reglu)(
            y, wg_e, wi_e, wo_e), None

    return jax.lax.scan(add_expert, jnp.zeros_like(y),
                        (wg, wi, wo, weight.T))[0]


def _experts(y, gates, p, config):
    """y (T, M): this chip's part of the routed sum under ``gates``
    (T, E): the held share is a slice of the expert ids."""
    first, held = config["first_expert_held"], p["wi"].shape[0]
    return _weighted_experts(y, gates[:, first:first + held], p["wg"],
                             p["wi"], p["wo"])


def whole_layer(y, n, p, config):
    """The UNCUT expert layer over tokens y (T, M) routed by n (T, M):
    every one of the router's experts present (``p``'s ``wg`` / ``wi``
    / ``wo`` lead with all of them)."""
    gates, _ = gates_over_all_experts(n, p["router"], config)
    return _weighted_experts(y, gates, p["wg"], p["wi"], p["wo"])


def _block(x, p, assignment, *, config, sliding, rotated):
    eps = config["rms_norm_eps"]
    b, s, m = x.shape
    n = _rms_norm(x, p["ln1"]["scale"], eps)
    u = x + _attention(n, p["attn"], config, sliding, rotated)
    y = _rms_norm(u, p["ln2"]["scale"], eps)
    tapped = {"mixer": n, "ffn": y}[config["router_tap"]]
    gates, chosen = gates_over_all_experts(
        tapped.reshape(b * s, m), p["moe"]["router"], config, assignment)
    out = _experts(y.reshape(b * s, m), gates, p["moe"], config)
    counts = jnp.sum(jax.nn.one_hot(chosen, gates.shape[-1],
                                    dtype=jnp.int32), (0, 1))
    return u + out.reshape(b, s, m), chosen, counts


def forward(config, params, state, inputs, assignments=None):
    """Logits (B, S, vocab) of ``inputs`` (B, S), and per layer the
    experts chosen ((T, k) indices) and the pairs each expert received.
    ``assignments`` (one entry a layer) forces the choice. ``state`` is
    empty: a softmax router carries none."""
    del state
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["embed"][inputs]
    aux = {"chosen": [], "tokens_per_expert": []}
    for i, (kind, rotated) in enumerate(zip(layer_kinds(config),
                                            rope_flags(config))):
        block = jax.checkpoint(functools.partial(
            _block, config=config, sliding=kind == SLIDING,
            rotated=rotated))
        x, chosen, counts = block(
            x, p["layer_%d" % i],
            None if assignments is None else assignments[i])
        aux["chosen"].append(chosen)
        aux["tokens_per_expert"].append(counts)
    x = _rms_norm(x, p["ln_f"]["scale"], config["rms_norm_eps"])
    return (_operand(x) @ _operand(p["lm_head"]).T,
            {k: jnp.stack(v) for k, v in aux.items()})


def cross_entropy(logits, targets):
    """Mean next-token cross entropy, by log-sum-exp."""
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def loss(config, params, state, tokens, assignments=None):
    """The cross entropy of ``tokens`` (B, S + 1) and the (empty) state
    after the step, like every reference."""
    logits, _ = forward(config, params, state, tokens[:, :-1], assignments)
    return cross_entropy(logits, tokens[:, 1:]), state
