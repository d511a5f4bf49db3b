"""Keye-VL-2.0-30B-A3B's LANGUAGE MODEL (huggingface
``Kwai-Keye/Keye-VL-2.0-30B-A3B``, ``model_type`` ``KeyeVL2``), text
tokens only, in plain ``jax.numpy`` and float32, as ONE chip's share of
an expert-parallel group sees it. The widths, the indexer's sizes and
``topk`` are the config's keys; the block's equations are the public
``qwen3_moe`` modeling code's, which every other key of the config
equals at 30B-A3B sizes; the selection's are DeepSeek-V3.2-Exp's
(technical report, section 2.1, equations 1 and 2, and the public
inference code's ``Indexer``). Each is listed with its origin under
``assumed`` in ``benchmark/configs/keye-vl-2.0-30b-a3b.json``.

All block norms are RMSNorm, scale only, eps ``rms_norm_eps``; no
projection has a bias; the head is untied. Every block alike:

    h = x + attn(ln1(x));  y = h + moe(ln2(h))

Attention, with ``u = ln1(x)``: ``q = u Wq`` (``H`` heads of
``head_dim``), ``k = u Wk``, ``v = u Wv`` (``H_kv`` heads); RMSNorm over
each head's ``head_dim`` on q and on k; rotary positions (rotate-half,
all of ``head_dim``, ``rope_theta``) on q and k. A TEXT token's three
position components (``rope_scaling.mrope_section``) are one index, and
the sectioned rotation is then exactly this one (``sectioned_rope``
below states the general form; a test holds the equality).

The indexer (``sa_config``; no gradient reaches it): ``qI = u W_qI``
(``indexer_num_heads`` of ``indexer_head_dim``), ``kI = LayerNorm(u
W_kI)`` (one head; scale and bias), ``w = u W_w / sqrt(heads x dim)``,
the same rotary positions on all of qI and kI, and for a key s at or
before its query t

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]).

``tau_t`` is the ``topk``-th largest of ``{I[t, s]: s <= t}`` (``-inf``
while t + 1 <= topk), by ``jnp.sort``; query t attends to ``S_t = {s <=
t: I[t, s] >= tau_t}``, ties included:

    out[t, h] = sum_{s in S_t} softmax_s(q[t, h] . k[s, g(h)] / sqrt(d))
                v[s, g(h)],   g(h) = h // (H // H_kv),

the heads concatenated through ``Wo``. DEPARTURES, each also in the
configuration file: the public ``Indexer``'s Hadamard rotation and FP8
cast (an inference-time quantisation) are left out; ties at ``tau`` are
all kept; ``q_chunk_size`` / ``kv_chunk_size`` are read as the tiling
in which scores and the running top-k are evaluated, with no effect on
the mathematics; the loss below has no term that teaches the indexer
(DeepSeek's KL term), so its four leaves receive exactly zero.

The expert layer, in float32: ``p = softmax(y Wr)`` over ALL
``experts_routed_over`` experts; the ``num_experts_per_tok`` largest;
the gates are ``p`` of the chosen divided by their sum
(``norm_topk_prob``). The layer's output is the sum over the chosen
experts THAT ARE HELD HERE (``first_expert_held`` onward,
``num_experts`` of them) of ``g_j E_j(y)``, each ``E`` a SwiGLU of
``moe_intermediate_size``; no shared expert; what the absent experts
would have added is left out. The loss is the mean next-token cross
entropy over the vocabulary held here plus ``router_aux_loss_coef`` x
the load-balancing term (``E sum_e f_e P_e`` over all experts, averaged
over the layers), as OLMoE's.

No kernel, no flax, no prefix, no recomputation policy, no bisection:
K and V are repeated to the query heads, the selection is an explicit
mask, and EVERY token goes through EVERY held expert, weighted by a (T,
held) matrix that is the gate where the expert was chosen and zero
elsewhere. ``whole_layer`` is the uncut expert layer, which the test of
the shares adds up to. Attention and the indexer are computed in query
blocks and the experts one at a time, each under ``jax.checkpoint``, so
that the float32 backward of one sequence of 8192 fits beside the
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
from benchmark.reference.olmoe import Q_BLOCK, _rms_norm, _rope, _rotate_half


def _operand(a):
    """Every matmul's operands pass through here (but the router's and
    the indexer's, which choose, and the attention probabilities): the
    identity. ``benchmark/keye_routing.py`` replaces it to compute this
    reference BELOW the configuration's stated precision, which the
    check has to refuse."""
    return a


def sectioned_rope(x, positions, theta, sections):
    """The multimodal rotation ``rope_scaling.mrope_section`` describes
    (the public ``qwen2_vl`` form): x (B, S, H, D), ``positions`` (3, B,
    S) a token's temporal, height and width index, ``sections`` pairs of
    the D / 2 for each component in turn. The angles of pair i come
    from the component whose section holds i. With three equal
    components this is ``_rope``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # 3,B,S,D/2
    owner = jnp.repeat(jnp.arange(len(sections)), jnp.array(sections),
                       total_repeat_length=d // 2)
    picked = sum(jnp.where(owner == c, freqs[c], 0.0)
                 for c in range(len(sections)))
    emb = jnp.concatenate([picked, picked], -1)[:, :, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def index_scores(u, p, config):
    """``I`` (B, S, S) of the block's normed input u (B, S, M): float32,
    ``-inf`` where the key comes after the query. By blocks of queries:
    (block, heads, S) products exist at once, never (heads, S, S)."""
    sa = config["sa_config"]
    theta, eps = config["rope_theta"], config["rms_norm_eps"]
    q_i = _rope(jnp.einsum("bsm,mjd->bsjd", u, p["index_wq"]), theta)
    k_i = _layer_norm(u @ p["index_wk"], p["index_k_norm"]["scale"],
                      p["index_k_norm"]["bias"], eps)
    k_i = _rope(k_i[:, :, None, :], theta)[:, :, 0]
    w = (u @ p["index_ww"]) * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]) ** -0.5
    b, s, j, d = q_i.shape
    block = min(Q_BLOCK, s)

    def of_block(args):
        q_b, w_b, start = args
        dots = jnp.einsum("bqjd,bsd->bqjs", q_b, k_i)
        scores = jnp.sum(w_b[..., None] * jax.nn.relu(dots), 2)
        visible = (start + jnp.arange(block))[:, None] \
            >= jnp.arange(s)[None, :]
        return jnp.where(visible[None], scores, -jnp.inf)

    scores = jax.lax.map(of_block, (
        q_i.reshape(b, s // block, block, j, d).swapaxes(0, 1),
        w.reshape(b, s // block, block, j).swapaxes(0, 1),
        jnp.arange(0, s, block)))
    return scores.swapaxes(0, 1).reshape(b, s, s)


def selection(scores, topk):
    """(B, S, S) bool: ``S_t`` of each query, from ``index_scores``'s
    result. A row of S entries, the future ones ``-inf``, sorted
    ascending: its ``topk``-th largest stands at S - topk, and is
    ``-inf`` while the query has no more than ``topk`` keys."""
    s = scores.shape[-1]
    if topk >= s:
        return scores > -jnp.inf
    tau = jnp.sort(scores, -1)[..., s - topk]
    return (scores >= tau[..., None]) & (scores > -jnp.inf)


def _attend_block(q, k, v, keep, q_start):
    """Queries q (B, Tq, H, D) at positions q_start.. against all keys
    k, v (B, S, H, D); ``keep`` (B, Tq, S) their selection."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    q_pos = (q_start + jnp.arange(q.shape[1]))[:, None]
    visible = (jnp.arange(k.shape[1])[None, :] <= q_pos)[None] & keep
    scores = jnp.where(visible[:, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(u, p, config, select=None):
    """(the attention branch's output, the selection it ran under)."""
    o = _operand
    eps, heads = config["rms_norm_eps"], config["num_attention_heads"]
    group = heads // config["num_key_value_heads"]
    if select is None:
        select = selection(
            index_scores(jax.lax.stop_gradient(u), p, config),
            config["sa_config"]["topk"])
    q = jnp.einsum("bsm,mhd->bshd", o(u), o(p["wq"]))
    k = jnp.einsum("bsm,mhd->bshd", o(u), o(p["wkv"][0]))
    v = jnp.einsum("bsm,mhd->bshd", o(u), o(p["wkv"][1]))
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), config["rope_theta"])
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), config["rope_theta"])
    q = o(q)
    k, v = (jnp.repeat(o(a), group, axis=2) for a in (k, v))
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    ctx = jax.lax.map(
        lambda args: jax.checkpoint(_attend_block)(args[0], k, v, args[1],
                                                   args[2]),
        (q.reshape(b, s // block, block, h, d).swapaxes(0, 1),
         select.reshape(b, s // block, block, s).swapaxes(0, 1),
         jnp.arange(0, s, block)))
    ctx = ctx.swapaxes(0, 1).reshape(b, s, h, d)
    return jnp.einsum("bshd,hdm->bsm", o(ctx), o(p["wo"])), select


def _swiglu(y, wg, wi, wo):
    o = _operand
    return o(jax.nn.silu(o(y) @ o(wg)) * (o(y) @ o(wi))) @ o(wo)


def gates_over_all_experts(y, router, config, assignment=None):
    """((T, E) gates: zero where an expert was not chosen; the experts
    chosen (T, k); the router's probabilities (T, E)) of tokens y."""
    probs = jax.nn.softmax(y @ router, -1)
    chosen = assignment
    if chosen is None:
        chosen = jax.lax.top_k(probs, config["num_experts_per_tok"])[1]
    picked = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1],
                                    dtype=probs.dtype), 1)
    gates = probs * picked
    if config["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return gates, chosen, probs


def _weighted_experts(y, weight, wg, wi, wo):
    """sum_e weight[:, e] * E_e(y), the experts one at a time."""
    def add_expert(out, expert):
        wg_e, wi_e, wo_e, w = expert
        return out + w[:, None] * jax.checkpoint(_swiglu)(
            y, wg_e, wi_e, wo_e), None

    return jax.lax.scan(add_expert, jnp.zeros_like(y),
                        (wg, wi, wo, weight.T))[0]


def _experts(y, p, config, assignment):
    """y (T, M): this chip's part of the routed sum; the experts chosen;
    the load-balancing term over ALL experts."""
    gates, chosen, probs = gates_over_all_experts(y, p["router"], config,
                                                  assignment)
    first, held = config["first_expert_held"], p["wi"].shape[0]
    routed = _weighted_experts(y, gates[:, first:first + held], p["wg"],
                               p["wi"], p["wo"])
    e, k = probs.shape[-1], chosen.shape[-1]
    share = jax.lax.stop_gradient(jnp.sum(jax.nn.one_hot(
        chosen, e, dtype=probs.dtype), (0, 1)) / (y.shape[0] * k))
    return routed, chosen, e * jnp.sum(share * jnp.mean(probs, 0))


def whole_layer(y, p, config):
    """The UNCUT expert layer over tokens y (T, M): every one of the
    router's experts present (``p``'s ``wg``/``wi``/``wo`` lead with
    all of them)."""
    gates, _, _ = gates_over_all_experts(y, p["router"], config)
    return _weighted_experts(y, gates, p["wg"], p["wi"], p["wo"])


def _block(x, p, assignment, select, *, config):
    eps = config["rms_norm_eps"]
    attn, select = _attention(_rms_norm(x, p["ln1"]["scale"], eps),
                              p["attn"], config, select)
    x = x + attn
    y = _rms_norm(x, p["ln2"]["scale"], eps)
    b, s, m = y.shape
    out, chosen, load_balance = _experts(y.reshape(b * s, m), p["moe"],
                                         config, assignment)
    return x + out.reshape(b, s, m), chosen, load_balance, select


def forward(config, params, inputs, assignments=None, selections=None):
    """Logits (B, S, vocab) of ``inputs`` (B, S), and per layer the
    experts chosen ((T, k) indices), the load-balancing term and the
    selection ((B, S, S) bool). ``assignments`` and ``selections`` (one
    entry a layer) force the routers' and the indexers' choices."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["embed"][inputs]
    aux = {"chosen": [], "load_balance": [], "select": []}
    block = jax.checkpoint(functools.partial(_block, config=config))
    for i in range(config["num_hidden_layers"]):
        x, chosen, load_balance, select = block(
            x, p["layer_%d" % i],
            None if assignments is None else assignments[i],
            None if selections is None else selections[i])
        aux["chosen"].append(chosen)
        aux["load_balance"].append(load_balance)
        aux["select"].append(select)
    x = _rms_norm(x, p["ln_f"]["scale"], config["rms_norm_eps"])
    return (_operand(x) @ _operand(p["lm_head"]).T,
            {k: jnp.stack(v) for k, v in aux.items()})


def loss(config, params, state, tokens, assignments=None, selections=None):
    """The cross entropy of ``tokens`` (B, S + 1) plus the weighted
    load-balancing term; and, where every reference returns the state
    after the step (this decoder has none), what the layers chose:
    ``chosen`` (L, T, k) and ``select`` (L, B, S, S)."""
    logits, aux = forward(config, params, tokens[:, :-1], assignments,
                          selections)
    return (cross_entropy(logits, tokens[:, 1:])
            + config["router_aux_loss_coef"] * jnp.mean(aux["load_balance"]),
            {"chosen": aux["chosen"], "select": aux["select"]})
