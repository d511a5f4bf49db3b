"""Phi-4-mini-flash-reasoning's decoder (huggingface
``microsoft/Phi-4-mini-flash-reasoning``, ``model_type`` ``phi4flash``:
SambaY, a decoder-hybrid-decoder with differential attention,
arXiv:2507.06607), in plain ``jax.numpy`` and float32, as ONE chip of
its deployment sees the layers it holds. The widths are the config's
keys; the Mamba sizes, the layer map and the differential form are the
family's public modeling code and papers, each listed with its origin
under ``assumed`` in ``benchmark/configs/phi-4-mini-flash-reasoning.json``.

All norms are LayerNorm (scale and bias); no positional encoding; no
bias on a linear layer. A block at published index l:

    h = x + mixer_l(ln1(x))
    y = h + (silu(h' Wg) * (h' Wi)) Wo,   h' = ln2(h)

The mixer by the layer's entry of ``layer_types``:

``mamba``: ``[x, z] = u W_in`` (E channels each); ``x' = silu(taps(x) +
b)``, a depthwise causal convolution of ``mamba_d_conv`` taps;
``[r, B, C] = x' W_x``; ``Delta = softplus(r W_dt + b_dt)``; ``A =
-exp(A_log)``; ``h_t = exp(Delta_t A) h_(t-1) + (Delta_t x'_t) outer
B_t`` from ``h_0 = 0``; ``y_t = h_t C_t + D x'_t``; ``(y * silu(z))
W_out``. The layer ``shared_scan_layer`` also PUBLISHES y.

``full_attention`` / ``sliding_attention``, differential: ``q = u Wq``
(H heads of D), ``k, v = u Wk, u Wv`` (H_kv heads); consecutive heads
are pairs, differential head i reads key/value pair ``i // (H / H_kv)``;
``a_j = softmax(q_j k_j^T / sqrt(D) + mask)``; ``o = a_1 [v_1 v_2] -
lambda a_2 [v_1 v_2]`` (2 D wide), ``lambda = exp(lq1 . lk1) - exp(lq2 .
lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o =
RMSNorm_2D(o) (1 - lambda_init)``; the heads concatenated through
``Wo``. The mask is causal, and in a sliding layer the
``sliding_window`` keys up to the query. The layer ``shared_kv_layer``
also PUBLISHES its k and v.

``memory_unit``: ``(M * silu(u W_1)) W_2``, M the published y.
``cross_attention``: ``q = u Wq`` alone, over the published k and v,
causal; the same differential form with vectors, norm and Wo of its own.

The output head is the embedding; the loss is the mean next-token cross
entropy over the vocabulary held here.

No kernel, no flax: the convolution is shifted multiplies, K and V are
repeated to the query heads by ``jnp.repeat``, the scan is a loop over
positions. It reads the parameter tree the program's
``models.Transformer`` makes. Attention is computed in query blocks and
the scan in blocks of positions, each under ``jax.checkpoint``, so that
the float32 backward of one sequence of 8,192 fits beside the
parameters and two gradient trees. Call it under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# A block of queries against all keys under the causal mask and a
# window (Trinity's reference; it uses no operand hook), the cross
# entropy (GLM's), LayerNorm with scale and bias (GPT-2's) and the size
# of a block of queries (OLMoE's).
from benchmark.reference.afmoe import _attend_block
from benchmark.reference.glm4_moe_lite import cross_entropy
from benchmark.reference.gpt2 import _layer_norm
from benchmark.reference.olmoe import Q_BLOCK

MAMBA, MEMORY_UNIT = "mamba", "memory_unit"
FULL, SLIDING = "full_attention", "sliding_attention"
CROSS = "cross_attention"
SCAN_BLOCK = 256     # positions a checkpointed block of the scan's loop


def _operand(a):
    """Every matmul's operands pass through here (but the attention
    probabilities): the identity. ``benchmark/phi4flash_probe.py``
    replaces it to compute this reference BELOW the configuration's
    stated precision, which the check has to refuse."""
    return a


def layer_kinds(config):
    """``layer_types`` of the layers held here, ``layers_kept``."""
    return [config["layer_types"][l] for l in config["layers_kept"]]


def lambda_init(layer):
    """Of the PUBLISHED layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _taps(x, w):
    """``sum_j w[:, j] x_(t - L + 1 + j)`` per channel, zeros before
    position 0."""
    taps, s = w.shape[1], x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        out = out + w[:, j] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
    return out


def scan(x, delta, a, b, c, d):
    """The selective scan by a loop over positions: x, delta (B, T, E),
    a (E, N), b, c (B, T, N), d (E,)."""
    bsz, t, e = x.shape
    block = max(n for n in range(1, SCAN_BLOCK + 1) if t % n == 0)

    def step(h, row):
        x_t, dt, b_t, c_t = row
        h = jnp.exp(dt[..., None] * a) * h \
            + (dt * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("ben,bn->be", h, c_t) + d * x_t

    @jax.checkpoint
    def of_block(h, rows):
        return jax.lax.scan(step, h, rows)

    rows = tuple(jnp.moveaxis(v, 1, 0).reshape((t // block, block)
                                               + v.shape[:1] + v.shape[2:])
                 for v in (x, delta, b, c))
    _, y = jax.lax.scan(of_block, jnp.zeros((bsz, e, a.shape[1]), x.dtype),
                        rows)
    return jnp.moveaxis(y.reshape(t, bsz, e), 0, 1)


def _mamba(u, p, config):
    """(the branch's output, the scan's output y)."""
    o = _operand
    n, rank = config["mamba_d_state"], config["mamba_dt_rank"]
    x, z = jnp.split(o(u) @ o(p["w_in"]), 2, axis=-1)
    x = jax.nn.silu(_taps(x, p["w"]) + p["b"])
    r, b, c = jnp.split(o(x) @ o(p["w_x"]), (rank, rank + n), axis=-1)
    delta = jax.nn.softplus(o(r) @ o(p["w_dt"]) + p["b_dt"])
    y = scan(x, delta, -jnp.exp(p["a_log"]), b, c, p["d"])
    return o(y * jax.nn.silu(z)) @ o(p["w_out"]), y


def _differential(q, k, v, p, layer, window):
    """q (B, S, H, D), k, v (B, S, H_kv, D) -> (B, S, H D)."""
    o = _operand
    b, s, h, d = q.shape
    group = h // k.shape[2]
    q1, q2 = o(q[:, :, 0::2]), o(q[:, :, 1::2])
    k1, k2 = (jnp.repeat(o(k[:, :, i::2]), group, axis=2) for i in (0, 1))
    both = jnp.repeat(o(jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], -1)),
                      group, axis=2)                       # (B, S, H/2, 2D)
    # The layer's six small vectors, one (6, D) leaf of the program's.
    lq1, lk1, lq2, lk2 = p["diff"][:4]
    lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
           + lambda_init(layer))
    block = min(Q_BLOCK, s)
    attend = jax.checkpoint(functools.partial(_attend_block, window=window))

    def of_block(args):
        qa, qb, start = args
        return attend(qa, k1, both, start) - lam * attend(qb, k2, both, start)

    def blocks(t):
        return t.reshape(b, s // block, block, h // 2, d).swapaxes(0, 1)

    out = jax.lax.map(of_block, (blocks(q1), blocks(q2),
                                 jnp.arange(0, s, block)))
    out = out.swapaxes(0, 1).reshape(b, s, h // 2, 2 * d)
    out = out * jax.lax.rsqrt(jnp.mean(out * out, -1, keepdims=True) + 1e-5)
    out = out * p["diff"][4:].reshape(2 * d) * (1.0 - lambda_init(layer))
    return jnp.einsum("bshd,hdm->bsm", o(out.reshape(b, s, h, d)),
                      o(p["wo"]))


def _attention(u, p, config, layer, kind):
    """(the branch's output, (k, v))."""
    o = _operand
    q = jnp.einsum("bsm,mhd->bshd", o(u), o(p["wq"]))
    k = jnp.einsum("bsm,mhd->bshd", o(u), o(p["wkv"][0]))
    v = jnp.einsum("bsm,mhd->bshd", o(u), o(p["wkv"][1]))
    window = config["sliding_window"] if kind == SLIDING else None
    return _differential(q, k, v, p, layer, window), (k, v)


def _cross_attention(u, p, kv, layer):
    q = jnp.einsum("bsm,mhd->bshd", _operand(u), _operand(p["wq"]))
    return _differential(q, kv[0], kv[1], p, layer, None)


def _memory_unit(u, p, memory):
    o = _operand
    return o(memory * jax.nn.silu(o(u) @ o(p["w_1"]))) @ o(p["w_2"])


def _swiglu(y, p):
    o = _operand
    return o(jax.nn.silu(o(y) @ o(p["wg"])) * (o(y) @ o(p["wi"]))) \
        @ o(p["wo"])


def block(x, p, reads, *, config, kind, layer):
    """(the block's output, what its mixer publishes or None).
    ``reads``: the published array a reading kind is handed."""
    eps = config["layer_norm_eps"]
    u = _layer_norm(x, p["ln1"], eps)
    published = None
    if kind == MAMBA:
        branch, published = _mamba(u, p["mamba"], config)
    elif kind == MEMORY_UNIT:
        branch = _memory_unit(u, p["gmu"], reads)
    elif kind == CROSS:
        branch = _cross_attention(u, p["attn"], reads, layer)
    else:
        branch, published = _attention(u, p["attn"], config, layer, kind)
    x = x + branch
    return x + _swiglu(_layer_norm(x, p["ln2"], eps), p["mlp"]), published


def forward(config, params, state, inputs):
    """Logits (B, S, vocab) of ``inputs`` (B, S)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    x = p["embed"][inputs]
    kept = config["layers_kept"]
    published = {}
    for i, (kind, layer) in enumerate(zip(layer_kinds(config), kept)):
        reads = {MEMORY_UNIT: published.get(config["shared_scan_layer"]),
                 CROSS: published.get(config["shared_kv_layer"])}.get(kind)
        x, published[layer] = jax.checkpoint(functools.partial(
            block, config=config, kind=kind, layer=layer))(
            x, p["layer_%d" % i], reads)
    x = _layer_norm(x, p["ln_f"], config["layer_norm_eps"])
    return _operand(x) @ _operand(p["embed"]).T


def loss(config, params, state, tokens):
    """The cross entropy of ``tokens`` (B, S + 1) and the state (the
    decoder has none), like every reference."""
    return (cross_entropy(forward(config, params, state, tokens[:, :-1]),
                          tokens[:, 1:]), state)
