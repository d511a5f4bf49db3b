"""Decoder-only transformer — the multi-axis-parallelism flagship.

The reference is a pure data-parallel framework; its model-parallel
building blocks are generic collectives (SURVEY.md §2.3). This model shows
how horovod_tpu composes those blocks TPU-first: parameters carry
partitioning metadata (Megatron-style tensor parallelism over the
``model`` axis), activations shard batch over ``data`` and optionally
sequence over ``seq`` (ring attention / Ulysses,
``horovod_tpu.parallel.sequence``), and the feed-forward may be a
mixture of experts (``horovod_tpu.parallel.moe``).

What KIND of block the decoder is made of is data, a ``BlockSpec``:
GPT-2's (LayerNorm, GELU, learned positions, tied output embedding) is
the default; OLMoE's is RMSNorm, SwiGLU experts, rotary positions,
RMSNorm on q and k, an output head of its own; GLM-4.7-Flash's is
latent attention (``LatentAttention``), leading dense blocks of their
own width, then expert blocks with a sigmoid router under a correction
bias, a shared expert, and only this chip's share of the experts held.
A ``BlockSpec`` may also give the heads a width of their own, fewer
key/value heads than query heads, a per-layer pattern of sliding-window
and full attention (with rotary positions in some kinds only), a norm
on q and k per head, a sigmoid gate on the attention output, norms on
each branch's output and a multiplier on the embedding: what the
``afmoe`` family's ``config.json`` keys say. The pattern's third kind of
layer is no attention at all: ``conv``, the ``lfm2`` family's gated
short convolution (``ShortConv``), which mixes each token with the few
before it and has neither heads nor positions. The fourth,
``sparse_attention``, is full attention over the keys each query CHOSE:
a small indexer scores every causal pair, a query keeps its
``index_topk`` best (DeepSeek sparse attention, as ``Keye-VL-2.0``'s
language model carries it), and the attention kernels take that choice
as data. The fifth is ``mamba``, a selective scan over the WHOLE
sequence (``Mamba``; the scan is two Pallas kernels of ours,
``ops/pallas_scan.py``). And two kinds READ what an earlier layer
published instead of making it (``Phi-4-mini-flash-reasoning``'s
decoder-hybrid-decoder): ``memory_unit`` gates the scan output of layer
``BlockSpec.scan_from``, ``cross_attention`` attends with its own
queries over the keys and values of layer ``BlockSpec.kv_from``. With
``BlockSpec.diff_attention`` every attention is differential: two
softmax maps over paired heads, the second subtracted.

Param layout (tensor parallel over 'model'):
- attention QKV projections shard the head dim;
- attention output projection shards the head (input) dim;
- MLP wi shards the hidden dim, wo shards the hidden (input) dim;
so each layer needs exactly one psum (after wo) per sublayer — the
standard Megatron communication pattern, inserted automatically by XLA
from the shardings.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from flax.linen import partitioning as nn_partitioning
from jax.ad_checkpoint import checkpoint_name
from horovod_tpu.jax.introspect import (
    SAVED_ATTN_GATE,
    SAVED_ATTN_OUT,
    SAVED_ATTN_PRENORM,
    SAVED_CONV_IN,
    SAVED_CONV_OUT,
    SAVED_FLASH_K,
    SAVED_FLASH_LSE,
    SAVED_FLASH_OUT,
    SAVED_FLASH_Q,
    SAVED_FLASH_SELECT,
    SAVED_FLASH_V,
    SAVED_GMU_GATE,
    SAVED_GMU_OUT,
    SAVED_MLP_GATE,
    SAVED_MLP_OUT,
    SAVED_MLP_UP,
    SAVED_MOE_OUT,
    SAVED_SSM_IN,
    SAVED_SSM_OUT,
    SAVED_SSM_PROJ,
    SAVED_SSM_STATES,
    SAVED_SSM_X,
    SAVED_SSM_Y,
    SCOPE_ATTN_GATE,
    SCOPE_CONV_GATE,
    SCOPE_DIFF_ATTN,
    SCOPE_DSA_INDEX,
    SCOPE_DSA_SELECT,
    SCOPE_EMBED,
    SCOPE_GMU,
    SCOPE_LOGITS,
    SCOPE_LOOP_EXIT,
    SCOPE_LOOP_PASS,
    SCOPE_LOOP_READOUT,
    SCOPE_MLA_LATENT,
    SCOPE_ROPE,
    SCOPE_SSM_CONV,
)
from horovod_tpu.parallel.mesh import traced_axis_size
from horovod_tpu.utils import metrics as _metrics
from horovod_tpu.utils.timeline import trace_span

logger = logging.getLogger("horovod_tpu")

param_with_axes = nn.with_partitioning

# The kinds of layer, by their token mixer, as ``config.json``
# ``layer_types`` spells them: two of attention, and the gated short
# convolution; and full attention over a learned selection of the keys,
# which is what every layer of a spec with ``index_topk`` and no
# ``layer_types`` is.
FULL_ATTENTION, SLIDING_ATTENTION = "full_attention", "sliding_attention"
CONV = "conv"
SPARSE_ATTENTION = "sparse_attention"
# A selective scan (``Mamba``), and the two kinds that read what an
# earlier layer PUBLISHED: a gate on layer ``scan_from``'s scan output,
# and attention over layer ``kv_from``'s keys and values.
MAMBA = "mamba"
MEMORY_UNIT = "memory_unit"
CROSS_ATTENTION = "cross_attention"
_KINDS = (FULL_ATTENTION, SLIDING_ATTENTION, CONV, SPARSE_ATTENTION, MAMBA,
          MEMORY_UNIT, CROSS_ATTENTION)
# What a reading kind reads, by the name ``Transformer`` keeps it under.
_READS = {MEMORY_UNIT: "scan", CROSS_ATTENTION: "kv"}

# Counted at trace time: the layers one traced model makes, by the kind
# of their token mixer (the name dates from when every mixer was an
# attention).
_M_ATTN_LAYERS = _metrics.counter(
    "hvd_attn_layers_total",
    "Layers per traced model, by the kind of their token mixer "
    "(full_attention / sliding_attention / conv / sparse_attention / "
    "mamba / memory_unit / cross_attention; counted at trace time, not "
    "per device step).",
    ("kind",))

# Counted at trace time: the arrays one traced model's layers publish to
# later layers (a scan output, a layer's keys and values), and the
# layers that read one.
_M_SHARED_ARRAYS = _metrics.counter(
    "hvd_shared_arrays_total",
    "Arrays a layer of one traced model hands to later layers "
    "(role=published: the scan output of BlockSpec.scan_from, the keys "
    "and values of BlockSpec.kv_from) and the layers that read one "
    "(role=read); counted at trace time, not per device step.",
    ("role",))

# Counted at trace time: the (query, key) pairs of one traced sparse
# layer, all causal ones and those a selection of ``index_topk`` keeps
# (ties at a row's threshold apart, which the sown ``dsa_kept`` counts).
_M_DSA_PAIRS = _metrics.counter(
    "hvd_dsa_pairs_total",
    "Query-key pairs per traced sparse_attention layer: causal = B S (S "
    "+ 1) / 2, kept = B sum_t min(t + 1, index_topk) (counted at trace "
    "time, not per device step).",
    ("kind",))

# Counted at trace time: the blocks traced under ``cfg.remat``, by what
# the recomputation keeps from forward to backward.
_M_REMAT_BLOCKS = _metrics.counter(
    "hvd_remat_blocks_total",
    "Decoder blocks traced under recomputation, by what they keep from "
    "forward to backward (counted at trace time, not per device step).",
    ("keeps",))

# Counted at trace time: the block applications of one traced looped
# model (``TransformerConfig.passes`` > 1), blocks x passes.
_M_LOOP_PASSES = _metrics.counter(
    "hvd_loop_passes_total",
    "Block applications per traced looped model: the blocks of the ONE "
    "stack times TransformerConfig.passes (counted at trace time, not per "
    "device step).")

# Set by ``record_loop_stats`` from what ``looped_loss`` returned: the
# mean share of the positions that exit after each pass.
_M_LOOP_EXIT_SHARE = _metrics.gauge(
    "hvd_loop_exit_share",
    "Mean exit probability of each pass of a looped model, from the last "
    "statistics handed to models.transformer.record_loop_stats (the shares "
    "of one step sum to one).",
    ("pass",))


def _axis_bound(axis) -> bool:
    try:
        traced_axis_size(axis)
        return True
    except NameError:
        return False


def _use_onehot_embed(cfg) -> bool:
    """Whether the vocab-sharded embedding lookup must avoid gather.

    XLA's PartitionGather CHECK-crashes partitioning a sliced-operand
    gather under manual subgroups, i.e. whenever we trace inside a
    shard_map that leaves the embed's ``model`` axis auto. So: one-hot
    iff some mesh axis is manual and the mesh has a ``model`` axis that
    is not (if ``model`` itself is manual, or the mesh has none, params
    arrive as local shards and no SPMD partitioning of the gather
    happens). ``cfg.vocab_onehot_lookup``
    forces either path (e.g. False for a pure-DP mesh with an
    unsharded embed, where the gather is safe and cheaper).
    """
    if cfg.vocab_onehot_lookup is not None:
        return cfg.vocab_onehot_lookup
    mesh = jax.sharding.get_abstract_mesh()
    return (bool(mesh.manual_axes) and "model" in mesh.axis_names
            and "model" not in mesh.manual_axes)


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """The kind of decoder block, as a public model's ``config.json``
    describes it. The default is GPT-2's."""

    norm: str = "layernorm"          # | 'rmsnorm' (scale only)
    norm_eps: float = 1e-6
    # | 'swiglu': silu(gate) * up, then down | 'reglu': relu(gate) * up
    ffn: str = "gelu"
    positions: str = "learned"       # | 'rope' (rotate-half, on q and k)
    rope_theta: float = 10000.0
    qk_norm: bool = False            # the norm on q and k, over all heads
    qk_norm_per_head: bool = False   # ... over each head's dims instead
    tied_head: bool = True           # the output projection is ``embed``
    # The heads: 0 = ``d_model // n_heads`` wide, and as many key/value
    # heads as query heads. With fewer, query head h reads key/value
    # head ``h // (n_heads // n_kv_heads)`` and the projections are
    # ``wq`` and ``wkv`` instead of ``wqkv``.
    head_dim: int = 0
    n_kv_heads: int = 0
    # One kind a layer: FULL_ATTENTION, SLIDING_ATTENTION (a query sees
    # the ``sliding_window`` keys up to itself) or CONV (no attention: a
    # gated causal convolution over ``conv_taps`` tokens, ``ShortConv``);
    # () = all full.
    layer_types: tuple = ()
    sliding_window: int = 0
    conv_taps: int = 0
    # A MAMBA layer: ``ssm_expand * d_model`` channels of ``ssm_state``
    # states each (0 = no such layer) behind ``conv_taps`` causal taps;
    # the step's rank is ``ceil(d_model / 16)``.
    ssm_state: int = 0
    ssm_expand: int = 2
    # The layers whose scan output the MEMORY_UNIT layers read, and
    # whose keys and values the CROSS_ATTENTION layers; -1 = none.
    scan_from: int = -1
    kv_from: int = -1
    # Differential attention in every attention layer: consecutive
    # heads are pairs, ``softmax(q1 k1) [v1 v2] - lambda softmax(q2 k2)
    # [v1 v2]``, a norm over the 2 D dims, times ``1 - lambda_init``;
    # ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` at the layer's index l in
    # ``layer_ids`` (the PUBLISHED index of each layer; () = its own).
    diff_attention: bool = False
    layer_ids: tuple = ()
    # A SPARSE_ATTENTION layer's indexer (0 = none, and no such layer):
    # ``index_heads`` heads of ``index_head_dim`` over ONE key head
    # score each causal pair; a query attends to its ``index_topk``
    # best keys (all of them while it has no more).
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # The kinds whose q and k carry the rotary positions; None = every
    # layer, if ``positions`` is 'rope'.
    rope_layers: Optional[tuple] = None
    attn_gate: bool = False          # attention out * sigmoid(x Wg), per dim
    post_norms: bool = False         # a norm on each branch's OUTPUT too
    embed_scale: float = 1.0         # the embedding's output times this
    # 0 = one dense feed-forward; >0 = that many experts, each token
    # through its ``experts_per_token`` most probable (parallel/moe.py).
    num_experts: int = 0
    experts_per_token: int = 1
    # 'heads': q, k, v straight from the block's input. 'latent': q and
    # k/v through low-rank latents with norms of their own, a rotary
    # part of k that all heads share (``LatentAttention``); then the
    # ranks and the three head dims are the config's keys.
    attention_kind: str = "heads"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # The first blocks carry a dense feed-forward of this width, whatever
    # the rest carry.
    first_dense_layers: int = 0
    dense_ff: int = 0
    # The expert layer's router (parallel/moe.py ``route``): 'softmax'
    # over all experts, or 'sigmoid_bias': sigmoid scores, the choice by
    # score + a correction bias that is carried state, the gates from
    # the scores alone.
    router: str = "softmax"
    # The array an expert layer's router reads: 'ffn', the rows its
    # experts read (the norm after the mixer); 'mixer', the block's
    # normed INPUT, which its mixer reads too, so that the choice of
    # experts depends on nothing the mixer makes.
    router_tap: str = "ffn"
    norm_topk: bool = False          # gates / their sum over the chosen
    routed_scale: float = 1.0        # then times this
    shared_experts: int = 0          # experts every token goes through
    # The experts whose weights live here, ``first_expert_held`` onward;
    # the router still scores all ``num_experts``. 0 = all of them.
    experts_held: int = 0
    first_expert_held: int = 0


GPT2_BLOCK = BlockSpec()


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    # 'dense' | 'flash' (fused Pallas kernel, ops/pallas_attention.py) |
    # 'ring' (ring attention over the seq axis, sequence parallelism) |
    # 'ulysses' (all_to_all head/seq re-sharding).
    attention: str = "dense"
    seq_axis: Optional[str] = None  # mesh axis for ring/ulysses attention
    # Recompute each block in the backward pass, except what a matmul
    # or the flash kernel made and the backward pass reads
    # (``_remat_block`` lists it): the recomputed forward is norms,
    # activations, rotations and adds (and, where a norm stands on q
    # and k, their two projections). Kept a layer, T tokens of
    # ``dtype``: about T x (3 H D + 2 M + 2 F) for a block of H heads of
    # D, width M and a feed-forward F wide, less with grouped key/value
    # heads, more with a gate or a norm on a branch's output; 350-440
    # MB at 8192 tokens of 2048 in bf16, 490-730 MB for a layer with a
    # dense feed-forward of 3 to 5 times the width.
    remat: bool = False
    # None = auto (one-hot lookup only under manual subgroups, see
    # _use_onehot_embed); True/False forces the lookup style.
    vocab_onehot_lookup: Optional[bool] = None
    block: BlockSpec = GPT2_BLOCK
    # A looped model (``total_ut_steps`` of the ``ouro`` family): the ONE
    # stack of ``n_layers`` blocks is applied ``passes`` times a step
    # with the same weights, ``ln_f`` after every pass, and the model
    # hands back the ``passes`` normed hidden states (``looped_loss``
    # reads them out) where a model of one pass hands back logits.
    # ``loop_norm`` says whether the next pass reads the normed state
    # (True) or ``ln_f`` stands on the readouts alone.
    passes: int = 1
    loop_norm: bool = True


def _norm(cfg, name):
    """The block's norm as a flax module: statistics in float32, the
    result in the compute dtype."""
    kind = {"layernorm": nn.LayerNorm, "rmsnorm": nn.RMSNorm}[cfg.block.norm]
    return kind(epsilon=cfg.block.norm_eps, dtype=cfg.dtype, name=name)


def _first_position(cfg, s_local):
    """The position of this shard's first token: 0, or under a bound
    ``seq_axis`` (shard_map) this shard's offset into the sequence."""
    if cfg.seq_axis is not None and _axis_bound(cfg.seq_axis):
        return jax.lax.axis_index(cfg.seq_axis) * s_local
    return 0


def rope(x, first_position, theta):
    """Rotary position embedding of x (B, S, H, D), the rotate-half
    convention: with the head's two halves (a, b) and the angle
    ``position * theta ** (-2i / D)`` of pair i, (a cos - b sin,
    b cos + a sin). Computed in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    positions = first_position + jnp.arange(x.shape[1])
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def _dense_causal_attention(q, k, v, dtype, window=None, select=None):
    # q: (B, S, H, D); k, v: (B, S, H_kv, D), H_kv a divisor of H;
    # select: (B, S, S), True where the query keeps the key.
    d = q.shape[-1]
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    s = scores.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        causal &= ~jnp.tril(jnp.ones((s, s), bool), -window)
    causal = causal[None, None]
    if select is not None:
        causal = causal & select[:, None]
    scores = jnp.where(causal, scores, jnp.asarray(-1e9, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attend(cfg, q, k, v, window=None, select=None):
    """Causal attention of q (B, S, H, D) over k, v (B, S, H_kv, D) by
    ``cfg.attention``; under ``window`` a query sees that many keys up
    to itself, under ``select`` those it keeps: (B, S, S) bool, or for
    'flash' the planes already packed."""
    if cfg.attention == "dense":
        return _dense_causal_attention(q, k, v, cfg.dtype, window, select)
    if cfg.attention == "flash":
        from horovod_tpu.ops.pallas_attention import (
            Selection,
            flash_attention,
            pack_selection,
        )

        if select is not None:
            if not isinstance(select, Selection):
                with jax.named_scope(SCOPE_DSA_SELECT):
                    select = pack_selection(select)
            return flash_attention(q, k, v, causal=True,
                                   select=select).astype(cfg.dtype)
        return flash_attention(q, k, v, causal=True,
                               window=window).astype(cfg.dtype)
    if window is not None or select is not None \
            or k.shape[2] != q.shape[2]:
        raise ValueError(
            "attention=%r has no sliding window and no grouped key/value "
            "heads, nor a learned selection (window %r, %d query heads "
            "over %d): use 'flash' or 'dense'"
            % (cfg.attention, window, q.shape[2], k.shape[2]))
    if cfg.attention == "ring":
        from horovod_tpu.parallel.sequence import ring_attention

        return ring_attention(q, k, v, axis=cfg.seq_axis, causal=True)
    if cfg.attention == "ulysses":
        from horovod_tpu.parallel.sequence import ulysses_attention

        return ulysses_attention(q, k, v, axis=cfg.seq_axis, causal=True)
    raise ValueError("Unknown attention impl %r" % (cfg.attention,))


def _differential_output(first, second, lam, lambda_init, scale):
    """``RMSNorm(first - lam second) (1 - lambda_init)`` over the last
    dimension (a pair's 2 D dims), eps 1e-5, in float32."""
    out = first.astype(jnp.float32) - lam * second.astype(jnp.float32)
    out = out * jax.lax.rsqrt(jnp.mean(out * out, -1, keepdims=True) + 1e-5)
    return out * (scale * (1.0 - lambda_init))


def kth_largest(scores, k):
    """Each row's EXACT ``k``-th largest of float32 ``scores`` (..., n),
    ``-inf`` entries included (so ``-inf`` where a row has fewer than k
    others). No sort: the floats' bits, made to order as unsigned
    integers do, are bisected from the top bit down, 32 counts of the
    entries at or above a candidate; the largest candidate that still
    has ``k`` is the k-th largest itself."""
    scores = scores + 0.0      # -0.0 orders as +0.0, as floats compare
    k = min(k, scores.shape[-1])
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    top = jnp.uint32(1 << 31)
    keys = jnp.where(bits >= top, ~bits, bits | top)

    def narrow(i, found):
        candidate = found | (top >> i.astype(jnp.uint32))
        count = jnp.sum(keys >= candidate[..., None], axis=-1,
                        dtype=jnp.int32)
        return jnp.where(count >= k, candidate, found)

    found = jax.lax.fori_loop(
        0, 32, narrow, jnp.zeros(scores.shape[:-1], jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(found >= top, found ^ top, ~found), jnp.float32)


def select_rows(scores, topk):
    """(..., n) bool: of each row of float32 ``scores``, the entries at
    or above its ``topk``-th largest, ties included; never a ``-inf``
    one (a key after its query). All of a row's finite entries while
    there are no more than ``topk``."""
    return (scores >= kth_largest(scores, topk)[..., None]) \
        & (scores > -jnp.inf)


def index_scores(q_i, k_i, w_i, first_query):
    """The indexer's score of each (query, key) pair, float32:
    ``I[t, s] = sum_j w[t, j] relu(q_i[t, j] . k_i[s])`` for queries
    q_i (B, C, J, D) at positions ``first_query``.., keys k_i (B, S, D)
    and weights w_i (B, C, J); ``-inf`` for a key after its query."""
    dots = jnp.einsum("bcjd,bsd->bcjs", q_i, k_i,
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(w_i[..., None] * nn.relu(dots), axis=2)
    rows = first_query + jnp.arange(q_i.shape[1])
    causal = jnp.arange(k_i.shape[1])[None, :] <= rows[:, None]
    return jnp.where(causal[None], scores, -jnp.inf)


# Queries a pass of the indexer: its (C, J, S) float32 products are
# what exists at once, 268 MB at 16 heads over 8192 keys; never (J, S, S).
_INDEX_CHUNK = 512


def learned_selection(q_i, k_i, w_i, topk):
    """(B, S, S) bool: the keys each query keeps, its ``topk`` highest
    ``index_scores`` among those at or before it and whatever ties the
    last of them; every such key while there are no more than ``topk``.
    Exact, and evaluated a block of queries at a time."""
    b, s, j, d = q_i.shape
    chunk = _INDEX_CHUNK if s % _INDEX_CHUNK == 0 else s

    def of_chunk(args):
        q_c, w_c, first = args
        with jax.named_scope(SCOPE_DSA_INDEX):
            scores = index_scores(q_c, k_i, w_c, first)
        with jax.named_scope(SCOPE_DSA_SELECT):
            return select_rows(scores, topk)

    kept = jax.lax.map(of_chunk, (
        jnp.swapaxes(q_i.reshape(b, s // chunk, chunk, j, d), 0, 1),
        jnp.swapaxes(w_i.reshape(b, s // chunk, chunk, j), 0, 1),
        jnp.arange(0, s, chunk)))
    return jnp.swapaxes(kept, 0, 1).reshape(b, s, s)


class SelfAttention(nn.Module):
    """Multi-head attention by ``cfg.block``. ``window`` makes this
    layer a sliding one; ``rotary`` says whether this layer's q and k
    carry the rotary positions; ``sparse`` gives it the indexer, and
    its queries the keys they choose (``Block`` reads all three off the
    layer's kind). ``diff_layer`` makes the attention differential, with
    ``lambda_init`` of that layer index; ``cross`` leaves this layer
    with its queries alone, over the keys and values it is handed;
    ``publish`` returns the layer's own keys and values beside its
    result."""

    cfg: TransformerConfig
    window: Optional[int] = None
    rotary: bool = True
    sparse: bool = False
    diff_layer: Optional[int] = None
    cross: bool = False
    publish: bool = False

    @nn.nowrap
    def _differential(self, q, k, v):
        """Differential attention of q (B, S, H, D) over k, v (B, S,
        H_kv, D), consecutive heads pairs: ``softmax(q1 k1) [v1 v2] -
        lambda softmax(q2 k2) [v1 v2]`` (two runs of ``_attend`` at
        half the heads, each map over a V of 2 D: a pair's ``[v1 v2]``
        is v's two consecutive heads as they lie, a reshape),
        ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, an
        RMSNorm over each pair's 2 D dims, times ``1 - lambda_init``.
        Returns (B, S, H, D): a pair's 2 D dims as two heads again.

        The layer's six small vectors are ONE (6, D) leaf, ``diff``: lq1,
        lk1, lq2, lk2 (normal(0.1)) and the norm's scale in two halves
        (ones). The four lambda vectors' gradients are all one SCALAR,
        d loss / d lambda, times a fixed vector: a sum over every row with
        no sign of its own, which comes out near zero on some seeds, and
        a leaf of them alone then reads any relative distance at all
        against a float32 reference (0.04 on two seeds, 0.23 on a third:
        PERF.md section 6, PR 45). Beside the scale's 2 D gradients the
        leaf has a size that does not vanish, and a wrong lambda gradient
        still reads 0.8 of it."""
        cfg, d = self.cfg, q.shape[-1]
        lambda_init = 0.8 - 0.6 * math.exp(-0.3 * self.diff_layer)
        diff = self.param(
            "diff", lambda key, shape, dtype: jnp.concatenate([
                nn.initializers.normal(0.1)(key, (4, d), dtype),
                jnp.ones((2, d), dtype)]), (6, d), jnp.float32)
        vectors, scale = diff[:4], diff[4:].reshape(2 * d)
        (q1, q2), (k1, k2) = (
            (t[:, :, 0::2], t[:, :, 1::2]) for t in (q, k))
        b, s, h_kv, _ = v.shape
        vv = v.reshape(b, s, h_kv // 2, 2 * d)
        first = _attend(cfg, q1, k1, vv, self.window)
        second = _attend(cfg, q2, k2, vv, self.window)
        with jax.named_scope(SCOPE_DIFF_ATTN):
            lq1, lk1, lq2, lk2 = vectors
            lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                   + lambda_init)
            out = _differential_output(first, second, lam, lambda_init,
                                       scale)
            return out.astype(cfg.dtype).reshape(q.shape)

    @nn.nowrap
    def _selection(self, x, weight):
        """The learned choice of keys for the block's input x (B, S, M):
        ``q_i = x W_q`` (``index_heads`` of ``index_head_dim``), ``k_i =
        LayerNorm(x W_k)`` (one head), ``w = x W_w / sqrt(heads x dim)``,
        rotary positions on all of q_i and k_i, then
        ``learned_selection``: the (B, S, S) mask. Where the flash
        kernels will read it and the queries divide into passes of
        ``_INDEX_CHUNK``, the same choice by ONE Pallas call a pass of
        queries (``ops/pallas_selection.py``), which returns the two
        bit planes and makes no (S, S) array. Nothing here carries a
        gradient: the choice is piecewise constant in the four leaves,
        which stay in the tree and receive zeros (DeepSeek sparse
        attention trains them by a term of their own, which this model
        has not)."""
        cfg, spec = self.cfg, self.cfg.block
        j, d, topk = spec.index_heads, spec.index_head_dim, spec.index_topk
        if min(j, d, topk) < 1:
            raise ValueError("a sparse_attention layer needs BlockSpec."
                             "index_heads, index_head_dim and index_topk")
        if cfg.seq_axis is not None:
            raise ValueError("a sparse_attention layer chooses among all the "
                             "keys and exchanges none over seq_axis=%r"
                             % (cfg.seq_axis,))
        m, (b, s) = cfg.d_model, x.shape[:2]
        causal = s * (s + 1) // 2
        _M_DSA_PAIRS.labels(kind="causal").inc(b * causal)
        _M_DSA_PAIRS.labels(kind="kept").inc(b * (
            causal if topk >= s else topk * s - topk * (topk - 1) // 2))
        u = jax.lax.stop_gradient(x)
        with jax.named_scope(SCOPE_DSA_INDEX):
            q_i = jnp.einsum("bsm,mjd->bsjd", u, weight(
                "index_wq", (None, None, None), (m, j, d)))
            k_i = nn.LayerNorm(epsilon=spec.norm_eps, dtype=cfg.dtype,
                               name="index_k_norm")(
                u @ weight("index_wk", (None, None), (m, d)))
            w_i = (u @ weight("index_ww", (None, None), (m, j))).astype(
                jnp.float32) * float(j * d) ** -0.5
            q_i = rope(q_i, 0, spec.rope_theta)
            k_i = rope(k_i[:, :, None, :], 0, spec.rope_theta)[:, :, 0]
        if cfg.attention == "flash" and s % _INDEX_CHUNK == 0:
            # Imported where a sparse layer is first built, not with the
            # package: a ``pallas`` import costs every launch.
            from horovod_tpu.ops.pallas_attention import unpack_selection
            from horovod_tpu.ops.pallas_selection import choose

            with jax.named_scope(SCOPE_DSA_SELECT):
                select = choose(q_i, k_i, w_i, topk, _INDEX_CHUNK)
                self.sow("dsa", "dsa_kept", jnp.sum(
                    jax.lax.population_count(select.by_query),
                    dtype=jnp.int32))
            if self.is_mutable_collection("dsa_mask"):
                self.sow("dsa_mask", "select", unpack_selection(select, s))
            return select
        select = jax.lax.stop_gradient(learned_selection(q_i, k_i, w_i, topk))
        with jax.named_scope(SCOPE_DSA_SELECT):
            # The pairs this step's mask keeps: the count above plus ties.
            self.sow("dsa", "dsa_kept", jnp.sum(select, dtype=jnp.int32))
        # The mask itself, for whoever asks (a probe, a test): a
        # collection nothing else makes mutable.
        self.sow("dsa_mask", "select", select)
        return select

    @nn.compact
    def __call__(self, x, selection=None, kv=None):
        """``selection`` (B, S, S) forces a sparse layer's choice of
        keys (its indexer then runs nothing); ``kv`` is the (k, v)
        another layer published, which a ``cross`` layer reads."""
        cfg, spec = self.cfg, self.cfg.block
        h, m = cfg.n_heads, cfg.d_model
        d = spec.head_dim or m // h
        h_kv = spec.n_kv_heads or h
        init = nn.initializers.normal(0.02)

        def weight(name, axes, shape):
            return self.param(name, param_with_axes(init, axes), shape,
                              jnp.float32).astype(cfg.dtype)

        if self.cross:
            wq = weight("wq", (None, "model", None), (m, h, d))
        elif h_kv == h:
            wqkv = weight("wqkv", (None, None, "model", None), (3, m, h, d))
        else:
            wq = weight("wq", (None, "model", None), (m, h, d))
            wkv = weight("wkv", (None, None, "model", None), (2, m, h_kv, d))
        wo = weight("wo", ("model", None, None), (h, d, m))

        def projected(i):
            # 0, 1, 2: q, k, v. The slice of ``wqkv`` stays inside its
            # projection: the order of operations in the lowered step
            # of every configuration with one ``wqkv``.
            if h_kv == h:
                w = wqkv[i]
            else:
                w = wq if i == 0 else wkv[i - 1]
            return jnp.einsum("bsm,mhd->bshd", x, w)

        if self.cross:
            q, (k, v) = jnp.einsum("bsm,mhd->bshd", x, wq), kv
        else:
            q, k, v = projected(0), projected(1), projected(2)
        # q and k carry no name here, before their norms, although a
        # norm's backward reads its input: measured, a recomputed block
        # that holds the kernel's operands is faster multiplying these
        # two again than holding a second copy of them (``_REMAT_KEEPS``).
        if spec.qk_norm_per_head:
            # One scale vector of ``d`` for all heads of q, one for k.
            q, k = _norm(cfg, "q_norm")(q), _norm(cfg, "k_norm")(k)
        elif spec.qk_norm:
            # Over the whole width, before the heads are told apart.
            b, s = x.shape[:2]
            q = _norm(cfg, "q_norm")(q.reshape(b, s, h * d)).reshape(q.shape)
            k = _norm(cfg, "k_norm")(k.reshape(b, s, h_kv * d)).reshape(
                k.shape)
        if spec.positions == "rope" and self.rotary:
            with jax.named_scope(SCOPE_ROPE):
                first = _first_position(cfg, x.shape[1])
                q = rope(q, first, spec.rope_theta)
                k = rope(k, first, spec.rope_theta)
        select = None
        if self.sparse:
            select = selection
            if select is None:
                select = self._selection(x, weight)
        if self.diff_layer is not None:
            out = self._differential(q, k, v)
        else:
            out = _attend(cfg, q, k, v, self.window, select)
        if spec.attn_gate:
            gate = checkpoint_name(
                jnp.einsum("bsm,mhd->bshd", x, weight(
                    "wgate", (None, "model", None), (m, h, d))),
                SAVED_ATTN_GATE)
            with jax.named_scope(SCOPE_ATTN_GATE):
                out = out * nn.sigmoid(gate)
        out = checkpoint_name(jnp.einsum("bshd,hdm->bsm", out, wo),
                              SAVED_ATTN_OUT)
        return (out, (k, v)) if self.publish else out


def _to_every_head(k_pe, n_heads):
    """The one rotary key part (B, S, 1, R) as every head's."""
    return jnp.broadcast_to(
        k_pe, k_pe.shape[:2] + (n_heads,) + k_pe.shape[3:])


class LatentAttention(nn.Module):
    """Multi-head latent attention as trained (k and v computed, no
    weight absorption): ``c_q = norm(x Wqa)``, ``q = c_q Wqb`` split per
    head into a plain and a rotary part; ``[c_kv | k_pe] = x Wkva``,
    ``c_kv = norm(c_kv)``, ``c_kv Wkvb`` split per head into the plain
    part of k and v; RoPE on q's rotary part and on ``k_pe``, which ALL
    heads share. q.k is ``qk_nope_head_dim + qk_rope_head_dim`` wide and
    has to equal ``v_head_dim`` here ('flash' alone takes two widths)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg, spec = self.cfg, self.cfg.block
        h, m = cfg.n_heads, cfg.d_model
        nope, rot, dv = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                         spec.v_head_dim)
        if nope + rot != dv:
            raise ValueError(
                "latent attention with q.k %d + %d wide and v %d: the "
                "attention kernels take one head_dim" % (nope, rot, dv))
        init = nn.initializers.normal(0.02)

        def weight(name, axes, shape):
            return self.param(name, param_with_axes(init, axes), shape,
                              jnp.float32).astype(cfg.dtype)

        with jax.named_scope(SCOPE_MLA_LATENT):
            # The two down-projections' products, each read by a norm
            # (as ``SelfAttention``'s q and k before theirs).
            c_q = _norm(cfg, "q_a_norm")(checkpoint_name(
                x @ weight("q_a", (None, None), (m, spec.q_lora_rank)),
                SAVED_ATTN_PRENORM))
            q = jnp.einsum("bsr,rhd->bshd", c_q, weight(
                "q_b", (None, "model", None),
                (spec.q_lora_rank, h, nope + rot)))
            down = checkpoint_name(
                x @ weight("kv_a", (None, None),
                           (m, spec.kv_lora_rank + rot)),
                SAVED_ATTN_PRENORM)
            c_kv = _norm(cfg, "kv_a_norm")(down[..., :spec.kv_lora_rank])
            kv = jnp.einsum("bsr,rhd->bshd", c_kv, weight(
                "kv_b", (None, "model", None),
                (spec.kv_lora_rank, h, nope + dv)))
        with jax.named_scope(SCOPE_ROPE):
            first = _first_position(cfg, x.shape[1])
            q_pe = rope(q[..., nope:], first, spec.rope_theta)
            k_pe = rope(down[..., None, spec.kv_lora_rank:], first,
                        spec.rope_theta)
        with jax.named_scope(SCOPE_MLA_LATENT):
            q = jnp.concatenate([q[..., :nope], q_pe], -1)
            k = jnp.concatenate([kv[..., :nope], _to_every_head(k_pe, h)],
                                -1)
            v = kv[..., nope:]
        wo = weight("wo", ("model", None, None), (h, dv, m))
        return checkpoint_name(
            jnp.einsum("bshd,hdm->bsm", _attend(cfg, q, k, v), wo),
            SAVED_ATTN_OUT)


def _causal_taps(x, w):
    """``z_t = sum_j w[:, j] * x_(t - L + 1 + j)`` per channel, zeros
    before position 0: x (B, S, M), w (M, L), L shifted multiplies."""
    taps, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * w[:, j] for j in range(taps))


def _gated_taps(bcu, w):
    """``c * taps(b * u)`` of the in-projection's product ``bcu`` (B, S,
    3 M), its thirds b, c, u in that order, in ``bcu``'s dtype: what the
    compiler writes between these passes is then as narrow as the
    product itself."""
    b, c, u = jnp.split(bcu, 3, axis=-1)
    return c * _causal_taps(b * u, w.astype(bcu.dtype))


class ShortConv(nn.Module):
    """The ``lfm2`` family's token mixer, a double-gated short
    convolution: ``[b, c, u] = split3(x W_in)``, ``z`` the depthwise
    causal convolution of ``b * u`` over ``BlockSpec.conv_taps`` tokens
    (no bias, no activation), ``(c * z) W_out``. No heads, no
    positions, no state beyond ``conv_taps - 1`` tokens. Matmuls,
    gates and taps all run in the compute dtype."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg, m = self.cfg, self.cfg.d_model
        taps = cfg.block.conv_taps
        if taps < 1:
            raise ValueError("a conv layer needs BlockSpec.conv_taps")
        if cfg.seq_axis is not None:
            raise ValueError("a conv layer reads the tokens before its "
                             "own and exchanges none over seq_axis=%r"
                             % (cfg.seq_axis,))
        init = nn.initializers.normal(0.02)
        w_in = self.param("w_in", param_with_axes(init, (None, "model")),
                          (m, 3 * m), jnp.float32)
        w = self.param("w", param_with_axes(init, ("model", None)),
                       (m, taps), jnp.float32)
        w_out = self.param("w_out", param_with_axes(init, ("model", None)),
                           (m, m), jnp.float32)
        # Both products carry names: a recomputed block keeps them
        # (``_REMAT_KEEPS``) and makes the gates and the taps again.
        bcu = checkpoint_name(x @ w_in.astype(cfg.dtype), SAVED_CONV_IN)
        with jax.named_scope(SCOPE_CONV_GATE):
            y = _gated_taps(bcu, w)
        return checkpoint_name(y @ w_out.astype(cfg.dtype), SAVED_CONV_OUT)


def _step_bias(key, shape, dtype=jnp.float32):
    """Mamba's initial ``b_dt``: the inverse softplus of a step drawn
    log-uniformly from [1e-3, 1e-1]."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype)
                   * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    return step + jnp.log(-jnp.expm1(-step))


def _published_scan(y, gated):
    """What a publishing ``mamba`` layer hands its readers: the scan's
    output y, BEFORE the gate (``gated`` = ``y * silu(z)`` is the other
    reading of the family's code)."""
    return y


class Mamba(nn.Module):
    """A selective state-space mixer (Mamba-1): ``[x, z] = u W_in`` to E
    = ``ssm_expand * d_model`` channels each; ``x' = silu(taps(x) +
    b_c)``, a depthwise causal convolution of ``conv_taps``; ``[r, B, C]
    = x' W_x`` (rank ``ceil(d_model / 16)`` and twice ``ssm_state``);
    ``Delta = softplus(r W_dt + b_dt)``, ``A = -exp(A_log)``; the scan
    ``h_t = exp(Delta_t A) h_(t-1) + (Delta_t x'_t) outer B_t``, ``y_t =
    h_t C_t + D x'_t`` in float32 (``ops/pallas_scan.py``); ``(y *
    silu(z)) W_out``. ``publish`` returns y, the scan's output before
    the gate, beside the result."""

    cfg: TransformerConfig
    publish: bool = False

    @nn.compact
    def __call__(self, u):
        cfg, spec, m = self.cfg, self.cfg.block, self.cfg.d_model
        e, n, taps = spec.ssm_expand * m, spec.ssm_state, spec.conv_taps
        rank = -(-m // 16)
        if min(n, taps) < 1:
            raise ValueError("a mamba layer needs BlockSpec.ssm_state and "
                             "conv_taps")
        if cfg.seq_axis is not None:
            raise ValueError("a mamba layer carries its state over the "
                             "whole sequence and exchanges none over "
                             "seq_axis=%r" % (cfg.seq_axis,))
        # Imported where a mamba layer is first built, not with the
        # package: a ``pallas`` import costs every launch.
        from horovod_tpu.ops.pallas_scan import selective_scan

        init = nn.initializers.normal(0.02)

        def param(name, axes, shape, init=init):
            return self.param(name, param_with_axes(init, axes), shape,
                              jnp.float32)

        w_in = param("w_in", (None, "model"), (m, 2 * e))
        w = param("w", ("model", None), (e, taps))
        b_c = param("b", ("model",), (e,), nn.initializers.zeros)
        w_x = param("w_x", ("model", None), (e, rank + 2 * n))
        w_dt = param("w_dt", (None, "model"), (rank, e))
        b_dt = param("b_dt", ("model",), (e,), _step_bias)
        a_log = param("a_log", ("model", None), (e, n), lambda *_: jnp.log(
            jnp.broadcast_to(jnp.arange(1.0, n + 1.0), (e, n))))
        d = param("d", ("model",), (e,), nn.initializers.ones)
        w_out = param("w_out", ("model", None), (e, m))
        # The products, the scan's input and its output carry names: a
        # recomputed block keeps them (``_REMAT_KEEPS``) and makes the
        # taps, the activations, Delta and the gate again.
        xz = checkpoint_name(u @ w_in.astype(cfg.dtype), SAVED_SSM_IN)
        x, z = jnp.split(xz, 2, axis=-1)
        with jax.named_scope(SCOPE_SSM_CONV):
            x = checkpoint_name(
                nn.silu(_causal_taps(x, w.astype(cfg.dtype))
                        + b_c.astype(cfg.dtype)), SAVED_SSM_X)
        # ``[r, B, C]`` stays float32 (T x 192 numbers): B and C enter the
        # scan's float32 recurrence 8,192 steps deep as they are summed.
        proj = checkpoint_name(jnp.dot(
            x, w_x.astype(cfg.dtype), preferred_element_type=jnp.float32),
            SAVED_SSM_PROJ)
        r, b, c = jnp.split(proj, (rank, rank + n), axis=-1)
        delta = nn.softplus(jnp.dot(
            r.astype(cfg.dtype), w_dt.astype(cfg.dtype),
            preferred_element_type=jnp.float32) + b_dt)
        y = checkpoint_name(
            selective_scan(x, delta, -jnp.exp(a_log), b, c, d).astype(
                cfg.dtype), SAVED_SSM_Y)
        gated = y * nn.silu(z)
        out = checkpoint_name(gated @ w_out.astype(cfg.dtype), SAVED_SSM_OUT)
        return (out, _published_scan(y, gated)) if self.publish else out


class MemoryUnit(nn.Module):
    """A gated memory unit: ``(memory * silu(u W_1)) W_2`` with
    ``memory`` (B, S, E) the scan output an earlier ``mamba`` layer
    published. No state, no positions, no pairs."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u, memory):
        cfg, m, e = self.cfg, self.cfg.d_model, memory.shape[-1]
        init = nn.initializers.normal(0.02)
        w_1 = self.param("w_1", param_with_axes(init, (None, "model")),
                         (m, e), jnp.float32)
        w_2 = self.param("w_2", param_with_axes(init, ("model", None)),
                         (e, m), jnp.float32)
        gate = checkpoint_name(u @ w_1.astype(cfg.dtype), SAVED_GMU_GATE)
        with jax.named_scope(SCOPE_GMU):
            y = memory * nn.silu(gate)
        return checkpoint_name(y @ w_2.astype(cfg.dtype), SAVED_GMU_OUT)


class Mlp(nn.Module):
    """The dense feed-forward, ``cfg.d_ff`` wide unless ``width`` says
    otherwise (a leading dense block, a shared expert)."""

    cfg: TransformerConfig
    width: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        # One table for this gate and the grouped experts'.
        from horovod_tpu.parallel.moe import GATE_ACTIVATIONS

        cfg = self.cfg
        d_ff = self.width or cfg.d_ff
        init = nn.initializers.normal(0.02)
        wi = self.param("wi", param_with_axes(init, (None, "model")),
                        (cfg.d_model, d_ff), jnp.float32)
        wo = self.param("wo", param_with_axes(init, ("model", None)),
                        (d_ff, cfg.d_model), jnp.float32)
        # The three products carry names: a recomputed block keeps the
        # two the activation's backward reads, and the third where a
        # norm reads it (``_remat_block``), and runs the activation.
        y = checkpoint_name(x @ wi.astype(cfg.dtype), SAVED_MLP_UP)
        if cfg.block.ffn in GATE_ACTIVATIONS:
            wg = self.param("wg", param_with_axes(init, (None, "model")),
                            (cfg.d_model, d_ff), jnp.float32)
            y = GATE_ACTIVATIONS[cfg.block.ffn](checkpoint_name(
                x @ wg.astype(cfg.dtype), SAVED_MLP_GATE)) * y
        else:
            y = nn.gelu(y)
        return checkpoint_name(y @ wo.astype(cfg.dtype), SAVED_MLP_OUT)


class Block(nn.Module):
    """One decoder block. ``dense_width`` makes its feed-forward a dense
    one of that width whatever the model's other blocks carry;
    ``layer_type`` is its entry of ``BlockSpec.layer_types`` and says
    which token mixer it carries. With ``post_norms`` each branch's
    output passes a norm of its own before it joins the residual
    stream. ``layer_id`` is the layer's published index where the
    attention is differential. A ``publish``ing block returns (x,
    published): its mixer's scan output, or keys and values; a reading
    kind (``_READS``) is handed one as ``reads``."""

    cfg: TransformerConfig
    dense_width: Optional[int] = None
    layer_type: str = FULL_ATTENTION
    layer_id: Optional[int] = None
    publish: bool = False

    def _mixer(self):
        cfg, spec, kind = self.cfg, self.cfg.block, self.layer_type
        if kind not in _KINDS:
            raise ValueError("Unknown attention layer type %r (layer_types "
                             "knows %s)" % (kind, ", ".join(_KINDS)))
        _M_ATTN_LAYERS.labels(kind=kind).inc()
        if kind == CONV:
            return ShortConv(cfg, name="conv")
        if kind == MAMBA:
            return Mamba(cfg, self.publish, name="mamba")
        if kind == MEMORY_UNIT:
            return MemoryUnit(cfg, name="gmu")
        sliding = kind == SLIDING_ATTENTION
        if spec.attention_kind == "latent":
            if sliding or kind == SPARSE_ATTENTION:
                raise ValueError("latent attention has no sliding window "
                                 "and no learned selection")
            return LatentAttention(cfg, name="attn")
        if sliding and spec.sliding_window < 1:
            raise ValueError("a sliding_attention layer needs "
                             "BlockSpec.sliding_window")
        return SelfAttention(
            cfg, spec.sliding_window if sliding else None,
            spec.rope_layers is None or kind in spec.rope_layers,
            kind == SPARSE_ATTENTION, self.layer_id,
            kind == CROSS_ATTENTION, self.publish, name="attn")

    @nn.compact
    def __call__(self, x, assignment=None, selection=None, reads=None):
        with trace_span("block", layer=self.name, kind=self.layer_type,
                        ffn=self.cfg.block.ffn):
            cfg = self.cfg

            def joined(x, name, branch):
                if cfg.block.post_norms:
                    branch = _norm(cfg, name)(branch)
                return x + branch

            y = mixer_input = _norm(cfg, "ln1")(x)
            mixer = self._mixer()
            if self.layer_type == MEMORY_UNIT:
                branch = mixer(y, reads)
            elif self.layer_type == CROSS_ATTENTION:
                branch = mixer(y, kv=reads)
            elif self.layer_type == SPARSE_ATTENTION:
                branch = mixer(y, selection)
            else:
                branch = mixer(y)
            published = None
            if self.publish:
                branch, published = branch
            x = joined(x, "post_attn_norm", branch)
            y = _norm(cfg, "ln2")(x)
            if cfg.block.num_experts > 0 and self.dense_width is None:
                from horovod_tpu.parallel.moe import MoeMlp

                shared = None
                if cfg.block.shared_experts:
                    # Adopted by the expert layer: its weights are
                    # ``moe/shared``, its time the ``moe`` scope's.
                    shared = Mlp(cfg, cfg.block.shared_experts * cfg.d_ff,
                                 parent=None)
                # The experts read ``ln2``'s output; the layer's router
                # reads that too, or ``ln1``'s where
                # ``BlockSpec.router_tap`` is 'mixer': its routing half
                # then depends on nothing the mixer made.
                x = joined(x, "post_mlp_norm", MoeMlp(
                    cfg, shared, name="moe")(y, assignment, mixer_input))
            else:
                x = joined(x, "post_mlp_norm",
                           Mlp(cfg, self.dense_width, name="mlp")(y))
            return (x, published) if self.publish else x


# What a recomputed block keeps from forward to backward: the one place
# that says it. The rule: keep what a MATMUL (or the attention kernel)
# made and the backward pass reads, if it is T rows by a few model
# widths; remake what an elementwise pass makes (norms, residual adds,
# activations, casts, rotations, concatenations, transposes: HBM-bound
# passes of 0.05-0.2 ms). A name is saved only where the backward pass
# reads its array, so a block without the module, the norm or the gate
# in question keeps nothing for that entry. The routed experts' row
# arrays are not on the list: parallel/moe.py's backward rule recomputes
# them from the layer's inputs. Bytes a layer at T = 8192 tokens in
# bf16, GLM-4.7-Flash (20 heads of 256, M 2048) | Trinity-Mini (32 query
# over 4 key/value heads of 128, gated, head norms, post norms); PERF.md
# section 6 (PR 35) has the ms each entry spares a layer on a v5e.
_REMAT_KEEPS = (
    # The flash kernel's results: output 83.9 | 67.1 MB, log-sum-exp
    # 0.7 | 1.0 MB (float32). Without them the kernel runs twice.
    SAVED_FLASH_OUT, SAVED_FLASH_LSE,
    # Its operands as the backward kernels read them, (B, H, S, D):
    # 252 | 84 MB. Spares GLM's two up-projections, RoPE and the three
    # copies that put q, k and v together; Trinity's v projection and,
    # of q and k, the head norms, RoPE and transposes (not their two
    # projections, which the norms' backward needs: below).
    SAVED_FLASH_Q, SAVED_FLASH_K, SAVED_FLASH_V,
    # A learned selection as the kernels read it (a ``sparse_attention``
    # layer, Keye-VL-2.0: 16 index heads of 64, top 2048): two bit planes
    # of the mask, 8.4 MB each. Both kernels read theirs, so the
    # recomputed forward has no reader left for the indexer's three
    # projections, its (S, S) scores or the 8192 row selections.
    SAVED_FLASH_SELECT,
    # A projection's product that a norm reads: GLM's two latent
    # down-projections, 12.6 + 9.4 MB. Without it the projection is
    # multiplied again for the norm's backward, whatever else is kept.
    # NOT Trinity's q and k before their head norms (67.1 + 8.4 MB):
    # beside the kernel's operands that second copy cost 1.1 ms a step
    # more than it spared, so ``SelfAttention`` multiplies them again.
    SAVED_ATTN_PRENORM,
    # The output gate's projection, Trinity 67.1 MB: the multiply's
    # backward and the output projection's weight gradient read it.
    SAVED_ATTN_GATE,
    # The attention branch's output, 33.5 MB: from it the recomputed
    # forward reaches the feed-forward's input by a norm and an add.
    SAVED_ATTN_OUT,
    # ``Mlp``'s up and gate products (the dense layer 336 | 201 MB, a
    # shared expert 50 | 33.5 MB) and its output where a norm reads it
    # (Trinity 33.5 MB): the recomputed feed-forward is its activation.
    SAVED_MLP_UP, SAVED_MLP_GATE, SAVED_MLP_OUT,
    # What a share-held expert layer returns, where a norm reads it
    # (Trinity 33.5 MB): that layer's choice is not made a second time.
    SAVED_MOE_OUT,
    # A block WITHOUT a kernel, the gated short convolution
    # (``ShortConv``; LFM2-8B-A1B at T = 16,384, M 2048): its
    # in-projection's product, 3 M wide, 201 MB, which the gates' and
    # the taps' backward reads, and the branch's output, 67 MB; the
    # recomputed mixer is its gates and taps, one elementwise pass.
    SAVED_CONV_IN, SAVED_CONV_OUT,
    # A ``mamba`` block (Phi-4-mini-flash-reasoning at T = 8,192, M 2560,
    # E 5120, 16 states): the in-projection's product 168 MB, the scan's
    # input x' 84 MB (the taps' and ``silu``'s backward reads the
    # product, the scan's and the ``[r, B, C]`` projection's read x'),
    # that projection's product 6 MB (float32), the state at each chunk's start
    # 10.5 MB (float32; without it the forward kernel runs twice), the
    # scan's output y 84 MB (the gate's backward and the out-projection's
    # weight gradient read it; a publishing layer's readers hold the
    # same array) and the branch's output 42 MB. Delta (168 MB in
    # float32) is NOT kept: a 160-deep matmul and a ``softplus`` again.
    SAVED_SSM_IN, SAVED_SSM_X, SAVED_SSM_PROJ, SAVED_SSM_STATES,
    SAVED_SSM_Y, SAVED_SSM_OUT,
    # A ``memory_unit`` block: its gate projection's product 84 MB and
    # the branch's output 42 MB; the memory itself is the block's INPUT
    # (the publisher's y above, one array however many readers).
    SAVED_GMU_GATE, SAVED_GMU_OUT,
)
# A ``cross_attention`` block keeps all of these but the kernels' k and
# v operands: they are the publisher's keys and values, which the block
# holds as its input, transposed; a copy a reader would be 42 MB each.
_READER_KEEPS = tuple(name for name in _REMAT_KEEPS
                      if name not in (SAVED_FLASH_K, SAVED_FLASH_V))


@functools.cache
def _log_remat(cfg, keeps):
    logger.info(
        "Transformer remat: %d blocks recomputed in the backward pass, "
        "they keep %s (attention=%r): what the mixer's and the "
        "feed-forward's matmuls made and the backward pass reads, about "
        "T x (3 H D + 2 M + 2 F) of %s a layer (T x 4 M + 2 F under a "
        "convolution); norms, activations, gates and adds are made again",
        cfg.n_layers, ", ".join("%s in %d" % kc for kc in keeps),
        cfg.attention, jnp.dtype(cfg.dtype).name)


def _layer_kinds(cfg):
    """One entry of ``BlockSpec.layer_types`` a layer; where the spec
    names none, all full attention, over a learned selection if the
    spec has an indexer (``index_topk``)."""
    every = SPARSE_ATTENTION if cfg.block.index_topk else FULL_ATTENTION
    kinds = cfg.block.layer_types or (every,) * cfg.n_layers
    if len(kinds) != cfg.n_layers:
        raise ValueError("BlockSpec.layer_types names %d layers, the "
                         "model has %d" % (len(kinds), cfg.n_layers))
    return kinds


def _remat_block(cfg):
    """``Block`` under recomputation that keeps ``_REMAT_KEEPS``: the
    attention kernel's operands and results (the names
    ops/pallas_attention.py gives them: the backward kernels read all
    five, so the recomputed forward has no use for a second run of the
    kernel or of what made its operands) and the narrow matmul products
    of the mixer and the dense feed-forward that the backward
    pass reads, so that the recomputed forward multiplies nothing but
    the router's logits and, under a norm on q and k, those two
    projections. Likewise what an expert layer that holds a
    share of the experts returns, where the block reads it again
    (``post_norms``); that layer's backward rule recomputes from its
    inputs (parallel/moe.py ``_held_rows``). A block without a kernel
    (a ``conv`` layer, or any layer under another ``attention`` than
    'flash') carries no kernel names and keeps the products alone.
    Counted and logged at trace time."""
    kinds = _layer_kinds(cfg)
    kernel = sum(kind not in (CONV, MAMBA, MEMORY_UNIT) for kind in kinds) \
        if cfg.attention == "flash" else 0
    keeps = tuple((label, n) for label, n in (
        ("flash+products", kernel), ("products", len(kinds) - kernel)) if n)
    for label, n in keeps:
        _M_REMAT_BLOCKS.labels(keeps=label).inc(n)
    _log_remat(cfg, keeps)
    policy = jax.checkpoint_policies.save_only_these_names(*_REMAT_KEEPS)
    return nn.remat(Block, policy=policy)


def _remat_reader():
    """``Block`` under recomputation for a ``cross_attention`` layer:
    ``_READER_KEEPS``. A recomputed reader multiplies no key or value
    and runs no scan: what it reads is its input, kept once, by the
    layer that published it."""
    return nn.remat(Block, policy=jax.checkpoint_policies
                    .save_only_these_names(*_READER_KEEPS))


def _apply_block(block, x):
    return block(x)


def _kept(names):
    """``_apply_block`` under recomputation that keeps ``names``."""
    return nn.remat(_apply_block, policy=jax.checkpoint_policies
                    .save_only_these_names(*names))


class _LoopedStack(nn.Module):
    """``cfg.n_layers`` blocks made ONCE and applied ``cfg.passes``
    times, ``ln_f`` after every pass: (passes, B, S, M), the normed
    state each pass ends in. The loop is unrolled: each pass is a scope
    of its own (``hvd_loop_pass_<t>``), and under ``jax.grad`` a
    weight's gradient is the sum over its uses with no mechanism here.

    Under ``cfg.remat`` a block keeps ``_REMAT_KEEPS`` in EVERY pass,
    the one list ``_remat_block`` gives a block of one pass: the
    recomputed forward of a pass multiplies nothing. Weights that are
    used ``passes`` times keep a block's list that many times, and the
    compiler holds less than the lists' sum. Measured on a v5e at ONE
    shape (eight blocks, four passes, 4,096 tokens of 2048 in bf16), the
    kernel's five names alone early and the full list in the last 0 / 1
    / 2 / 3 / 4 passes: 8,651 / 8,752 / 9,021 / 9,281 / 9,408 tokens a
    second, 56.9 / 43.4 / 29.7 / 15.6 / 1.2 ms a step of forward made
    again, 13.97 / 14.73 / 14.97 / 15.22 / 16.09 GB at the peak of the
    16.91 the runtime allows; at the last XLA itself makes one readout's
    logits again to fit (PERF.md section 6, PR 49 and PR 50). Another
    sequence length, depth or pass count has not been measured. Counted
    at trace time, once a pass (``hvd_remat_blocks_total``:
    ``flash+products``)."""

    cfg: TransformerConfig

    def setup(self):
        cfg = self.cfg
        for i, kind in enumerate(_layer_kinds(cfg)):
            dense = i < cfg.block.first_dense_layers
            setattr(self, "layer_%d" % i, Block(
                cfg, cfg.block.dense_ff if dense else None, kind))
        self.ln_f = _norm(cfg, None)

    @nn.nowrap
    def _pass(self, x):
        cfg, apply = self.cfg, _apply_block
        if cfg.remat:
            apply = _kept(_REMAT_KEEPS)
            _M_REMAT_BLOCKS.labels(keeps="flash+products").inc(cfg.n_layers)
        for i in range(cfg.n_layers):
            x = apply(getattr(self, "layer_%d" % i), x)
        return x

    def __call__(self, x):
        cfg = self.cfg
        _M_LOOP_PASSES.inc(cfg.n_layers * cfg.passes)
        states = []
        for t in range(cfg.passes):
            with trace_span("loop_pass", **{"pass": t}), \
                    jax.named_scope("%s_%d" % (SCOPE_LOOP_PASS, t)):
                x = self._pass(x)
                h = self.ln_f(x)
            states.append(h)
            if cfg.loop_norm:
                x = h
        return jnp.stack(states)


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.nowrap
    def _looped(self, x, assignments, selections):
        """The ``passes`` > 1 path from the embedded tokens on: the
        normed hidden state each pass ends in, (passes, B, S, M). The
        stack's matrices are cast to the compute dtype ONCE, outside
        every pass and every recomputation (the stack is a module under
        ``nn.map_variables``; ``Block``'s own casts then cast nothing);
        the small vectors stay float32, as the norms read them. Declares
        the exit gate, which ``looped_loss`` reads: its weight and its
        bias are ONE (M + 1,) leaf (the bias's gradient alone is one
        scalar summed over every position and pass)."""
        cfg, spec = self.cfg, self.cfg.block
        if assignments is not None or selections is not None \
                or spec.num_experts or spec.index_topk \
                or max(spec.scan_from, spec.kv_from) >= 0:
            raise ValueError(
                "a looped stack (passes=%d) runs blocks that sow and "
                "publish nothing: no experts, no learned selection, no "
                "layer that hands an array to later ones" % cfg.passes)
        self.param("exit_gate", lambda key, shape, dtype: jnp.concatenate([
            nn.initializers.normal(0.02)(key, (shape[0] - 1,), dtype),
            jnp.zeros((1,), dtype)]), (cfg.d_model + 1,), jnp.float32)

        def cast(variables):
            if self.is_initializing():
                return variables
            return jax.tree.map(
                lambda a: a.astype(cfg.dtype) if a.ndim >= 2 else a,
                variables)

        return nn.map_variables(
            _LoopedStack, "params", cast,
            init=self.is_initializing())(cfg, name="stack")(x)

    @nn.compact
    def __call__(self, tokens, assignments=None, selections=None):
        """Logits (B, S, vocab) in float32. ``assignments`` (one entry a
        layer, each (B * S, experts_per_token) expert indices; a dense
        block's is ignored) forces the experts' routing; what the
        expert layers sow is in the ``moe`` collection, the routers'
        correction biases in ``moe_state`` (parallel/moe.py).
        ``selections`` (one entry a layer, each (B, S, S) bool; read by
        ``sparse_attention`` layers alone) forces the keys each query
        keeps; the pairs a free choice kept are sown as ``dsa_kept`` in
        the ``dsa`` collection."""
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        embed = self.param(
            "embed", param_with_axes(init, ("model", None)),
            (cfg.vocab_size, cfg.d_model), jnp.float32)
        if cfg.block.positions == "learned":
            pos = self.param(
                "pos", param_with_axes(init, (None, None)),
                (cfg.max_seq_len, cfg.d_model), jnp.float32)
        head = embed
        if not cfg.block.tied_head:
            head = self.param(
                "lm_head", param_with_axes(init, ("model", None)),
                (cfg.vocab_size, cfg.d_model), jnp.float32)
        # flax scopes every module call; the lookup and the output
        # projection sit loose at the root, so they get scopes of their own
        # (jax/introspect.py lists them).
        with jax.named_scope(SCOPE_EMBED):
            if _use_onehot_embed(cfg):
                # The one-hot contraction partitions cleanly under manual
                # subgroups (where the gather CHECK-crashes XLA's
                # partitioner, see _use_onehot_embed) and rides the MXU.
                # Outside that composition the plain gather is cheaper (no
                # [b, s, vocab] one-hot activation), so keep it.
                onehot = jax.nn.one_hot(tokens, cfg.vocab_size,
                                        dtype=cfg.dtype)
                x = jnp.einsum("bsv,vm->bsm", onehot,
                               embed.astype(cfg.dtype))
            else:
                x = embed.astype(cfg.dtype)[tokens]
            if cfg.block.embed_scale != 1.0:
                x = x * jnp.asarray(cfg.block.embed_scale, cfg.dtype)
            if cfg.block.positions == "learned":
                s_local = tokens.shape[1]
                if cfg.seq_axis is not None and _axis_bound(cfg.seq_axis):
                    # Sequence-sharded (shard_map): this shard holds
                    # positions [idx * S_local, (idx+1) * S_local).
                    pos_slice = jax.lax.dynamic_slice_in_dim(
                        pos.astype(cfg.dtype),
                        _first_position(cfg, s_local), s_local)
                else:
                    pos_slice = pos.astype(cfg.dtype)[:s_local]
                x = x + pos_slice[None]
        kinds = _layer_kinds(cfg)
        if cfg.passes > 1:
            return self._looped(x, assignments, selections)
        block = reader = _remat_block(cfg) if cfg.remat else Block
        if cfg.remat and CROSS_ATTENTION in kinds:
            reader = _remat_reader()
        spec = cfg.block
        published = {}     # "scan" / "kv": what a layer handed on
        for i in range(cfg.n_layers):
            dense = i < cfg.block.first_dense_layers
            made, extra, args = block, {}, ()
            if spec.diff_attention:
                extra["layer_id"] = (spec.layer_ids or range(cfg.n_layers))[i]
            publishes = {spec.scan_from: "scan", spec.kv_from: "kv"}.get(i)
            if publishes:
                if (kinds[i] == MAMBA) != (publishes == "scan") or kinds[i] \
                        not in (MAMBA, FULL_ATTENTION, SLIDING_ATTENTION):
                    raise ValueError(
                        "BlockSpec.scan_from names a mamba layer and kv_from "
                        "a full or sliding attention layer; layer %d is a %s"
                        % (i, kinds[i]))
                extra["publish"] = True
            if kinds[i] in _READS:
                if _READS[kinds[i]] not in published:
                    raise ValueError(
                        "layer %d is a %s and no earlier layer published "
                        "(BlockSpec.scan_from %d, kv_from %d)" % (
                            i, kinds[i], spec.scan_from, spec.kv_from))
                _M_SHARED_ARRAYS.labels(role="read").inc()
                args = (published[_READS[kinds[i]]],)
                if kinds[i] == CROSS_ATTENTION:
                    made = reader
            x = made(cfg, cfg.block.dense_ff if dense else None, kinds[i],
                     name="layer_%d" % i, **extra)(
                x, None if assignments is None else assignments[i],
                None if selections is None else selections[i], *args)
            if publishes:
                _M_SHARED_ARRAYS.labels(role="published").inc()
                x, published[publishes] = x
        x = _norm(cfg, "ln_f")(x)
        with jax.named_scope(SCOPE_LOGITS):
            logits = jnp.einsum("bsm,vm->bsv", x, head.astype(cfg.dtype))
            return logits.astype(jnp.float32)


def _logits(h, w):
    """h (B, S, M) and w (V, M) in the compute dtype -> (B, S, V) in
    float32, straight from the matmul's float32 sums."""
    with jax.named_scope(SCOPE_LOGITS):
        return jnp.einsum("bsm,vm->bsv", h, w,
                          preferred_element_type=jnp.float32)


@jax.custom_vjp
def _readout_loss(h, w, targets):
    """The cross entropy (B, S) of one pass's readout ``h w^T`` against
    ``targets``. Kept for the backward pass: h, and a float32 scalar a
    position; the backward rule makes the logits again, so nothing
    OBLIGES a pass's logits to outlive its readout. (Where memory
    allows, XLA merges that second product with the first and keeps
    them: in ``ouro-s4096-ut4-c1`` the compiled step multiplies each
    readout's logits once. PERF.md section 6, PR 49.)"""
    return _readout_fwd(h, w, targets)[0]


def _readout_fwd(h, w, targets):
    with jax.named_scope(SCOPE_LOOP_READOUT):
        z = _logits(h, w)
        lse = jax.nn.logsumexp(z, axis=-1)
        at = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
        return lse - at, (h, w, targets, lse)


def _readout_bwd(kept, ct):
    h, w, targets, lse = kept
    with jax.named_scope(SCOPE_LOOP_READOUT):
        z = _logits(h, w)
        hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
            == targets[..., None]
        dz = ((jnp.exp(z - lse[..., None]) - hit) * ct[..., None]).astype(
            h.dtype)
        with jax.named_scope(SCOPE_LOGITS):
            return (jnp.einsum("bsv,vm->bsm", dz, w),
                    jnp.einsum("bsv,bsm->vm", dz, h), None)


_readout_loss.defvjp(_readout_fwd, _readout_bwd)


def _exit_log_p(score):
    """log of the exit distribution (T, ...) from the gates' scores (T,
    ...): a pass's own ``log g`` on top of having stayed so far, ``sum_{j<t}
    log (1 - g_j)``; the last pass has no gate of its own to pass and
    takes what is left."""
    stays = jnp.cumsum(jax.nn.log_sigmoid(-score), axis=0)
    return jnp.concatenate([
        jax.nn.log_sigmoid(score[:1]),
        jax.nn.log_sigmoid(score[1:-1]) + stays[:-2],
        stays[-2:-1]])


def looped_loss(hidden, head, gate, targets, beta):
    """The loss of a looped model (``TransformerConfig.passes`` > 1)
    from what ``Transformer`` handed back: ``hidden`` (T, B, S, M), the
    normed state after each pass; ``head`` (V, M), the output embedding
    (``lm_head``, or ``embed`` where tied); ``gate`` (M + 1,), the exit
    gate's weight and bias (``exit_gate``); ``targets`` (B, S).

    Pass t is read out through the ONE head, ``l_t = CE(h_t head^T,
    targets)``, and exits with ``g_t = sigmoid(h_t . gate[:-1] +
    gate[-1])``: the exit distribution is ``p_t = g_t prod_{j<t} (1 -
    g_j)`` for t < T and the last pass takes what is left, ``p_T =
    prod_{j<T} (1 - g_j)``. The loss is the expected cross entropy under
    p less ``beta`` times p's entropy, averaged over the positions:
    ``mean_i [sum_t p_t l_t + beta sum_t p_t log p_t]``. Gradients reach
    the gate through p and the stack through both l and p.

    The head is cast to the compute dtype once for all passes; each
    pass's readout keeps its state and not its logits, which its
    backward rule makes again (``_readout_loss``). Returns the loss and, as statistics
    that carry no gradient, ``exit_share`` (T,), the mean of each p_t;
    ``entropy``, the mean entropy of p; ``cross_entropy`` (T,), the mean
    of each l_t."""
    with trace_span("readout"):
        passes = hidden.shape[0]
        w = head.astype(hidden.dtype)
        losses = jnp.stack([_readout_loss(hidden[t], w, targets)
                            for t in range(passes)])
        with jax.named_scope(SCOPE_LOOP_EXIT):
            score = jnp.sum(hidden.astype(jnp.float32) * gate[:-1], -1) \
                + gate[-1]
            log_p = _exit_log_p(score)
            p = jnp.exp(log_p)
            minus_entropy = jnp.sum(p * log_p, axis=0)
            loss = jnp.mean(jnp.sum(p * losses, axis=0) + beta * minus_entropy)
            stats = jax.lax.stop_gradient({
                "exit_share": jnp.mean(p, axis=(1, 2)),
                "entropy": -jnp.mean(minus_entropy),
                "cross_entropy": jnp.mean(losses, axis=(1, 2))})
        return loss, stats


def record_loop_stats(stats):
    """Hold ``looped_loss``'s statistics, fetched to the host, as the
    gauges ``hvd_loop_exit_share{pass}``."""
    for t, share in enumerate(stats["exit_share"]):
        _M_LOOP_EXIT_SHARE.labels(**{"pass": str(t)}).set(float(share))


def get_param_specs(cfg: TransformerConfig, sample_tokens):
    """PartitionSpecs for the parameter pytree, derived from the
    ``with_partitioning`` metadata (consumed by pjit NamedShardings)."""
    model = Transformer(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample_tokens))
    return nn.get_partition_spec(abstract)
