"""Decoder-only transformer — the multi-axis-parallelism flagship.

The reference is a pure data-parallel framework; its model-parallel
building blocks are generic collectives (SURVEY.md §2.3). This model shows
how horovod_tpu composes those blocks TPU-first: parameters carry
partitioning metadata (Megatron-style tensor parallelism over the
``model`` axis), activations shard batch over ``data`` and optionally
sequence over ``seq`` (ring attention / Ulysses,
``horovod_tpu.parallel.sequence``), and MoE layers route tokens over the
``expert`` axis with all_to_all.

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
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from flax.linen import partitioning as nn_partitioning
from horovod_tpu.jax.introspect import SCOPE_EMBED, SCOPE_LOGITS
from horovod_tpu.parallel.mesh import traced_axis_size

param_with_axes = nn.with_partitioning


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
    # MoE: 0 = dense MLP; >0 = top-1 routed experts over the 'expert' axis.
    num_experts: int = 0
    expert_axis: Optional[str] = None
    remat: bool = False
    # None = auto (one-hot lookup only under manual subgroups, see
    # _use_onehot_embed); True/False forces the lookup style.
    vocab_onehot_lookup: Optional[bool] = None


def _dense_causal_attention(q, k, v, dtype):
    # q, k, v: (B, S, H, D)
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(q.dtype)
    s = scores.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, jnp.asarray(-1e9, scores.dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class SelfAttention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        h, d = cfg.n_heads, cfg.d_model // cfg.n_heads
        init = nn.initializers.normal(0.02)
        wqkv = self.param(
            "wqkv",
            param_with_axes(init, (None, None, "model", None)),
            (3, cfg.d_model, h, d), jnp.float32)
        wo = self.param(
            "wo",
            param_with_axes(init, ("model", None, None)),
            (h, d, cfg.d_model), jnp.float32)
        wqkv = wqkv.astype(cfg.dtype)
        wo = wo.astype(cfg.dtype)
        q = jnp.einsum("bsm,mhd->bshd", x, wqkv[0])
        k = jnp.einsum("bsm,mhd->bshd", x, wqkv[1])
        v = jnp.einsum("bsm,mhd->bshd", x, wqkv[2])
        if cfg.attention == "dense":
            ctx = _dense_causal_attention(q, k, v, cfg.dtype)
        elif cfg.attention == "flash":
            from horovod_tpu.ops.pallas_attention import flash_attention

            ctx = flash_attention(q, k, v, causal=True).astype(cfg.dtype)
        elif cfg.attention == "ring":
            from horovod_tpu.parallel.sequence import ring_attention

            ctx = ring_attention(q, k, v, axis=cfg.seq_axis, causal=True)
        elif cfg.attention == "ulysses":
            from horovod_tpu.parallel.sequence import ulysses_attention

            ctx = ulysses_attention(q, k, v, axis=cfg.seq_axis, causal=True)
        else:
            raise ValueError("Unknown attention impl %r" % (cfg.attention,))
        return jnp.einsum("bshd,hdm->bsm", ctx, wo)


class Mlp(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        wi = self.param("wi", param_with_axes(init, (None, "model")),
                        (cfg.d_model, cfg.d_ff), jnp.float32)
        wo = self.param("wo", param_with_axes(init, ("model", None)),
                        (cfg.d_ff, cfg.d_model), jnp.float32)
        y = x @ wi.astype(cfg.dtype)
        y = nn.gelu(y)
        return y @ wo.astype(cfg.dtype)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        y = nn.LayerNorm(dtype=cfg.dtype, name="ln1")(x)
        x = x + SelfAttention(cfg, name="attn")(y)
        y = nn.LayerNorm(dtype=cfg.dtype, name="ln2")(x)
        if cfg.num_experts > 0:
            from horovod_tpu.parallel.moe import MoeMlp

            x = x + MoeMlp(cfg, name="moe")(y)
        else:
            x = x + Mlp(cfg, name="mlp")(y)
        return x


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens):
        cfg = self.cfg
        init = nn.initializers.normal(0.02)
        embed = self.param(
            "embed", param_with_axes(init, ("model", None)),
            (cfg.vocab_size, cfg.d_model), jnp.float32)
        pos = self.param(
            "pos", param_with_axes(init, (None, None)),
            (cfg.max_seq_len, cfg.d_model), jnp.float32)
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
            s_local = tokens.shape[1]
            if cfg.seq_axis is not None and _axis_bound(cfg.seq_axis):
                # Sequence-sharded (shard_map): this shard holds positions
                # [idx * S_local, (idx+1) * S_local).
                offset = jax.lax.axis_index(cfg.seq_axis) * s_local
                pos_slice = jax.lax.dynamic_slice_in_dim(
                    pos.astype(cfg.dtype), offset, s_local)
            else:
                pos_slice = pos.astype(cfg.dtype)[:s_local]
            x = x + pos_slice[None]
        block = Block
        if cfg.remat:
            block = nn.remat(Block)
        for i in range(cfg.n_layers):
            x = block(cfg, name="layer_%d" % i)(x)
        x = nn.LayerNorm(dtype=cfg.dtype, name="ln_f")(x)
        with jax.named_scope(SCOPE_LOGITS):
            logits = jnp.einsum("bsm,vm->bsv", x, embed.astype(cfg.dtype))
            return logits.astype(jnp.float32)


def get_param_specs(cfg: TransformerConfig, sample_tokens):
    """PartitionSpecs for the parameter pytree, derived from the
    ``with_partitioning`` metadata (consumed by pjit NamedShardings)."""
    model = Transformer(cfg)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), sample_tokens))
    return nn.get_partition_spec(abstract)
