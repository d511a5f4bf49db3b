"""Mixture-of-Experts layers.

- ``MoeMlp`` — what ``models.Transformer`` calls: top-k routing that
  drops no token. Softmax over the router logits in float32, the k most
  probable experts of each token, a stable sort of the T x k
  (token, expert) pairs by expert, one gather of rows, grouped matmuls
  over the ragged groups, a gate-weighted sum back per token. No
  ``(T, E, C)`` tensor and no capacity: an expert takes whatever the
  router sends it. GPT-2's block gets GELU experts and one expert a
  token, OLMoE's SwiGLU experts and 8 of 64 (``BlockSpec``).
- ``top1_dispatch`` / ``moe_ffn`` / ``expert_parallel_moe`` — the older
  Switch-style top-1 form with a capacity, which DROPS overflow tokens,
  and its explicit shard_map formulation over the ``expert`` axis (two
  ``all_to_all``s). Nothing in the model calls them any more; they go
  when the four-chip form of ``MoeMlp``'s layer replaces them
  (ROADMAP, D14).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
import flax.linen as nn

from horovod_tpu.jax.introspect import (
    SCOPE_MOE_COMBINE,
    SCOPE_MOE_DISPATCH,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTER,
)
from horovod_tpu.parallel.mesh import EXPERT_AXIS
from horovod_tpu.parallel.mesh import traced_axis_size


def top1_dispatch(router_logits, capacity: int):
    """Switch-style top-1 routing tensors.

    Returns (dispatch (T, E, C) one-hot, combine (T, E, C) gate-weighted).
    Tokens overflowing an expert's capacity are dropped (standard Switch
    behavior).
    """
    t, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # (T, E)
    # Position of each token within its expert's queue.
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (T, E)
    keep = (pos < capacity) * onehot
    pos_clipped = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    cap_onehot = jax.nn.one_hot(pos_clipped, capacity,
                                dtype=jnp.float32)  # (T, E, C)
    dispatch = keep[..., None] * cap_onehot
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def moe_ffn(x, router_w, wi, wo, capacity: int, dtype=jnp.float32):
    """Dense (single-device) MoE forward: the numerical reference.

    x: (T, M); router_w: (M, E); wi: (E, M, F); wo: (E, F, M).
    """
    logits = x @ router_w.astype(dtype)
    dispatch, combine = top1_dispatch(logits, capacity)
    expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(dtype), x)
    h = nn.gelu(jnp.einsum("ecm,emf->ecf", expert_in, wi.astype(dtype)))
    expert_out = jnp.einsum("ecf,efm->ecm", h, wo.astype(dtype))
    return jnp.einsum("tec,ecm->tm", combine.astype(dtype), expert_out)


def expert_parallel_moe(x, router_w, wi_local, wo_local, capacity: int,
                        *, axis=EXPERT_AXIS, dtype=jnp.float32):
    """Expert-parallel MoE forward inside shard_map.

    Per-chip inputs: x (T_local, M) token shard; wi_local/wo_local
    (E/n, ...) expert-weight shards; router_w replicated. Tokens route to
    all E experts; the dispatch all_to_all sends each chip's per-expert
    queues to the expert's owner, the return all_to_all brings results
    back.
    """
    n = traced_axis_size(axis)
    e = router_w.shape[1]
    if e % n:
        raise ValueError("num experts (%d) must divide expert axis (%d)"
                         % (e, n))
    logits = x @ router_w.astype(dtype)
    dispatch, combine = top1_dispatch(logits, capacity)
    expert_in = jnp.einsum("tec,tm->ecm", dispatch.astype(dtype), x)
    # (E, C, M) -> exchange -> (E/n, C*n, M): this chip now holds every
    # chip's queue for its local experts.
    expert_in = lax.all_to_all(expert_in, axis, split_axis=0,
                               concat_axis=1, tiled=True)
    h = nn.gelu(jnp.einsum("ecm,emf->ecf", expert_in,
                           wi_local.astype(dtype)))
    expert_out = jnp.einsum("ecf,efm->ecm", h, wo_local.astype(dtype))
    # Return: (E/n, C*n, M) -> (E, C, M) with each chip's own queue back.
    expert_out = lax.all_to_all(expert_out, axis, split_axis=1,
                                concat_axis=0, tiled=True)
    return jnp.einsum("tec,ecm->tm", combine.astype(dtype), expert_out)


def route(logits, k, assignment=None):
    """(probs (T, E), gates (T, k), experts (T, k)) from router logits:
    softmax in float32 over all experts, then the k largest
    probabilities of each token and their indices, NOT renormalised.
    ``assignment`` (T, k) forces the experts; the gates are still this
    router's probabilities of them."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if assignment is None:
        gates, experts = lax.top_k(probs, k)
    else:
        experts = assignment
        gates = jnp.take_along_axis(probs, experts, axis=-1)
    return probs, gates, experts


def aux_losses(logits, probs, counts):
    """The two auxiliary losses of one expert layer. Load balancing:
    ``E * sum_e f_e P_e`` with ``f_e`` the share of the T x k
    assignments that went to expert e (``counts``, a constant to the
    gradient) and ``P_e`` the mean probability of e; 1.0 when both are
    uniform. Router z-loss: the mean of ``logsumexp(logits) ** 2``
    (OLMoE, arXiv:2409.02060)."""
    e = probs.shape[-1]
    share = counts.astype(jnp.float32) / jnp.sum(counts)
    load_balance = e * jnp.sum(share * jnp.mean(probs, axis=0))
    z_loss = jnp.mean(jnp.square(
        jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)))
    return load_balance, z_loss


@jax.custom_vjp
def _permute(rows, index, inverse):
    """``rows[index]`` for a permutation ``index`` whose inverse is
    ``inverse``: the backward pass is a gather too, not a scatter-add."""
    return rows[index]


def _permute_fwd(rows, index, inverse):
    return rows[index], (index, inverse)


def _permute_bwd(res, d_out):
    index, inverse = res
    return d_out[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def sorted_by_expert(experts):
    """The T x k (token, slot) pairs in a stable order by expert.
    Returns (order, inverse): pair ``order[r]`` sits in sorted row r,
    pair j in sorted row ``inverse[j]``; pair j is token ``j // k``."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    return order, inverse


def grouped_ffn(rows, group_sizes, wi, wo, wg=None):
    """Each expert's feed-forward over its own rows: ``rows`` (N, M)
    sorted by expert, ``group_sizes`` (E,) rows each; weights (E, M, F),
    (E, F, M). With ``wg`` the experts are gated (SwiGLU:
    ``silu(rows wg) * (rows wi)``), else GELU."""
    up = lax.ragged_dot(rows, wi, group_sizes)
    if wg is None:
        hidden = nn.gelu(up)
    else:
        hidden = nn.silu(lax.ragged_dot(rows, wg, group_sizes)) * up
    return lax.ragged_dot(hidden, wo, group_sizes)


class MoeMlp(nn.Module):
    """The expert feed-forward of a transformer block: top-k, dropless
    (module docstring). Expert weights carry ``expert``-axis
    partitioning metadata. ``assignment`` (T, k) forces the routing.

    Sown into the ``moe`` collection on every call outside ``init``:
    ``load_balance`` and ``z_loss`` (``aux_losses``),
    ``tokens_per_expert`` (E,), which sums to T x k whatever the
    imbalance, and ``experts`` (T, k), the choice made. ``sown_stats``
    stacks them over layers; what a step does not use costs nothing."""

    cfg: object  # TransformerConfig

    @nn.compact
    def __call__(self, x, assignment=None):
        cfg = self.cfg
        e, k = cfg.block.num_experts, cfg.block.experts_per_token
        b, s, m = x.shape
        t = b * s
        init = nn.initializers.normal(0.02)
        experts_init = nn.with_partitioning(init, ("expert", None, None))

        wr = self.param("router", nn.with_partitioning(init, (None, None)),
                        (m, e), jnp.float32)
        wi = self.param("wi", experts_init, (e, m, cfg.d_ff), jnp.float32)
        wo = self.param("wo", experts_init, (e, cfg.d_ff, m), jnp.float32)
        wg = None
        if cfg.block.ffn == "swiglu":
            wg = self.param("wg", experts_init, (e, m, cfg.d_ff),
                            jnp.float32).astype(cfg.dtype)

        tokens = x.reshape(t, m)
        with jax.named_scope(SCOPE_MOE_ROUTER):
            # The choice of 8 among 64 is discrete: the logits are made
            # in float32 whatever the compute dtype.
            logits = jnp.dot(tokens.astype(jnp.float32), wr,
                             precision=lax.Precision.HIGHEST)
            probs, gates, experts = route(logits, k, assignment)
            counts = jnp.sum(jax.nn.one_hot(experts.reshape(-1), e,
                                            dtype=jnp.int32), axis=0)
            load_balance, z_loss = aux_losses(logits, probs, counts)
        with jax.named_scope(SCOPE_MOE_DISPATCH):
            order, inverse = sorted_by_expert(experts)
            pairs = jnp.broadcast_to(tokens[:, None], (t, k, m))
            rows = _permute(pairs.reshape(t * k, m), order, inverse)
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            out = grouped_ffn(rows, counts, wi.astype(cfg.dtype),
                              wo.astype(cfg.dtype), wg)
        with jax.named_scope(SCOPE_MOE_COMBINE):
            out = _permute(out, inverse, order).reshape(t, k, m)
            out = jnp.einsum("tk,tkm->tm", gates, out.astype(jnp.float32))
        if not self.is_initializing():
            self.sow("moe", "load_balance", load_balance)
            self.sow("moe", "z_loss", z_loss)
            self.sow("moe", "tokens_per_expert", counts)
            self.sow("moe", "experts", experts)
        return out.astype(cfg.dtype).reshape(b, s, m)


def sown_stats(variables):
    """What the expert layers of one ``Transformer.apply(...,
    mutable=["moe"])`` sowed, stacked over layers in order:
    ``{"load_balance": (L,), "z_loss": (L,), "tokens_per_expert":
    (L, E), "experts": (L, T, k)}``."""
    from flax import traverse_util

    def layer_number(item):     # ('layer_10', 'moe', <name>): 10
        return int(item[0][0].rsplit("_", 1)[1])

    by_name = {}
    for path, (value,) in sorted(
            traverse_util.flatten_dict(variables["moe"]).items(),
            key=layer_number):
        by_name.setdefault(path[-1], []).append(value)
    return {name: jnp.stack(values) for name, values in by_name.items()}
