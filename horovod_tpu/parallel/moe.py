"""Mixture-of-Experts layers.

- ``MoeMlp`` — what ``models.Transformer`` calls: top-k routing that
  drops no token. Softmax over the router logits in float32, the k most
  probable experts of each token, a stable sort of the T x k
  (token, expert) pairs by expert, then everything in TOKEN-MAJOR form:
  sorted row r is gathered straight from the (T, M) tokens
  (``tokens[order[r] // k]``), the grouped matmuls run over the ragged
  groups with the row's gate multiplied into their activation (by
  linearity ``sum_j g_j (h_j Wo) = sum_j (g_j h_j) Wo``), and a token's
  output is the plain float32 sum of its k rows. An array of T x k rows
  exists only as an operand or a cotangent of a grouped matmul: nothing
  is broadcast, and backward every gather is a gather (two of the four
  read the (T, M) array), never a scatter-add. No ``(T, E, C)`` tensor
  and no capacity: an expert takes whatever the router sends it.
  GPT-2's block gets GELU experts and one expert a token, where the
  sum over k is the identity; OLMoE's SwiGLU experts and 8 of 64
  (``BlockSpec``).
- ``top1_dispatch`` / ``moe_ffn`` / ``expert_parallel_moe`` — the older
  Switch-style top-1 form with a capacity, which DROPS overflow tokens,
  and its explicit shard_map formulation over the ``expert`` axis (two
  ``all_to_all``s). Nothing in the model calls them any more; they go
  when the four-chip form of ``MoeMlp``'s layer replaces them
  (ROADMAP, D14).
"""

from __future__ import annotations

import functools

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
from horovod_tpu.utils import metrics as _metrics

# Counted at trace time: the gathers of M-wide rows one traced expert
# layer makes, by where (``dispatch_fwd`` / ``combine_fwd`` /
# ``combine_bwd`` / ``dispatch_bwd``) and from what they read:
# ``tokens`` (the (T, M) array) or ``rows`` (a (T x k, M) one).
_M_ROW_GATHERS = _metrics.counter(
    "hvd_moe_row_gathers_total",
    "Gathers of rows per traced expert layer, by site and by the array "
    "they read (counted at trace time, not per device step).",
    ("site", "source"))


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
    router's probabilities of them.

    The gates are read off ``probs`` by a one-hot sum (exact: one term
    and zeros), so their gradient reaches ``probs`` as a select per
    (token, slot, expert) where ``top_k``'s own would be a scatter-add
    of T x k scalars."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    experts = lax.top_k(probs, k)[1] if assignment is None else assignment
    chosen = jax.nn.one_hot(experts, probs.shape[-1], dtype=probs.dtype)
    gates = jnp.sum(probs[:, None, :] * chosen, axis=-1)
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


def _sorted_by(keys, values):
    """``values`` in the order that sorts the permutation ``keys``."""
    return lax.sort((keys, values), num_keys=1)[1]


@jax.custom_vjp
def _permute(values, index, inverse):
    """``values[index]`` of a vector, for a permutation ``index`` whose
    inverse is ``inverse``: carries the T x k gates into sorted order.
    Both ways as a sort on the other permutation's keys: on the chip a
    sort of 32,768 pairs takes 0.025 ms, a gather of as many scalars
    0.28 (PERF.md, PR 29). Rows go through ``_dispatch`` and
    ``_combine``."""
    return _sorted_by(inverse, values)


def _permute_fwd(values, index, inverse):
    return _sorted_by(inverse, values), index


def _permute_bwd(index, d_out):
    return _sorted_by(index, d_out), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _rows_of_tokens(site, tokens, order, k):
    """(T x k, M): sorted row r is the row of token ``order[r] // k``."""
    _M_ROW_GATHERS.labels(site=site, source="tokens").inc()
    return tokens[order // k]


def _sum_per_token(site, rows, inverse, k):
    """(T, M): the float32 sum of each token's k sorted rows, rounded
    once to the rows' dtype."""
    _M_ROW_GATHERS.labels(site=site, source="rows").inc()
    pairs = rows[inverse].reshape(-1, k, rows.shape[-1])
    return jnp.sum(pairs, axis=1, dtype=jnp.float32).astype(rows.dtype)


# Dispatch and combine are each other's transposes, so each one's
# backward pass is the other's forward: two gathers from the (T, M)
# array, two from (T x k, M) rows, no broadcast and no scatter-add.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(tokens, order, inverse, k):
    """The tokens' rows in sorted order, (T x k, M)."""
    return _rows_of_tokens("dispatch_fwd", tokens, order, k)


def _dispatch_fwd(tokens, order, inverse, k):
    return _rows_of_tokens("dispatch_fwd", tokens, order, k), inverse


def _dispatch_bwd(k, inverse, d_rows):
    return _sum_per_token("dispatch_bwd", d_rows, inverse, k), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(rows, order, inverse, k):
    """Each token's sum over its k sorted rows, (T, M)."""
    return _sum_per_token("combine_fwd", rows, inverse, k)


def _combine_fwd(rows, order, inverse, k):
    return _sum_per_token("combine_fwd", rows, inverse, k), order


def _combine_bwd(k, order, d_out):
    return _rows_of_tokens("combine_bwd", d_out, order, k), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def sorted_by_expert(experts):
    """The T x k (token, slot) pairs in a stable order by expert.
    Returns (order, inverse): pair ``order[r]`` sits in sorted row r,
    pair j in sorted row ``inverse[j]``; pair j is token ``j // k``."""
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32)


def grouped_ffn(rows, row_gates, group_sizes, wi, wo, wg=None):
    """Each expert's feed-forward over its own rows, times the row's
    gate: ``rows`` (N, M) sorted by expert, ``row_gates`` (N,) float32,
    ``group_sizes`` (E,) rows each; weights (E, M, F), (E, F, M). With
    ``wg`` the experts are gated (SwiGLU: ``silu(rows wg) * (rows
    wi)``), else GELU. The gate multiplies the activation, F wide, in
    float32, and the product is rounded once to the rows' dtype; the
    gates' gradient is that fusion's reduction over F."""
    up = lax.ragged_dot(rows, wi, group_sizes).astype(jnp.float32)
    if wg is None:
        hidden = nn.gelu(up)
    else:
        hidden = nn.silu(lax.ragged_dot(rows, wg, group_sizes)
                         .astype(jnp.float32)) * up
    hidden = (hidden * row_gates[:, None]).astype(rows.dtype)
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
            rows = _dispatch(tokens, order, inverse, k)
            row_gates = _permute(gates.reshape(-1), order, inverse)
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            out = grouped_ffn(rows, row_gates, counts, wi.astype(cfg.dtype),
                              wo.astype(cfg.dtype), wg)
        with jax.named_scope(SCOPE_MOE_COMBINE):
            out = _combine(out, order, inverse, k)
        if not self.is_initializing():
            self.sow("moe", "load_balance", load_balance)
            self.sow("moe", "z_loss", z_loss)
            self.sow("moe", "tokens_per_expert", counts)
            self.sow("moe", "experts", experts)
        return out.reshape(b, s, m)


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
