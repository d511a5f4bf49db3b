"""Mixture-of-Experts layers.

- ``MoeMlp`` — what ``models.Transformer`` calls: top-k routing that
  drops no token. Softmax over the router logits in float32, the k most
  probable experts of each token, a stable sort of the T x k
  (token, expert) pairs by expert, then everything in TOKEN-MAJOR form:
  sorted row r is gathered straight from the (T, M) tokens
  (``tokens[order[r] // k]``), the grouped matmuls run over the ragged
  groups with the row's gate multiplied into their activation (by
  linearity ``sum_j g_j (h_j Wo) = sum_j (g_j h_j) Wo``), and a token's
  output is the plain float32 sum of its LIVE sorted rows, rounded once,
  made by one Pallas kernel (``ops/pallas_gather_sum.py``) that reads
  the chunks of sorted rows a block of tokens owns rows in and adds
  them through a 0/1 matrix on the MXU. An array of T x k rows exists
  only as an operand or a cotangent of a grouped matmul: nothing is
  broadcast, no array of a token's k pairs is made to be summed, and
  backward a gather's transpose is that kernel and the kernel's a
  gather from the (T, M) array, never a scatter-add. What multiplies a
  group's rows by its expert's panel is a Pallas kernel of ours
  (``ops/pallas_grouped_matmul.py``: row tiles walked group by group,
  the live ones only, named where they are called) wherever its tiles
  divide the shape, and ``jax.lax.ragged_dot`` elsewhere. No ``(T, E, C)``
  tensor and no capacity: an expert takes whatever the router sends it.
  GPT-2's block gets GELU experts and one expert a token, where the
  sum over k is the identity; OLMoE's SwiGLU experts and 8 of 64
  (``BlockSpec``). The layer may HOLD fewer experts than it routes over
  (one chip's share of an expert-parallel group, GLM-4.7-Flash's 8 of
  64): the router, its top-k and the gates are over all experts, the
  pairs of the held experts sort first, their rows are the ragged
  groups, and every row past them is a dead row: never multiplied,
  never read back. Such a layer's row arrays are C rows long, a static
  PREFIX of the sorted rows: C is twice the balanced share,
  ``2 T k held / E`` rounded up to a whole 512-row tile (``prefix_rows``;
  arithmetic on the layer's shapes, not a setting). A top-k router CAN
  send every pair to the held experts, so no bound short of T x k drops
  nothing whatever it does: each step, on the device, the layer compares
  the live rows it counted with C (``lax.cond``) and runs the same body
  over the whole length T x k when they overflow the prefix. Nothing is
  dropped or clipped in either, and where both apply they add the same
  numbers in the same order (a token's rows in sorted order, by expert).
  What absent experts would have added is left out; the exchange that
  brings other chips' rows here wraps this layer later (ROADMAP, D14).
  A ``sigmoid_bias`` router (``route``) and shared experts beside the
  routed sum are the same layer's options. The layer is TWO HALVES.
  ``_routing`` reads the ROUTER's input, the router's weight and its
  bias and nothing else: logits, top-k, gates, the count of each
  expert's pairs, the sort's order and inverse, the held experts' group
  sizes and live rows (a ``_Plan``). ``_held_sum`` reads the EXPERTS'
  input, that plan and the experts' weights. With
  ``BlockSpec.router_tap`` 'ffn' the two inputs are one array, the
  norm after the mixer; with 'mixer' (the ``smallthinker`` family's
  "router placed before attention") the router's is the block's normed
  input, made an attention earlier, and the routing half runs under
  ``hvd_moe_preroute``: nothing in it waits for the mixer. Either way
  the backward rule of a layer that holds a share remakes its sorted
  rows from ``tokens, order, inverse, gates``: the experts' input and
  the plan. The gated activation is ``BlockSpec.ffn``'s: ``silu`` (SwiGLU)
  or ``relu`` (ReGLU) on the gate projection.
- ``top1_dispatch`` / ``moe_ffn`` / ``expert_parallel_moe`` — the older
  Switch-style top-1 form with a capacity, which DROPS overflow tokens,
  and its explicit shard_map formulation over the ``expert`` axis (two
  ``all_to_all``s). Nothing in the model calls them any more; they go
  when the four-chip form of ``MoeMlp``'s layer replaces them
  (ROADMAP, D14).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
import flax.linen as nn

from horovod_tpu.jax.introspect import (
    SAVED_MOE_OUT,
    SCOPE_MOE_COMBINE,
    SCOPE_MOE_DISPATCH,
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_PREROUTE,
    SCOPE_MOE_ROUTER,
    SCOPE_MOE_ROWS,
    SCOPE_MOE_SHARED,
)
from horovod_tpu.ops import pallas_gather_sum, pallas_grouped_matmul
from horovod_tpu.parallel.mesh import DATA_AXIS, EXPERT_AXIS
from horovod_tpu.parallel.mesh import traced_axis_size
from horovod_tpu.utils import metrics as _metrics
from horovod_tpu.utils.timeline import trace_span

# Counted at trace time: the gathers of M-wide rows one traced expert
# layer makes, by where (``dispatch_fwd`` / ``combine_bwd``) and from
# what they read: ``tokens`` (the (T, M) array; nothing gathers from
# sorted ``rows`` since the sums below are a kernel).
_M_ROW_GATHERS = _metrics.counter(
    "hvd_moe_row_gathers_total",
    "Gathers of rows per traced expert layer, by site, by the array "
    "they read and by the length of the sorted-row arrays (counted at "
    "trace time, not per device step).",
    ("site", "source", "rows"))
# Also at trace time: the sums over a token's sorted rows one traced
# expert layer makes, by where (``combine_fwd`` / ``dispatch_bwd``), by
# the length of the row arrays and by what makes them: ``kernel``
# (ops/pallas_gather_sum.py, the one form there is; ``xla`` would be a
# gather of T x k rows, a mask and a reduction, and reads 0).
_M_ROW_SUMS = _metrics.counter(
    "hvd_moe_row_sums_total",
    "Sums over a token's sorted rows per traced expert layer, by site, "
    "by the length of the sorted-row arrays and by what makes them "
    "(counted at trace time, not per device step).",
    ("site", "rows", "via"))
# The traced bodies of the expert layer by the length of their sorted-row
# arrays: ``whole`` (T x k rows) or ``prefix`` (``prefix_rows``). A layer
# that holds a share of the experts traces both, forward and backward.
_M_ROW_ARRAYS = _metrics.counter(
    "hvd_moe_row_arrays_total",
    "Traced bodies of the expert layer by the length of their sorted-row "
    "arrays: whole (T x k) or prefix (counted at trace time).", ("rows",))
# Also at trace time: the experts one traced expert layer holds
# (``held``) and routes over (``routed``).
_M_EXPERTS = _metrics.counter(
    "hvd_moe_experts_total",
    "Experts per traced expert layer: those whose weights it holds and "
    "those its router scores (counted at trace time).", ("kind",))
# Also at trace time: the expert layers one traced model makes, by the
# array their router reads (``BlockSpec.router_tap``).
_M_LAYERS = _metrics.counter(
    "hvd_moe_layers_total",
    "Expert layers per traced model, by what their router reads: ffn (the "
    "rows the experts read) or mixer (the block's normed input, known "
    "before the mixer runs); counted at trace time.", ("tap",))


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


def route(logits, k, assignment=None, *, scoring="softmax", bias=None,
          norm_topk=False, scale=1.0):
    """(scores (T, E), gates (T, k), experts (T, k)) from router logits,
    in float32 over all experts. ``scoring`` 'softmax': the k largest
    probabilities of each token and their indices. 'sigmoid_bias': the
    scores are sigmoids, the CHOICE is the top k of ``scores + bias``
    (``bias`` (E,), carried state, no gradient), the gates are the
    chosen experts' scores WITHOUT the bias. ``norm_topk`` divides a
    token's gates by their sum, ``scale`` multiplies them.
    ``assignment`` (T, k) forces the experts; the gates are still this
    router's scores of them.

    The gates are read off the scores by a one-hot sum (exact: one term
    and zeros), so their gradient reaches the scores as a select per
    (token, slot, expert) where ``top_k``'s own would be a scatter-add
    of T x k scalars."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError("Unknown router scoring %r" % (scoring,))
    if assignment is None:
        experts = lax.top_k(scores if bias is None else scores + bias, k)[1]
    else:
        experts = assignment
    chosen = jax.nn.one_hot(experts, scores.shape[-1], dtype=scores.dtype)
    gates = jnp.sum(scores[:, None, :] * chosen, axis=-1)
    if norm_topk:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        gates = gates * scale
    return scores, gates, experts


def corrected_bias(bias, tokens_per_expert, rate):
    """The router's correction bias after one step (DeepSeek-V3's
    auxiliary-loss-free balancing): up by ``rate`` for an expert that
    received fewer (token, slot) pairs than the mean over ALL experts,
    down for one that received more. ``tokens_per_expert`` is what the
    layer sowed, summed over the replicas where there are several
    (``updated_router_bias`` does that)."""
    load = tokens_per_expert.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


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


def _row_arrays(n, pairs):
    """The counters' name for sorted-row arrays ``n`` long of ``pairs``."""
    return "whole" if n == pairs else "prefix"


def _rows_of_tokens(site, tokens, order, k):
    """(n, M): sorted row r is the row of token ``order[r] // k``, for
    the ``n`` sorted rows ``order`` names."""
    _M_ROW_GATHERS.labels(
        site=site, source="tokens",
        rows=_row_arrays(order.shape[0], tokens.shape[0] * k)).inc()
    return tokens[order // k]


def _sum_per_token(site, rows, visits, t, k):
    """(T, M): the float32 sum of each token's live sorted rows, rounded
    once to the rows' dtype, by the kernel of ops/pallas_gather_sum.py
    over ``visits`` (its ``plan`` of these rows): what a dead row holds
    is never added, and no array of T x k rows is made. ``rows`` may be
    a prefix of the T x k sorted rows that holds every live one."""
    _M_ROW_SUMS.labels(site=site, rows=_row_arrays(rows.shape[0], t * k),
                       via="kernel").inc()
    return pallas_gather_sum.gather_sum(rows, visits, t)


# Dispatch and combine are each other's transposes, so each one's
# backward pass is the other's forward: two gathers from the (T, M)
# array, two sums over a token's sorted rows, no broadcast and no
# scatter-add. ``order`` names the sorted rows the arrays hold (all
# T x k, or a prefix with every live one); ``visits`` is the sums' plan
# over them, which knows the live rows.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(tokens, order, visits, k, t):
    """The ``t`` tokens' rows in sorted order, (n, M)."""
    return _rows_of_tokens("dispatch_fwd", tokens, order, k)


def _dispatch_fwd(tokens, order, visits, k, t):
    return _rows_of_tokens("dispatch_fwd", tokens, order, k), visits


def _dispatch_bwd(k, t, visits, d_rows):
    return (_sum_per_token("dispatch_bwd", d_rows, visits, t, k), None,
            None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(rows, order, visits, k, t):
    """Each of the ``t`` tokens' sum over its sorted rows, (T, M)."""
    return _sum_per_token("combine_fwd", rows, visits, t, k)


def _combine_fwd(rows, order, visits, k, t):
    return _sum_per_token("combine_fwd", rows, visits, t, k), order


def _combine_bwd(k, t, order, d_out):
    return _rows_of_tokens("combine_bwd", d_out, order, k), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def sorted_by_expert(experts, first=0, num_experts=None):
    """The T x k (token, slot) pairs in a stable order by expert, the
    experts counted from ``first`` round ``num_experts`` (so that the
    experts a layer holds, ``first`` onward, sort first).
    Returns (order, inverse): pair ``order[r]`` sits in sorted row r,
    pair j in sorted row ``inverse[j]``; pair j is token ``j // k``."""
    flat = experts.reshape(-1)
    if first:
        flat = (flat - first) % num_experts
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32)


def _grouped_matmul(lhs, rhs, group_sizes):
    """Each group's rows of ``lhs`` (N, K) times its panel of ``rhs``
    (E, K, F), the rows past the last group left unwritten: the kernels
    of ops/pallas_grouped_matmul.py where their tiles divide the shape
    (every cell of the benchmark), ``lax.ragged_dot`` where they do not
    (odd widths, a few rows: the definition they are tested against)."""
    if pallas_grouped_matmul.divides(lhs.shape, rhs.shape):
        return pallas_grouped_matmul.grouped_matmul(lhs, rhs, group_sizes)
    pallas_grouped_matmul.M_GROUPED_MATMULS.labels(kind="forward",
                                                   via="xla").inc()
    return lax.ragged_dot(lhs, rhs, group_sizes)


# The activation on a gated feed-forward's gate projection, by
# ``BlockSpec.ffn`` (``grouped_ffn`` here, ``Mlp`` in models/transformer.py).
GATE_ACTIVATIONS = {"swiglu": nn.silu, "reglu": nn.relu}


def grouped_ffn(rows, row_gates, group_sizes, wi, wo, wg=None, live=None,
                *, ffn):
    """Each expert's feed-forward over its own rows, times the row's
    gate: ``rows`` (N, M) sorted by expert, ``row_gates`` (N,) float32,
    ``group_sizes`` (E,) rows each; weights (E, M, F), (E, F, M). Three
    activations: without ``wg`` GELU, ``gelu(rows wi)``; with it the
    experts are gated, by ``ffn`` SwiGLU, ``silu(rows wg) * (rows wi)``,
    or ReGLU, ``relu(rows wg) * (rows wi)``. The gate multiplies the
    activation, F wide, in float32, and the product is rounded once to
    the rows' dtype; the gates' gradient is that fusion's reduction
    over F.

    ``live`` = ``sum(group_sizes)`` where that is below N: the rows past
    it belong to no group and a grouped matmul leaves its result there
    UNWRITTEN, forward and in its input's gradient. So both up
    projections are zeroed there before the activation reads them, and
    so is ``hidden``, whose select's transpose zeroes the gradient the
    down projection hands back. The caller masks the two ends
    (``_combine`` forward, ``_dispatch`` backward)."""
    def live_rows(x):
        if live is None:
            return x
        row = lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
        return jnp.where(row < live, x, 0)

    up = live_rows(_grouped_matmul(rows, wi, group_sizes)).astype(jnp.float32)
    if wg is None:
        hidden = nn.gelu(up)
    else:
        hidden = GATE_ACTIVATIONS[ffn](
            live_rows(_grouped_matmul(rows, wg, group_sizes))
            .astype(jnp.float32)) * up
    hidden = live_rows((hidden * row_gates[:, None]).astype(rows.dtype))
    return _grouped_matmul(hidden, wo, group_sizes)


# The grouped matmuls' row tile: the prefix is a whole number of them.
_ROW_TILE = 512


def prefix_rows(t, k, held, e):
    """C, the static length of the sorted-row arrays of a layer that
    holds ``held`` of ``e`` experts: twice the balanced share of the
    ``t * k`` pairs, rounded up to a whole row tile; ``t * k`` where that
    is no shorter (every expert held, or too few rows for a tile)."""
    return min(-(-2 * t * k * held // (e * _ROW_TILE)) * _ROW_TILE, t * k)


def _expert_rows(n, k, tokens, order, inverse, gates, sizes, live, wi, wo,
                 wg, ffn):
    """(T, M): each token's gate-weighted sum over the experts held
    here, through sorted-row arrays ``n`` long: all T x k pairs, or a
    prefix that holds the ``live`` ones. ``gates`` (T, k) float32;
    ``sizes`` the held experts' rows; the weights are used in the
    tokens' dtype."""
    _M_ROW_ARRAYS.labels(rows=_row_arrays(n, order.shape[0])).inc()
    t, m = tokens.shape
    with jax.named_scope(SCOPE_MOE_DISPATCH):
        head = order[:n]      # (a slice of the whole length traces nothing)
        # Shared by the two sums over a token's rows: combine forward
        # and dispatch backward.
        visits = pallas_gather_sum.plan(head, k, live, t, m, tokens.dtype,
                                        sizes.shape[0])
        rows = _dispatch(tokens, head, visits, k, t)
        row_gates = _permute(gates.reshape(-1), order, inverse)[:n]
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        out = grouped_ffn(rows, row_gates, sizes, wi.astype(rows.dtype),
                          wo.astype(rows.dtype),
                          wg if wg is None else wg.astype(rows.dtype), live,
                          ffn=ffn)
    with jax.named_scope(SCOPE_MOE_COMBINE):
        return _combine(out, head, visits, k, t)


def _rows_branch(n, k, ffn):
    """``_expert_rows`` over ``n`` rows as one branch of the choice.

    Two names a device trace's readers need (``instruction_scopes``).
    The weights pass a barrier under the experts' scope: what enters a
    branch from outside would bring the ``cond``'s name, no part's, to
    whatever the compiler names after an operand (a cast it moves into
    the branch; ``lax.ragged_dot``'s own Mosaic calls, which carry no
    name and are filed under their largest operand's, where a shape
    keeps that path); the barrier runs nothing. And the whole
    is under the choice's scope once more: differentiated inside
    ``_held_rows_bwd``, the transform's name wraps THIS segment
    (``transpose(jvp(hvd_moe_rows))``) and the parts' below it stay
    what those readers look for."""
    def branch(tokens, order, inverse, gates, sizes, live, *weights):
        with jax.named_scope(SCOPE_MOE_ROWS):
            with jax.named_scope(SCOPE_MOE_EXPERTS):
                weights = lax.optimization_barrier(weights)
            return _expert_rows(n, k, tokens, order, inverse, gates, sizes,
                                live, *weights, ffn)
    return branch


# A layer that holds a share of the experts chooses its row arrays'
# length on the device, each step: the prefix where the live rows fit
# it, the whole length where they overflow. ONE differentiation rule
# round the choice, whose residuals are its inputs: a ``lax.cond``
# differentiated from outside hands back the union of both branches'
# residuals, the untaken branch's as freshly written zeros, which would
# put whole-length writes back into the prefix's path. The backward
# pass makes the choice again and recomputes the branch it takes (under
# a block's recomputation the outer recomputed forward is then dead
# code; where the block reads the layer's output again, a norm on it, it
# keeps that output: ``SAVED_MOE_OUT``).
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 11))
def _held_rows(c, k, tokens, order, inverse, gates, sizes, live, wi, wo, wg,
               ffn):
    with jax.named_scope(SCOPE_MOE_ROWS):
        return lax.cond(
            live <= c, _rows_branch(c, k, ffn),
            _rows_branch(order.shape[0], k, ffn),
            tokens, order, inverse, gates, sizes, live, wi, wo, wg)


def _held_rows_fwd(c, k, *operands_and_ffn):
    *operands, ffn = operands_and_ffn
    return _held_rows(c, k, *operands, ffn), tuple(operands)


def _held_rows_bwd(c, k, ffn, operands, d_out):
    tokens, order, inverse, gates, sizes, live, *weights = operands

    def gradients(n):
        def rows(tokens, gates, *weights):
            return _rows_branch(n, k, ffn)(tokens, order, inverse, gates,
                                           sizes, live, *weights)
        return lambda d_out, *floating: jax.vjp(rows, *floating)[1](d_out)

    with jax.named_scope(SCOPE_MOE_ROWS):
        d_tokens, d_gates, *d_weights = lax.cond(
            live <= c, gradients(c), gradients(order.shape[0]),
            d_out, tokens, gates, *weights)
    # The weight gradients leave the choice as the grouped matmuls made
    # them, in the compute dtype. Without the barrier the compiler moves
    # their conversion to the parameters' float32 INTO both branches, a
    # pass over each (held, M, F) array that the optimizer's fusion
    # otherwise does as it reads (the compiled GLM-4.7-Flash step handed
    # each gradient back twice, 50 + 100 MB).
    return (d_tokens, None, None, d_gates, None, None,
            *lax.optimization_barrier(d_weights))


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


class _Plan(NamedTuple):
    """What the ROUTING half of an expert layer hands the expert half:
    everything that depends on the router's input alone. ``gates`` (T,
    k) float32 and ``experts`` (T, k), over all experts; ``counts`` (E,)
    pairs each expert received; ``order`` / ``inverse``, the pairs
    sorted by expert, held experts first (``sorted_by_expert``);
    ``sizes``, the held experts' rows; ``live``, their sum (None where
    every expert is held); ``aux``, the softmax router's two auxiliary
    losses, else None."""

    gates: jax.Array
    experts: jax.Array
    counts: jax.Array
    order: jax.Array
    inverse: jax.Array
    sizes: jax.Array
    live: Optional[jax.Array]
    aux: Optional[tuple]


def _routing(spec, tokens, wr, bias, assignment):
    """The ROUTING half: the ``_Plan`` of ``tokens`` (T, M), the array
    the router reads. Reads the router's weight and bias and nothing of
    the experts; differentiable through the gates alone."""
    e, k = spec.num_experts, spec.experts_per_token
    held, first = spec.experts_held or e, spec.first_expert_held
    with jax.named_scope(SCOPE_MOE_ROUTER):
        # The choice of k among E is discrete: the logits are made
        # in float32 whatever the compute dtype.
        logits = jnp.dot(tokens.astype(jnp.float32), wr,
                         precision=lax.Precision.HIGHEST)
        scores, gates, experts = route(
            logits, k, assignment, scoring=spec.router, bias=bias,
            norm_topk=spec.norm_topk, scale=spec.routed_scale)
        counts = jnp.sum(jax.nn.one_hot(experts.reshape(-1), e,
                                        dtype=jnp.int32), axis=0)
        aux = None
        if spec.router == "softmax":
            aux = aux_losses(logits, scores, counts)
        # The held experts' rows are the first ``live`` sorted rows,
        # in ``held`` ragged groups; all T x k where all are held.
        sizes, live = counts, None
        if held < e:
            sizes = counts[first:first + held]
            live = jnp.sum(sizes)
    with jax.named_scope(SCOPE_MOE_DISPATCH):
        order, inverse = sorted_by_expert(experts, first, e)
    return _Plan(gates, experts, counts, order, inverse, sizes, live, aux)


def _held_sum(cfg, plan, tokens, wi, wo, wg):
    """The EXPERT half: (T, M), each of ``tokens``' gate-weighted sum
    over the experts held here under ``plan``, and whether the live
    rows overflowed the prefix. ``tokens`` are the rows the experts
    read, which need not be the array the plan was made from."""
    spec = cfg.block
    e, k = spec.num_experts, spec.experts_per_token
    t = tokens.shape[0]
    # The sorted-row arrays' length, and whether it is chosen each
    # step between it and the whole T x k.
    c = prefix_rows(t, k, spec.experts_held or e, e)
    if c == t * k:
        return _expert_rows(t * k, k, tokens, plan.order, plan.inverse,
                            plan.gates, plan.sizes, plan.live, wi, wo, wg,
                            spec.ffn), 0
    # Cast before the choice: a branch hands its weight
    # gradients back in the compute dtype.
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        wi, wo, wg = (w if w is None else w.astype(cfg.dtype)
                      for w in (wi, wo, wg))
    # Named for a block's recomputation to keep where it reads
    # the output again (models/transformer.py ``_remat_block``).
    out = checkpoint_name(
        _held_rows(c, k, tokens, plan.order, plan.inverse, plan.gates,
                   plan.sizes, plan.live, wi, wo, wg, spec.ffn),
        SAVED_MOE_OUT)
    return out, (plan.live > c).astype(jnp.int32)


class MoeMlp(nn.Module):
    """The expert feed-forward of a transformer block: top-k, dropless
    (module docstring). Expert weights carry ``expert``-axis
    partitioning metadata. ``assignment`` (T, k) forces the routing.

    The layer is TWO halves. The routing half (``_routing``) reads the
    router's input, the router's weight and its bias, and makes the
    plan: logits in float32, the top k, the gates, the pairs each
    expert received, the order that sorts the pairs by expert with its
    inverse, the held experts' group sizes and their live rows. The
    expert half (``_held_sum``) reads the experts' input, the plan and
    the experts' weights: rows gathered in the plan's order, the grouped
    matmuls with the gates inside their activation, each token's sum.
    The block hands in ``mixer_input``, its normed INPUT (what its mixer
    reads too), and ``BlockSpec.router_tap`` says which array the router
    reads. With 'ffn' both halves read ``x`` and ``mixer_input`` is not
    looked at. With 'mixer' the router reads ``mixer_input``: the plan
    then depends on nothing the mixer makes, the routing half runs under
    ``hvd_moe_preroute`` (still inside this module's scope: the weight
    stays ``moe/router``), and the router's gradient reaches the
    block's input through ``mixer_input`` alone. The backward rule of a
    layer that holds a share (``_held_rows``) remakes its sorted rows
    from ``tokens, order, inverse, gates``: the plan is its residual,
    whichever array made it.

    With ``BlockSpec.experts_held`` the weights are those of experts
    ``first_expert_held`` onward and the output is THEIR part of the
    routed sum. ``shared`` is a feed-forward that every token goes
    through beside the routed sum (the model's block hands in a dense
    one of ``shared_experts`` experts' width, made with ``parent=None``
    so that its weights live under this layer's ``shared``). A
    ``sigmoid_bias`` router reads its correction bias from the
    ``moe_state`` collection (``router_bias`` (E,), zeros at ``init``);
    ``updated_router_bias`` makes the next step's.

    Sown into the ``moe`` collection on every call outside ``init``:
    ``tokens_per_expert`` (E,), which sums to T x k whatever the
    imbalance, ``rows_held``, the pairs whose expert is held here,
    ``rows_overflow``, 1 where they overflowed the prefix and the step
    ran the whole length (0 else, and wherever no choice is made),
    ``experts`` (T, k), the choice made, and under the softmax router
    ``load_balance`` and ``z_loss`` (``aux_losses``). ``sown_stats``
    stacks them over layers; what a step does not use costs nothing."""

    cfg: object  # TransformerConfig
    shared: Optional[nn.Module] = None

    @nn.compact
    def __call__(self, x, assignment=None, mixer_input=None):
        cfg, spec = self.cfg, self.cfg.block
        e = spec.num_experts
        held = spec.experts_held or e
        tap = spec.router_tap
        _M_EXPERTS.labels(kind="held").inc(held)
        _M_EXPERTS.labels(kind="routed").inc(e)
        _M_LAYERS.labels(tap=tap).inc()
        with trace_span("experts", held=held, routed=e, tap=tap,
                        ffn=spec.ffn):
            b, s, m = x.shape
            t = b * s
            init = nn.initializers.normal(0.02)
            experts_init = nn.with_partitioning(init, ("expert", None, None))

            wr = self.param("router", nn.with_partitioning(init, (None, None)),
                            (m, e), jnp.float32)
            wi = self.param("wi", experts_init, (held, m, cfg.d_ff),
                            jnp.float32)
            wo = self.param("wo", experts_init, (held, cfg.d_ff, m),
                            jnp.float32)
            wg = None
            if spec.ffn in GATE_ACTIVATIONS:
                wg = self.param("wg", experts_init, (held, m, cfg.d_ff),
                                jnp.float32)
            bias = None
            if spec.router == "sigmoid_bias":
                bias = self.variable("moe_state", "router_bias", jnp.zeros,
                                     (e,), jnp.float32).value

            tokens = x.reshape(t, m)
            if tap == "mixer":
                with jax.named_scope(SCOPE_MOE_PREROUTE):
                    plan = _routing(spec, mixer_input.reshape(t, m), wr,
                                    bias, assignment)
            else:
                plan = _routing(spec, tokens, wr, bias, assignment)
            out, rows_overflow = _held_sum(cfg, plan, tokens, wi, wo, wg)
            if self.shared is not None:
                with jax.named_scope(SCOPE_MOE_SHARED):
                    out = out + self.shared(tokens)
            if not self.is_initializing():
                if plan.aux is not None:
                    self.sow("moe", "load_balance", plan.aux[0])
                    self.sow("moe", "z_loss", plan.aux[1])
                self.sow("moe", "tokens_per_expert", plan.counts)
                self.sow("moe", "rows_held", t * spec.experts_per_token
                         if plan.live is None else plan.live)
                self.sow("moe", "rows_overflow", rows_overflow)
                self.sow("moe", "experts", plan.experts)
            return out.reshape(b, s, m)


def _layer_number(path):     # ('layer_10', 'moe', <name>): 10
    return int(path[0].rsplit("_", 1)[1])


def updated_router_bias(state, tokens_per_expert, rate, axis=DATA_AXIS):
    """The ``moe_state`` collection after one step: each expert layer's
    ``router_bias`` through ``corrected_bias`` with that layer's row of
    ``tokens_per_expert`` ((L, E), ``sown_stats``' order). The rule is
    over the step's whole batch: where the trace binds ``axis`` (the
    mesh axes the batch is split over) with more than one chip, the
    counts are summed over it first, so that every replica carries the
    same bias on; otherwise nothing is traced for it."""
    from flax import traverse_util

    try:
        if traced_axis_size(axis) > 1:
            tokens_per_expert = lax.psum(tokens_per_expert, axis)
    except NameError:       # not inside a shard_map over ``axis``
        pass
    flat = traverse_util.flatten_dict(state)
    for row, path in enumerate(sorted(flat, key=_layer_number)):
        flat[path] = corrected_bias(flat[path], tokens_per_expert[row], rate)
    return traverse_util.unflatten_dict(flat)


def sown_stats(variables):
    """What the expert layers of one ``Transformer.apply(...,
    mutable=["moe"])`` sowed, stacked over the expert layers in order:
    ``{"tokens_per_expert": (L, E), "rows_held": (L,), "rows_overflow":
    (L,), "experts": (L, T, k)}`` and, under the softmax router,
    ``{"load_balance": (L,), "z_loss": (L,)}``."""
    from flax import traverse_util

    by_name = {}
    for path, (value,) in sorted(
            traverse_util.flatten_dict(variables["moe"]).items(),
            key=lambda item: _layer_number(item[0])):
        by_name.setdefault(path[-1], []).append(value)
    return {name: jnp.stack(values) for name, values in by_name.items()}
