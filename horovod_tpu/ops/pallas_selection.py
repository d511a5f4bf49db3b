"""The learned selection's choice of keys as ONE Pallas kernel.

A ``sparse_attention`` layer (``models/transformer.py``) scores every
causal (query, key) pair with its indexer, keeps each query's
``index_topk`` best keys, and hands the flash kernels the (S, S) mask as
two bit planes (``pallas_attention.Selection``). The plain definition is
``learned_selection`` + ``pack_selection``: scores, a bisection, a
boolean mask, its transpose and the two packings, with an S x S array
through HBM between every two of them.

Here none of those arrays exists. The grid walks blocks of ``chunk``
queries; for one block the kernel

1. scores the keys up to the block's LAST query, ``_SCORE_ROWS`` of
   them at a time, KEY-major: ``I[s, t] = sum_j w[j, t] relu(k[s] . q[j, t])``
   (``tile_scores``: operands as they come, float32 accumulation,
   float32 weights, the heads added in float32: ``index_scores``'
   arithmetic), a key after its query ``-inf``, and keeps them in a
   VMEM scratch as int32 that ORDER as the floats do;
2. finds each query's exact ``topk``-th largest there by
   ``kth_largest``'s rule, 32 counts of the entries at or above a
   candidate (``narrow``). Key-major, a query is a LANE: a count is a
   sum of whole registers over the keys and the candidates are one row.
   A block whose last query has no more than ``topk`` keys counts
   nothing;
3. writes the block's part of both planes: ``by_key`` (rows are keys,
   lanes queries: the tile's own orientation) by shifts and ORs into an
   output block that stays in VMEM while the queries that share a word
   go by; ``by_query`` from the transposed 128 x 128 pieces, one
   transpose a key group (the block's query groups ride it as bits).

What both planes hold is what ``pack_selection(learned_selection(...))``
holds, bit for bit wherever the float32 sum over the heads is exact;
elsewhere a pair that stands AT a row's threshold may fall on the other
side (the order of that sum is the MXU's and the loop's here, XLA's
there).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.jax.introspect import KERNEL_DSA_CHOOSE, SAVED_FLASH_SELECT
from horovod_tpu.ops import pallas_attention
from horovod_tpu.ops.pallas_attention import _LANES, _NN, _WORD, _dot, Selection
from horovod_tpu.utils.timeline import trace_span

_INT_MIN, _INT_MAX = -(1 << 31), (1 << 31) - 1
# ``-inf`` as ``_ordered`` maps it: below every number's image.
_NEG_INF = (0xff800000 - (1 << 32)) ^ _INT_MAX
# Keys a step of the scores (their sum over the heads, 128 queries wide,
# is 32 registers) and of one count (64 registers pass by). Kernel alone
# on a v5e (PERF.md, PR 42; 1 x 8192 queries, 16 heads of 64, top 2048;
# ms a call; XLA's plain path 7.68): blocks of 512 queries, 256 and 128
# keys 1.537; 128 / 128 1.566; 512 / 128 1.522; 256 / 256 1.946 (the
# count spills); 256 / 64 1.642; blocks of 256 queries 1.776. With no
# block counting (top = S) 0.904: the scores are MXU-bound at half the
# contraction width, the 32 counts three vector operations a register.
_SCORE_ROWS = 256
_COUNT_ROWS = 128


def tile_scores(k_tile, q_ref, w_ref):
    """(keys, queries) float32: the indexer's score of each pair of
    ``k_tile`` (keys, D) with the block's queries ``q_ref`` (J, D,
    queries) under the weights ``w_ref`` (J, queries), the heads added
    one after the other. A 128-lane group of queries at a time: its sum
    over the heads stays in registers."""
    def of_group(lanes):
        acc = jnp.zeros((k_tile.shape[0], _LANES), jnp.float32)
        for j in range(q_ref.shape[0]):
            dots = _dot(k_tile, q_ref[j, :, lanes], _NN)
            acc = acc + w_ref[j:j + 1, lanes] * jnp.maximum(dots, 0.0)
        return acc

    return jnp.concatenate(
        [of_group(slice(i, i + _LANES))
         for i in range(0, q_ref.shape[2], _LANES)], axis=1)


def _ordered(scores):
    """float32 -> int32 that compare as the floats do (``-0.0`` as
    ``+0.0``): ``kth_largest``'s unsigned keys with the top bit
    flipped."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    bits = jnp.where(bits == _INT_MIN, 0, bits)
    return jnp.where(bits < 0, bits ^ _INT_MAX, bits)


def _tree_sum(parts):
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])] \
            + parts[len(parts) // 2 * 2:]
    return parts[0]


def narrow(keys_ref, steps, found, i, k):
    """One step of ``kth_largest``'s bisection for every query (lane)
    of the block: ``found`` (1, queries) int32 holds the bits decided
    so far (as UNSIGNED keys); bit ``31 - i`` stays set where at least
    ``k`` of the first ``steps * _COUNT_ROWS`` rows of ``keys_ref``
    stand at or above the candidate."""
    candidate = found | lax.shift_right_logical(jnp.int32(_INT_MIN), i)
    at = candidate ^ _INT_MIN     # the candidate in the scratch's order
    rows, lanes = _COUNT_ROWS, keys_ref.shape[1]

    def count(t, acc):
        keys = keys_ref[pl.ds(pl.multiple_of(t * rows, rows), rows), :]
        hits = jnp.where(keys >= at, 1, 0)
        return acc + _tree_sum([hits[r:r + 8] for r in range(0, rows, 8)])

    acc = lax.fori_loop(0, steps, count, jnp.zeros((8, lanes), jnp.int32))
    # Counts of at most 2 ** 24 keys: exact in float32.
    total = jnp.sum(acc.astype(jnp.float32), axis=0, keepdims=True)
    return jnp.where(total >= k.astype(jnp.float32), candidate, found)


def _kernel(topk_ref, q_ref, k_ref, w_ref, by_query_ref, by_key_ref,
            keys_ref, *, chunk):
    """Grid (B, S / chunk). ``q_ref`` (J, D, chunk), ``k_ref`` (S, D)
    the batch's whole panel, ``w_ref`` (J, chunk); ``by_query_ref`` (W,
    chunk, 128) this block's rows, ``by_key_ref`` (W, S, 128) the
    batch's whole plane, resident; ``keys_ref`` (S, chunk) int32
    scratch."""
    qi, topk = pl.program_id(1), topk_ref[0]
    groups = chunk // _LANES         # 128-query groups a block
    first = qi * chunk               # the block's first query
    row = lax.broadcasted_iota(jnp.int32, (_SCORE_ROWS, chunk), 0)
    lane = lax.broadcasted_iota(jnp.int32, (_SCORE_ROWS, chunk), 1)

    def score(t, _):
        rows = pl.ds(pl.multiple_of(t * _SCORE_ROWS, _SCORE_ROWS),
                     _SCORE_ROWS)
        keys = _ordered(tile_scores(k_ref[rows, :], q_ref, w_ref))
        # Only the last tiles cross the diagonal; one body all the same.
        keys_ref[rows, :] = jnp.where(row + t * _SCORE_ROWS <= lane + first,
                                      keys, _NEG_INF)

    lax.fori_loop(0, (qi + 1) * (chunk // _SCORE_ROWS), score, None)

    def chosen():
        steps = (qi + 1) * (chunk // _COUNT_ROWS)
        found = lax.fori_loop(
            0, 32, lambda i, found: narrow(keys_ref, steps, found, i, topk),
            jnp.zeros((1, chunk), jnp.int32))
        return found ^ _INT_MIN      # in the scratch's order

    # What a query keeps: the keys at or above this. Every number while
    # the block's last query has no more than ``topk`` keys, and for a
    # query with fewer than ``topk`` (it finds ``-inf``).
    kept_from = jnp.maximum(_NEG_INF + 1, lax.cond(
        first + chunk > topk, chosen,
        lambda: jnp.full((1, chunk), _NEG_INF, jnp.int32)))

    # This block's queries are bits ``shift``.. of word ``word`` of
    # ``by_key``; the first block of a word clears it.
    word = lax.div(qi * groups, jnp.int32(_WORD))
    shift = lax.rem(qi * groups, jnp.int32(_WORD))

    @pl.when(shift == 0)
    def _():
        def clear(t, _):
            by_key_ref[word, pl.ds(pl.multiple_of(t * chunk, chunk), chunk),
                       :] = jnp.zeros((chunk, _LANES), jnp.int32)

        lax.fori_loop(0, by_key_ref.shape[1] // chunk, clear, None)

    by_query_ref[...] = jnp.zeros(by_query_ref.shape, jnp.int32)

    def pack(g, _):
        rows = pl.ds(pl.multiple_of(g * _LANES, _LANES), _LANES)
        kept = keys_ref[rows, :] >= kept_from          # (128 keys, chunk)
        # Bit i of a word: the pair with the block's i-th query group.
        bits = functools.reduce(jnp.bitwise_or, (
            jnp.where(kept[:, i * _LANES:(i + 1) * _LANES], 1 << i, 0)
            for i in range(groups)))
        by_key_ref[word, rows, :] |= bits << shift
        turned = bits.T                                # (128 queries, keys)
        g_word, g_bit = lax.div(g, jnp.int32(_WORD)), lax.rem(
            g, jnp.int32(_WORD))
        for i in range(groups):
            by_query_ref[g_word, i * _LANES:(i + 1) * _LANES, :] |= \
                ((turned >> i) & 1) << g_bit

    lax.fori_loop(0, (qi + 1) * groups, pack, None)


def _vmem_bytes(s, chunk, heads, d, words, dtype):
    """What the call holds in VMEM: the scratch, the resident plane and
    the key panel in two buffers each (lanes padded to 128), the block's
    queries, weights and rows, and a few float32 tiles of temporaries."""
    item = jnp.dtype(dtype).itemsize
    return (s * chunk * 4 + 2 * words * s * _LANES * 4
            + 2 * s * max(d, _LANES) * item
            + 2 * heads * chunk * (d * item + 4)
            + 2 * words * chunk * _LANES * 4
            + 8 * chunk * chunk * 4 + (4 << 20))


def choose(q_i, k_i, w_i, topk, chunk) -> Selection:
    """The two bit planes of ``learned_selection(q_i, k_i, w_i, topk)``
    (queries q_i (B, S, J, D), keys k_i (B, S, D), weights w_i (B, S, J)
    float32; S a multiple of ``chunk``, itself one of ``_SCORE_ROWS``
    and a divisor of a plane's 4096-pair word), a block of
    ``chunk`` queries a pass, named as ``pack_selection`` names them.
    No gradient reaches the operands: a choice has none."""
    q_i, k_i, w_i = map(lax.stop_gradient, (q_i, k_i, w_i))
    s = q_i.shape[1]
    if s % chunk or chunk % _SCORE_ROWS or (_LANES * _WORD) % chunk:
        raise ValueError("%d queries do not divide into blocks of %d, or "
                         "those into steps of %d keys and words of %d"
                         % (s, chunk, _SCORE_ROWS, _LANES * _WORD))
    planes = _choose(jnp.transpose(q_i, (0, 2, 3, 1)), k_i,
                     jnp.swapaxes(w_i, 1, 2),
                     jnp.full((1,), topk, jnp.int32), chunk,
                     pallas_attention._should_interpret(None))
    return Selection(*(checkpoint_name(p, SAVED_FLASH_SELECT)
                       for p in planes))


# Jitted, as ``pallas_gather_sum._gather_sum``: every sparse layer of a
# model traces the same call, once a signature.
@functools.partial(jax.jit, static_argnums=(4, 5))
def _choose(q, k, w, topk, chunk, interpret):
    b, heads, d, s = q.shape
    words = pl.cdiv(s, _LANES * _WORD)
    plane = jax.ShapeDtypeStruct((b, words, s, _LANES), jnp.int32)
    need = _vmem_bytes(s, chunk, heads, d, words, q.dtype)
    with trace_span("kernel", kernel=KERNEL_DSA_CHOOSE):
        return pl.pallas_call(
            functools.partial(_kernel, chunk=chunk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, s // chunk),
                in_specs=[
                    pl.BlockSpec((None, heads, d, chunk),
                                 lambda bi, qi, _: (bi, 0, 0, qi)),
                    pl.BlockSpec((None, s, d), lambda bi, qi, _: (bi, 0, 0)),
                    pl.BlockSpec((None, heads, chunk),
                                 lambda bi, qi, _: (bi, 0, qi)),
                ],
                out_specs=[
                    pl.BlockSpec((None, words, chunk, _LANES),
                                 lambda bi, qi, _: (bi, 0, qi, 0)),
                    pl.BlockSpec((None, words, s, _LANES),
                                 lambda bi, qi, _: (bi, 0, 0, 0)),
                ],
                scratch_shapes=[pltpu.VMEM((s, chunk), jnp.int32)]),
            out_shape=[plane, plane],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=(None if need <= (16 << 20)
                                  else min(need, 100 << 20))),
            interpret=interpret,
            name=KERNEL_DSA_CHOOSE,
        )(topk, q, k, w)
