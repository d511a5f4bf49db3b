"""Operations and bytes Keye-VL-2.0's language model needs as ONE chip's
share of an expert-parallel group, from shapes alone; the counting
rules are ``flops.py``'s (a multiply-accumulate is 2 operations; no
recomputation, only the (query, key) pairs the MASK keeps, no
elementwise pass, no lookup) and the expert layer is counted as
``flops_glm.py`` counts GLM's: at the balanced load.

New here: the mask is a learned selection. A query keeps ``min(t + 1,
topk)`` keys (ties apart), which is the count a sliding window of
``topk`` keeps; the attention matmuls are counted over THOSE pairs,
however the kernels honour the mask. The indexer that chooses them
scores ALL causal pairs, forward only: nothing differentiates it.
"""

from __future__ import annotations

from benchmark.flops import causal_pairs, matmul_ops
from benchmark.flops_afmoe import window_pairs
from benchmark.flops_glm import expert_layer_forward_ops


def kept_pairs(seq_len, topk):
    """(query, key) pairs a selection of ``topk`` keeps of one plane."""
    return window_pairs(seq_len, topk)


def attention_forward_ops(seq_len, *, hidden, n_head, n_kv, head_dim, topk):
    """One attention layer WITHOUT its indexer: q and output projections
    ``n_head * head_dim`` wide, k and v ``n_kv * head_dim``, and the two
    attention matmuls over the kept pairs."""
    wide, narrow = n_head * head_dim, n_kv * head_dim
    return (2 * matmul_ops(seq_len, hidden, wide)
            + 2 * matmul_ops(seq_len, hidden, narrow)
            + n_head * 2 * 2 * kept_pairs(seq_len, topk) * head_dim)


def indexer_forward_ops(seq_len, *, hidden, index_heads, index_dim):
    """The indexer of one layer over one sequence: its three projections
    and ``index_heads`` dot products ``index_dim`` long for every causal
    pair."""
    return (matmul_ops(seq_len, hidden, index_heads * index_dim)
            + matmul_ops(seq_len, hidden, index_dim)
            + matmul_ops(seq_len, hidden, index_heads)
            + causal_pairs(seq_len) * index_heads * index_dim * 2)


def keye_step_ops(batch, seq_len, *, vocab, n_layer, hidden, n_head, n_kv,
                  head_dim, topk, index_heads, index_dim, expert_width, k,
                  held, routed):
    """Forward + backward of ``batch`` sequences: every matmul's
    backward is two of its size, but the indexer's, which has none; the
    untied output head over the vocabulary held here is one forward
    matmul."""
    layer = (attention_forward_ops(
        seq_len, hidden=hidden, n_head=n_head, n_kv=n_kv, head_dim=head_dim,
        topk=topk) + expert_layer_forward_ops(
            seq_len, hidden=hidden, expert_width=expert_width, k=k,
            held=held, routed=routed, shared=0))
    indexer = indexer_forward_ops(seq_len, hidden=hidden,
                                  index_heads=index_heads,
                                  index_dim=index_dim)
    return batch * (3 * (n_layer * layer + matmul_ops(seq_len, hidden, vocab))
                    + n_layer * indexer)


def plane_bytes(batch, seq_len):
    """The bit plane that carries one layer's selection in, read once a
    direction: ``seq_len`` rows of ``ceil(seq_len / 4096)`` words of 128
    lanes of int32."""
    return batch * seq_len * -(-seq_len // 4096) * 128 * 4


def index_work(batch, seq_len, *, index_heads, index_dim, itemsize=2):
    """(operations, HBM bytes) of ONE layer's scores and selection, the
    least any algorithm needs: the dot products of every causal pair;
    ``qI``, ``kI`` and ``w`` read once, and one pass over a row's
    float32 scores (written, then read by the selection)."""
    ops = batch * causal_pairs(seq_len) * index_heads * index_dim * 2
    operands = batch * seq_len * (
        index_heads * index_dim + index_dim + index_heads) * itemsize
    return ops, operands + 2 * batch * causal_pairs(seq_len) * 4
