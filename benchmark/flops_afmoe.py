"""Operations and bytes the ``afmoe`` family's decoder (Trinity-Mini)
needs as ONE chip's share of an expert-parallel group, from shapes
alone; the counting rules are ``flops.py``'s (a multiply-accumulate is 2
operations; no recomputation, only the (query, key) pairs the MASK
keeps, no elementwise pass, no lookup) and the expert layer is counted
as ``flops_glm.py`` counts GLM's: at the balanced load.

New here: a sliding-window layer keeps ``window_pairs`` of a full
layer's ``causal_pairs``, and the key/value panels are ``n_kv`` heads
wide where q's are ``n_head`` (``flops.attention_work`` takes both).
"""

from __future__ import annotations

from benchmark.flops import attention_work, causal_pairs, matmul_ops
from benchmark.flops_glm import expert_layer_forward_ops, swiglu_forward_ops

SLIDING = "sliding_attention"


def window_pairs(seq_len, window):
    """(query, key) pairs a causal mask with a window keeps: query i
    sees ``min(i + 1, window)`` keys; ``window`` None is no window."""
    if window is None or window >= seq_len:
        return causal_pairs(seq_len)
    return window * seq_len - window * (window - 1) // 2


def attention_forward_ops(seq_len, *, hidden, n_head, n_kv, head_dim,
                          window=None):
    """One attention layer over one sequence: q, gate and output
    projections ``n_head * head_dim`` wide, k and v ``n_kv * head_dim``,
    and the two attention matmuls over the visible pairs."""
    wide, narrow = n_head * head_dim, n_kv * head_dim
    projections = (3 * matmul_ops(seq_len, hidden, wide)
                   + 2 * matmul_ops(seq_len, hidden, narrow))
    attention = n_head * 2 * 2 * window_pairs(seq_len, window) * head_dim
    return projections + attention


def afmoe_step_ops(batch, seq_len, *, vocab, kinds, window, n_dense,
                   dense_width, hidden, n_head, n_kv, head_dim, expert_width,
                   k, held, routed, shared):
    """Forward + backward of ``batch`` sequences through the layers of
    ``kinds`` (one ``layer_types`` entry each): every matmul's backward
    is two of its size; the untied output head over the vocabulary held
    here is one forward matmul."""
    attention = sum(attention_forward_ops(
        seq_len, hidden=hidden, n_head=n_head, n_kv=n_kv, head_dim=head_dim,
        window=window if kind == SLIDING else None) for kind in kinds)
    forward = (attention
               + n_dense * swiglu_forward_ops(seq_len, hidden, dense_width)
               + (len(kinds) - n_dense) * expert_layer_forward_ops(
                   seq_len, hidden=hidden, expert_width=expert_width, k=k,
                   held=held, routed=routed, shared=shared)
               + matmul_ops(seq_len, hidden, vocab))
    return 3 * batch * forward


def layer_attention_work(batch, seq_len, kind, *, n_head, n_kv, head_dim,
                         window):
    """``flops.attention_work`` of ONE attention layer of ``kind``: the
    pairs its mask keeps (a ``sliding_attention`` layer its window's),
    q.k and v both ``head_dim`` wide."""
    return attention_work(
        window_pairs(seq_len, window if kind == SLIDING else None), seq_len,
        batch=batch, n_head=n_head, n_kv=n_kv, d=head_dim, d_v=head_dim)
