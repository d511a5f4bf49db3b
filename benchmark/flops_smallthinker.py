"""Operations the ``smallthinker`` family's decoder (SmallThinker-21BA3B)
needs as ONE chip's share of an expert-parallel group, from shapes
alone; the counting rules are ``flops.py``'s (a multiply-accumulate is 2
operations; no recomputation, only the (query, key) pairs the MASK
keeps, no elementwise pass, no lookup): a matmul's backward is two of
its size, attention's is the FIVE products of ``flops.attention_work``
beside the forward's two (3.5 times the forward); a window layer's
pairs are ``flops_afmoe.window_pairs`` (``layer_attention_work`` is that
module's); the expert layer is counted as
``flops_glm.py`` counts GLM's, at the balanced load, three matmuls a
gated expert (a ReLU gate spares no operation that is REQUIRED: which
products it zeroes is known only once the gate projection is made).

New here: EVERY layer is an expert layer with no shared expert and no
dense sibling, the attention has no gate projection (q, k, v, o alone),
and the router's outputs are read off the block's input, which moves no
operation.
"""

from __future__ import annotations

from benchmark.flops import matmul_ops
# One attention layer's required work by its kind (a window layer its
# window's pairs, grouped key/value panels): Trinity's, unchanged.
from benchmark.flops_afmoe import layer_attention_work
from benchmark.flops_glm import expert_layer_forward_ops


def projection_forward_ops(seq_len, *, hidden, n_head, n_kv, head_dim):
    """The four projections of one attention layer over one sequence: q
    and output ``n_head * head_dim`` wide, k and v ``n_kv * head_dim``."""
    return (2 * matmul_ops(seq_len, hidden, n_head * head_dim)
            + 2 * matmul_ops(seq_len, hidden, n_kv * head_dim))


def smallthinker_step_ops(batch, seq_len, *, vocab, kinds, window, hidden,
                          n_head, n_kv, head_dim, expert_width, k, held,
                          routed):
    """Forward + backward of ``batch`` sequences through the layers of
    ``kinds`` (``sliding_attention`` or ``full_attention`` each); the
    untied output head over the vocabulary held here is one forward
    matmul."""
    heads = dict(n_head=n_head, n_kv=n_kv, head_dim=head_dim)
    matmuls = (len(kinds) * (
        projection_forward_ops(seq_len, hidden=hidden, **heads)
        + expert_layer_forward_ops(
            seq_len, hidden=hidden, expert_width=expert_width, k=k,
            held=held, routed=routed, shared=0))
        + matmul_ops(seq_len, hidden, vocab))
    attention = sum(
        sum(ops for ops, _ in layer_attention_work(
            1, seq_len, kind, window=window, **heads).values())
        for kind in kinds)
    return batch * (3 * matmuls + attention)
