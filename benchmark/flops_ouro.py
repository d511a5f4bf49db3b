"""Operations the ``ouro`` family's looped decoder (Ouro-2.6B) needs in
one training step, from shapes alone; the counting rules are
``flops.py``'s (a multiply-accumulate is 2 operations; no recomputation,
only the causal (query, key) pairs, no elementwise pass, no lookup).

New here: the ONE stack of ``n_layer`` blocks is applied ``passes``
times a step, so the blocks' work counts ``n_layer x passes`` block
APPLICATIONS, and each pass is read out through the head, so the
vocabulary-sized projection counts ``passes`` times. A projection's
backward is two matmuls of its size (3 x forward); attention's forward
is TWO products over the causal pairs and its backward FIVE
(``flops.attention_work``), 3.5 x forward. The exit gate is one dot of
``hidden`` a position and pass.
"""

from __future__ import annotations

from benchmark.flops import causal_pairs, matmul_ops


def block_projection_ops(seq_len, *, hidden, n_head, head_dim, width):
    """One application of one block to one sequence, forward: q, k, v
    and output projections ``n_head * head_dim`` wide, the SwiGLU's gate,
    up and down ``width`` wide."""
    return (4 * matmul_ops(seq_len, hidden, n_head * head_dim)
            + 3 * matmul_ops(seq_len, hidden, width))


def block_attention_ops(seq_len, *, n_head, head_dim):
    """One application's two attention products over the causal pairs,
    forward."""
    return n_head * 2 * 2 * causal_pairs(seq_len) * head_dim


def block_pass_ops(seq_len, **sizes):
    """One application of one block, forward + backward."""
    heads = {k: sizes[k] for k in ("n_head", "head_dim")}
    return (3 * block_projection_ops(seq_len, **sizes)
            + 7 * block_attention_ops(seq_len, **heads) // 2)


def readout_ops(batch, seq_len, *, vocab, hidden, passes):
    """The ``passes`` readouts of ``batch`` sequences through the one
    head, forward + backward."""
    return 3 * batch * passes * matmul_ops(seq_len, hidden, vocab)


def ouro_step_ops(batch, seq_len, *, vocab, hidden, n_head, head_dim, width,
                  n_layer, passes):
    """Forward + backward of ``batch`` sequences: ``n_layer x passes``
    block applications, ``passes`` readouts, ``passes`` gates."""
    blocks = n_layer * passes * block_pass_ops(
        seq_len, hidden=hidden, n_head=n_head, head_dim=head_dim,
        width=width)
    gate = 3 * passes * matmul_ops(seq_len, hidden, 1)
    return (batch * (blocks + gate)
            + readout_ops(batch, seq_len, vocab=vocab, hidden=hidden,
                          passes=passes))
