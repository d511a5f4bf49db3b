"""Operations and bytes a sparse-expert decoder needs, from shapes
alone; the counting rules are ``flops.py``'s (a multiply-accumulate is
2 operations; no recomputation, the causal half only, no elementwise
pass). An expert layer counts the experts a token USES: every token's
``k`` experts, not all ``E``.
"""

from __future__ import annotations

from benchmark.flops import causal_pairs, matmul_ops


def expert_forward_ops(tokens, hidden, expert_width, k):
    """The three matmuls (gate, up, down) of a gated expert over the
    ``tokens * k`` (token, expert) rows."""
    return 3 * matmul_ops(tokens * k, hidden, expert_width)


def olmoe_layer_forward_ops(seq_len, *, hidden, n_head, head_dim,
                            n_experts, k, expert_width):
    """One block over one sequence: the four attention projections, the
    two attention matmuls over the visible pairs, the router, and the
    experts each token uses."""
    projections = 4 * matmul_ops(seq_len, hidden, n_head * head_dim)
    attention = n_head * 2 * 2 * causal_pairs(seq_len) * head_dim
    router = matmul_ops(seq_len, hidden, n_experts)
    return (projections + attention + router
            + expert_forward_ops(seq_len, hidden, expert_width, k))


def olmoe_step_ops(batch, seq_len, *, vocab, n_layer, **layer):
    """Forward + backward of ``batch`` sequences: every matmul's
    backward is two of its size; the untied output head is one forward
    matmul and the embedding lookup multiplies nothing."""
    forward = (n_layer * olmoe_layer_forward_ops(seq_len, **layer)
               + matmul_ops(seq_len, layer["hidden"], vocab))
    return 3 * batch * forward


def expert_matmul_work(tokens, *, hidden, expert_width, n_experts, k,
                       row_itemsize=2, weight_itemsize=4):
    """(operations, HBM bytes) of ONE expert layer's grouped matmuls,
    forward + backward. Operations: three forward matmuls, and two
    backward matmuls for each. Bytes, the least any algorithm moves:
    each expert's three weight panels (float32) read once forward and
    once backward, their gradients written once; the rows in and out
    forward, the rows and the output's gradient in and the rows'
    gradient out backward (bf16). The (rows, expert_width) intermediates
    are not counted, so the share of this roof cannot pass 100%."""
    ops = 3 * expert_forward_ops(tokens, hidden, expert_width, k)
    panels = 3 * n_experts * hidden * expert_width * weight_itemsize
    rows = tokens * k * hidden * row_itemsize
    return ops, 3 * panels + 5 * rows
