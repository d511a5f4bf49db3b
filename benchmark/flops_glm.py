"""Operations and bytes GLM-4.7-Flash's decoder needs as ONE chip's
share of an expert-parallel group, from shapes alone; the counting
rules are ``flops.py``'s (a multiply-accumulate is 2 operations; no
recomputation, the causal half only, no elementwise pass, no lookup).

An expert layer here holds ``held`` of the ``routed`` experts its
router scores. What it REQUIRES is counted at the balanced load: of the
``tokens * k`` (token, expert) rows, ``held / routed`` are this chip's.
The true count wanders round that from step to step and from layer to
layer (``rows_held``, which the layer sows; PERF.md says by how much).
"""

from __future__ import annotations

from benchmark.flops import causal_pairs, matmul_ops


def latent_attention_forward_ops(seq_len, *, hidden, n_head, q_rank, kv_rank,
                                 nope, rope, v_dim):
    """Latent attention over one sequence: the two down-projections, the
    two up-projections, the output projection, and the two attention
    matmuls over the visible pairs (q.k ``nope + rope`` wide, p.v
    ``v_dim`` wide). k and v are computed explicitly."""
    projections = (matmul_ops(seq_len, hidden, q_rank)
                   + matmul_ops(seq_len, q_rank, n_head * (nope + rope))
                   + matmul_ops(seq_len, hidden, kv_rank + rope)
                   + matmul_ops(seq_len, kv_rank, n_head * (nope + v_dim))
                   + matmul_ops(seq_len, n_head * v_dim, hidden))
    attention = n_head * 2 * causal_pairs(seq_len) * (nope + rope + v_dim)
    return projections + attention


def swiglu_forward_ops(rows, hidden, width):
    """The three matmuls (gate, up, down) of a gated feed-forward."""
    return 3 * matmul_ops(rows, hidden, width)


def held_rows(tokens, k, held, routed):
    """The (token, expert) rows of the held experts at the balanced
    load; whole for every configuration in use."""
    return tokens * k * held // routed


def expert_layer_forward_ops(seq_len, *, hidden, expert_width, k, held,
                             routed, shared):
    """What follows attention in an expert block: the router over ALL
    ``routed`` experts, the shared experts over every token, the held
    experts over their share of the rows."""
    return (matmul_ops(seq_len, hidden, routed)
            + swiglu_forward_ops(seq_len, hidden, shared * expert_width)
            + swiglu_forward_ops(held_rows(seq_len, k, held, routed), hidden,
                                 expert_width))


def glm_step_ops(batch, seq_len, *, vocab, n_layer, n_dense, dense_width,
                 hidden, n_head, q_rank, kv_rank, nope, rope, v_dim,
                 expert_width, k, held, routed, shared):
    """Forward + backward of ``batch`` sequences: every matmul's
    backward is two of its size; the untied output head over the
    vocabulary held here is one forward matmul."""
    attention = latent_attention_forward_ops(
        seq_len, hidden=hidden, n_head=n_head, q_rank=q_rank,
        kv_rank=kv_rank, nope=nope, rope=rope, v_dim=v_dim)
    forward = (n_layer * attention
               + n_dense * swiglu_forward_ops(seq_len, hidden, dense_width)
               + (n_layer - n_dense) * expert_layer_forward_ops(
                   seq_len, hidden=hidden, expert_width=expert_width, k=k,
                   held=held, routed=routed, shared=shared)
               + matmul_ops(seq_len, hidden, vocab))
    return 3 * batch * forward


def held_expert_matmul_work(tokens, *, hidden, expert_width, k, held, routed,
                            row_itemsize=2, weight_itemsize=4):
    """(operations, HBM bytes) of ONE expert layer's grouped matmuls
    over the HELD experts, forward + backward, at the balanced load.
    Operations: three forward matmuls over ``held_rows`` rows, and two
    backward matmuls for each. Bytes, the least any algorithm moves:
    each held expert's three weight panels (float32) read once forward
    and once backward, their gradients written once; the live rows in
    and out forward, the rows and the output's gradient in and the
    rows' gradient out backward (bf16). Not counted: the (rows,
    expert_width) intermediates, the dead rows of the sorted-row arrays
    (a prefix of the ``T k`` rows since PR 33), the recomputed forward.
    So the share of this roof cannot pass 100%."""
    rows = held_rows(tokens, k, held, routed)
    ops = 3 * swiglu_forward_ops(rows, hidden, expert_width)
    panels = 3 * held * hidden * expert_width * weight_itemsize
    return ops, 3 * panels + 5 * rows * hidden * row_itemsize
