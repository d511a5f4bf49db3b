"""Operations and bytes the ``lfm2_moe`` family's decoder (LFM2-8B-A1B)
needs as ONE chip's share of an expert-parallel group, from shapes
alone; the counting rules are ``flops.py``'s (a multiply-accumulate is 2
operations; no recomputation, the causal half only, no elementwise
pass, no lookup) and the expert layer is counted as ``flops_glm.py``
counts GLM's: at the balanced load, here with no shared expert.

New here: a ``conv`` layer's mixer is two projections (its gates and
taps are elementwise passes and count nothing towards what the step
REQUIRES), and those passes' own work, which ``conv.gate_roofline``
reads: what the gates and the taps must move.
"""

from __future__ import annotations

from benchmark.flops import causal_pairs, matmul_ops
from benchmark.flops_glm import expert_layer_forward_ops, swiglu_forward_ops

CONV = "conv"


def conv_mixer_forward_ops(seq_len, hidden):
    """One gated short convolution over one sequence: the in-projection
    to three times the width and the out-projection."""
    return (matmul_ops(seq_len, hidden, 3 * hidden)
            + matmul_ops(seq_len, hidden, hidden))


def attention_forward_ops(seq_len, *, hidden, n_head, n_kv, head_dim):
    """One grouped-query attention layer over one sequence: the q and
    output projections ``n_head * head_dim`` wide, k and v ``n_kv *
    head_dim``, and the two attention matmuls over the causal pairs."""
    wide, narrow = n_head * head_dim, n_kv * head_dim
    projections = (2 * matmul_ops(seq_len, hidden, wide)
                   + 2 * matmul_ops(seq_len, hidden, narrow))
    return projections + n_head * 2 * 2 * causal_pairs(seq_len) * head_dim


def lfm2_step_ops(batch, seq_len, *, vocab, kinds, n_dense, dense_width,
                  hidden, n_head, n_kv, head_dim, expert_width, k, held,
                  routed):
    """Forward + backward of ``batch`` sequences through the layers of
    ``kinds`` (one ``layer_types`` entry each): every matmul's backward
    is two of its size; the tied output head over the vocabulary held
    here is one forward matmul, the lookup multiplies nothing; neither
    does the convolution itself, which is elementwise."""
    mixers = sum(
        conv_mixer_forward_ops(seq_len, hidden) if kind == CONV
        else attention_forward_ops(seq_len, hidden=hidden, n_head=n_head,
                                   n_kv=n_kv, head_dim=head_dim)
        for kind in kinds)
    forward = (mixers
               + n_dense * swiglu_forward_ops(seq_len, hidden, dense_width)
               + (len(kinds) - n_dense) * expert_layer_forward_ops(
                   seq_len, hidden=hidden, expert_width=expert_width, k=k,
                   held=held, routed=routed, shared=0)
               + matmul_ops(seq_len, hidden, vocab))
    return 3 * batch * forward


def conv_gate_work(tokens, hidden, taps, itemsize=2):
    """(operations, HBM bytes) of ONE ``conv`` layer's gates and taps,
    forward + backward, the least any algorithm moves. Forward: the
    in-projection's product read (3 widths), the gated convolution
    written (1). Backward: the product and the output's gradient read
    (3 + 1), the product's gradient written (3); the taps' own gradient
    is ``hidden * taps`` numbers. Operations, an element of one width:
    forward ``b u`` (1), the taps (``2 taps - 1``), ``c z`` (1);
    backward the forward's ``b u`` and ``z`` again (they are not kept),
    ``dc`` and ``dz`` (2), the taps transposed (``2 taps - 1``), the
    taps' gradient (``2 taps``), ``db`` and ``du`` (2). Memory-bound by
    two orders: the operations are counted so that the roof says so."""
    forward = 2 * taps + 1
    backward = forward - 1 + 2 + (2 * taps - 1) + 2 * taps + 2
    ops = tokens * hidden * (forward + backward)
    return ops, 11 * tokens * hidden * itemsize + hidden * taps * 4
