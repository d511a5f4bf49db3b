"""Operations and bytes the ``phi4flash`` family's decoder
(Phi-4-mini-flash-reasoning) needs on ONE chip, from shapes alone; the
counting rules are ``flops.py``'s (a multiply-accumulate is 2
operations; no recomputation, only the (query, key) pairs the MASK
keeps, no elementwise pass, no lookup).

New here: differential attention is two softmax maps a PAIR of heads,
each map's q.k ``head_dim`` wide and its p.v TWICE that (V at its true
width, whatever the program's kernels make of it); a ``cross_attention``
layer projects no key and no value; a ``memory_unit`` is two
projections; a ``mamba`` layer is four projections and the selective
scan, whose multiplies and adds are counted (they are what the layer IS:
no matmul stands for them) and whose own work ``ssm.scan_roofline``
reads.
"""

from __future__ import annotations

from benchmark.flops import matmul_ops
from benchmark.flops_afmoe import window_pairs
from benchmark.flops_glm import swiglu_forward_ops

MAMBA, MEMORY_UNIT = "mamba", "memory_unit"
SLIDING, CROSS = "sliding_attention", "cross_attention"
# Multiplies and adds of the recurrence a (position, channel, state):
# forward ``Delta A``, ``decay h``, ``u B``, their sum, ``h C`` and its
# sum over the states; backward the four of the state made again, then
# ``g + C dy`` (2), ``h dy`` and ``g u`` summed (2 + 2), ``g B`` summed
# (2), ``g h decay`` (2), times A summed (2), times Delta added (2),
# ``g decay`` (1). The exponentials count nothing.
SCAN_FORWARD, SCAN_BACKWARD = 6, 4 + 15


def diff_attention_forward_ops(seq_len, *, hidden, n_head, n_kv, head_dim,
                               window=None, cross=False):
    """One differential attention layer over one sequence: q and the
    output ``n_head * head_dim`` wide, k and v ``n_kv * head_dim``
    (none in a ``cross`` layer); a pair of heads runs two maps, each
    q.k over the kept pairs at ``head_dim`` and p.v at twice it."""
    wide, narrow = n_head * head_dim, n_kv * head_dim
    projections = 2 * matmul_ops(seq_len, hidden, wide)
    if not cross:
        projections += 2 * matmul_ops(seq_len, hidden, narrow)
    a_map = 2 * window_pairs(seq_len, window) * (head_dim + 2 * head_dim)
    return projections + (n_head // 2) * 2 * a_map


def scan_forward_ops(seq_len, channels, states):
    """The recurrence and its read-out, and a position's ``Delta x``,
    ``D x`` and their add a channel."""
    return seq_len * channels * (SCAN_FORWARD * states + 3)


def mamba_forward_ops(seq_len, *, hidden, channels, states, rank):
    """One mamba layer over one sequence: the in-projection to twice
    the channels, ``[r, B, C]``, the step's projection, the scan, the
    out-projection. The taps are elementwise."""
    return (matmul_ops(seq_len, hidden, 2 * channels)
            + matmul_ops(seq_len, channels, rank + 2 * states)
            + matmul_ops(seq_len, rank, channels)
            + scan_forward_ops(seq_len, channels, states)
            + matmul_ops(seq_len, channels, hidden))


def memory_unit_forward_ops(seq_len, hidden, channels):
    return 2 * matmul_ops(seq_len, hidden, channels)


def phi4flash_step_ops(batch, seq_len, *, vocab, kinds, window, hidden,
                       n_head, n_kv, head_dim, dense_width, channels, states,
                       rank):
    """Forward + backward of ``batch`` sequences through the layers of
    ``kinds``: every matmul's backward is two of its size (the scan's
    counted by the same rule); the tied output head over the vocabulary
    held here is one forward matmul, the lookup multiplies nothing."""
    def mixer(kind):
        if kind == MAMBA:
            return mamba_forward_ops(seq_len, hidden=hidden,
                                     channels=channels, states=states,
                                     rank=rank)
        if kind == MEMORY_UNIT:
            return memory_unit_forward_ops(seq_len, hidden, channels)
        return diff_attention_forward_ops(
            seq_len, hidden=hidden, n_head=n_head, n_kv=n_kv,
            head_dim=head_dim, window=window if kind == SLIDING else None,
            cross=kind == CROSS)

    forward = (sum(mixer(kind) for kind in kinds)
               + len(kinds) * swiglu_forward_ops(seq_len, hidden, dense_width)
               + matmul_ops(seq_len, hidden, vocab))
    return 3 * batch * forward


def scan_work(tokens, channels, states):
    """(operations, HBM bytes) of ONE mamba layer's selective scan,
    forward + backward, the least any algorithm moves, in float32 (the
    scan's stated precision). Forward: x' and Delta read, y written (3
    rows of ``channels``), B and C read. Backward: x', Delta and y's
    gradient read, the gradients of x' and Delta written (5 rows), B and
    C read and their gradients written; A, D and their gradients are
    ``channels * (states + 1)`` numbers twice. No state passes through
    HBM in the count. Memory-bound by an order on the matrix unit's
    peak, which this work cannot use: the operations are counted so
    that the roof says which binds."""
    ops = tokens * channels * ((SCAN_FORWARD + SCAN_BACKWARD) * states + 3 + 6)
    nbytes = 4 * (8 * tokens * channels + 6 * tokens * states
                  + 3 * channels * (states + 1))
    return ops, nbytes
