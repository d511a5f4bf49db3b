"""Operations and bytes an algorithm needs, from shapes alone.

The yardstick for ``model.mfu_pct`` and the ``kernel.flash_*``
rooflines: what the forward and backward passes REQUIRE, whatever the
program computes to get there and whatever kernels it calls. A
multiply-accumulate counts as 2 operations. Not counted: recomputation,
the causally masked half of attention (nothing needs it), the
optimizer's and the norms' elementwise passes, table lookups.
"""

from __future__ import annotations


def matmul_ops(m, k, n):
    return 2 * m * k * n


# ------------------------------------------------------------- GPT-2 ------

def causal_pairs(seq_len):
    """(query, key) pairs a causal mask keeps, the diagonal included."""
    return seq_len * (seq_len + 1) // 2


def gpt2_layer_forward_ops(seq_len, d_model, n_head, d_ff):
    """One decoder block over one sequence: the QKV, output and two MLP
    projections, and the two attention matmuls over the visible pairs."""
    head = d_model // n_head
    projections = (matmul_ops(seq_len, d_model, 3 * d_model)
                   + matmul_ops(seq_len, d_model, d_model)
                   + 2 * matmul_ops(seq_len, d_model, d_ff))
    attention = n_head * 2 * 2 * causal_pairs(seq_len) * head
    return projections + attention


def gpt2_step_ops(batch, seq_len, *, vocab, d_model, n_head, d_ff, n_layer):
    """Forward + backward of ``batch`` sequences. Every matmul's
    backward is two matmuls of its own size (one for each operand); the
    tied output projection is one forward matmul, the embedding lookup
    and the position table multiply nothing."""
    forward = (n_layer * gpt2_layer_forward_ops(seq_len, d_model, n_head,
                                                d_ff)
               + matmul_ops(seq_len, d_model, vocab))
    return 3 * batch * forward


def attention_work(pairs, seq_len, *, n_head, n_kv, d, d_v, batch=1,
                   itemsize=2, plane_bytes=0):
    """What ONE layer's attention REQUIRES of the chip, whatever kernels
    compute it and however many: ``{"fwd": (operations, HBM bytes),
    "bwd": (operations, HBM bytes)}`` of ``batch`` sequences of
    ``seq_len``, ``n_head`` query heads over ``n_kv`` key/value heads,
    q.k ``d`` wide and v ``d_v`` wide, each query head keeping ``pairs``
    (query, key) pairs: ``causal_pairs``, a window's
    (``flops_afmoe.window_pairs``) or a selection's ``sum_t min(t + 1,
    topk)`` (``flops_keye.kept_pairs``).

    Operations, one product = ``2 pairs width`` a query head. Forward,
    TWO products: S = q k^T (``d``) and O = P v (``d_v``). Backward,
    FIVE, the products any exact backward multiplies that keeps no
    (S, S) array from the forward: S = q k^T AGAIN (``d``; P is remade
    from it and the kept log-sum-exp row), dP = dO v^T (``d_v``),
    dV = P^T dO (``d_v``), dK = dS^T q (``d``), dQ = dS k (``d``):
    ``2 pairs (3 d + 2 d_v)``. A backward in two kernels that makes S
    and dP once in each runs SEVEN; the two more are its own repetition
    and count as time, not as work.

    Bytes, every operand read once and every result written once by the
    forward as a whole and by the backward as a whole: q, o, dO, dQ
    ``n_head`` heads wide, k, v, dK, dV ``n_kv``; the float32
    log-sum-exp row written forward and read backward; forward reads q,
    k, v and writes o; backward reads q, k, v, o (its row sums with dO)
    and dO and writes dQ, dK, dV; ``plane_bytes`` (a selection's bit
    plane) once a direction."""
    per_width = batch * n_head * 2 * pairs
    q = batch * n_head * seq_len * d * itemsize          # also dQ
    o = batch * n_head * seq_len * d_v * itemsize        # also dO
    k = batch * n_kv * seq_len * d * itemsize            # also dK
    v = batch * n_kv * seq_len * d_v * itemsize          # also dV
    row = batch * n_head * seq_len * 4
    return {
        "fwd": (per_width * (d + d_v), q + k + v + o + row + plane_bytes),
        "bwd": (per_width * (3 * d + 2 * d_v),
                2 * (q + k + v + o) + row + plane_bytes),
    }


def add_work(works):
    """The sum of several layers' ``attention_work``, direction by
    direction; ``{}`` of none. One (operations, bytes) for layers of
    unlike masks is exact for the time at the roof while they sit under
    the same roof (every layer in use is compute-bound)."""
    works = list(works)
    return {name: tuple(sum(work[name][i] for work in works) for i in (0, 1))
            for name in (works[0] if works else ())}


# --------------------------------------------------------- ResNet-50 ------

def resnet_convs(stage_sizes, num_filters, image_size, channels, expansion):
    """Every convolution of a bottleneck ResNet (v1.5: the stride sits in
    the 3x3) as (name, k, c_in, c_out, out_size), in forward order."""
    convs = []
    size = image_size // 2
    convs.append(("conv_init", 7, channels, num_filters, size))
    size //= 2                                            # max pool
    c_in = num_filters
    for stage, blocks in enumerate(stage_sizes):
        width = num_filters * 2 ** stage
        for j in range(blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            tag = "stage%d.block%d." % (stage + 1, j)
            convs.append((tag + "conv1x1a", 1, c_in, width, size))
            out = size // stride
            convs.append((tag + "conv3x3", 3, width, width, out))
            convs.append((tag + "conv1x1b", 1, width, width * expansion,
                          out))
            if j == 0:
                convs.append((tag + "proj", 1, c_in, width * expansion,
                              out))
            size, c_in = out, width * expansion
    return convs


def conv_forward_ops(k, c_in, c_out, out_size):
    return 2 * out_size * out_size * k * k * c_in * c_out


def resnet_step_ops(batch, *, stage_sizes, num_filters, image_size, channels,
                    expansion, num_classes):
    """Forward + backward of ``batch`` images. Each convolution's
    backward is two convolutions of its size, except the first, whose
    input gradient nothing needs."""
    convs = resnet_convs(stage_sizes, num_filters, image_size, channels,
                         expansion)
    forward = [conv_forward_ops(*c[1:]) for c in convs]
    final = num_filters * 2 ** (len(stage_sizes) - 1) * expansion
    dense = matmul_ops(1, final, num_classes)
    return batch * (3 * sum(forward) - forward[0] + 3 * dense)


# ---------------------------------------------------------- roofline ------

def roofline_seconds(ops, hbm_bytes, peak):
    """The least time a chip with ``peak`` (an entry of peaks.json) could
    take, and which roof binds."""
    t_ops = ops / peak["bf16_flops"]
    t_bytes = hbm_bytes / peak["hbm_bytes_per_s"]
    return max(t_ops, t_bytes), "compute" if t_ops >= t_bytes else "memory"
