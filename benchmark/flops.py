"""Operations and bytes an algorithm needs, from shapes alone.

The yardstick for ``model.mfu_pct`` and ``kernel.flash_roofline``: what
the forward and backward passes REQUIRE, whatever the program computes
to get there. A multiply-accumulate counts as 2 operations. Not counted:
recomputation, the causally masked half of attention (nothing needs
it), the optimizer's and the norms' elementwise passes, table lookups.
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


def flash_kernel_work(batch, seq_len, n_head, head_dim, itemsize=2):
    """Per call of each of the three kernels of ops/pallas_attention.py:
    (operations, HBM bytes) the kernel's own algorithm needs. Forward:
    QK^T and PV. dK/dV: QK^T again, dV, dP, dK. dQ: QK^T again, dP, dQ.
    Each matmul covers the visible pairs only. Bytes: every operand read
    once and every result written once (bf16 panels, float32 log-sum-exp
    and delta rows)."""
    per_matmul = batch * n_head * 2 * causal_pairs(seq_len) * head_dim
    panel = batch * n_head * seq_len * head_dim * itemsize
    row = batch * n_head * seq_len * 4
    return {
        "fwd": (2 * per_matmul, 4 * panel + row),
        "dkv": (4 * per_matmul, 6 * panel + 2 * row),
        "dq": (3 * per_matmul, 5 * panel + 2 * row),
    }


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
