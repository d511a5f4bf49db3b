"""``flops.py`` against counts made by hand."""

import json
import os

import pytest

from benchmark import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def test_one_gpt2_medium_layer_by_hand():
    s, d, h, ff = 1024, 1024, 16, 4096
    qkv = 2 * s * d * (3 * d)
    out = 2 * s * d * d
    mlp = 2 * (2 * s * d * ff)
    # Two attention matmuls per head over the s(s+1)/2 visible pairs.
    attention = h * 2 * (2 * (s * (s + 1) // 2) * (d // h))
    assert flops.gpt2_layer_forward_ops(s, d, h, ff) == \
        qkv + out + mlp + attention
    assert attention == 2 * 2 * 524800 * 1024
    assert qkv + out + mlp == 2 * 1024 * 12 * 1024 * 1024


def test_gpt2_medium_step_per_token():
    sizes = dict(vocab=50257, d_model=1024, n_head=16, d_ff=4096, n_layer=24)
    at_1024 = flops.gpt2_step_ops(4, 1024, **sizes) / 4096
    at_4096 = flops.gpt2_step_ops(1, 4096, **sizes) / 4096
    # 6 x 353.4M matmul parameters + 6 * S * d * L of causal attention.
    assert abs(at_1024 - 2.272e9) < 2e6
    assert abs(at_4096 - 2.725e9) < 2e6


def test_attention_work_by_hand_at_two_widths_and_grouped_heads():
    """One layer, 1 x 4096, 16 query over 4 key/value heads, q.k 64 and
    v 128: two products forward, FIVE backward (S again, dK and dQ at
    the q.k width; dP and dV at v's), each over the causal pairs of
    every QUERY head; every operand read and every result written once
    a direction."""
    pairs = 4096 * 4097 // 2
    work = flops.attention_work(pairs, 4096, n_head=16, n_kv=4, d=64,
                                d_v=128)
    assert sorted(work) == ["bwd", "fwd"]
    qk, pv = 16 * 2 * pairs * 64, 16 * 2 * pairs * 128
    assert work["fwd"][0] == qk + pv
    assert work["bwd"][0] == qk + pv + pv + qk + qk      # S, dP, dV, dK, dQ
    assert work["bwd"][0] == 16 * 2 * pairs * (3 * 64 + 2 * 128)
    q, o = 16 * 4096 * 64 * 2, 16 * 4096 * 128 * 2
    k, v = 4 * 4096 * 64 * 2, 4 * 4096 * 128 * 2
    row = 16 * 4096 * 4
    assert work["fwd"][1] == q + k + v + o + row
    assert work["bwd"][1] == (q + k + v + o + o) + (q + k + v) + row
    # Equal widths and heads (GPT-2 medium's layer at S 4096): 4 and 10
    # widths of pairs; seven was the two backward kernels' own count.
    same = flops.attention_work(pairs, 4096, n_head=16, n_kv=16, d=64, d_v=64)
    assert same["fwd"][0] == 2 * 16 * 2 * pairs * 64
    assert same["bwd"][0] == 5 * 16 * 2 * pairs * 64
    panel = 16 * 4096 * 64 * 2
    assert same["fwd"][1] == 4 * panel + row
    assert same["bwd"][1] == 8 * panel + row
    # A batch multiplies everything.
    two = flops.attention_work(pairs, 4096, n_head=16, n_kv=4, d=64, d_v=128,
                               batch=2)
    assert two == {name: (2 * ops, 2 * nbytes)
                   for name, (ops, nbytes) in work.items()}


def test_attention_work_under_a_window_and_a_selection():
    """The pairs are the mask's: a window of 2048 over 8192 keeps
    ``W S - W (W - 1) / 2`` a head, a selection of 2048 keys a query the
    same count (``sum_t min(t + 1, topk)``) plus its bit plane, read
    once a direction; the panels' bytes do not shrink with the mask."""
    from benchmark import flops_afmoe, flops_keye

    s, w = 8192, 2048
    kept = sum(min(t + 1, w) for t in range(s))
    assert kept == w * s - w * (w - 1) // 2 == 14_681_088
    assert flops_afmoe.window_pairs(s, w) == kept
    assert flops_keye.kept_pairs(s, w) == kept
    sizes = dict(n_head=32, n_kv=4, head_dim=128, window=w)
    sliding = flops_afmoe.layer_attention_work(
        1, s, flops_afmoe.SLIDING, **sizes)
    full = flops_afmoe.layer_attention_work(1, s, "full_attention", **sizes)
    assert sliding["fwd"][0] == 32 * 2 * kept * (128 + 128)
    assert sliding["bwd"][0] == 32 * 2 * kept * 5 * 128
    assert full["bwd"][0] == 32 * 2 * flops.causal_pairs(s) * 5 * 128
    assert sliding["fwd"][1] == full["fwd"][1]
    plane = flops_keye.plane_bytes(1, s)
    assert plane == s * 2 * 128 * 4                      # 8.4 MB
    chosen = flops.attention_work(kept, s, n_head=32, n_kv=4, d=128, d_v=128,
                                  plane_bytes=plane)
    assert chosen["fwd"] == (sliding["fwd"][0], sliding["fwd"][1] + plane)
    assert chosen["bwd"] == (sliding["bwd"][0], sliding["bwd"][1] + plane)
    # Layers add direction by direction; none is nothing.
    both = flops.add_work([sliding, full, sliding])
    assert both["bwd"] == (2 * sliding["bwd"][0] + full["bwd"][0],
                           2 * sliding["bwd"][1] + full["bwd"][1])
    assert flops.add_work([]) == {}


def _window(s, w):
    return sum(min(t + 1, w) for t in range(s))


# cell -> (per-chip batch, S, query heads, key/value heads, q.k width, v
# width, softmax maps a head, the kept pairs of each attention layer a
# head), written from the published configurations and the cells' cuts,
# not read from them; None: the builder states no attention.
REQUIRED = {
    "gpt2m-s1024-c1": (4, 1024, 16, 16, 64, 64, 1, 24 * [1024 * 1025 // 2]),
    "gpt2m-s4096-c1": (1, 4096, 16, 16, 64, 64, 1, 24 * [4096 * 4097 // 2]),
    "gpt2m-s1024-dp4": (4, 1024, 16, 16, 64, 64, 1, 24 * [1024 * 1025 // 2]),
    "olmoe-s4096-c1": (1, 4096, 16, 16, 128, 128, 1, [4096 * 4097 // 2]),
    # Latent attention: q.k 192 + 64 rotary, v 256; ONE forward's work a
    # layer though every block is recomputed.
    "glm47f-s8192-ep8-c1": (1, 8192, 20, 20, 256, 256, 1,
                            5 * [8192 * 8193 // 2]),
    # Sliding, sliding, full, sliding, sliding at a window of 2048.
    "trinity-s8192-ep8-c1": (1, 8192, 32, 4, 128, 128, 1,
                             [14_681_088, 14_681_088, 8192 * 8193 // 2,
                              14_681_088, 14_681_088]),
    # ONE attention layer in five; the conv layers have no pairs.
    "lfm2-s16384-ep4-c1": (1, 16384, 32, 8, 64, 64, 1, [16384 * 16385 // 2]),
    # Differential attention: TWO maps a pair of heads, 20 maps' heads
    # over 10, q.k 64 over a V of 128; sliding at 512, full, cross.
    "phi4flash-s8192-yoco-c1": (1, 8192, 20, 10, 64, 128, 2,
                                [4_063_488, 8192 * 8193 // 2,
                                 8192 * 8193 // 2]),
    "keye-s8192-dsa-ep8-c1": None,
    "resnet50-b256-c1": None,
}


def test_the_window_counts_above_by_a_loop():
    assert _window(8192, 2048) == 14_681_088
    assert _window(8192, 512) == 4_063_488


@pytest.mark.parametrize("name", sorted(REQUIRED))
def test_each_builder_states_what_its_layers_attention_requires(name):
    """``attention_work()`` of every builder, at its cell's real sizes,
    against the configuration's layers by hand: forward two products and
    backward five over each layer's kept pairs; no call is counted, no
    ``remat`` doubles anything, no kernel's name appears."""
    from benchmark import cell as cells

    cell = cells.load(name)
    model = cell.builder.build(cell.config, cell.traffic)
    work = model.attention_work(int(cell.traffic["per_chip_batch"]))
    if REQUIRED[name] is None:
        assert work == {}
        return
    batch, s, h, kv, d, d_v, maps, pairs = REQUIRED[name]
    assert (int(cell.traffic["per_chip_batch"]),
            int(cell.traffic["seq_len"])) == (batch, s)
    products = batch * maps * h * 2 * sum(pairs)
    assert work["fwd"][0] == products * (d + d_v)
    assert work["bwd"][0] == products * (3 * d + 2 * d_v)
    panels = batch * s * 2 * (h * (d + d_v) + kv * (d + d_v))
    row = batch * s * h * 4
    assert work["fwd"][1] == maps * len(pairs) * (panels + row)
    assert work["bwd"][1] == maps * len(pairs) * (2 * panels + row)
    # The forward is what ``model.mfu_pct`` counts for the same pairs:
    # a third of the attention part of the step's required operations.
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert {flops.roofline_seconds(*w, peak)[1] for w in work.values()} \
        == {"compute"}


def test_the_two_fossils_are_read_by_no_reader():
    """``kernels()`` survives on two builders for a test outside the
    benchmark's paths; no file of the benchmark but those builders and
    their tests names it."""
    root = os.path.dirname(CONFIGS)
    users = []
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and "tests" not in folder.split(os.sep):
                with open(path) as f:
                    if ".kernels(" in f.read():
                        users.append(os.path.relpath(path, root))
    assert users == []


def test_resnet50_first_stage_by_hand():
    convs = flops.resnet_convs([3, 4, 6, 3], 64, 224, 3, 4)
    assert len(convs) == 1 + 16 * 3 + 4
    assert convs[0] == ("conv_init", 7, 3, 64, 112)
    stage1 = [c for c in convs if c[0].startswith("stage1.")]
    macs = sum(flops.conv_forward_ops(*c[1:]) for c in stage1) // 2
    # 56 x 56 positions. Block 0: 64->64, 3x3 64->64, 64->256, projection
    # 64->256; blocks 1 and 2: 256->64, 3x3 64->64, 64->256.
    by_hand = 56 * 56 * ((64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
                         + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    assert macs == by_hand
    # v1.5's stride-2 3x3 runs at the reduced size.
    assert [c for c in convs if c[0] == "stage2.block0.conv3x3"] == \
        [("stage2.block0.conv3x3", 3, 128, 128, 28)]


def test_resnet50_step_counts_a_mac_as_two():
    with open(os.path.join(CONFIGS, "resnet50.json")) as f:
        cfg = json.load(f)
    per_image = flops.resnet_step_ops(
        1, stage_sizes=cfg["stage_sizes"], num_filters=cfg["num_filters"],
        image_size=cfg["image_size"], channels=cfg["image_channels"],
        expansion=cfg["bottleneck_expansion"],
        num_classes=cfg["num_classes"])
    # 4.09 GMAC forward (v1.5), two operations a MAC, three passes, less
    # the first convolution's unneeded input gradient: XLA's
    # cost_analysis() of the whole step says 2.409e10 an image.
    assert 2.40e10 < per_image < 2.47e10
    assert per_image > 1.9 * 3 * 4.09e9


def test_roofline_names_the_binding_roof():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(197e12, 1, peak) == (1.0, "compute")
    assert flops.roofline_seconds(1, 819e9, peak) == (1.0, "memory")
