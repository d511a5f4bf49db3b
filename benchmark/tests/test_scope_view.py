"""``scope_view.py``: self-times that add up, the phase and part of real
``op_name``s (copied from the steps compiled for a v5e), and every new
reader on a ``ctx`` built from the recorded trace and a hand-written
compiled step whose instruction names are the trace's."""

import os
import re
from types import SimpleNamespace

import pytest

from benchmark import scope_view
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import reader
from benchmark.trace_reduce import Event

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "small.xplane.pb")
MOSAIC = ('%hvd_flash_fwd.1 = bf16[1,2,256,64] custom-call(%a, %b, %c), '
          'custom_call_target="tpu_custom_call"')
UNNAMED = MOSAIC.replace("%hvd_flash_fwd.1", "%k.1")
ALL_REDUCE = "%all-reduce.81 = (f32[4194304], f32[3149824]) all-reduce(%x, %y)"
STEP = "jit(hvd_bench_step)/"
LAYER = STEP + "jvp(Transformer)/layer_3/"
LAYER_BWD = STEP + "transpose(jvp(Transformer))/layer_3/"
RESNET = STEP + "jvp(ResNet)/"

# (op_name in the compiled step, the event's HLO text, phase, part)
REAL_OP_NAMES = [
    (LAYER + "attn/bsm,mhd->bshd/dot_general", "", "forward", "attn"),
    (LAYER + "attn/hvd_flash/hvd_flash_fwd/pallas_call", MOSAIC,
     "forward", "flash_kernel"),
    (LAYER + "attn/hvd_flash/hvd_flash_fwd/pallas_call",
     "%slice-start.9 = (bf16[8]) slice-start(%q)", "forward", "flash_glue"),
    (LAYER_BWD + "attn/hvd_flash/hvd_flash_dkv/pallas_call",
     MOSAIC.replace("fwd.1", "dkv.7"), "backward", "flash_kernel"),
    (LAYER_BWD + "attn/hvd_flash/hvd_flash_bwd/pallas_call",
     MOSAIC.replace("fwd.1", "bwd"), "backward", "flash_kernel"),
    # A Mosaic call under ``hvd_flash`` that is not NAMED a flash kernel
    # (the masked ``hvd_dsa_*``, a call without a name) is glue.
    (LAYER_BWD + "attn/hvd_flash/hvd_flash_dkv/pallas_call", UNNAMED,
     "backward", "flash_glue"),
    (LAYER + "attn/hvd_flash/hvd_dsa_fwd/pallas_call",
     MOSAIC.replace("hvd_flash_fwd.1", "hvd_dsa_fwd.3"), "forward",
     "flash_glue"),
    (LAYER_BWD + "attn/hvd_flash/reduce_sum", "", "backward", "flash_glue"),
    (LAYER_BWD + "attn/hvd_flash/broadcast_in_dim", "", "backward",
     "flash_glue"),
    (LAYER_BWD + "mlp/dot_general", "", "backward", "mlp"),
    (LAYER + "ln1/reduce_sum", "", "forward", "norm"),
    (STEP + "transpose(jvp(Transformer))/ln_f/add_any", "", "backward",
     "norm"),
    (LAYER + "add", "", "forward", "other"),
    (STEP + "jvp(Transformer)/embed/gather", "", "forward", "head"),
    (STEP + "transpose(jvp(Transformer))/logits/bsm,vm->bsv/dot_general", "",
     "backward", "head"),
    (STEP + "transpose(jvp(Transformer))/logits/convert_element_type",
     "%while.4 = (bf16[1,4096,50257], bf16[205852672]) while(%tuple.9)",
     "backward", "head"),
    (STEP + "transpose(jvp())/mul", "%while.3 = (f32[8]) while(%tuple.8)",
     "backward", "head"),
    (STEP + "jvp()/reduce_sum", "", "forward", "head"),
    (STEP + "jvp(jit(take_along_axis))/gather", "", "forward", "head"),
    (STEP + "transpose(jvp(jit(_take)))/scatter-add", "", "backward", "head"),
    (STEP + "add", "", "update", "update"),
    (STEP + "shard_map/add", "", "update", "update"),
    (STEP + "hvd_update/jit(clip)/max", "", "update", "update"),
    (STEP + "shard_map/hvd_update/mul;jit(hvd_bench_step)/shard_map", "",
     "update", "update"),
    (STEP + "shard_map/hvd_sync/bucket_7_float32/psum", ALL_REDUCE,
     "sync", "sync_collective"),
    (STEP + "shard_map/hvd_sync/bucket_7_float32/psum",
     "%slice-start.2281 = (f32[1024,1024]) slice-start(%gte.5)", "sync",
     "sync_pack"),
    (STEP + "shard_map/hvd_sync/bucket_0_float32/concatenate", "", "sync",
     "sync_pack"),
    (STEP + "hvd_sync/bucket_3_float32/slice", "", "sync", "sync_pack"),
    (STEP + "shard_map", "", "unscoped", "unscoped"),
    ("", "", "unscoped", "unscoped"),
    (RESNET + "BottleneckBlock_7/Conv_1/conv_general_dilated", "", "forward",
     "conv"),
    (RESNET + "conv_init/conv_general_dilated", "", "forward", "conv"),
    (RESNET + "BottleneckBlock_0/conv_proj/conv_general_dilated", "",
     "forward", "conv"),
    (RESNET + "BottleneckBlock_12/BatchNorm_1/mul", "", "forward", "bn"),
    (RESNET + "BottleneckBlock_0/norm_proj/reduce_sum", "", "forward", "bn"),
    (STEP + "transpose(jvp(ResNet))/bn_init/div", "", "backward", "bn"),
    (RESNET + "BottleneckBlock_7/jit(relu)/max", "", "forward", "other"),
    (RESNET + "reduce_window_max", "", "forward", "other"),
    (STEP + "transpose(jvp(ResNet))/select_and_scatter", "", "backward",
     "other"),
    (STEP + "transpose(jvp(ResNet))/Dense_0/dot_general", "", "backward",
     "head"),
]


def test_phase_and_part_of_real_op_names():
    wrong = [(scope, text, scope_view.classify(scope, text), (phase, part))
             for scope, text, phase, part in REAL_OP_NAMES
             if scope_view.classify(scope, text) != (phase, part)]
    assert not wrong
    assert {phase for _, _, phase, _ in REAL_OP_NAMES} == set(
        scope_view.PHASES)


def test_self_times_of_nested_events_add_to_the_busy_union():
    events = [
        Event("%while.3 = (f32[8]) while(%t)", 0, 100),
        Event("%dynamic-update-slice.9 = f32[8] dynamic-update-slice()",
              10, 40),
        Event("%fusion.2 = f32[8] fusion(%p)", 40, 70),
        Event("%copy.1 = f32[8] copy(%q)", 60, 90),      # straddles fusion.2
        Event("%late.1 = f32[8] add(%a, %b)", 95, 130),  # straddles the loop
        Event("%alone.1 = f32[8] add(%a, %b)", 200, 210),
    ]
    own = scope_view.self_times(events)
    assert own == [15, 30, 20, 30, 35, 10]
    assert sum(own) == tr.length(tr.spans(events)) == 140
    assert scope_view.self_times([]) == []


# The trace was recorded (PR 22) before the kernels carried a ``name=``
# (PR 24): its three Mosaic calls bear the compiler's names. A program
# of today names them; the tests rename the recorded events and the
# instructions of the hand-written step alike, times as recorded.
NAMED = {"jvp__.1": "hvd_flash_fwd.1", "transpose_jvp___.2": "hvd_flash_dkv.2",
         "transpose_jvp___.3": "hvd_flash_dq.3"}


def named(text, names=None):
    """``text`` (an event's name, a compiled step) with the recorded
    Mosaic calls under ``names`` (default: today's)."""
    for old, new in (NAMED if names is None else names).items():
        text = re.sub("%%%s(?![\\w.])" % re.escape(old), "%" + new, text)
    return text


def named_window(win, names=None):
    return win._replace(ops=[Event(named(e.name, names), e.start, e.end)
                             for e in win.ops])


# The recorded program's seventeen instructions, as a compiled step would
# name them had it been a layer of the transformer.
AS_RECORDED = """\
HloModule jit_small_step, is_scheduled=true

ENTRY %main (x.1: bf16[256,512], w.1: bf16[512,512], q.1: bf16[1,256,2,64]) -> bf16[512,512] {
  %x.1 = bf16[256,512]{1,0} parameter(0), metadata={op_name="x"}
  %w.1 = bf16[512,512]{1,0} parameter(1), metadata={op_name="w"}
  %q.1 = bf16[1,256,2,64]{3,2,1,0} parameter(2), metadata={op_name="q"}
  %copy-start = (bf16[256,512]{1,0:S(1)}, bf16[256,512]{1,0}, u32[]{:S(2)}) copy-start(%x.1)
  %copy.6 = bf16[1,256,2,64]{3,1,2,0} copy(%q.1), metadata={op_name="jit(small_step)/jvp(Transformer)/layer_0/attn/transpose"}
  %jvp__.1 = (bf16[1,2,256,64]{3,2,1,0}, f32[1,2,256,1]{3,2,1,0}) custom-call(%copy.6, %copy.6, %copy.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(small_step)/jvp(Transformer)/layer_0/attn/hvd_flash/hvd_flash_fwd/pallas_call"}
  %convert_reduce_fusion.1 = (f32[], f32[2,256]{1,0}) fusion(%jvp__.1), kind=kLoop, calls=%f, metadata={op_name="jit(small_step)/transpose(jvp(Transformer))/layer_0/attn/hvd_flash/reduce_sum"}
  %copy.4 = f32[1,2,256,1]{3,2,1,0} copy(%convert_reduce_fusion.1)
  %copy-start.1 = (bf16[512,512]{1,0:S(1)}, bf16[512,512]{1,0}, u32[]{:S(2)}) copy-start(%w.1)
  %transpose.10 = bf16[1,2,256,64]{3,2,1,0} broadcast(%copy.4), dimensions={}
  %transpose_jvp___.2 = (bf16[1,2,256,64]{3,2,1,0}, bf16[1,2,256,64]{3,2,1,0}) custom-call(%copy.6, %copy.6, %copy.6, %transpose.10, %copy.4, %copy.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(small_step)/transpose(jvp(Transformer))/layer_0/attn/hvd_flash/hvd_flash_dkv/pallas_call"}
  %transpose_jvp___.3 = bf16[1,2,256,64]{3,2,1,0} custom-call(%copy.6, %copy.6, %copy.6, %transpose.10, %copy.4, %copy.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(small_step)/transpose(jvp(Transformer))/layer_0/attn/hvd_flash/hvd_flash_dq/pallas_call"}
  %bitcast_subtract_fusion = bf16[1,256,2,64]{3,1,2,0} fusion(%copy.6, %transpose_jvp___.3), kind=kLoop, calls=%f, metadata={op_name="jit(small_step)/hvd_update/sub"}
  %copy.7 = bf16[1,256,2,64]{1,3,2,0} copy(%bitcast_subtract_fusion)
  %copy-done = bf16[256,512]{1,0:S(1)} copy-done(%copy-start)
  %copy-done.1 = bf16[512,512]{1,0:S(1)} copy-done(%copy-start.1)
  %convolution_tanh_fusion = bf16[256,512]{1,0} fusion(%copy-done, %copy-done.1), kind=kOutput, calls=%f, metadata={op_name="jit(small_step)/jvp(Transformer)/layer_0/mlp/dot_general"}
  %convert_reduce_fusion = (f32[], bf16[256,512]{1,0}) fusion(%convolution_tanh_fusion, %copy-done.1), kind=kLoop, calls=%f, metadata={op_name="jit(small_step)/jvp()/reduce_sum"}
  %fusion.6 = bf16[512,512]{1,0} fusion(%convolution_tanh_fusion, %convert_reduce_fusion), kind=kOutput, calls=%f, metadata={op_name="jit(small_step)/transpose(jvp(Transformer))/layer_0/mlp/dot_general"}
  ROOT %fusion.1 = bf16[512,512]{1,0} fusion(%copy-done.1, %fusion.6, %copy-done), kind=kOutput, calls=%f, metadata={op_name="jit(small_step)/hvd_sync/bucket_0_bfloat16/div"}
}
"""
RECORDED_STEP = named(AS_RECORDED)
NEW_READERS = [
    "model.fwd_ms", "model.bwd_ms", "model.update_ms", "model.head_ms",
    "kernel.flash_glue_ms", "kernel.flash_fwd_roofline",
    "kernel.flash_bwd_roofline", "kernel.flash_roofline", "sync.pack_ms",
    "device.unscoped_pct", "images.model.fwd_ms", "images.model.bwd_ms",
    "images.model.update_ms", "images.device.unscoped_pct"]


PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ctx(hlo_text, names=None):
    """The recorded window with its Mosaic calls under ``names``
    (default: today's), beside ``hlo_text`` as the compiled step."""
    trace = tr.load(RECORDED)
    win = named_window(tr.device_window(trace.devices[0], "jit_small_step"),
                       names)
    work = {"fwd": (2e6, 3e5), "bwd": (5e6, 7e5)}
    return SimpleNamespace(
        win0=win, hlo_text=named(hlo_text, names), n_steps=len(win.steps),
        attention=work, peak=PEAK)


def test_every_new_reader_on_the_recorded_trace(capsys):
    ctx = _ctx(RECORDED_STEP)
    got = {name: reader(name)(ctx) for name in NEW_READERS}
    assert all(v is not None for v in got.values()), got
    logged = capsys.readouterr().err
    assert logged.count("[bench] scope view, self-time per step") == 1
    assert "largest scopes" in logged and "layer_*/mlp" in logged
    assert "asynchronous pairs" in logged

    # The phases are the whole of the busy time, per step.
    busy_ms = 1e3 * ctx.win0.busy_s / ctx.n_steps
    table = scope_view.table(ctx)
    assert sum(table.phase_s.values()) == pytest.approx(table.busy_s)
    assert (got["model.fwd_ms"] + got["model.bwd_ms"]
            + got["model.update_ms"] + 1e3 * table.phase_s["sync"]
            + got["device.unscoped_pct"] / 100 * busy_ms
            == pytest.approx(busy_ms, rel=1e-6))
    # Every instruction found a scope: copies by inheritance (copy.4 the
    # delta's, copy.7 the update's, the prefetches their first user's).
    assert got["device.unscoped_pct"] == 0.0
    assert got["model.head_ms"] == pytest.approx(1.138e-3, rel=2e-2)
    assert got["images.model.bwd_ms"] == got["model.bwd_ms"]
    # flash_glue: the delta fusion, its copy and the broadcast.
    assert got["kernel.flash_glue_ms"] == pytest.approx(
        (0.468 + 0.306 + 0.025) * 1e-3, rel=2e-2)
    assert got["sync.pack_ms"] == pytest.approx(2.433e-3, rel=2e-2)

    # The kernels are found by NAME, and weighted by their time the two
    # directions' rooflines are the whole's.
    took = {k: s for k, (s, _) in tr.kernel_seconds(ctx.win0.ops).items()}
    assert sorted(took) == ["dkv", "dq", "fwd"]
    assert took["fwd"] == pytest.approx(3 * 1.491e-6, rel=1e-2)
    bwd = took["dkv"] + took["dq"]
    least = {d: max(ops / 197e12, nbytes / 819e9)
             for d, (ops, nbytes) in ctx.attention.items()}
    assert got["kernel.flash_fwd_roofline"] == pytest.approx(
        100 * 3 * least["fwd"] / took["fwd"])
    assert got["kernel.flash_bwd_roofline"] == pytest.approx(
        100 * 3 * least["bwd"] / bwd)
    assert got["kernel.flash_roofline"] == pytest.approx(
        100 * 3 * (least["fwd"] + least["bwd"]) / (took["fwd"] + bwd))
    assert 0 < got["kernel.flash_fwd_roofline"] < 100
    # The two retired names read the backward's number.
    assert reader("kernel.flash_dkv_roofline")(ctx) \
        == reader("kernel.flash_dq_roofline")(ctx) \
        == got["kernel.flash_bwd_roofline"]

    # The trace AS RECORDED names no kernel (3 and 6 operands under the
    # compiler's names): no flash kernel, no roofline, the rest stays.
    unnamed = _ctx(AS_RECORDED, names={})
    assert tr.kernel_seconds(unnamed.win0.ops) == {}
    assert reader("kernel.flash_fwd_roofline")(unnamed) is None
    assert reader("kernel.flash_roofline")(unnamed) is None
    assert reader("model.fwd_ms")(unnamed) == pytest.approx(
        got["model.fwd_ms"])
    # ... and what ran under ``hvd_flash`` is then glue, all of it.
    assert reader("kernel.flash_glue_ms")(unnamed) == pytest.approx(
        got["kernel.flash_glue_ms"]
        + 1e3 * (took["fwd"] + bwd) / ctx.n_steps)
    # A builder that states no attention (ResNet-50's): nothing.
    none = _ctx(RECORDED_STEP)
    none.attention = {}
    assert reader("kernel.flash_roofline")(none) is None
    # A program with no scopes to join, or a ctx a reader cannot use:
    # nothing, and no exception.
    assert reader("model.fwd_ms")(_ctx("HloModule jit_small_step")) == 0.0
    assert reader("device.unscoped_pct")(
        _ctx("HloModule jit_small_step")) == pytest.approx(100.0)
    broken = SimpleNamespace(win0=None, hlo_text="", n_steps=0, attention={})
    assert all(reader(name)(broken) is None for name in NEW_READERS)
    assert "scope view failed" in capsys.readouterr().err


def _synthetic(kernels):
    """One step 0..100 us: a fusion, then the Mosaic calls of ``kernels``
    (name, start, end), as a ``ctx`` the flash readers can use."""
    call = ('%%%s = bf16[1,2,256,64] custom-call(%%a, %%b, %%c, %%d), '
            'custom_call_target="tpu_custom_call"')
    ops = [Event("%fusion.1 = f32[8] fusion(%p)", 0, 10_000)] + [
        Event(call % name, start, end) for name, start, end in kernels]
    win = tr.device_window({"XLA Modules": [Event("jit_step(1)", 0, 100_000)],
                            "XLA Ops": ops}, "jit_step")
    return SimpleNamespace(win0=win, n_steps=1, peak=PEAK, hlo_text="",
                           attention={"fwd": (4e8, 1e5), "bwd": (1e9, 2e5)})


def test_one_backward_kernel_and_two_of_equal_time_read_the_same():
    """THE property: a kernel's roofline reads the same work whatever
    implements it. Two traces of equal times, one with ``hvd_flash_dkv``
    + ``hvd_flash_dq`` and one with a single ``hvd_flash_bwd``: every
    reader gives the same number; so does a backward of three."""
    two = _synthetic([("hvd_flash_fwd.4", 10_000, 20_000),
                      ("hvd_flash_dkv.4", 30_000, 50_000),
                      ("hvd_flash_dq.4", 50_000, 65_000)])
    one = _synthetic([("hvd_flash_fwd.4", 10_000, 20_000),
                      ("hvd_flash_bwd.4", 30_000, 65_000)])
    three = _synthetic([("hvd_flash_fwd.4", 10_000, 20_000),
                        ("hvd_flash_dk", 30_000, 40_000),
                        ("hvd_flash_dv.1", 40_000, 50_000),
                        ("hvd_flash_dq.9", 50_000, 65_000)])
    names = ["kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
             "kernel.flash_roofline", "kernel.flash_share_pct",
             "kernel.flash_dkv_roofline", "kernel.flash_dq_roofline"]
    read = [{name: reader(name)(ctx) for name in names}
            for ctx in (two, one, three)]
    assert read[0] == read[1] == read[2]
    # By hand: 4e8 / 197e12 = 2.030 us of 10; 1e9 / 197e12 = 5.076 of 35.
    assert read[0]["kernel.flash_fwd_roofline"] == pytest.approx(20.30, 1e-3)
    assert read[0]["kernel.flash_bwd_roofline"] == pytest.approx(14.50, 1e-3)
    assert read[0]["kernel.flash_roofline"] == pytest.approx(
        100 * (4e8 + 1e9) / 197e12 / 45e-6)
    assert read[0]["kernel.flash_share_pct"] == pytest.approx(100 * 45 / 55)
    # No backward kernel in the trace: that reader alone gives nothing.
    forward = _synthetic([("hvd_flash_fwd.4", 10_000, 20_000)])
    assert reader("kernel.flash_bwd_roofline")(forward) is None
    assert reader("kernel.flash_fwd_roofline")(forward) == pytest.approx(
        20.30, 1e-3)
    # A Mosaic call of another name, whatever its operands: no kernel.
    other = _synthetic([("hvd_moe_gmm.2", 10_000, 20_000),
                        ("hvd_dsa_fwd.1", 30_000, 40_000)])
    assert all(reader(name)(other) is None for name in names)
