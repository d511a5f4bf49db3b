"""``trace_reduce.py`` against a small trace recorded on a TPU v5e
(``record_trace.py``: three runs of a program of 17 instructions, the
three flash kernels among them, 2 ms of host sleep between runs; PR 22,
before the kernels carried a ``name=``) and against hand-built events."""

import os

import pytest

from benchmark import trace_reduce as tr
from benchmark import trace_view
from benchmark.trace_reduce import Event

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "small.xplane.pb")


@pytest.fixture(scope="module")
def window():
    trace = tr.load(RECORDED)
    assert sorted(trace.devices) == [0]
    return trace, tr.device_window(trace.devices[0], "jit_small_step")


def test_recorded_window_busy_and_idle(window):
    _, win = window
    assert len(win.steps) == 3
    assert len(win.ops) == 3 * 17
    # The device ran each program for 13.7 us and slept between them.
    assert [round(s.seconds * 1e6, 1) for s in win.steps] == [13.7, 13.7, 13.9]
    assert win.window_s == pytest.approx(7.8326e-3, rel=1e-4)
    assert win.busy_s == pytest.approx(36.5e-6, rel=1e-2)
    assert win.busy_s <= sum(s.seconds for s in win.steps)
    idle = tr.gaps(tr.spans(win.ops), win.lo, win.hi)
    assert sum(hi - lo for lo, hi in idle) * 1e-9 == pytest.approx(
        win.window_s - win.busy_s)


def test_recorded_kernels_are_told_apart_by_name_alone(window):
    """The recorded Mosaic calls bear the compiler's names (``jvp__.1``:
    3 operands; ``transpose_jvp___.2`` / ``.3``: 6): no flash kernel.
    Under the names a program of today gives them they are the three,
    with the times as recorded."""
    from benchmark.tests.test_scope_view import named_window

    _, win = window
    mosaic = [e for e in win.ops if tr.is_mosaic_call(e.name)]
    assert len(mosaic) == 9
    assert {tr.opcode(e.name) for e in mosaic} == {"custom-call"}
    assert tr.instruction_name(mosaic[0].name) == "jvp__.1"
    assert {e.name.split(" custom-call(")[1].split("), ")[0].count("%")
            for e in mosaic} == {3, 6}
    assert not any(tr.flash_kernel(e.name) for e in win.ops)
    assert tr.kernel_seconds(win.ops) == {}
    assert not any(tr.is_collective(e.name) for e in win.ops)
    ops = named_window(win).ops
    by_kernel = tr.time_by([e for e in ops if tr.flash_kernel(e.name)],
                           tr.flash_kernel)
    assert list(by_kernel) == ["dkv", "fwd", "dq"]
    assert by_kernel["fwd"] == pytest.approx(3 * 1.491e-6, rel=1e-2)
    assert tr.kernel_seconds(ops) == {
        name: (pytest.approx(seconds), 3)
        for name, seconds in by_kernel.items()}
    assert tr.time_by(ops, tr.category)["flash dkv"] == by_kernel["dkv"]


def _call(name, operands, results=1):
    shape = "bf16[1,2,256,64]{3,2,1,0}"
    return "%%%s = %s custom-call(%s), custom_call_target=\"%s\"" % (
        name, shape if results == 1 else "(%s)" % ", ".join([shape] * results),
        ", ".join("%%a.%d" % i for i in range(operands)), "tpu_custom_call")


@pytest.mark.parametrize("name,operands,results,kernel", [
    # The name decides, whatever the operands and results.
    ("hvd_flash_fwd.3", 3, 2, "fwd"),
    ("hvd_flash_fwd", 7, 2, "fwd"),
    ("hvd_flash_fwd.12", 4, 1, "fwd"),
    ("hvd_flash_dkv.47", 6, 2, "dkv"),
    ("hvd_flash_dq.1", 6, 1, "dq"),
    ("hvd_flash_bwd.2", 9, 3, "bwd"),
    ("hvd_flash_dq_fused.5", 8, 1, "dq_fused"),
    # 3 or 6 operands under another name is no flash kernel.
    ("jvp__.1", 3, 2, ""),
    ("transpose_jvp___.2", 6, 2, ""),
    ("transpose_jvp___.3", 6, 1, ""),
    ("hvd_moe_gmm.4", 3, 1, ""),
    ("hvd_ssm_scan_bwd.2", 6, 2, ""),
    ("hvd_dsa_fwd.1", 3, 2, ""),
    ("hvd_dsa_choose", 6, 2, ""),
    ("my_hvd_flash_fwd.1", 3, 2, ""),
])
def test_a_flash_kernel_is_told_by_its_name(name, operands, results, kernel):
    assert tr.flash_kernel(_call(name, operands, results)) == kernel
    assert tr.category(_call(name, operands, results)) == \
        "flash " + (kernel or "other")


def test_named_kernels_of_other_families_and_their_direction():
    assert tr.named_kernel(_call("hvd_dsa_dkv.6", 7, 2), "hvd_dsa_") == "dkv"
    assert tr.named_kernel(_call("hvd_dsa_choose.1", 4, 2),
                           "hvd_dsa_") == "choose"
    assert tr.named_kernel(_call("hvd_flash_fwd.1", 3), "hvd_dsa_") == ""
    # Not a Mosaic call: no kernel, whatever it is called.
    assert tr.flash_kernel("%hvd_flash_fwd.1 = bf16[8] fusion(%a)") == ""
    assert tr.flash_kernel("hvd_flash_fwd") == ""
    # Every name but the forward's works for the backward pass.
    assert tr.direction("fwd") == "fwd"
    assert [tr.direction(k) for k in ("dkv", "dq", "bwd", "dq_fused")] \
        == ["bwd"] * 4
    events = [Event(_call("hvd_flash_dkv.1", 6, 2), 0, 2000),
              Event(_call("hvd_flash_dkv.2", 6, 2), 3000, 4000),
              Event(_call("hvd_moe_gmm", 5), 5000, 9000),
              Event(_call("hvd_flash_fwd.1", 3, 2), 9000, 9500)]
    assert tr.kernel_seconds(events) == {
        "dkv": (pytest.approx(3e-6), 2), "fwd": (pytest.approx(0.5e-6), 1)}
    assert tr.kernel_seconds(events, "hvd_moe_") == {
        "gmm": (pytest.approx(4e-6), 1)}


def test_recorded_gaps_go_to_the_host_span_under_them(window):
    trace, win = window
    notes = tr.annotations(trace, ("dispatch", "loss_fetch", "window_edge"))
    assert [e.name for e in notes].count("dispatch") == 3
    offset = tr.host_offset(win, [e for e in notes if e.name == "dispatch"])
    named = tr.attribute_gaps(win, notes, offset, top=2)
    # The two long gaps are the host blocking on the loss, then asleep.
    assert [n for n, _ in named] == ["loss_fetch", "loss_fetch"]
    assert named[0][1] == pytest.approx(4.4747e-3, rel=1e-3)


def test_union_gaps_uncovered_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert tr.length([(0, 2), (1, 3), (5, 7)]) == 5
    assert tr.gaps([(1, 3), (5, 7)], 0, 10) == [(0, 1), (3, 5), (7, 10)]
    assert tr.gaps([], 2, 4) == [(2, 4)]
    assert tr.uncovered([(0, 10)], [(2, 4), (3, 6), (9, 12)]) == 5


def _op(name, opcode, start, end, operands="%x"):
    return Event("%%%s = f32[8]{0} %s(f32[8]{0} %s)" % (name, opcode,
                                                       operands), start, end)


def test_exposed_collective_on_a_hand_built_overlap():
    """One step 0..100. A synchronous all-reduce 10..30 with nothing
    beside it; an asynchronous one from 40 to 90 (start 40..41, done
    80..90) with a fusion 41..70 under it: exposed are 20 of the first,
    and of the second its start (1), the uncovered 70..80 and its done
    (10)."""
    step = Event("jit_step(1)", 0, 100)
    ops = [_op("fusion.1", "fusion", 0, 10),
           _op("all-reduce.1", "all-reduce", 10, 30),
           _op("all-reduce-start.2", "all-reduce-start", 40, 41),
           _op("fusion.2", "fusion", 41, 70),
           _op("all-reduce-done.2", "all-reduce-done", 80, 90),
           _op("fusion.3", "fusion", 90, 100)]
    async_ops = [_op("all-reduce-start.2", "all-reduce-start", 40, 90)]
    win = tr.device_window({"XLA Modules": [step], "XLA Ops": ops,
                            "Async XLA Ops": async_ops}, "jit_step")
    assert tr.length(tr.collective_intervals(win)) == 70
    assert tr.exposed_collective(win) == 20 + 1 + 10 + 10
    assert win.busy_s == pytest.approx(80e-9)
    assert tr.time_by(win.ops, tr.category) == {
        "other fusion": pytest.approx(49e-9),
        "collective": pytest.approx(31e-9)}


def test_hlo_counts_of_a_compiled_step():
    text = "\n".join([
        "HloModule jit_step, entry_computation_layout={()}",
        "  %all-reduce.1 = f32[1048576]{0} all-reduce(f32[1048576]{0} %p), "
        "replica_groups={{0,1,2,3}}, to_apply=%add",
        "  %all-reduce-start.2 = (f32[16,8]{1,0}, bf16[4]{0}) "
        "all-reduce-start(f32[16,8]{1,0} %a, bf16[4]{0} %b)",
        "  %all-reduce-done.2 = (f32[16,8]{1,0}, bf16[4]{0}) "
        "all-reduce-done(%all-reduce-start.2)",
        "  %k.3 = bf16[2]{0} custom-call(bf16[2]{0} %q), "
        'custom_call_target="tpu_custom_call"',
        "  ROOT %fusion.9 = f32[2]{0} fusion(f32[2]{0} %z), kind=kLoop",
    ])
    assert trace_view.hlo_counts(text) == {
        "collectives": {
            "all-reduce": {"calls": 1, "result_bytes": 4 * 1048576},
            "all-reduce-start": {"calls": 1, "result_bytes": 16 * 8 * 4 + 8}},
        "tpu_custom_calls": 1}
