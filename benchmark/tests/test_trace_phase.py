"""``trace_phase_view.py`` and the five readers it feeds, on a launch
made by hand (``test_launch.py``'s, with ``trace/*`` spans inside the
check's trace of the step and inside the step's own); then once on the
program's own log, so that what the view looks for is what
``trace_span`` files."""

from types import SimpleNamespace

import pytest

from benchmark import launch_view, trace_phase_view
from benchmark.layer_metrics import reader
from benchmark.tests.test_launch import HLO, T0, _span, _spans

READERS = ("step_trace_kernels_s", "step_trace_model_s",
           "step_trace_update_s", "step_trace_rest_s", "kernel_bodies")


def _trace(i, part, start, end, parent=None, **args):
    return dict(_span(i, "trace/" + part, start, end, **args), parent=parent)


def _check_trace():
    """Inside the check's trace of the step's function (20 to 22 s)."""
    return [
        _trace(30, "block", 20.5, 21.5, layer="layer_0",
               kind="full_attention"),
        _trace(31, "kernel", 20.75, 21.25, parent=30, kernel="hvd_flash_fwd",
               widths="64"),
        _trace(32, "update", 21.5, 21.75),
    ]


def _step_trace():
    """Inside the step's own trace (40 to 44 s): one pass of two blocks,
    the first with an expert layer, a kernel body in each and in the
    expert layer, a backward kernel body outside every block (its rule
    runs when jax transposes), the sync and the update."""
    return [
        _trace(40, "loop_pass", 40.25, 42.25, **{"pass": 0}),
        _trace(41, "block", 40.5, 41.5, parent=40, layer="layer_0",
               kind="full_attention"),
        _trace(42, "kernel", 40.75, 41.0, parent=41, kernel="hvd_flash_fwd",
               widths="64"),
        _trace(43, "experts", 41.0, 41.25, parent=41, held=8, routed=64),
        _trace(44, "kernel", 41.0625, 41.125, parent=43,
               kernel="hvd_moe_gmm"),
        _trace(45, "block", 41.5, 42.0, parent=40, layer="layer_1",
               kind="sliding_attention"),
        _trace(46, "kernel", 41.75, 41.875, parent=45,
               kernel="hvd_flash_fwd", widths="64"),
        _trace(47, "kernel", 42.5, 42.75, kernel="hvd_flash_bwd",
               widths="64"),
        _trace(48, "sync", 43.0, 43.125, leaves=12),
        _trace(49, "update", 43.125, 43.5),
        # An open span is not read.
        dict(_trace(50, "block", 43.75, 43.75), end=None),
    ]


def _ctx(spans, dropped=0):
    return SimpleNamespace(
        hlo_text=HLO, launch_spans=spans, launch_dropped=dropped,
        launch_counters={"miss": 0.0},
        timeline={"init_s": 12.0, "compile_s": 10.5})


@pytest.fixture()
def traced(monkeypatch):
    monkeypatch.setattr(launch_view, "process_start", lambda: T0)
    return _ctx(_spans() + _check_trace() + _step_trace())


def _read(ctx):
    return {name: reader("launch." + name)(ctx) for name in READERS}


def test_the_readers_on_a_traced_launch_made_by_hand(traced):
    read = _read(traced)
    # Four bodies: 0.25 + 0.0625 + 0.125 in the blocks, 0.25 outside.
    assert read["step_trace_kernels_s"] == 0.6875
    assert read["kernel_bodies"] == 4
    # The pass covers 2.0 (its blocks 1.5 with the expert layer inside
    # the first: counted once), less the 0.4375 of kernels inside it.
    assert read["step_trace_model_s"] == 1.5625
    assert read["step_trace_update_s"] == 0.5
    assert read["step_trace_rest_s"] == 4.0 - 0.6875 - 1.5625 - 0.5
    # The four seconds are the step's trace, divided.
    assert sum(read[name] for name in READERS[:4]) \
        == reader("launch.step_trace_s")(traced) == 4.0
    # Every other reader of the launch stands as it was.
    assert reader("launch.setup_compile_s")(traced) == 13.125


def test_the_checks_earlier_trace_is_left_out(traced):
    """The check traces the step's function first, under the same name:
    its ``trace/*`` spans are none of the step's."""
    with_check = _read(traced)
    assert _read(_ctx(_spans() + _step_trace())) == with_check
    # Only the check's trace holds any: the step's own has nothing to read.
    assert set(_read(_ctx(_spans() + _check_trace())).values()) == {None}


def test_a_program_without_the_spans_gives_nothing(monkeypatch):
    """The parent commit: a log, the step's three phases, no ``trace/*``
    span; and a program without the log at all."""
    import horovod_tpu

    assert set(_read(_ctx(_spans())).values()) == {None}
    assert reader("launch.step_trace_s")(_ctx(_spans())) == 4.0
    no_step = [s for s in _spans() + _step_trace()
               if s["args"].get("fun_name") != "hvd_bench_step"]
    assert set(_read(_ctx(no_step)).values()) == {None}
    monkeypatch.delattr(horovod_tpu, "launch_spans")
    assert set(_read(SimpleNamespace(hlo_text=HLO)).values()) == {None}


@pytest.mark.parametrize("by_hand, the_logs", [(1, 0), (300, 0), (None, 7)])
def test_a_log_that_dropped_a_span_gives_nothing(traced, by_hand, the_logs,
                                                 monkeypatch, capsys):
    """A partial sum is not a reading. A ``ctx`` that brings no count of
    its own is read off the program's log."""
    from horovod_tpu.utils import timeline

    monkeypatch.setattr(timeline.LAUNCH_LOG, "dropped", the_logs)
    traced.launch_dropped = by_hand
    assert set(_read(traced).values()) == {None}
    assert capsys.readouterr().err.count(
        "trace phase: the span log dropped") == 1


def test_one_line_says_each_part_apart(traced, capsys):
    _read(traced)
    _read(traced)
    lines = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("[bench] trace phase:")]
    (line,) = lines
    assert "the step's trace 4.000 s = kernels 0.688 + model 1.562 + " \
        "update 0.500 + rest 1.250" in line
    # Each part with what nests in it, and the lifting: the pass less
    # its blocks.
    for said in ("block 1.500 x2", "experts 0.250 x1", "loop_pass 2.000 x1",
                 "sync 0.125 x1", "update 0.375 x1",
                 "loop_pass less its blocks 0.500",
                 "4 kernel bodies: hvd_flash_bwd 0.250 x1, hvd_flash_fwd "
                 "0.375 x2, hvd_moe_gmm 0.062 x1",
                 "10 trace/* spans in the step's trace, 13 in the log, "
                 "dropped 0"):
        assert said in line, said


def test_a_step_of_no_block_and_no_kernel_reads_zeros():
    """ResNet-50's: an update and nothing of the model's."""
    spans = _spans() + [_trace(60, "sync", 43.0, 43.25, leaves=161),
                        _trace(61, "update", 43.25, 44.0)]
    assert _read(_ctx(spans)) == {
        "step_trace_kernels_s": 0.0, "step_trace_model_s": 0.0,
        "step_trace_update_s": 1.0, "step_trace_rest_s": 3.0,
        "kernel_bodies": 0}


def test_the_readers_on_the_programs_own_log(monkeypatch):
    """What the view looks for is what ``trace_span`` files: a step
    traced here under the benchmark's name, with the program's helper
    round two of its parts, is divided; the parts the view knows are
    read off the program's ``dropped`` count too."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.utils import timeline
    from horovod_tpu.utils.compile_cache import install_compile_listeners

    install_compile_listeners()
    # Other tests of this process may have filled the log.
    monkeypatch.setattr(timeline.LAUNCH_LOG, "dropped", 0)

    def hvd_bench_step(x):
        with timeline.trace_span("block", layer="layer_0", kind="conv"):
            with timeline.trace_span("kernel", kernel="hvd_test"):
                y = jnp.cos(x)
            y = y * 2.0
        with timeline.trace_span("update"):
            return y + 1.0

    compiled = jax.jit(hvd_bench_step).lower(jnp.arange(5.0)).compile()
    real = SimpleNamespace(hlo_text=compiled.as_text(),
                           timeline={"init_s": 1e9})
    read = _read(real)
    assert read["kernel_bodies"] == 1
    assert all(read[name] > 0.0 for name in READERS)
    assert sum(read[name] for name in READERS[:4]) == pytest.approx(
        reader("launch.step_trace_s")(real), rel=1e-9)
    assert trace_phase_view.dropped(real) == 0
    # And with what the program's log says it lost: nothing.
    monkeypatch.setattr(timeline.LAUNCH_LOG, "dropped", 2)
    again = SimpleNamespace(hlo_text=real.hlo_text, timeline=real.timeline)
    assert set(_read(again).values()) == {None}
