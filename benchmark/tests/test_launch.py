"""``launch_view.py`` and the six ``launch.*`` readers it feeds, on a
``ctx`` and a span list made by hand; then once on the program's own
log, so that the names the readers look for are the names it files."""

from types import SimpleNamespace

import pytest

from benchmark import launch_view
from benchmark.layer_metrics import reader

HLO = "HloModule jit_hvd_bench_step, is_scheduled=true, entry={...}\n"
T0 = 1000.0          # the process's start, on time.time()


def _span(i, name, start, end, **args):
    return {"id": i, "parent": None, "launch": 1, "name": name,
            "start": T0 + start, "end": T0 + end, "args": args}


def _spans():
    """One launch: 3 s of jax's import, the program's import, the
    runtime's start, init, two plans and an apply; then the check's
    program under the STEP's name, the weights, the step itself (a
    cache hit), a baseline step and a tiny program after the window."""
    return [
        _span(1, "import", 3.0, 4.0),
        _span(2, "init", 10.0, 10.5),
        _span(3, "init/metrics_server", 10.25, 10.5),
        _span(4, "plan", 10.5, 10.75, chips=1, axes={"data": 1}),
        _span(5, "plan", 10.75, 11.0, chips=1, axes={"data": 1}),
        _span(6, "plan/apply", 11.0, 11.5, axes={"data": 1}),
        _span(7, "compile/trace", 20.0, 22.0, fun_name="hvd_bench_step"),
        _span(8, "compile/lower", 22.0, 23.0, fun_name="hvd_bench_step"),
        _span(9, "compile/backend", 23.0, 31.0, fun_name="hvd_bench_step",
              cache="hit", cache_read_s=0.5, saved_s=90.0),
        _span(10, "compile/trace", 31.0, 31.5, fun_name="init"),
        # Two threads' phases overlap: covered once.
        _span(11, "compile/lower", 31.25, 32.0, fun_name="init"),
        _span(12, "compile/backend", 32.0, 33.0, fun_name="init",
              cache="miss"),
        _span(13, "compile/trace", 40.0, 44.0, fun_name="hvd_bench_step"),
        # A program compiled while the step was traced: the step's time.
        _span(14, "compile/backend", 41.0, 42.0, fun_name="constant",
              cache="off"),
        _span(15, "compile/lower", 44.0, 47.0, fun_name="hvd_bench_step"),
        _span(16, "compile/backend", 47.25, 50.25,
              fun_name="hvd_bench_step", cache="hit", cache_read_s=2.5,
              saved_s=60.0),
        _span(17, "compile/trace", 15.0, 16.0, fun_name="hvd_bench_baseline"),
        _span(18, "compile/backend", 16.0, 19.0,
              fun_name="hvd_bench_baseline", cache="hit"),
        _span(19, "compile/backend", 70.0, 70.125, fun_name="_mean",
              cache="hit"),
        # A later plan, and a span still open: neither is the launch's.
        _span(20, "plan", 60.0, 61.0, chips=1, axes={"data": 1}),
        dict(_span(21, "compile/trace", 80.0, 80.0, fun_name="late"),
             end=None),
    ]


@pytest.fixture()
def ctx(monkeypatch):
    monkeypatch.setattr(launch_view, "process_start", lambda: T0)
    return SimpleNamespace(
        hlo_text=HLO, launch_spans=_spans(), launch_counters={"miss": 1.0},
        timeline={"init_s": 12.0, "compile_s": 10.5})


def test_covered_is_a_union_less_what_stands_apart():
    assert launch_view.covered([]) == 0.0
    assert launch_view.covered([(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)]) == 4.0
    assert launch_view.covered([(0.0, 10.0)], but=[(2.0, 3.0), (9.0, 12.0)]) \
        == 8.0
    assert launch_view.covered([(0.0, 1.0), (0.0, 1.0)], but=[(0.0, 1.0)]) \
        == 0.0


def test_the_readers_on_a_launch_made_by_hand(ctx):
    read = {name: reader("launch." + name)(ctx) for name in (
        "program_s", "step_trace_s", "step_lower_s", "step_backend_s",
        "setup_compile_s", "cache_misses")}
    # import 1.0 + init 0.5 (its child inside it) + two plans 0.5 + apply
    # 0.5; the plan made after the first device array (12 s) is left out.
    assert read["program_s"] == 2.5
    # The step's own spans are the NEWEST of its name, not the check's.
    assert (read["step_trace_s"], read["step_lower_s"],
            read["step_backend_s"]) == (4.0, 3.0, 3.0)
    assert read["step_trace_s"] + read["step_lower_s"] \
        + read["step_backend_s"] <= ctx.timeline["compile_s"]
    # The check's program 11.0 + the weights 2.0 (an overlap counted once)
    # + the mean after the window 0.125; the step's three, what compiled
    # inside them and the baseline's stand apart.
    assert read["setup_compile_s"] == 13.125
    assert read["cache_misses"] == 1.0
    assert launch_view.step_fun_name(ctx) == "hvd_bench_step"


def test_a_program_without_the_log_gives_nothing(monkeypatch):
    import horovod_tpu

    monkeypatch.delattr(horovod_tpu, "launch_spans")
    bare = SimpleNamespace(hlo_text=HLO, timeline={"init_s": 12.0})
    for name in ("program_s", "step_trace_s", "step_lower_s",
                 "step_backend_s", "setup_compile_s", "cache_misses"):
        assert reader("launch." + name)(bare) is None, name


def test_a_log_without_the_steps_spans_leaves_its_phases_out(ctx):
    ctx.launch_spans = [s for s in _spans()
                        if s["args"].get("fun_name") != "hvd_bench_step"]
    assert reader("launch.step_trace_s")(ctx) is None
    assert reader("launch.step_backend_s")(ctx) is None
    assert reader("launch.setup_compile_s")(ctx) == 2.0 + 1.0 + 0.125
    assert reader("launch.program_s")(ctx) == 2.5


def test_without_a_process_start_every_span_of_the_program_counts(
        ctx, monkeypatch):
    monkeypatch.setattr(launch_view, "process_start", lambda: None)
    assert reader("launch.program_s")(ctx) == 3.5


def test_the_readers_on_the_programs_own_log():
    """The names the readers look for are what the program files: a
    step compiled here under the benchmark's name is found, phase by
    phase, and the counter is the registry's."""
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.utils import metrics
    from horovod_tpu.utils.compile_cache import install_compile_listeners

    install_compile_listeners()
    hvd.shutdown()
    hvd.init()   # a fresh `init` span: the log keeps only the newest
    assert launch_view.COMPILES == "hvd_compiles_total"
    assert set(launch_view.PROGRAM_SPANS) \
        == set(hvd.utils.timeline.LAUNCH_PHASES)

    def hvd_bench_step(x):
        return jnp.cos(x) * 2.0

    x = jnp.arange(5.0)
    compiled = jax.jit(hvd_bench_step).lower(x).compile()
    real = SimpleNamespace(hlo_text=compiled.as_text(),
                           timeline={"init_s": 1e9})
    assert launch_view.step_fun_name(real) == "hvd_bench_step"
    newest = launch_view.step_spans(real)
    assert sorted(newest) == ["backend", "lower", "trace"]
    assert newest["trace"]["end"] <= newest["lower"]["end"] \
        <= newest["backend"]["end"]
    for phase in launch_view.PHASES:
        assert reader("launch.step_%s_s" % phase)(real) \
            == newest[phase]["end"] - newest[phase]["start"] > 0.0
    assert reader("launch.program_s")(real) > 0.0
    assert reader("launch.setup_compile_s")(real) >= 0.0
    assert reader("launch.cache_misses")(real) \
        == (metrics.value("hvd_compiles_total", cache="miss") or 0.0)
