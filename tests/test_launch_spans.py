"""The launch's span log and compile listeners (docs/timeline.md#launch).

``utils/timeline.py`` ``SpanLog`` keeps what a launch was made of;
``utils/compile_cache.py`` ``CompileListener`` turns jax's own compile
events into its ``compile/*`` spans and into ``hvd_compile_seconds_total``
/ ``hvd_compiles_total``. Both are exercised here on objects of the
test's own where they can be, and through ``hvd.launch_spans()`` where
the real listener has to be seen at work.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.utils import compile_cache, metrics, timeline  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
READ = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"


def _read_timeline(path):
    with open(path) as f:
        return json.loads(f.read().rstrip().rstrip(",") + "]")


# --- the span log -------------------------------------------------------------

def test_nesting_gives_parent_and_a_span_filed_late_finds_its_own():
    log = timeline.SpanLog()
    with log.span("init", size=2) as args:
        with log.span("init/core_start"):
            pass
        began = time.time()
        args["more"] = 1
        log.record("compile/trace", began, time.time(), fun_name="f")
    log.record("compile/backend", began - 60.0, began - 59.0)
    by_name = {s["name"]: s for s in log.spans()}
    init = by_name["init"]
    assert init["parent"] is None and init["args"] == {"size": 2, "more": 1}
    assert by_name["init/core_start"]["parent"] == init["id"]
    assert by_name["compile/trace"]["parent"] == init["id"]
    assert by_name["compile/trace"]["args"] == {"fun_name": "f"}
    assert by_name["compile/backend"]["parent"] is None
    assert [s["id"] for s in log.spans()] == [1, 2, 3, 4]
    assert all(s["start"] <= s["end"] and s["launch"] == 1
               for s in log.spans())


def test_a_span_begun_before_the_open_one_is_not_its_child():
    log = timeline.SpanLog()
    before = time.time() - 1.0
    with log.span("plan"):
        log.record("compile/lower", before, before + 0.5)
    assert log.spans()[1]["parent"] is None


def test_an_open_span_reads_end_none_and_another_thread_has_no_parent():
    log = timeline.SpanLog()
    seen = []

    def other():
        with log.span("plan"):
            seen.extend(log.spans())

    with log.span("init"):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
    assert [(s["name"], s["end"], s["parent"]) for s in seen] == [
        ("init", None, None), ("plan", None, None)]


def test_the_buffer_keeps_the_newest():
    log = timeline.SpanLog(capacity=8)
    for i in range(20):
        log.record("compile/trace", float(i), float(i) + 0.5, n=i)
    kept = log.spans()
    assert [s["args"]["n"] for s in kept] == list(range(12, 20))
    assert [s["id"] for s in kept] == list(range(13, 21))
    assert timeline.LAUNCH_LOG._spans.maxlen == 4096


def test_spans_are_copies():
    log = timeline.SpanLog()
    log.record("plan", 1.0, 2.0, chips=4)
    log.spans()[0]["args"]["chips"] = 8
    assert log.spans()[0]["args"] == {"chips": 4}


def test_launch_counts_the_inits_begun():
    log = timeline.SpanLog()
    log.record("import", 1.0, 2.0)
    assert log.begin_launch() == 1
    log.record("init", 2.0, 3.0)
    assert log.begin_launch() == 2
    log.record("init", 4.0, 5.0)
    assert [(s["name"], s["launch"]) for s in log.spans()] == [
        ("import", 1), ("init", 1), ("init", 2)]


def test_the_phase_gauge_holds_the_newest_launch():
    reg = metrics.MetricsRegistry()
    gauge = reg.gauge("hvd_launch_phase_seconds", "t", ("phase",))
    log = timeline.SpanLog(phase_gauge=gauge)
    log.record("import", 0.0, 3.0)
    log.begin_launch()
    log.record("init", 3.0, 4.0)
    log.record("plan", 4.0, 4.25)
    log.record("plan", 4.25, 4.5)
    log.record("plan/apply", 5.0, 7.0)
    log.record("compile/trace", 7.0, 9.0)

    def read():
        return {p: reg.value("hvd_launch_phase_seconds", phase=p)
                for p in ("import", "init", "plan", "apply")}

    assert read() == {"import": 3.0, "init": 1.0, "plan": 0.5, "apply": 2.0}
    log.begin_launch()
    log.record("init", 10.0, 10.5)
    assert read() == {"import": 3.0, "init": 0.5, "plan": 0.0, "apply": 0.0}


def test_an_attached_timeline_receives_the_pair(tmp_path):
    log = timeline.SpanLog()
    log.record("import", time.time() - 5.0, time.time() - 4.0)
    path = str(tmp_path / "t.json")
    tl = timeline.Timeline(path)
    try:
        with log.span("init"):
            log.attach(tl)   # as hvd.init() opens HOROVOD_TIMELINE
            with log.span("init/core_start", size=2):
                pass
        log.attach(None)
        log.record("plan", time.time(), time.time())
    finally:
        tl.close()
    events = [e for e in _read_timeline(path) if e.get("cat") == "launch"]
    assert [(e["name"], e["ph"]) for e in events] == [
        ("import", "B"), ("import", "E"),
        ("init/core_start", "B"), ("init/core_start", "E"),
        ("init", "B"), ("init", "E")]
    by = {(e["name"], e["ph"]): e for e in events}
    assert by[("import", "B")]["ts"] < by[("import", "E")]["ts"] < 0
    assert by[("import", "E")]["ts"] - by[("import", "B")]["ts"] \
        == pytest.approx(1e6, rel=1e-3)
    assert by[("init", "B")]["ts"] <= by[("init/core_start", "B")]["ts"]
    spans = {s["name"]: s for s in log.spans()}
    assert by[("init/core_start", "B")]["args"] == {
        "size": 2, "id": spans["init/core_start"]["id"], "launch": 1,
        "parent": spans["init"]["id"]}
    assert all(e["tid"] == "launch" for e in events)


def test_a_fresh_process_files_its_import():
    """``import horovod_tpu`` alone: one span, first to last line of the
    package's ``__init__``, launch 1, and its gauge."""
    code = ("import json, time; t0 = time.time(); import horovod_tpu as hvd; "
            "t1 = time.time(); "
            "print(json.dumps([t0, t1, hvd.launch_spans(), "
            "hvd.metrics_snapshot()['hvd_launch_phase_seconds']]))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=_REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    t0, t1, spans, gauge = json.loads(out.stdout.strip().splitlines()[-1])
    (only,) = spans
    assert (only["name"], only["launch"], only["parent"], only["id"]) \
        == ("import", 1, None, 1)
    assert t0 <= only["start"] < only["end"] <= t1
    assert only["end"] - only["start"] > 0.5 * (t1 - t0)
    assert gauge["values"] == [{"labels": {"phase": "import"},
                                "value": only["end"] - only["start"]}]


def _since(last_id):
    return [s for s in hvd.launch_spans() if s["id"] > last_id]


def _last_id():
    return max((s["id"] for s in hvd.launch_spans()), default=0)


def test_init_files_its_parts_under_the_next_launch(tmp_path):
    hvd.shutdown()
    path = str(tmp_path / "launch.json")
    hvd.init()
    try:
        launch = max(s["launch"] for s in hvd.launch_spans())
        hvd.shutdown()
        last = _last_id()
        hvd.init()
        hvd.init()   # on an initialized process: nothing
        hvd.start_timeline(path)
        plan = hvd.plan(param_bytes=4096, batch=8, chips=1)
        plan.apply(devices=jax.devices()[:1])
        hvd.stop_timeline()
        mine = _since(last)
        assert {s["launch"] for s in mine} == {launch + 1}
        names = [s["name"] for s in mine]
        assert names[0] == "init" and names.count("init") == 1
        made = next(s for s in mine if s["name"] == "plan")
        assert made["args"] == {"chips": 1, "axes": {"data": 1}}
        applied = next(s for s in mine if s["name"] == "plan/apply")
        assert applied["args"] == {"axes": {"data": 1}}
        assert metrics.value("hvd_launch_phase_seconds", phase="init") \
            == pytest.approx(mine[0]["end"] - mine[0]["start"])
        assert metrics.value("hvd_launch_phase_seconds", phase="import") > 0
        written = {(e["name"], e["ph"]) for e in _read_timeline(path)
                   if e.get("cat") == "launch"}
        # What closed before the file was opened is replayed into it.
        assert {("init", "B"), ("init", "E"), ("plan", "B"),
                ("plan/apply", "E")} <= written
    finally:
        from horovod_tpu.parallel import reset_global_mesh

        reset_global_mesh()
        hvd.shutdown()


_TWO_RANK_INIT = """
import json
import numpy as np
import horovod_tpu as hvd
from horovod_tpu.ops import eager

sent = []
broadcast = eager.broadcast
def recording(tensor, root_rank, name=None, **kw):
    sent.append(name)
    return broadcast(tensor, root_rank, name=name, **kw)
eager.broadcast = recording
hvd.init()
eager.broadcast = broadcast
spans = hvd.launch_spans()
total = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name="formed")
print("TWO_RANK_INIT " + json.dumps({
    "size": hvd.size(), "sum": float(np.asarray(total)[0]), "sent": sent,
    "spans": [[s["name"], s["parent"], s["id"]] for s in spans]}))
hvd.shutdown()
"""


def test_a_two_rank_init_is_the_core_start_and_sends_nothing(tmp_path):
    """A world of two forms under the ``init`` span with the core's start
    as its part and no eager broadcast: no ``init/flash_tile_sync``, no
    object named ``flash_tune.cache_sync`` (the tile tuner's, which every
    multi-rank init once paid for)."""
    from tests.test_native_core import _launch

    script = tmp_path / "two_rank_init.py"
    script.write_text(_TWO_RANK_INIT)
    codes, outputs = _launch(2, str(script))
    for rank, (code, out) in enumerate(zip(codes, outputs)):
        assert code == 0, "rank %d failed:\n%s" % (rank, out)
        (line,) = [ln for ln in out.splitlines()
                   if ln.startswith("TWO_RANK_INIT ")]
        got = json.loads(line[len("TWO_RANK_INIT "):])
        assert (got["size"], got["sum"]) == (2, 2.0)
        assert got["sent"] == []
        (init,) = [s for s in got["spans"] if s[0] == "init"]
        parts = [name for name, parent, _ in got["spans"]
                 if parent == init[2]]
        assert parts == ["init/core_start"]
        assert not any("flash" in name for name, _, _ in got["spans"])


# --- the listener, fed by hand --------------------------------------------------

def _fed():
    reg = metrics.MetricsRegistry()
    log = timeline.SpanLog()
    listener = compile_cache.CompileListener(
        log,
        seconds=reg.counter("hvd_compile_seconds_total", "t", ("phase",)),
        compiles=reg.counter("hvd_compiles_total", "t", ("cache",)))

    def phase(event, start, end, fun_name, inside=()):
        listener.on_scalar(event, start, fun_name=fun_name)
        for call in inside:
            call()
        listener.on_time_span(event, start, end, fun_name=fun_name)

    def seconds(name):
        return reg.value("hvd_compile_seconds_total", phase=name)

    def compiles(cache):
        return reg.value("hvd_compiles_total", cache=cache)

    return listener, log, phase, seconds, compiles


def test_cache_events_are_filed_with_the_backend_span_that_closes_next():
    listener, log, phase, seconds, compiles = _fed()
    # A hit: asked, hit, the two durations, then the phase ends.
    phase(TRACE, 10.0, 10.5, "step")
    phase(LOWER, 10.5, 11.5, "jit(step)")
    phase(BACKEND, 11.5, 13.5, "jit(step)", inside=[
        lambda: listener.on_event(ASKED),
        lambda: listener.on_event(HIT),
        lambda: listener.on_duration(SAVED, 40.0),
        lambda: listener.on_duration(READ, 1.75)])
    # A miss: asked, compiled, written.
    phase(BACKEND, 20.0, 50.0, "jit(init)", inside=[
        lambda: listener.on_event(ASKED),
        lambda: listener.on_event(MISS)])
    # Asked and never written (under the cache's thresholds): a miss too.
    phase(BACKEND, 50.0, 51.0, "jit(add)", inside=[
        lambda: listener.on_event(ASKED)])
    # No cache.
    phase(BACKEND, 60.0, 64.0, "jit(mean)")
    # Other events of jax's are not this listener's.
    listener.on_event("/jax/compilation_cache/tasks_using_cache")
    listener.on_duration("/jax/pjit/something_else", 3.0)
    listener.on_scalar("/jax/other", 1.0)
    listener.on_time_span("/jax/other", 1.0, 2.0)
    assert [(s["name"], s["end"] - s["start"], s["args"])
            for s in log.spans()] == [
        ("compile/trace", 0.5, {"fun_name": "step"}),
        ("compile/lower", 1.0, {"fun_name": "step"}),
        ("compile/backend", 2.0, {"fun_name": "step", "cache": "hit",
                                  "saved_s": 40.0, "cache_read_s": 1.75}),
        ("compile/backend", 30.0, {"fun_name": "init", "cache": "miss"}),
        ("compile/backend", 1.0, {"fun_name": "add", "cache": "miss"}),
        ("compile/backend", 4.0, {"fun_name": "mean", "cache": "off"})]
    assert (seconds("trace"), seconds("lower"), seconds("backend"),
            seconds("cache_read")) == (0.5, 1.0, 37.0, 1.75)
    assert (compiles("hit"), compiles("miss"), compiles("off")) == (1, 2, 1)


def test_a_phase_inside_another_is_part_of_it():
    listener, log, phase, seconds, compiles = _fed()
    inner = [lambda: phase(TRACE, 1.0, 1.25, "add"),
             lambda: phase(TRACE, 1.25, 1.5, "inner", inside=[
                 lambda: phase(TRACE, 1.3, 1.4, "multiply")]),
             lambda: phase(BACKEND, 1.5, 1.75, "jit(constant)", inside=[
                 lambda: listener.on_event(ASKED)])]
    phase(TRACE, 0.0, 2.0, "step", inside=inner)
    phase(BACKEND, 2.0, 3.0, "jit(step)")
    assert [(s["name"], s["args"]) for s in log.spans()] == [
        ("compile/trace", {"fun_name": "step"}),
        ("compile/backend", {"fun_name": "step", "cache": "off"})]
    assert (seconds("trace"), seconds("backend")) == (2.0, 1.0)
    # A program that reached the backend is counted wherever it did, and
    # what the cache said of it is not kept for the next one.
    assert (compiles("miss"), compiles("off")) == (1, 1)


def test_an_end_without_its_beginning_is_still_filed():
    """Listeners installed while a phase is under way hear only its end."""
    listener, log, _, seconds, _ = _fed()
    listener.on_time_span(LOWER, 5.0, 6.0, fun_name="jit(step)")
    assert [s["name"] for s in log.spans()] == ["compile/lower"]
    assert seconds("lower") == 1.0


def test_threads_do_not_share_a_depth_or_a_cache_verdict():
    listener, log, phase, _, _ = _fed()
    listener.on_scalar(TRACE, 0.0, fun_name="slow")   # open on this thread
    listener.on_event(ASKED)

    def other():
        phase(BACKEND, 1.0, 2.0, "jit(quick)")

    worker = threading.Thread(target=other)
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive()
    assert [(s["name"], s["args"]) for s in log.spans()] == [
        ("compile/backend", {"fun_name": "quick", "cache": "off"})]


# --- the listener at work -------------------------------------------------------

def _counters():
    out = {}
    for name, label in (("hvd_compile_seconds_total", "phase"),
                        ("hvd_compiles_total", "cache")):
        family = metrics.REGISTRY.get(name)
        for values, child in family._items():
            out[(name, values)] = child.get()
    return out


def test_installing_twice_registers_once():
    from jax._src import monitoring

    first = compile_cache.install_compile_listeners()
    before = [len(monitoring.get_event_listeners()),
              len(monitoring.get_event_duration_listeners()),
              len(monitoring.get_event_time_span_listeners()),
              len(monitoring.get_scalar_listeners())]
    assert compile_cache.install_compile_listeners() is first
    compile_cache.enable_compile_cache()
    hvd.init()
    assert before == [len(monitoring.get_event_listeners()),
                      len(monitoring.get_event_duration_listeners()),
                      len(monitoring.get_event_time_span_listeners()),
                      len(monitoring.get_scalar_listeners())]
    assert first.on_time_span in monitoring.get_event_time_span_listeners()


def test_enable_compile_cache_installs_on_both_paths(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(compile_cache, "install_compile_listeners",
                        lambda: calls.append(1))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == [1]


def test_a_jitted_function_adds_its_three_spans_once():
    compile_cache.install_compile_listeners()

    def hvd_test_launch_fn(x):
        return jnp.tanh(x) * 3.0 + jnp.where(x > 0, x, 0.0)

    fn = jax.jit(hvd_test_launch_fn)
    x = jnp.arange(7.0)
    x.block_until_ready()
    last, counted = _last_id(), _counters()
    fn(x).block_until_ready()
    added = _since(last)
    mine = [s for s in added
            if s["args"].get("fun_name") == "hvd_test_launch_fn"]
    assert [s["name"] for s in mine] == [
        "compile/trace", "compile/lower", "compile/backend"]
    # What the function calls is traced inside its trace: no span.
    assert not [s for s in added
                if s["args"].get("fun_name") in ("tanh", "_where")]
    assert mine[2]["args"]["cache"] in ("hit", "miss", "off")
    assert mine[0]["end"] <= mine[1]["start"] + 1e-3
    assert mine[1]["end"] <= mine[2]["start"] + 1e-3
    assert abs(mine[2]["end"] - time.time()) < 60.0   # time.time()'s clock
    after = _counters()
    cache = ("hvd_compiles_total", (mine[2]["args"]["cache"],))
    assert after[cache] - counted.get(cache, 0.0) >= 1
    for s in mine:
        key = ("hvd_compile_seconds_total", (s["name"].split("/")[1],))
        assert after[key] - counted.get(key, 0.0) \
            >= (s["end"] - s["start"]) * 0.999
    # The second call is jax's own cache hit: nothing is traced.
    last, counted = _last_id(), _counters()
    fn(x).block_until_ready()
    assert not _since(last) and _counters() == counted


def test_a_compiled_step_run_in_a_window_adds_nothing():
    """A window as ``benchmark/run.py`` runs one (``run_steps``: a step
    compiled before it, two steps in flight, the last state awaited):
    no span is filed and no counter moves, so nothing of the
    instrumentation runs inside a window."""
    compile_cache.install_compile_listeners()

    def run_steps(step, carry, pool, start, n_steps):
        losses = []
        jax.block_until_ready(carry)
        for i in range(n_steps):
            *carry, loss = step(*carry, pool[(start + i) % len(pool)])
            losses.append(loss)
            if i >= 2:
                losses[i - 2].block_until_ready()
        jax.block_until_ready(carry)
        return carry, losses

    def step(w, batch):
        loss, grad = jax.value_and_grad(
            lambda w: jnp.mean((batch @ w) ** 2))(w)
        return w - 0.1 * grad, loss

    w = jnp.ones((16, 4))
    pool = [jnp.full((8, 16), float(i)) for i in range(3)]
    compiled = jax.jit(step).lower(w, pool[0]).compile()
    (w,), _ = run_steps(compiled, (w,), pool, 0, n_steps=3)   # warm-up
    spans, counted = hvd.launch_spans(), _counters()
    (w,), losses = run_steps(compiled, (w,), pool, 3, n_steps=20)
    assert len(losses) == 20
    assert hvd.launch_spans() == spans and _counters() == counted
    assert metrics.value("hvd_launch_phase_seconds", phase="import") > 0


# --- the trace phase from inside ------------------------------------------------

def test_dropped_counts_what_fell_off_the_old_end():
    log = timeline.SpanLog(capacity=4)
    for i in range(4):
        log.record("compile/trace", float(i), float(i) + 0.5)
    assert log.dropped == 0
    with log.span("plan"):
        log.record("compile/lower", 9.0, 9.5)
    assert log.dropped == 2 and len(log.spans()) == 4
    assert timeline.LAUNCH_LOG.dropped >= 0


def _fed_by_hand(monkeypatch):
    """``trace_span`` on a listener and a log of the test's own."""
    listener, log, phase, _, _ = _fed()
    monkeypatch.setattr(compile_cache, "_installed", listener)
    monkeypatch.setattr(timeline, "LAUNCH_LOG", log)
    return listener, log, phase


def test_trace_span_files_only_while_a_phase_is_open(monkeypatch):
    listener, log, phase = _fed_by_hand(monkeypatch)
    assert not listener.tracing() and not compile_cache.tracing()
    with timeline.trace_span("block", layer="layer_0") as filed:
        assert filed is None
    assert log.spans() == []

    def callee():
        with timeline.trace_span("update"):
            pass

    def traced():
        assert listener.tracing() and compile_cache.tracing()
        with timeline.trace_span("block", layer="layer_0",
                                 kind="conv") as filed:
            with timeline.trace_span("kernel", kernel="hvd_flash_fwd"):
                pass
            filed["more"] = 1
        # A phase inside the phase (a jitted callee) does not close it.
        phase(TRACE, 1.0, 1.5, "callee", inside=[callee])
        assert listener.tracing()

    seen = []
    worker = threading.Thread(   # another thread's phase is not this one's
        target=lambda: seen.append(compile_cache.tracing()))
    phase(TRACE, 0.0, 2.0, "step", inside=[
        traced, worker.start, lambda: worker.join(timeout=10)])
    assert seen == [False] and not listener.tracing()
    with timeline.trace_span("block"):
        pass
    by_name = {s["name"]: s for s in log.spans()}
    assert sorted(by_name) == ["compile/trace", "trace/block",
                               "trace/kernel", "trace/update"]
    block = by_name["trace/block"]
    assert block["args"] == {"layer": "layer_0", "kind": "conv", "more": 1}
    assert block["parent"] is None and block["launch"] == 1
    assert by_name["trace/kernel"]["parent"] == block["id"]
    assert by_name["trace/kernel"]["args"] == {"kernel": "hvd_flash_fwd"}
    assert by_name["compile/trace"]["args"] == {"fun_name": "step"}


def test_before_the_listeners_are_installed_nothing_is_filed(monkeypatch):
    monkeypatch.setattr(compile_cache, "_installed", None)
    before = hvd.launch_spans()
    assert not compile_cache.tracing()
    with timeline.trace_span("block") as filed:
        assert filed is None
    assert hvd.launch_spans() == before


def test_an_attached_timeline_receives_a_trace_span(monkeypatch, tmp_path):
    listener, log, phase = _fed_by_hand(monkeypatch)
    path = str(tmp_path / "t.json")
    tl = timeline.Timeline(path)
    try:
        log.attach(tl)

        def traced():
            with timeline.trace_span("experts", held=8, routed=64):
                pass

        phase(TRACE, time.time(), time.time() + 1.0, "step", inside=[traced])
    finally:
        tl.close()
    events = [e for e in _read_timeline(path) if e.get("cat") == "launch"]
    assert [(e["name"], e["ph"]) for e in events] == [
        ("trace/experts", "B"), ("trace/experts", "E"),
        ("compile/trace", "B"), ("compile/trace", "E")]
    assert events[0]["args"] == {"held": 8, "routed": 64, "id": 1,
                                 "launch": 1}


def _tiny_step(attention="dense", n_layers=3, **block):
    """A tiny ``Transformer``'s SGD step, jitted, with its arguments."""
    import optax

    from flax.core import meta
    from horovod_tpu import models
    from horovod_tpu.jax import DistributedOptimizer

    cfg = models.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=n_layers, d_ff=64,
        max_seq_len=16, dtype=jnp.float32, attention=attention,
        block=models.BlockSpec(**block))
    model = models.Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), tokens))
    tx = DistributedOptimizer(optax.sgd(0.1))
    opt_state = tx.init(params)

    def hvd_tiny_step(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply(p, tokens)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return model, jax.jit(hvd_tiny_step), (params, opt_state, tokens)


def _trace_spans_since(last):
    return [s for s in _since(last) if s["name"].startswith("trace/")]


def test_a_traced_step_files_a_block_a_layer_and_an_eager_call_nothing():
    compile_cache.install_compile_listeners()
    kinds = ("full_attention", "sliding_attention", "full_attention")
    model, step, args = _tiny_step(
        layer_types=kinds, sliding_window=4, ffn="swiglu", num_experts=4,
        experts_per_token=2, first_dense_layers=1)
    last = _last_id()
    with jax.disable_jit():   # eager: every module runs, nothing is traced
        model.apply(args[0], args[2])
    assert not _trace_spans_since(last)

    last = _last_id()
    step.trace(*args)
    added = _since(last)
    (whole,) = [s for s in added if s["name"] == "compile/trace"]
    assert whole["args"] == {"fun_name": "hvd_tiny_step"}
    mine = [s for s in added if s["name"].startswith("trace/")]
    assert all(whole["start"] <= s["start"] <= s["end"] <= whole["end"]
               for s in mine)
    blocks = [s for s in mine if s["name"] == "trace/block"]
    assert [s["args"] for s in blocks] == [
        {"layer": "layer_%d" % i, "kind": kind, "ffn": "swiglu"}
        for i, kind in enumerate(kinds)]
    # The first layer's feed-forward is dense; each other block holds its
    # expert layer, the child of the span it lies in.
    experts = [s for s in mine if s["name"] == "trace/experts"]
    assert [(s["args"], s["parent"]) for s in experts] == [
        ({"held": 4, "routed": 4, "tap": "ffn", "ffn": "swiglu"},
         block["id"]) for block in blocks[1:]]
    # No axis is bound here: the sync traces nothing and files nothing;
    # the update does, outside every block.
    (update,) = [s for s in mine if s["name"] == "trace/update"]
    assert update["parent"] is None and update["args"] == {}
    assert update["start"] >= blocks[-1]["end"]
    assert {s["name"] for s in mine} <= {
        "trace/block", "trace/experts", "trace/kernel", "trace/update"}
    # jax's own cache: a second trace of the same step runs no Python.
    last = _last_id()
    step.trace(*args)
    assert not _trace_spans_since(last)


def test_the_sync_is_filed_where_an_axis_is_bound():
    import numpy as np
    import optax
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.jax import DistributedOptimizer
    from horovod_tpu.parallel.mesh import shard_map_compat

    compile_cache.install_compile_listeners()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    tx = DistributedOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
    opt_state = tx.init(params)

    def hvd_tiny_synced(params, opt_state, x):
        def local(params, opt_state, x):
            grads = jax.grad(
                lambda p: jnp.mean((x @ p["w"] + p["b"]) ** 2))(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

        return shard_map_compat(
            local, mesh=mesh, in_specs=(P(), P(), P("data")),
            out_specs=(P(), P()))(params, opt_state, x)

    last = _last_id()
    jax.jit(hvd_tiny_synced).trace(params, opt_state, jnp.ones((8, 4)))
    mine = _trace_spans_since(last)
    assert [(s["name"], s["args"]) for s in mine] == [
        ("trace/sync", {"leaves": 2}), ("trace/update", {})]


def test_kernel_spans_count_the_bodies_traced():
    """Two identical calls through a jitted callee (the selective
    scan's) are ONE body traced; through the flash call, which is no
    jitted callee, two."""
    from horovod_tpu.jax import introspect
    from horovod_tpu.ops.pallas_attention import flash_attention
    from horovod_tpu.ops.pallas_scan import selective_scan

    compile_cache.install_compile_listeners()
    # Shapes no other test of this process traces: jax's cache of traced
    # callees is the process's.
    t, e, n = 24, 384, 3
    x = jnp.ones((1, t, e))
    a, bc, d = jnp.ones((e, n)), jnp.ones((1, t, n)), jnp.ones((e,))
    q = jnp.ones((1, 40, 3, 8))

    def hvd_tiny_kernels(x, q):
        for _ in range(2):
            x = selective_scan(x, x, a, bc, bc, d)
        for _ in range(2):
            q = flash_attention(q, q, q)
        return x, q

    last = _last_id()
    jax.jit(hvd_tiny_kernels).trace(x, q)
    kernels = [s for s in _trace_spans_since(last)]
    assert all(s["name"] == "trace/kernel" for s in kernels)
    assert [s["args"] for s in kernels] == [
        {"kernel": introspect.KERNEL_SSM_SCAN_FWD},
        {"kernel": introspect.KERNEL_FLASH_FWD, "widths": "8"},
        {"kernel": introspect.KERNEL_FLASH_FWD, "widths": "8"}]


def test_the_lowered_step_is_the_same_without_the_spans(monkeypatch):
    """No name in the program moved: with ``trace_span`` replaced by a
    no-op in every module that calls it, the step lowers to the same
    text."""
    import contextlib

    from horovod_tpu.jax import optimizer
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import (
        pallas_attention,
        pallas_gather_sum,
        pallas_grouped_matmul,
    )
    from horovod_tpu.parallel import moe

    compile_cache.install_compile_listeners()
    blocks = dict(ffn="swiglu", num_experts=4, experts_per_token=2,
                  first_dense_layers=1)
    _, step, args = _tiny_step(attention="flash", n_layers=2, **blocks)
    last = _last_id()
    with_spans = step.lower(*args).as_text()
    assert {s["name"] for s in _trace_spans_since(last)} == {
        "trace/block", "trace/experts", "trace/kernel", "trace/update"}

    @contextlib.contextmanager
    def nothing(part, **args):
        yield None

    for module in (transformer, moe, optimizer, pallas_attention,
                   pallas_gather_sum, pallas_grouped_matmul):
        monkeypatch.setattr(module, "trace_span", nothing)
    _, step, args = _tiny_step(attention="flash", n_layers=2, **blocks)
    last = _last_id()
    assert step.lower(*args).as_text() == with_spans
    assert not _trace_spans_since(last)
