"""Where jax's persistent compilation cache lives.

The directory is part of the cache key's lookup path, so it must not
move between runs: never a tempdir, a pid or a timestamp. Whoever runs
the program may place the cache from outside with
``JAX_COMPILATION_CACHE_DIR`` (jax reads that variable itself); when
they do not, it sits at one fixed path inside the checkout.

This is also where the process listens to what jax says about its own
compile path (``install_compile_listeners``): every program it traces,
lowers and compiles or reads from this cache becomes ``compile/trace``,
``compile/lower`` and ``compile/backend`` spans of the launch's span
log (``utils/timeline.py``) and moves ``hvd_compile_seconds_total`` and
``hvd_compiles_total`` (docs/metrics.md#launch).
"""

from __future__ import annotations

import os
import threading

from horovod_tpu.utils import metrics as _metrics
from horovod_tpu.utils import timeline as _timeline

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Make this process and the workers it spawns share one persistent
    compile cache; returns its directory. Call before the first compile.

    With ``JAX_COMPILATION_CACHE_DIR`` set the directory is left alone:
    jax read the variable at import and no other is set in code.
    Otherwise the cache goes to ``<checkout>/.jax_cache``, exported
    through the same variable so spawned workers (hvdrun slots overlay
    ``os.environ``) land in the same place. Either way the compile
    listeners are installed.
    """
    install_compile_listeners()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


# --- what jax says about its compile path ------------------------------------

_M_COMPILE_SECONDS = _metrics.counter(
    "hvd_compile_seconds_total",
    "Seconds this process spent in jax's compile path, by phase: trace "
    "(Python to jaxpr), lower (jaxpr to StableHLO), backend (XLA "
    "compile, or the persistent cache's read and load) and cache_read "
    "(the part of backend a cache hit spent reading). A phase inside "
    "another on the same thread is part of it and not counted again.",
    ("phase",))
_M_COMPILES = _metrics.counter(
    "hvd_compiles_total",
    "Programs that reached the backend, by what the persistent compile "
    "cache did: hit (read from it), miss (asked, compiled) or off (not "
    "asked). A step that moves this after warm-up recompiled; the "
    "function's name is in the compile/backend span "
    "(hvd.launch_spans()).",
    ("cache",))

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


def _bare(fun_name: str) -> str:
    """``jit(step)`` -> ``step``: jax names a traced function bare and
    the lowered and compiled program by its wrapper."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


class CompileListener:
    """jax's monitoring events -> spans of ``log`` and the two counter
    families.

    jax announces a phase as it begins (a scalar) and as it ends (a
    time span on ``time.time()``), each with ``fun_name``. Phases nest
    -- every jitted function called under a trace is traced in turn --
    so only the outermost phase of a thread is filed: what runs inside
    it is its own time. The cache's events carry no name and fire inside
    the backend phase, before it ends: they are held per thread and
    filed with the backend span that closes next.
    """

    def __init__(self, log, seconds=_M_COMPILE_SECONDS,
                 compiles=_M_COMPILES):
        self._log = log
        self._seconds = seconds
        self._compiles = compiles
        self._local = threading.local()

    def _state(self):
        local = self._local
        if not hasattr(local, "depth"):
            local.depth, local.cache = 0, {}
        return local

    def tracing(self) -> bool:
        """Whether a compile phase is open on this thread: jax is
        running the program's Python to trace it."""
        return getattr(self._local, "depth", 0) > 0

    def on_scalar(self, event, value, **kwargs):
        if event in _PHASES:
            self._state().depth += 1

    def on_event(self, event, **kwargs):
        if event == _CACHE_HIT:
            self._state().cache["cache"] = "hit"
        elif event in (_CACHE_ASKED, _CACHE_MISS):
            self._state().cache.setdefault("cache", "miss")

    def on_duration(self, event, duration, **kwargs):
        key = _CACHE_SECONDS.get(event)
        if key is not None:
            self._state().cache[key] = duration

    def on_time_span(self, event, start, end, **kwargs):
        phase = _PHASES.get(event)
        if phase is None:
            return
        state = self._state()
        state.depth = max(state.depth - 1, 0)
        args = {}
        if phase == "backend":
            args["cache"] = "off"
            args.update(state.cache)
            state.cache = {}
            self._compiles.labels(cache=args["cache"]).inc()
        if state.depth:   # inside another phase: that one's time
            return
        self._seconds.labels(phase=phase).inc(end - start)
        if "cache_read_s" in args:
            self._seconds.labels(phase="cache_read").inc(
                args["cache_read_s"])
        self._log.record("compile/" + phase, start, end,
                         fun_name=_bare(str(kwargs.get("fun_name", ""))),
                         **args)


_install_lock = threading.Lock()
_installed = None


def install_compile_listeners() -> CompileListener:
    """Register the process's ONE ``CompileListener`` with
    ``jax.monitoring``, the first time this is called
    (``enable_compile_cache()`` and ``hvd.init()`` both call it)."""
    global _installed
    with _install_lock:
        if _installed is None:
            from jax import monitoring

            listener = CompileListener(_timeline.LAUNCH_LOG)
            monitoring.register_scalar_listener(listener.on_scalar)
            monitoring.register_event_listener(listener.on_event)
            monitoring.register_event_duration_secs_listener(
                listener.on_duration)
            monitoring.register_event_time_span_listener(
                listener.on_time_span)
            _installed = listener
        return _installed


def tracing() -> bool:
    """``CompileListener.tracing()`` of the process's listener; False
    before it is installed (nothing says when a phase begins)."""
    return _installed is not None and _installed.tracing()
