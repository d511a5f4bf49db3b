"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, ``hvd.init()``, weights and data made on the device from
the seed, the reference check, compile or cache read, warm-up), then a
window of ``--seconds`` in which nothing compiles. With ``--trace 0``
the last line of stdout carries the cell's end-to-end metrics; with
``--trace 1`` a short profiled window follows and the line carries the
per-layer metrics, each read by its own file under ``layer_metrics/``.
Everything else goes to stderr as ``[bench]`` lines.

There is no CPU fallback: without the chips the cell asks for this
exits non-zero and prints no result. ``--rehearse-cpu`` runs the same
control flow at the builder's tiny sizes on the CPU; its last line has
no ``metrics``, so no CPU number can be read as a device number.
"""

from __future__ import annotations

import os
import sys
import time

_T_IMPORT = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT  # the package root replaces the script's directory

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402

ANNOTATIONS = ("dispatch", "loss_fetch", "window_edge")
IN_FLIGHT = 2   # steps the host may run ahead of the device


def log(msg):
    print("[bench] " + msg, file=sys.stderr, flush=True)


def process_start():
    """The wall-clock time this process was started at, from the kernel
    where it tells (Linux), else the moment this file began to load."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - age
    except (OSError, ValueError, IndexError):
        return _T_IMPORT


def check_devices(cell, rehearse):
    """The chips the cell asks for, or exit non-zero naming what jax
    found. The device kind must be in the peak table."""
    import jax

    from benchmark.cell import HERE, read_json

    devices = jax.devices()
    found = sorted({d.platform for d in devices})
    if rehearse:
        if len(devices) < cell.chips:
            raise SystemExit("rehearsal wants %d virtual devices, jax has "
                             "%d" % (cell.chips, len(devices)))
        return devices, None
    if found != ["tpu"] or len(devices) != cell.chips:
        raise SystemExit(
            "benchmark: cell %s needs %d TPU chip(s); jax found %d device(s)"
            " of platform %s (%r)" % (cell.name, cell.chips, len(devices),
                                      "/".join(found),
                                      devices[0].device_kind))
    peaks = read_json(HERE, "peaks.json")["by_device_kind"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit("benchmark: device_kind %r is not in peaks.json "
                         "(%s)" % (kind, ", ".join(sorted(peaks))))
    return devices, peaks[kind]


class CompileCounter:
    """Counts what jax traces or compiles, through jax's own monitoring
    events; the window must see none."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event.startswith("/jax/core/compile/"):
            self.count += 1


def device_footprint(device, compiled, live_bytes):
    """Peak bytes on one chip. libtpu's ``peak_bytes_in_use`` counts the
    arrays the process holds and leaves out the room an executable
    reserves for its temporaries, which it reports apart as
    ``peak_bytes_reserved`` (ResNet-50 at 256 images: 1.5 GB in use,
    9.0 GB reserved, 9.05 GB of temporaries in ``memory_analysis()`` of
    the same executable; PERF.md PR 22). So: the larger of the in-use
    peak and the bytes live when the window starts (state, data pool)
    plus that reservation (``memory_analysis()`` where the runtime does
    not report one)."""
    stats = device.memory_stats() or {}
    reserved = stats.get("peak_bytes_reserved") \
        or compiled.memory_analysis().temp_size_in_bytes
    return max(stats.get("peak_bytes_in_use", 0), live_bytes + reserved)


def run_steps(step, carry, pool, start, n_steps=None, seconds=None):
    """Take steps from ``start`` for ``seconds`` or ``n_steps``, at most
    IN_FLIGHT ahead of the device, cycling the pool. The clock starts
    on a drained device and stops when the last state is ready. Losses
    stay on the device. Returns (carry, losses, seconds)."""
    import jax
    from jax.profiler import TraceAnnotation

    losses = []
    with TraceAnnotation("window_edge"):
        jax.block_until_ready(carry)
    t0 = time.perf_counter()
    i = 0
    while (i < n_steps) if n_steps is not None \
            else (time.perf_counter() - t0 < seconds):
        with TraceAnnotation("dispatch"):
            *carry, loss = step(*carry, pool[(start + i) % len(pool)])
        losses.append(loss)
        if i >= IN_FLIGHT:
            with TraceAnnotation("loss_fetch"):
                losses[i - IN_FLIGHT].block_until_ready()
        i += 1
    with TraceAnnotation("window_edge"):
        jax.block_until_ready(carry)
    return carry, losses, time.perf_counter() - t0


def pool_of_batches(asm, key, global_batch, data, shardings=None):
    """The traffic generator's pool, made on the device in one jitted
    call, as a list of global batches."""
    import jax

    from benchmark import traffic

    pool = jax.jit(
        lambda k: traffic.make_pool(
            k, data, global_batch=global_batch, config=asm.cell.config,
            **asm.model.pool_kwargs),
        out_shardings=shardings)(key)
    n = jax.tree.leaves(pool)[0].shape[0]
    return [jax.tree.map(lambda a: a[j], pool) for j in range(n)]


def baseline_units_per_s(asm, key, n_steps):
    """The sheet's single-worker baseline: the same model, optimizer
    and per-chip batch as a plain ``jax.jit`` step with optax on one
    device, with no ``hvd`` wrapper, no ``shard_map`` and no mesh, timed
    without the profiler. Runs before the cell's own state exists, so
    that the two never share the chip's memory."""
    import jax
    import optax

    from benchmark.cell import make_optimizer

    model, cell = asm.model, asm.cell
    tx = make_optimizer(cell.config["optimizer"])

    def hvd_bench_baseline(params, state, opt_state, batch):
        (loss, state), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, state, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), state, opt_state, loss

    k_init, k_data = jax.random.split(key)
    params, state = jax.jit(model.init)(k_init)
    opt_state = jax.jit(tx.init)(params)
    pool = pool_of_batches(asm, k_data, asm.per_chip_batch,
                           cell.traffic["data"])
    step = jax.jit(hvd_bench_baseline, donate_argnums=(0, 1, 2)).lower(
        params, state, opt_state, pool[0]).compile()
    carry = (params, state, opt_state)
    carry, _, _ = run_steps(step, carry, pool, 0, n_steps=3)
    carry, _, seconds = run_steps(step, carry, pool, 3, n_steps=n_steps)
    del carry, params, state, opt_state, pool
    return n_steps * asm.per_chip_batch * model.units_per_item / seconds


def read_layer_metrics(cell, ctx):
    """Each per-layer metric of this cell through its own reader,
    ``layer_metrics/<name>.py``'s ``read(ctx)``. A reader that finds
    nothing returns None and its metric is left out of the line."""
    from benchmark.cell import metrics_of
    from benchmark.layer_metrics import reader

    reported = {m["name"] for m in metrics_of(cell, "end_to_end")}
    out = {}
    for metric in metrics_of(cell, "per_layer"):
        if metric["moves"] not in reported:
            continue
        value = reader(metric["name"])(ctx)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main():
    t_start = process_start()
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny sizes on the CPU; prints no metric")
    args = p.parse_args()

    from benchmark import cell as cells

    cell = cells.load(args.workload, tiny=args.rehearse_cpu)

    import jax
    import jax.numpy as jnp

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # Every program of a run goes to the cache, however quick to build,
    # so that the second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    counter = CompileCounter()

    devices, peak = check_devices(cell, args.rehearse_cpu)
    from benchmark import check

    asm = cells.assemble(cell, devices)
    jnp.zeros(()).block_until_ready()
    init_s = time.time() - t_start
    log("cell %s: plan %s" % (cell.name, asm.plan.summary()))
    log("planner's unconstrained choice: %r" % (asm.free_choice.mesh_axes,))

    k_init, k_data, k_check, k_base = jax.random.split(
        jax.random.PRNGKey(args.seed), 4)
    timeline = {"init_s": init_s}
    baseline = None
    if args.trace and cell.traffic.get("baseline_steps"):
        t0 = time.time()
        baseline = baseline_units_per_s(asm, k_base,
                                        int(cell.traffic["baseline_steps"]))
        timeline["baseline_s"] = time.time() - t0

    # Weights, on the device, from the seed, in one jitted call.
    params, state = jax.jit(
        asm.model.init, out_shardings=asm.replicated)(k_init)

    pooled = jax.tree.map(   # a pool leads with its own, unsharded axis
        lambda s: jax.sharding.NamedSharding(
            s.mesh, jax.sharding.PartitionSpec(None, *s.spec)),
        asm.batch_sharding)

    via = cell.config["check"]["via"]
    verdict = None
    t_check = 0.0
    if via == "sgd_step":
        # Before the optimizer's state takes its room.
        t0 = time.time()
        sample = int(cell.config["check"]["sample_per_chip"]) * cell.chips
        (check_batch,) = pool_of_batches(
            asm, k_check, sample, dict(cell.traffic["data"], pool=1), pooled)
        lifted, grads, loss = check.sgd_step_gradients(
            asm, params, state, check_batch, k_check)
        verdict = check.against_reference(asm, grads, loss, lifted, state,
                                          check_batch)
        del check_batch, lifted, grads
        t_check = time.time() - t0

    opt_state = jax.jit(asm.tx.init, out_shardings=asm.replicated)(params)
    pool = pool_of_batches(asm, k_data, asm.global_batch,
                           cell.traffic["data"], pooled)

    t0 = time.time()
    compiled = asm.step.lower(params, state, opt_state, pool[0]).compile()
    timeline["compile_s"] = time.time() - t0
    hlo_text = compiled.as_text()

    carry = (params, state, opt_state)
    del params, state, opt_state
    warmup = int(cell.traffic["warmup_steps"])
    t0 = time.time()
    *carry, loss = compiled(*carry, pool[0])
    if via == "first_moment":
        # The measured executable's own first step is the normal path.
        t1 = time.time()
        grads = jax.jit(
            lambda s: cells.first_moment_gradients(
                cell.config["optimizer"], s))(carry[2])
        fresh_params, fresh_state = jax.jit(
            asm.model.init, out_shardings=asm.replicated)(k_init)
        verdict = check.against_reference(
            asm, grads, float(jnp.mean(loss)), fresh_params, fresh_state,
            pool[0])
        del grads, fresh_params, fresh_state
        t_check = time.time() - t1
        t0 += t_check
    carry, _, _ = run_steps(compiled, carry, pool, 1, n_steps=warmup - 1)
    timeline["warmup_s"] = time.time() - t0
    timeline["check_s"] = t_check
    log("reference check: " + json.dumps(verdict))

    live_bytes = {d.id: (d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in devices[:cell.chips]}
    log("memory_stats of chip 0 after warm-up: %r; step temporaries %d"
        % (devices[0].memory_stats(),
           compiled.memory_analysis().temp_size_in_bytes))

    # ------------------------------------------------------ the window ---
    compiles_before = counter.count
    setup_s = time.time() - t_start
    if args.trace:
        # A short untraced window (loss trend, host-clock step time),
        # then the profiled steps.
        carry, losses, seconds = run_steps(compiled, carry, pool, warmup,
                                           n_steps=20)
    else:
        carry, losses, seconds = run_steps(compiled, carry, pool, warmup,
                                           seconds=args.seconds)
    compiles_in_window = counter.count - compiles_before
    steps = len(losses)
    host_losses = [float(jnp.mean(x)) for x in losses]
    finite = [x == x and abs(x) != float("inf") for x in host_losses]
    falling = (steps >= 10 and statistics.fmean(host_losses[-5:])
               < statistics.fmean(host_losses[:5]))
    units_per_s = steps * asm.units_per_step / seconds
    log("window: %d steps in %.3f s, %.1f %s/s, loss %.4f -> %.4f, %d "
        "compilations inside" % (steps, seconds, units_per_s,
                                 cell.config["sample_unit"], host_losses[0],
                                 host_losses[-1], compiles_in_window))
    log("set-up: " + json.dumps(dict(timeline, setup_s=setup_s,
                                     cache_dir=cache_dir)))

    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    xplane = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        n_trace = int(cell.traffic["trace_steps"])
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            carry, _, traced_seconds = run_steps(
                compiled, carry, pool, warmup + steps, n_steps=n_trace)
        finally:
            jax.profiler.stop_trace()
        (xplane,) = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        log("traced %d steps in %.3f s (%.1f ms a step; untraced %.1f ms)"
            % (n_trace, traced_seconds, 1e3 * traced_seconds / n_trace,
               1e3 * seconds / steps))

    memory_peak = max(device_footprint(d, compiled, live_bytes.get(d.id, 0))
                      for d in devices[:cell.chips])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    correct = bool(verdict["ok"] and falling and compiles_in_window == 0
                   and all(finite))
    line = {"correct": correct, "attempted": steps,
            "failed": finite.count(False)}

    if args.rehearse_cpu:
        line.update(rehearsal=True, device=device, check=verdict)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(line), flush=True)
        return 0 if correct else 1

    if not args.trace:
        metrics = {"setup_s": setup_s,
                   cell.config["sample_unit"] + "_per_s": units_per_s}
        wanted = {m["name"]: m["unit"]
                  for m in cells.metrics_of(cell, "end_to_end")}
        if set(metrics) != set(wanted):
            raise SystemExit("cell %s: BENCHMARK.json lists %s, the run "
                             "makes %s" % (cell.name, sorted(wanted),
                                           sorted(metrics)))
        line["metrics"] = {k: {"value": v, "unit": wanted[k]}
                           for k, v in metrics.items()}
    else:
        from benchmark import trace_view

        ctx = trace_view.build(
            cell=cell, asm=asm, peak=peak, xplane=xplane, hlo_text=hlo_text,
            timeline=timeline, memory_peak=memory_peak, baseline=baseline,
            untraced_step_s=seconds / steps, log=log)
        line["metrics"] = read_layer_metrics(cell, ctx)
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        line["breakdown"] = ctx.breakdown
        shutil.rmtree(trace_dir, ignore_errors=True)
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
