"""Per-chip throughput on all chips over the throughput of the plain
one-device step at the same per-chip batch (``run.baseline_units_per_s``),
both on the host clock without the profiler, in this process."""


def read(ctx):
    if not ctx.baseline_units_per_s:
        return None
    per_chip = ctx.units_per_step / ctx.untraced_step_s / ctx.chips
    return 100.0 * per_chip / ctx.baseline_units_per_s
