"""The attention module's device time where the keys a query attends to
are a learned selection (``sparse_attention`` layers), by the program's
own scopes.

The same join as ``swa_view``: an ``XLA Ops`` event's instruction name
-> its ``op_name`` in the compiled step -> the segments of that scope.
Everything under a layer's ``attn`` module counts with its SELF-time
(projections, head norms, RoPE, the indexer, the selection, the masked
kernels and their glue, the output projection; forward, recomputed
forward and backward). Inside it: ``hvd_dsa_index`` (the indexer's three
projections, its norm, RoPE and the (S, S) float32 scores),
``hvd_dsa_select`` (each row's ``topk``-th largest, the mask and its two
bit planes), and the masked kernels: every Mosaic call named
``hvd_dsa_*`` (``hvd_dsa_fwd`` / ``_dkv`` / ``_dq`` today, the names
``pallas_call(name=)`` gives them, constants of
``horovod_tpu/jax/introspect.py``) but ``hvd_dsa_choose``, which is the
selection's; they take their whole duration.

The rooflines count the MATHEMATICS: the work attention REQUIRES over
the pairs the selection keeps (``flops.attention_work``: forward two
products, backward five, whatever kernels run them), however the kernels
honour the mask (the first form computes every causal tile, so it cannot
pass ``kept / causal`` of what the static kernels reach), and the
indexer's dot products over every causal pair with one pass over the
scores (``flops_keye.index_work``).

A program without the scopes or the kernels (every commit before them,
every other configuration) gives nothing: each reader returns None and
never raises.
"""

from __future__ import annotations

from benchmark import scope_view
from benchmark import trace_reduce as tr

# What these metrics are computed from, so spelled out here.
MODULE, INDEX, SELECT = "attn", "hvd_dsa_index", "hvd_dsa_select"
MASKED, CHOOSE = "hvd_dsa_", "choose"


def _times(ctx):
    """{"attn" | "index" | "select": seconds a step, "kernels": {what
    follows ``hvd_dsa_``: seconds a step}}; None where the trace holds no
    masked kernel."""
    if not hasattr(ctx, "_dsa_times"):
        try:
            from horovod_tpu.jax import introspect

            scopes = introspect.instruction_scopes(ctx.hlo_text)
            times = dict.fromkeys(("attn", "index", "select"), 0.0)
            kernels = {}
            for event, own in zip(ctx.win0.ops,
                                  scope_view.self_times(ctx.win0.ops)):
                path = scope_view._path(
                    scopes.get(tr.instruction_name(event.name), ""))
                if MODULE not in path:
                    continue
                times["attn"] += own
                for part, scope in (("index", INDEX), ("select", SELECT)):
                    if scope in path:
                        times[part] += own
                short = tr.named_kernel(event.name, MASKED)
                if short and short != CHOOSE:
                    kernels[short] = (kernels.get(short, 0.0)
                                      + event.end - event.start)
            if not kernels:
                raise LookupError("no masked hvd_dsa_* kernel in this step")
            per_step = 1e-9 / max(ctx.n_steps, 1)
            ctx._dsa_times = dict(
                {k: v * per_step for k, v in times.items()},
                kernels={k: ns * per_step for k, ns in kernels.items()})
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("dsa view: nothing to read: %s: %s"
                            % (type(e).__name__, e))
            ctx._dsa_times = None
    return ctx._dsa_times


def part_ms(ctx, part):
    """Milliseconds a step under ``part`` ('attn', 'index', 'select');
    None where the trace holds nothing there."""
    times = _times(ctx)
    return None if times is None else 1e3 * times[part] or None


def sparse_ms(ctx):
    """Milliseconds a step in the masked kernels."""
    times = _times(ctx)
    if times is None:
        return None
    return 1e3 * sum(times["kernels"].values()) or None


def _sizes(ctx):
    config, traffic = ctx.cell.config, ctx.cell.traffic
    sizes = ctx.cell.builder.sizes_of(config)
    return (int(traffic["per_chip_batch"]), int(traffic["seq_len"]), sizes)


def sparse_roofline(ctx):
    """The least time for what every layer's attention REQUIRES over the
    kept pairs, forward and backward (``flops.attention_work`` with
    ``flops_keye.kept_pairs`` and the bit plane once a direction), over
    ``sparse_ms``."""
    from benchmark import flops, flops_keye

    took_ms = sparse_ms(ctx)
    if not took_ms:
        return None
    try:
        batch, seq_len, sizes = _sizes(ctx)
        work = flops.attention_work(
            flops_keye.kept_pairs(seq_len, sizes["topk"]), seq_len,
            batch=batch, n_head=sizes["n_head"], n_kv=sizes["n_kv"],
            d=sizes["head_dim"], d_v=sizes["head_dim"],
            plane_bytes=flops_keye.plane_bytes(batch, seq_len))
        least = ctx.cell.config["num_hidden_layers"] * sum(
            flops.roofline_seconds(*work[direction], ctx.peak)[0]
            for direction in work)
        scope_view._log("masked kernels: %.3f ms a step, %.3f ms at the "
                        "roof for the kept pairs" % (took_ms, 1e3 * least))
        return 100.0 * 1e3 * least / took_ms
    except Exception as e:   # noqa: BLE001 - a reader never raises
        scope_view._log("dsa.sparse_roofline failed: %s: %s"
                        % (type(e).__name__, e))
        return None


def index_roofline(ctx):
    """The least time for every layer's scores and selection
    (``flops_keye.index_work``) over ``index`` + ``select``."""
    from benchmark import flops, flops_keye

    times = _times(ctx)
    if times is None:
        return None
    took = times["index"] + times["select"]
    if not took:
        return None
    try:
        batch, seq_len, sizes = _sizes(ctx)
        least, roof = flops.roofline_seconds(
            *flops_keye.index_work(batch, seq_len,
                                   index_heads=sizes["index_heads"],
                                   index_dim=sizes["index_dim"]), ctx.peak)
        least *= ctx.cell.config["num_hidden_layers"]
        scope_view._log("indexer and selection: %.3f ms a step, %.3f ms at "
                        "the %s roof" % (1e3 * took, 1e3 * least, roof))
        return 100.0 * least / took
    except Exception as e:   # noqa: BLE001 - a reader never raises
        scope_view._log("dsa.index_roofline failed: %s: %s"
                        % (type(e).__name__, e))
        return None
