"""The latent-attention module's device time, by the program's own
scopes.

The same join as ``moe_view``: an ``XLA Ops`` event's instruction name
-> its ``op_name`` in the compiled step -> the segments of that scope,
each event with its SELF-time, forward, recomputed forward and backward
together, per step on chip 0. An attention module counts here only if
the ``hvd_mla_latent`` scope occurs under it (the constant
``SCOPE_MLA_LATENT`` of ``horovod_tpu/jax/introspect.py``, set by
``models/transformer.py`` ``LatentAttention``).

A program without that scope (a commit before the module existed, a
model with plain heads) gives nothing: every reader returns None and
never raises.
"""

from __future__ import annotations

from benchmark import scope_view
from benchmark import trace_reduce as tr

# What these metrics are computed from, so spelled out here.
MODULE = "attn"
LATENT = "hvd_mla_latent"


def _times(ctx):
    """{MODULE: seconds a step under the attention modules, LATENT: of
    that under ``hvd_mla_latent``}; None where no instruction carries
    the latent scope."""
    if not hasattr(ctx, "_mla_times"):
        try:
            from horovod_tpu.jax import introspect

            scopes = introspect.instruction_scopes(ctx.hlo_text)
            times = {MODULE: 0.0, LATENT: 0.0}
            for event, own in zip(ctx.win0.ops,
                                  scope_view.self_times(ctx.win0.ops)):
                path = scope_view._path(
                    scopes.get(tr.instruction_name(event.name), ""))
                if MODULE in path:
                    times[MODULE] += own
                    if LATENT in path:
                        times[LATENT] += own
            per_step = 1e-9 / max(ctx.n_steps, 1)
            ctx._mla_times = {k: v * per_step for k, v in times.items()} \
                if times[LATENT] else None
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("mla view failed: %s: %s"
                            % (type(e).__name__, e))
            ctx._mla_times = None
    return ctx._mla_times


def scope_ms(ctx, scope):
    """Milliseconds a step under ``scope`` (``MODULE`` or ``LATENT``)."""
    times = _times(ctx)
    return None if times is None else 1e3 * times[scope]
