"""The comparison that decides the first condition of ``correct``.

Before the window, at the cell's real widths: the loss and the gradients
the optimizer receives, computed through the normal path, against the
configuration's plain float32 reference (``benchmark/reference/``) on
the same weights and batch. The gradients are taken AFTER the
framework's reduction, so that a sum where a mean belongs is 4x off on
four chips (AdamW's normalised update would hide it). Two ways to get
them with no change to the program; the configuration's ``check.via``
names one:

``first_moment``: from the compiled training step itself. After its
first run on fresh optimizer state the optimizer's first moment holds
the reduced gradient (Adam's ``mu`` is ``(1 - b1) * g``), so the check
costs no program of its own and covers the executable that is measured,
on its real global batch. Needs a reference that can hold that batch.

``sgd_step``: one step of ``plan.optimizer(optax.sgd(1.0))`` under the
same ``Plan.shard_map`` on a small seeded sample, whose negated updates
are the reduced gradients; for a model whose float32 reference cannot
hold the real batch (ResNet-50 at 256 images). There a leaf that is all
zeros (the last batch-norm scale of each block) is given seeded normal
noise for the check only: a zero scale multiplies the gradient of
everything beneath it by zero, and half the network would go unchecked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _distances(got, want):
    """Per leaf: (|got - want|, |want|) as float32 scalars."""
    return jax.tree.map(
        lambda g, w: jnp.stack([jnp.linalg.norm((g - w).astype(jnp.float32)),
                                jnp.linalg.norm(w.astype(jnp.float32))]),
        got, want)


def _lift_zeros(key, params):
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        jnp.where(jnp.all(p == 0),
                  0.1 * jax.random.normal(k, p.shape, p.dtype), p)
        for k, p in zip(keys, leaves)])


def sgd_step_gradients(asm, params, state, batch, key):
    """(lifted parameters, reduced gradients, loss) through one
    ``sgd(1.0)`` step of the normal path."""
    params = jax.jit(_lift_zeros, out_shardings=asm.replicated)(key, params)
    normal = jax.jit(asm.sharded_step(asm.check_tx, False))
    grads, _, _, shard_losses = normal(
        params, state, asm.check_tx.init(params), batch)
    return params, grads, float(jnp.mean(shard_losses))


def against_reference(asm, grads, loss, params, state, batch):
    """Compare the normal path's reduced ``grads`` and ``loss`` at
    ``params`` with the reference's. Returns a dict: ``ok``, the two
    losses, their relative distance and, per parameter leaf, that of
    the gradient (relative L2), with the worst leaf named."""
    tol = asm.cell.config["check"]
    model = asm.model

    def reference(params, state, batch):
        (loss, _), grads = jax.value_and_grad(
            model.reference_loss, has_aux=True)(params, state, batch)
        return grads, loss

    with jax.default_matmul_precision("highest"):
        ref_grads, ref_loss = jax.jit(reference)(params, state, batch)
    dist = jax.device_get(jax.jit(_distances)(grads, ref_grads))
    del grads, ref_grads

    ref_loss = float(ref_loss)
    leaves = {jax.tree_util.keystr(path): (float(d[0]), float(d[1]))
              for path, d in jax.tree_util.tree_leaves_with_path(dist)}
    # A leaf whose gradient is exactly zero on both sides says nothing.
    rel = {k: (d / w if w > 0 else (np.inf if d > 0 else 0.0))
           for k, (d, w) in leaves.items()}
    worst = max(rel, key=rel.get)
    loss_rel = abs(loss - ref_loss) / abs(ref_loss)
    ok = bool(np.isfinite(loss) and loss_rel <= tol["loss_rtol"]
              and rel[worst] <= tol["grad_rel_l2"])
    return {"ok": ok, "via": tol["via"], "loss": loss,
            "reference_loss": ref_loss,
            "loss_rel": loss_rel, "loss_rtol": tol["loss_rtol"],
            "grad_rel_l2_max": rel[worst], "grad_worst_leaf": worst,
            "grad_rel_l2_median": float(np.median(list(rel.values()))),
            "grad_rel_l2_tol": tol["grad_rel_l2"], "leaves": len(rel),
            "leaves_all_zero": sum(w == 0 for _, w in leaves.values())}
