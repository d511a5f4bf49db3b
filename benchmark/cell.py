"""From a cell's name in ``BENCHMARK.json`` to a compiled training step
through the program's normal path.

``load`` reads the data: the cell's entry, its configuration file, its
traffic file, and the builder module the configuration names.
``assemble`` drives the program: ``hvd.init()`` -> ``hvd.plan()`` ->
``Plan.apply()`` -> ``Plan.optimizer()`` -> ``Plan.shard_map()`` -> one
jitted, donated step, on one device as on four. It sets no ``HVD_*`` or
``HOROVOD_*`` variable: the library's defaults are what is measured.
"""

from __future__ import annotations

import importlib
import json
import os
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load(name, *, tiny=False):
    """The cell ``name``: its entry, configuration, traffic and builder.
    ``tiny`` applies the builder's CPU-rehearsal sizes."""
    bench = read_json(ROOT, "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit("no workload %r in BENCHMARK.json (have: %s)"
                         % (name, ", ".join(sorted(by_name))))
    entry = by_name[name]
    (cfg_entry,) = [c for c in bench["configs"]
                    if c["name"] == entry["config"]]
    config = read_json(ROOT, cfg_entry["file"])
    traffic = read_json(HERE, "workloads", entry["traffic"] + ".json")
    builder = importlib.import_module(
        "benchmark.builders." + config["builder"])
    if tiny:
        config.update(builder.TINY["config"])
        traffic.update(builder.TINY["traffic"])
    return SimpleNamespace(name=name, chips=int(entry["chips"]), bench=bench,
                           config=config, traffic=traffic, builder=builder)


def metrics_of(cell, group):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in cell.bench[group]
            if cell.name in m.get("workloads", [cell.name])]


def make_optimizer(spec):
    import optax

    if spec["name"] == "adamw":
        rate = optax.linear_schedule(0.0, spec["learning_rate"],
                                     spec["warmup_steps"])
        return optax.adamw(rate, b1=spec["b1"], b2=spec["b2"],
                           weight_decay=spec["weight_decay"])
    if spec["name"] == "sgd":
        return optax.sgd(spec["learning_rate"], momentum=spec["momentum"])
    raise ValueError("no optimizer %r" % (spec["name"],))


def first_moment_gradients(spec, opt_state):
    """The gradients the optimizer of ``spec`` received in its FIRST
    update on fresh state, read back from its first moment."""
    import jax
    import optax

    if spec["name"] == "adamw":
        mu = optax.tree_utils.tree_get(opt_state, "mu")
        return jax.tree.map(lambda m: m / (1.0 - spec["b1"]), mu)
    if spec["name"] == "sgd" and spec.get("momentum"):
        return optax.tree_utils.tree_get(opt_state, "trace")
    raise ValueError("optimizer %r keeps no first moment" % (spec["name"],))


def assemble(cell, devices):
    """Everything up to, not including, compilation. ``devices`` are the
    chips to run on (real ones, or described ones for a compile-only
    rehearsal); nothing here puts an array on them."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    config, traffic, chips = cell.config, cell.traffic, cell.chips
    model = cell.builder.build(config, traffic)
    per_chip = int(traffic["per_chip_batch"])
    abstract_params, abstract_state = jax.eval_shape(
        model.init, jax.random.PRNGKey(0))

    plan_args = dict(batch=per_chip * chips, chips=chips, **model.plan_kwargs)
    free_choice = hvd.plan(abstract_params, **plan_args)
    plan = free_choice
    if traffic.get("require_axes"):
        plan = hvd.plan(abstract_params,
                        require_axes=dict(traffic["require_axes"]),
                        **plan_args)
    mesh = plan.apply(devices=list(devices)[:chips])
    batch_specs = model.batch_specs(plan)
    loss_spec = plan.batch_spec(1, seq_dim=None)

    def sharded_step(tx, keep_params):
        """value_and_grad -> tx.update on each chip's shard. The training
        step returns the new parameters; the check's step returns the
        negated updates instead, which under sgd(1.0) are the gradients
        after the framework's reduction."""
        def hvd_bench_step(params, state, opt_state, batch):
            (loss, state), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, state, batch)
            updates, opt_state = tx.update(grads, opt_state, params)
            if keep_params:
                out = optax.apply_updates(params, updates)
            else:
                out = jax.tree.map(lambda u: -u, updates)
            # Per-shard loss out, averaged on the host after the window:
            # the only collectives in the step are the framework's own.
            return out, state, opt_state, loss[None]

        return plan.shard_map(
            hvd_bench_step, mesh=mesh,
            in_specs=(P(), P(), P(), batch_specs),
            out_specs=(P(), P(), P(), loss_spec))

    tx = plan.optimizer(make_optimizer(config["optimizer"]))
    replicated = NamedSharding(mesh, P())
    return SimpleNamespace(
        cell=cell, model=model, plan=plan, free_choice=free_choice,
        mesh=mesh, tx=tx, per_chip_batch=per_chip,
        global_batch=per_chip * chips,
        units_per_step=per_chip * chips * model.units_per_item,
        abstract_params=abstract_params, abstract_state=abstract_state,
        replicated=replicated,
        batch_sharding=jax.tree.map(
            lambda spec: NamedSharding(mesh, spec), batch_specs,
            is_leaf=lambda x: isinstance(x, P)),
        step=jax.jit(sharded_step(tx, True), donate_argnums=(0, 1, 2)),
        check_tx=plan.optimizer(optax.sgd(1.0)),
        sharded_step=sharded_step)


def abstract_step_args(asm, global_batch=None):
    """``ShapeDtypeStruct``s, with shardings, of the step's arguments:
    what ``.lower()`` needs where no array can be made."""
    import jax

    from benchmark import traffic as traffic_gen

    def on(sharding):
        return lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=sharding)

    cell = asm.cell
    data = dict(cell.traffic["data"], pool=1)
    pool = jax.eval_shape(
        lambda key: traffic_gen.make_pool(
            key, data, global_batch=global_batch or asm.global_batch,
            config=cell.config, **asm.model.pool_kwargs),
        jax.random.PRNGKey(0))
    batch = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=s),
        pool, asm.batch_sharding)
    opt_state = jax.eval_shape(asm.tx.init, asm.abstract_params)
    rep = on(asm.replicated)
    return (jax.tree.map(rep, asm.abstract_params),
            jax.tree.map(rep, asm.abstract_state),
            jax.tree.map(rep, opt_state), batch)
