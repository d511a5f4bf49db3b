"""The comparisons behind ``keye-vl-2.0-30b-a3b.json``'s ``check``
bounds, at the cell's real widths on the chip, outside any timed window:

    python3 benchmark/keye_routing.py --seeds <n>[,<n>...] [--assert]
                                      [--only a,b] [--rehearse-cpu]

One seeded sequence a seed, the program in its compute dtype against the
plain float32 reference ("highest" matmul precision) given the SAME
share (held experts, sliced vocabulary). Three readings that refuse
nothing:

- ``forced``: loss and gradient distances with the REFERENCE's routing
  and selection handed to both sides (what is left is arithmetic);
- ``selection_agreement``: per layer, the share of causal pairs on which
  the program's own mask (bf16 indexer, bisection) equals the
  reference's (float32, ``jnp.sort``), both free; and the pairs each
  kept;
- ``rows_held`` and ``dsa_kept`` of the program at free choices.

Then, each THROUGH THE HARNESS'S OWN COMPARISON with the configuration's
limits (``check.sgd_step_gradients`` on the assembled normal path, then
``check.against_reference``: the ``ok`` that decides ``correct`` in
``run.py``): the sound program (``free``), which has to come out ``ok``,
and the defects, which have to come out NOT ``ok`` or, where the chip's
check cannot see them, under this probe's own floor (below):

- ``reference_fp8``: the reference itself computed below the stated
  precision: every matmul operand and every cotangent that reaches one
  rounded to ``float8_e4m3``'s 3 mantissa bits, accumulation in float32;
- ``no_relu``: the indexer's dot products not rectified;
- ``no_weights``: the indexer's per-head weights ``w`` left out;
- ``one_key_fewer``: the 2047th largest for the 2048th;
- ``future_key``: the key after the query admitted, by the indexer and
  by the kernels' causal mask;
- ``dkv_unmasked``: the selection ignored by dK/dV alone;
- ``no_norm``: the gates not divided by their sum over the chosen;
- ``sum_for_mean``: the reduced gradients four times what they are.

The four defects of the selection replace ``models.transformer``'s
``index_scores`` / ``kth_largest`` (and ``future_key`` the kernels'
``_Tiles.visible``), ``dkv_unmasked`` ``ops.pallas_attention._flash_bwd``;
they add no option to the program. A defect of the SELECTION moves which
pairs are kept and not how they are weighted, and at random weights the
indexer's choice is close to arbitrary: the reference check may pass it
(another arbitrary choice of 2048 keys). For those the probe's own two
floors decide, which the sound program has to pass:
``selection_agreement`` of the program with the reference at or above
``AGREEMENT_FLOOR`` in every layer, and every layer's ``dsa_kept`` at
or above the pairs a selection of ``topk`` keeps (``sum_t min(t + 1,
topk)``: ties only add, one key fewer a row is 6,144 pairs fewer a
layer, which no distance shows). With ``--assert`` the exit code is 1
unless ``free`` passes the check and both floors and every defect is
refused by one of the three.
The last line of stdout is one JSON object. No CPU fallback: without the
chip it exits non-zero, unless ``--rehearse-cpu`` (tiny sizes, where the
verdicts are not asserted).
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
from types import SimpleNamespace  # noqa: E402

CELL = "keye-s8192-dsa-ep8-c1"
SELECTION_DEFECTS = ("no_relu", "no_weights", "one_key_fewer", "future_key")
DEFECTS = ("reference_fp8",) + SELECTION_DEFECTS + (
    "dkv_unmasked", "no_norm", "sum_for_mean")
# The share of causal pairs on which a program's mask has to equal the
# reference's, in every layer; the configuration file's ``check.why``
# has the readings on both sides of it (sound 0.9946 at the least, the
# nearest defect 0.862).
AGREEMENT_FLOOR = 0.93


@contextlib.contextmanager
def spoiled_selection(defect):
    """``models.transformer``'s selection with one of
    ``SELECTION_DEFECTS`` while the block runs."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer
    from horovod_tpu.ops import pallas_attention

    sound = (transformer.index_scores, transformer.kth_largest,
             pallas_attention._Tiles.visible)

    def index_scores(q_i, k_i, w_i, first_query):
        dots = jnp.einsum("bcjd,bsd->bcjs", q_i, k_i,
                          preferred_element_type=jnp.float32)
        if defect != "no_relu":
            dots = jax.nn.relu(dots)
        if defect != "no_weights":
            dots = w_i[..., None] * dots
        rows = first_query + jnp.arange(q_i.shape[1]) \
            + (defect == "future_key")
        causal = jnp.arange(k_i.shape[1])[None, :] <= rows[:, None]
        return jnp.where(causal[None], jnp.sum(dots, axis=2), -jnp.inf)

    def visible(self, key_axis, q_start, k_start):
        return sound[2](self, key_axis, q_start + 1, k_start)

    transformer.index_scores = index_scores
    if defect == "one_key_fewer":
        transformer.kth_largest = lambda scores, k: sound[1](scores, k - 1)
    if defect == "future_key":
        pallas_attention._Tiles.visible = visible
    try:
        yield
    finally:
        (transformer.index_scores, transformer.kth_largest,
         pallas_attention._Tiles.visible) = sound


@contextlib.contextmanager
def dkv_unmasked():
    """dK/dV reading a plane that keeps every pair; forward and dQ keep
    the selection."""
    import jax.numpy as jnp

    from horovod_tpu.ops import pallas_attention

    sound = pallas_attention._flash_bwd

    def bwd(causal, window, block_q, block_k, scale, interpret, res, g):
        select = res[5]
        if select is not None:
            res = res[:5] + (select._replace(
                by_key=jnp.full_like(select.by_key, -1)),)
        return sound(causal, window, block_q, block_k, scale, interpret,
                     res, g)

    pallas_attention._flash_bwd = bwd
    try:
        yield
    finally:
        pallas_attention._flash_bwd = sound


def masks_of(model, params, tokens):
    """(L, B, S, S) bool: the program's own selections at free choices,
    and what its layers sowed."""
    import jax.numpy as jnp

    from benchmark.builders.keye_vl2 import sown_kept
    from horovod_tpu.parallel import moe

    n = model.module.cfg.n_layers
    _, sown = model.module.apply(
        {"params": params["params"]}, tokens[:, :-1],
        mutable=["moe", "dsa", "dsa_mask"])
    stats = moe.sown_stats(sown)
    return (jnp.stack([sown["dsa_mask"]["layer_%d" % i]["attn"]["select"][0]
                       for i in range(n)]),
            {"rows_held": stats["rows_held"],
             "rows_overflow": stats["rows_overflow"],
             "dsa_kept": sown_kept(sown, n)})


def agreement(mine, theirs):
    """Per layer, the share of CAUSAL pairs on which two (L, B, S, S)
    masks agree."""
    import jax.numpy as jnp

    s = mine.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    same = jnp.sum((mine == theirs) & causal, axis=(1, 2, 3))
    return same / (mine.shape[1] * jnp.sum(causal))


def verdicts(cell, devices, asm, params, state, batch, key, only=None):
    """name -> ``check.against_reference``'s dict for the sound program
    and each defect (with ``selection_agreement`` beside it), and the
    three readings that refuse nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmark import cell as cells
    from benchmark import check, flops_keye
    from benchmark.glm_routing import _three_bits
    from benchmark.reference import keye_vl2 as reference

    config = cell.config
    spec = cell.builder.block_spec(config)
    s = batch.shape[1] - 1
    pairs = batch.shape[0] * flops_keye.kept_pairs(
        s, config["sa_config"]["topk"])
    # The check hands both sides the parameters with every all-zero leaf
    # lifted (the indexer's LayerNorm bias): so does every row here.
    lifted = jax.jit(check._lift_zeros, out_shardings=asm.replicated)(
        key, params)

    def spoiled(**changes):
        """The normal path assembled round a model with ``changes`` to
        its ``BlockSpec``: a path of its own, because the sound one's
        traced step is cached."""
        block = dataclasses.replace(spec, **changes)
        builder = SimpleNamespace(
            build=lambda config, traffic: cell.builder.build(
                config, traffic, block),
            block_spec=cell.builder.block_spec,
            sizes_of=cell.builder.sizes_of)
        return cells.assemble(
            SimpleNamespace(**dict(vars(cell), builder=builder)), devices)

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: reference.forward(config, p, x)[1])(
            lifted, batch[:, :-1])
    ref_select = ref["select"]

    def through_the_program(path, agree=True):
        _, grads, loss = check.sgd_step_gradients(path, params, state,
                                                  batch, key)
        v = check.against_reference(asm, grads, loss, lifted, state, batch)
        if agree:
            mine, sown = jax.jit(
                lambda p, x: masks_of(path.model, p, x))(lifted, batch)
            v["selection_agreement"] = [
                float(a) for a in agreement(mine, ref_select)]
            v["sown"] = {k: [int(x) for x in np.asarray(a)]
                         for k, a in sown.items()}
        return v

    def with_spoiled_selection(defect):
        with spoiled_selection(defect):
            return through_the_program(spoiled())

    def with_dkv_unmasked():
        with dkv_unmasked():
            return through_the_program(spoiled())

    def four_times():
        path = spoiled()
        path.check_tx = optax.chain(path.check_tx, optax.scale(4.0))
        return through_the_program(path, agree=False)

    def reference_below_its_precision():
        _, both_ways = _three_bits()

        def side(params, state, batch):
            (loss, _), grads = jax.value_and_grad(
                asm.model.reference_loss, has_aux=True)(params, state, batch)
            return grads, loss

        whole = reference._operand
        reference._operand = both_ways
        try:
            with jax.default_matmul_precision("highest"):
                grads, loss = jax.jit(side)(lifted, state, batch)
        finally:
            reference._operand = whole
        return check.against_reference(asm, grads, float(loss), lifted,
                                       state, batch)

    def forced():
        """Both sides under the reference's own routing and selection."""
        chosen, select = list(ref["chosen"]), list(ref_select)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: asm.model.loss_and_stats(p, batch, chosen,
                                               select)[0]))(lifted)
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_grads = jax.jit(jax.value_and_grad(
                lambda p: reference.loss(config, p, state, batch, chosen,
                                         select)[0]))(lifted)
        dist = jax.device_get(jax.jit(check._distances)(grads, ref_grads))
        rel = {jax.tree_util.keystr(path): float(d[0] / d[1])
               for path, d in jax.tree_util.tree_leaves_with_path(dist)
               if d[1] > 0}
        worst = max(rel, key=rel.get)
        return {"loss_rel": abs(float(loss) - float(ref_loss))
                / abs(float(ref_loss)),
                "grad_rel_l2_max": rel[worst], "grad_worst_leaf": worst,
                "grad_rel_l2_median": float(np.median(list(rel.values())))}

    rows = {"free": lambda: through_the_program(asm),
            "reference_fp8": reference_below_its_precision}
    rows.update({d: lambda d=d: with_spoiled_selection(d)
                 for d in SELECTION_DEFECTS})
    rows.update({
        "dkv_unmasked": with_dkv_unmasked,
        "no_norm": lambda: through_the_program(spoiled(norm_topk=False),
                                               agree=False),
        "sum_for_mean": four_times})
    out = {}
    if not only or "forced" in only:
        out["forced"] = forced()
    for name, row in rows.items():
        if only and name not in only:
            continue
        v = row()
        v["refused_by"] = [limit for limit, over in (
            ("loss_rtol", not v["loss_rel"] <= v["loss_rtol"]),
            ("grad_rel_l2", not v["grad_rel_l2_max"]
             <= v["grad_rel_l2_tol"]),
            ("selection_agreement", min(v.get(
                "selection_agreement", [1.0])) < AGREEMENT_FLOOR),
            ("dsa_kept", min(v.get("sown", {}).get(
                "dsa_kept", [pairs])) < pairs)) if over]
        out[name] = v
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seeds", required=True,
                   help="comma-separated, one sequence each")
    p.add_argument("--assert", dest="asserted", action="store_true")
    p.add_argument("--only", default="",
                   help="comma-separated verdicts (default: all)")
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args()

    from benchmark import cell as cells

    cell = cells.load(CELL, tiny=args.rehearse_cpu)

    import jax

    from benchmark import run
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices, _ = run.check_devices(cell, args.rehearse_cpu)
    asm = cells.assemble(cell, devices)
    out = {"rehearsal": args.rehearse_cpu,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind},
           "compute_dtype": cell.config["compute_dtype"],
           "limits": dict({k: cell.config["check"][k]
                           for k in ("loss_rtol", "grad_rel_l2")},
                          selection_agreement=AGREEMENT_FLOOR,
                          dsa_kept="sum_t min(t + 1, topk) a layer"),
           "seeds": {}}
    failures = []
    only = [n for n in args.only.split(",") if n]
    for seed in [int(s) for s in args.seeds.split(",")]:
        # The weights and the check's one sequence as run.py makes them.
        k_init, _, k_check, _ = jax.random.split(jax.random.PRNGKey(seed), 4)
        params, state = jax.jit(asm.model.init,
                                out_shardings=asm.replicated)(k_init)
        (batch,) = run.pool_of_batches(
            asm, k_check, 1, dict(cell.traffic["data"], pool=1))
        here = verdicts(cell, devices, asm, params, state, batch, k_check,
                        only)
        del params, state, batch
        out["seeds"][str(seed)] = here
        for name, v in here.items():
            run.log("seed %d %-14s %s loss_rel %.3g worst leaf %.4g %s "
                    "median %.3g agreement %s" % (
                        seed, name,
                        "refused by %s" % v["refused_by"]
                        if v.get("refused_by") else "passes",
                        v["loss_rel"], v["grad_rel_l2_max"],
                        v["grad_worst_leaf"], v["grad_rel_l2_median"],
                        v.get("selection_agreement")))
            if name != "forced" and bool(v["refused_by"]) \
                    != (name in DEFECTS):
                failures.append("%d:%s" % (seed, name))
    out["not_as_it_has_to_be"] = failures
    print(json.dumps(out), flush=True)
    return 1 if args.asserted and failures and not args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
