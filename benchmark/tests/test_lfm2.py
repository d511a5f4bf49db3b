"""LFM2-8B-A1B's share on the CPU at the builder's ``TINY`` widths (hidden
64; a dense ``conv`` layer of 96, then a ``full_attention`` and a
``conv`` expert layer that hold 2 of the 8 experts of 32 they route
over, 2 a token, no shared expert; 4 query heads over 2 key/value heads
of 16 = hidden / heads; three taps; vocabulary 512, the head tied): the
program against ``benchmark/reference/lfm2_moe.py`` on seeded weights
and a NONZERO bias, block by block and whole; the convolution against a
loop over positions; the bias's update; recomputation; the four shares
against the uncut layer; the counting of ``flops_lfm2.py`` by hand; the
new scopes through the scope view and their readers.

Tolerances. With the program computing in float32 the two are the same
mathematics in another order (and the gates' 1e-20 where the reference
has the family's 1e-6, 5e-7 of a gate): logits to 1e-4 of their largest
entry, the loss to 1e-5, every gradient leaf to 1e-3 relative L2. That
holds at FREE routing too: no token of these seeds changes an expert
(asserted).
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops, flops_afmoe, flops_glm, flops_lfm2, scope_view
from benchmark import traffic
from benchmark.layer_metrics import reader
from benchmark.reference import lfm2_moe as reference
from benchmark.tests.test_olmoe import _leaf_distances, _rel
from benchmark.tests.test_reference import _compare
from benchmark.tests.test_scope_view import RECORDED_STEP, _ctx
from benchmark.tests.test_trinity import _biased, _seen

CELL = "lfm2-s16384-ep4-c1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
CONV, FULL = "conv", "full_attention"


def _assembled(dtype, attention="flash"):
    cell = cells.load(CELL, tiny=True)
    cell.config.update(compute_dtype=dtype, attention=attention)
    asm = cells.assemble(cell, jax.devices()[:1])
    key = jax.random.PRNGKey(11)
    params, state = jax.jit(asm.model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **asm.model.pool_kwargs)
    return cell, asm.model, _seen(params), _biased(state, 0.05), pool[0]


def _random_assignments(key, config, tokens):
    """Per layer (None for the dense one), k distinct experts a token,
    nothing to do with any router."""
    n = config["num_hidden_layers"]
    noise = jax.random.uniform(
        key, (n, tokens, config["experts_routed_over"]))
    picks = jnp.argsort(noise, -1)[..., :config["num_experts_per_tok"]]
    return [None if i < config["num_dense_layers"]
            else picks[i].astype(jnp.int32) for i in range(n)]


# ------------------------------------------------ program = reference -----

@pytest.mark.parametrize("routing,attention", [
    ("free", "flash"), ("forced", "flash"), ("free", "dense")])
def test_float32_program_is_the_reference(routing, attention):
    from horovod_tpu.parallel import moe

    cell, model, params, state, tokens = _assembled("float32", attention)
    config = cell.config
    assert reference.layer_kinds(config) == [CONV, FULL, CONV]
    t = tokens.shape[0] * (tokens.shape[1] - 1)
    assignments = None
    if routing == "forced":
        assignments = _random_assignments(jax.random.PRNGKey(5), config, t)

    want, aux = jax.jit(lambda p, s, x: reference.forward(
        config, p, s, x, assignments))(params, state, tokens[:, :-1])
    got, sown = jax.jit(lambda p, s, x: model.module.apply(
        {"params": p["params"], "moe_state": s}, x, assignments,
        mutable=["moe"]))(params, state, tokens[:, :-1])
    stats = moe.sown_stats(sown)
    # The same experts on both sides, and the bias moved the choice.
    assert (np.sort(np.asarray(stats["experts"]), -1)
            == np.sort(np.asarray(aux["chosen"]), -1)).all()
    assert (np.asarray(stats["tokens_per_expert"])
            == np.asarray(aux["tokens_per_expert"])).all()
    if routing == "free":
        unbiased = jax.jit(lambda p, s, x: reference.forward(
            config, p, jax.tree.map(jnp.zeros_like, s), x)[1]["chosen"])(
                params, state, tokens[:, :-1])
        assert (np.sort(np.asarray(unbiased), -1)
                != np.sort(np.asarray(aux["chosen"]), -1)).mean() > 0.05
    assert float(jnp.max(jnp.abs(got - want))) \
        < 1e-4 * float(jnp.max(jnp.abs(want)))
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts.shape == (2, 8)
    assert (counts.sum(-1) == t * config["num_experts_per_tok"]).all()
    assert (np.asarray(stats["rows_held"]) == counts[:, :2].sum(-1)).all()
    assert (np.asarray(stats["rows_held"]) > 0).all()

    def both(loss):
        return jax.jit(jax.value_and_grad(
            lambda p: loss(p, state, tokens, assignments)[0]))(params)

    (loss, grads), (ref_loss, ref_grads) = both(model.loss_and_stats), both(
        lambda p, s, x, a: reference.loss(config, p, s, x, a))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    distances = _leaf_distances(grads, ref_grads)
    # embed (the head too), ln_f; two block norms a layer; two conv
    # mixers of 3 leaves, one attention of 5; 3 dense; router + 3 held
    # in each expert layer.
    assert len(distances) == 2 + 3 * 2 + 2 * 3 + 5 + 3 + 2 * 4
    assert max(distances.values()) < 1e-3, distances
    assert all(float(jnp.linalg.norm(g)) > 0
               for g in jax.tree.leaves(ref_grads))


def test_bf16_program_at_forced_routing_is_inside_gpt2s_bounds():
    cell, model, params, state, tokens = _assembled("bfloat16")
    config = cell.config
    with open(os.path.join(CONFIGS, "gpt2-medium.json")) as f:
        bounds = json.load(f)["check"]
    chosen = jax.jit(lambda p, s, x: reference.forward(
        config, p, s, x)[1]["chosen"])(params, state, tokens[:, :-1])
    chosen = [None] * config["num_dense_layers"] + list(chosen)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_and_stats(p, state, tokens, chosen)[0]))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(config, p, state, tokens, chosen)[0]))(
            params)
    assert abs(float(loss) - float(ref_loss)) \
        < bounds["loss_rtol"] * float(ref_loss)
    distances = _leaf_distances(grads, ref_grads)
    assert max(distances.values()) < bounds["grad_rel_l2"], distances
    assert max(distances.values()) > 1e-3, distances


def test_the_check_of_the_cell_in_float32():
    """``run.py``'s own comparison (``check.sgd_step_gradients`` against
    the reference, free routing, the bias at its initial zero)."""
    got = _compare(CELL, "float32", 1)
    assert got["loss_rel"] < 1e-5 and got["grad_rel_l2_max"] < 1e-3, got
    assert got["leaves"] == 30 and got["leaves_all_zero"] == 0, got


def _tiny_cfg(**changes):
    cell = cells.load(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    cfg = cell.builder.module_of(cell.config, cell.traffic).cfg
    return cell.config, dataclasses.replace(cfg, **changes)


def _x(key, s=96, m=64):
    return jax.random.normal(jax.random.PRNGKey(key), (1, s, m))


def _conv_by_hand(x, p, taps):
    """The mixer as a loop over positions, in numpy float64: position t
    reads positions t - taps + 1 .. t of ``b * u`` and nothing else."""
    x, w_in, w, w_out = (np.asarray(a, np.float64) for a in (
        x, p["w_in"], p["w"], p["w_out"]))
    m = x.shape[-1]
    out = np.zeros_like(x)
    for batch in range(x.shape[0]):
        bcu = x[batch] @ w_in
        b, c, u = bcu[:, :m], bcu[:, m:2 * m], bcu[:, 2 * m:]
        gated = b * u
        for t in range(x.shape[1]):
            z = np.zeros(m)
            for j in range(taps):
                at = t - (taps - 1) + j
                if at >= 0:
                    z += w[:, j] * gated[at]
            out[batch, t] = (c[t] * z) @ w_out
    return out


def test_the_conv_mixer_against_a_loop_over_positions():
    """``ShortConv``, the reference's ``_conv`` and the loop agree; the
    mixer is causal (output t unmoved by input t + 1, moved by inputs t,
    t - 1 and t - 2, not by t - 3); each gate is seen."""
    from flax.core import meta
    from horovod_tpu.models import transformer

    config, cfg = _tiny_cfg()
    layer = transformer.ShortConv(cfg)
    x = _x(0, s=24)
    params = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    # normal(0.02) taps would make the mixer's output tiny: unit ones.
    params = {"params": dict(
        params["params"],
        w=jax.random.normal(jax.random.PRNGKey(2), (64, 3)))}
    assert jax.tree.map(jnp.shape, params["params"]) == {
        "w_in": (64, 192), "w": (64, 3), "w_out": (64, 64)}
    got = jax.jit(layer.apply)(params, x)
    by_hand = _conv_by_hand(x, params["params"], 3)
    assert _rel(got, jnp.asarray(by_hand, jnp.float32)) < 1e-5
    assert _rel(reference._conv(x, params["params"], config),
                jnp.asarray(by_hand, jnp.float32)) < 1e-5
    t = 10
    for moved, seen in ((t + 1, False), (t, True), (t - 1, True),
                        (t - 2, True), (t - 3, False)):
        other = jax.jit(layer.apply)(params, x.at[0, moved].add(1.0))
        assert bool(jnp.any(other[0, t] != got[0, t])) == seen, moved
    # Position 0 sees itself alone: the last tap of b u, times c.
    y = x[0, 0] @ params["params"]["w_in"]
    first = (y[64:128] * params["params"]["w"][:, 2] * y[:64] * y[128:]) \
        @ params["params"]["w_out"]
    assert _rel(got[0, 0], first) < 1e-5
    # Both gates and the order of the thirds are seen.
    p = params["params"]
    for spoiled in (
            dict(p, w_in=jnp.concatenate(
                [p["w_in"][:, 64:128], p["w_in"][:, :64],
                 p["w_in"][:, 128:]], 1)),           # b and c trade places
            dict(p, w=p["w"][:, ::-1])):             # the taps reversed
        assert _rel(jax.jit(layer.apply)({"params": spoiled}, x), got) > 1e-2


def test_the_attention_block():
    """The ``full_attention`` kind here: grouped heads of hidden / heads,
    a norm per head, rotary positions on all of each head, no gate,
    through the dense path and the flash kernels."""
    from flax.core import meta
    from horovod_tpu.models.transformer import SelfAttention

    for attention in ("dense", "flash"):
        config, cfg = _tiny_cfg(attention=attention)
        layer = SelfAttention(cfg)
        x = _x(0)
        params = _seen(meta.unbox(jax.jit(layer.init)(
            jax.random.PRNGKey(1), x)))
        assert jax.tree.map(jnp.shape, params["params"]) == {
            "wq": (64, 4, 16), "wkv": (2, 64, 2, 16), "wo": (4, 16, 64),
            "q_norm": {"scale": (16,)}, "k_norm": {"scale": (16,)}}
        got = jax.jit(layer.apply)(params, x)
        want = reference._attention(x, params["params"], config)
        assert _rel(got, want) < 1e-5, attention
        # Another theta, no positions, another grouping: something else.
        assert _rel(reference._attention(
            x, params["params"], dict(config, rope_theta=1e4)), want) > 1e-3
        assert _rel(jax.jit(SelfAttention(cfg, None, False).apply)(
            params, x), want) > 1e-2
        swapped = dict(params["params"],
                       wkv=params["params"]["wkv"][:, :, ::-1])
        assert _rel(jax.jit(layer.apply)({"params": swapped}, x),
                    want) > 1e-2


@pytest.mark.parametrize("kind", [CONV, FULL])
def test_the_dense_block_by_kind(kind):
    """Layer 0's kind of block under the same ``Block``: the mixer by
    ``layer_type``, a dense SwiGLU of ``intermediate_size``, two norms."""
    from flax.core import meta
    from horovod_tpu.models.transformer import Block

    config, cfg = _tiny_cfg()
    block = Block(cfg, cfg.block.dense_ff, kind)
    x = _x(2)
    params = _seen(meta.unbox(jax.jit(block.init)(jax.random.PRNGKey(3), x)))
    assert sorted(params["params"]) == [
        "attn" if kind == FULL else "conv", "ln1", "ln2", "mlp"]
    assert params["params"]["mlp"]["wi"].shape == (64, 96)
    got = jax.jit(block.apply)(params, x)
    want, _, _ = reference._block(x, params["params"], None, None,
                                  config=config, kind=kind)
    assert _rel(got, want) < 1e-5


def _expert_layer(cfg):
    """The expert layer as ``models.transformer.Block`` makes it: no
    shared expert."""
    from horovod_tpu.parallel.moe import MoeMlp

    assert cfg.block.shared_experts == 0
    return MoeMlp(cfg)


def test_the_expert_block_chooses_by_score_plus_bias_and_gates_by_score():
    from flax.core import meta

    config, cfg = _tiny_cfg()
    layer = _expert_layer(cfg)
    x = _x(4)
    variables = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(5), x))
    params = variables["params"]
    assert sorted(params) == ["router", "wg", "wi", "wo"]
    assert params["wi"].shape == (2, 64, 32)       # the two HELD
    assert params["router"].shape == (64, 8)       # scores all 8
    # A bias that hands every token to experts 1 (held) and 5 (absent).
    bias = jnp.zeros(8).at[1].set(5.0).at[5].set(4.0)
    out, sown = jax.jit(lambda b: layer.apply(
        {"params": params, "moe_state": {"router_bias": b}}, x,
        mutable=["moe"]))(bias)
    assert (np.sort(np.asarray(sown["moe"]["experts"][0]), -1)
            == [1, 5]).all()
    # By hand: the two sigmoids WITHOUT the bias, renormalised, times
    # routed_scaling_factor 1; only expert 1's term is computed here and
    # nothing stands beside the routed sum.
    y = x[0]
    s = jax.nn.sigmoid(y @ params["router"])
    g1 = s[:, 1] / (s[:, 1] + s[:, 5] + 1e-6)
    want = g1[:, None] * reference._swiglu(
        y, params["wg"][1], params["wi"][1], params["wo"][1])
    assert _rel(out[0], want) < 1e-5
    ref, chosen, _ = reference._experts(y, params, bias, config, None)
    assert _rel(out[0], ref) < 1e-5
    assert (np.sort(np.asarray(chosen), -1) == [1, 5]).all()


def test_the_four_shares_are_the_whole_layer():
    """What ties the share to the model: chips 0..3 each hold two of the
    8 experts; their routed parts add up to the uncut reference's layer
    (no shared expert to count once)."""
    from flax.core import meta

    config, cfg = _tiny_cfg()
    x = _x(6)
    y = x[0]
    whole = _expert_layer(dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, experts_held=0)))
    p = meta.unbox(jax.jit(whole.init)(jax.random.PRNGKey(7), x))["params"]
    assert p["wi"].shape == (8, 64, 32)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(8), (8,))
    state = {"router_bias": bias}
    want = reference.whole_layer(y, p, bias, config)
    total, rows = jnp.zeros_like(y), 0
    for chip in range(4):
        first = 2 * chip
        layer = _expert_layer(dataclasses.replace(
            cfg, block=dataclasses.replace(
                cfg.block, experts_held=2, first_expert_held=first)))
        mine = dict(p, **{w: p[w][first:first + 2]
                          for w in ("wi", "wg", "wo")})
        out, sown = jax.jit(lambda q, layer=layer: layer.apply(
            {"params": q, "moe_state": state}, x, mutable=["moe"]))(mine)
        assert int(sown["moe"]["tokens_per_expert"][0].sum()) == 96 * 2
        rows += int(sown["moe"]["rows_held"][0])
        total = total + out[0]
        ref, _, _ = reference._experts(
            y, mine, bias, dict(config, first_expert_held=first), None)
        assert _rel(out[0], ref) < 1e-5
    assert rows == 96 * 2               # each pair computed exactly once
    assert _rel(total, want) < 1e-5
    out = jax.jit(lambda q: whole.apply(
        {"params": q, "moe_state": state}, x, mutable=["moe"])[0])(p)
    assert _rel(out[0], want) < 1e-5


def test_the_bias_after_a_step():
    cell, model, params, state, tokens = _assembled("float32")
    config = cell.config
    (_, new), _ = jax.jit(jax.value_and_grad(model.loss, has_aux=True))(
        params, state, tokens)
    _, want = jax.jit(lambda p, s, x: reference.loss(config, p, s, x))(
        params, state, tokens)
    counts = jax.jit(lambda p, s, x: model.loss_and_stats(p, s, x)[1][
        "tokens_per_expert"])(params, state, tokens)
    assert sorted(new) == ["layer_1", "layer_2"]
    for row, name in enumerate(sorted(new)):
        old = np.asarray(state[name]["moe"]["router_bias"])
        got = np.asarray(new[name]["moe"]["router_bias"])
        np.testing.assert_allclose(
            got, want[name]["moe"]["router_bias"], rtol=0, atol=1e-7)
        c = np.asarray(counts[row], np.float64)
        np.testing.assert_allclose(
            got - old,
            config["router_bias_update_rate"] * np.sign(c.mean() - c),
            atol=1e-7)


def test_recomputation_changes_no_gradient():
    cell, model, params, state, tokens = _assembled("float32")
    plain = cells.load(CELL, tiny=True)
    plain.config["compute_dtype"] = "float32"
    plain.traffic["remat"] = False
    assert cell.traffic["remat"] is True
    other = plain.builder.build(plain.config, plain.traffic)
    assert model.module.cfg.remat and not other.module.cfg.remat

    def run(m):
        return jax.jit(jax.value_and_grad(m.loss, has_aux=True))(
            params, state, tokens)

    ((loss, new), grads), ((loss2, new2), grads2) = run(model), run(other)
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    assert max(_leaf_distances(grads, grads2).values()) < 1e-5
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()),
                                     new, new2))


def _matmuls(jaxpr, inside=False):
    """(operand shapes, inside a ``checkpoint``?) of every
    ``dot_general`` of ``jaxpr``, a kernel's own left out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "dot_general":
            yield tuple(v.aval.shape for v in eqn.invars), inside
        within = inside or eqn.primitive.name == "remat2"
        for value in eqn.params.values():
            for cand in value if isinstance(value, (list, tuple)) else (
                    value,):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from _matmuls(inner, within)


def test_a_recomputed_conv_block_multiplies_nothing_but_its_router():
    """The gradient's jaxpr under ``remat``: inside the ``checkpoint``
    equations (a block's recomputed forward and its backward) no
    ``dot_general`` has the operand shapes of a FORWARD product of the
    convolution mixer or of a feed-forward, because those products are
    kept (``_REMAT_KEEPS``); the expert blocks multiply their routers'
    logits again, and the attention block the q and k projections that
    stand before its head norms."""
    cell, model, params, state, tokens = _assembled("float32")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, state, tokens)[0]))(params)
    t, m = 2 * 128, 64
    recomputed = [shapes for shapes, inside in _matmuls(jaxpr.jaxpr)
                  if inside]
    forward = {
        "conv in": ((2, 128, m), (m, 3 * m)),
        "conv out": ((2, 128, m), (m, m)),
        "dense up or gate": ((2, 128, m), (m, 96)),
        "dense down": ((2, 128, 96), (96, m)),
        "router": ((t, m), (m, 8)),
        "q": ((2, 128, m), (m, 4, 16)),
        "k or v": ((2, 128, m), (m, 2, 16)),
    }
    count = {name: recomputed.count(shapes)
             for name, shapes in forward.items()}
    # conv out's (x, w) shapes are also its input gradient's (dy, w^T is
    # contracted otherwise but reads the same shapes): two conv layers.
    assert count["conv in"] == 0, recomputed
    assert count["dense up or gate"] == 0 and count["dense down"] == 0
    assert count["router"] == 2            # one an expert layer
    assert count["q"] == 1 and count["k or v"] == 1
    # The control: with nothing kept, every product is made again.
    from horovod_tpu.models import transformer

    kept = transformer._REMAT_KEEPS
    transformer._REMAT_KEEPS = ()
    try:
        bare = jax.make_jaxpr(jax.grad(lambda p: cell.builder.build(
            cell.config, cell.traffic).loss(p, state, tokens)[0]))(params)
    finally:
        transformer._REMAT_KEEPS = kept
    again = [shapes for shapes, inside in _matmuls(bare.jaxpr) if inside]
    assert again.count(forward["conv in"]) == 2
    assert again.count(forward["dense up or gate"]) == 2


def test_the_remat_counter_tells_a_block_without_a_kernel():
    """``hvd_remat_blocks_total{keeps}``: the attention layer under
    ``flash+products``, the two conv layers under ``products``; and
    ``hvd_attn_layers_total{kind}`` counts the mixers by kind."""
    from horovod_tpu.models import transformer

    cell, model, params, state, tokens = _assembled("float32")

    def read():
        return ({k: transformer._M_REMAT_BLOCKS.labels(keeps=k).get()
                 for k in ("flash+products", "products")},
                {k: transformer._M_ATTN_LAYERS.labels(kind=k).get()
                 for k in (CONV, FULL, "sliding_attention")})

    before = read()
    jax.eval_shape(lambda p: model.loss_and_stats(p, state, tokens)[0],
                   params)
    after = read()
    assert {k: after[0][k] - before[0][k] for k in after[0]} == {
        "flash+products": 1, "products": 2}
    moved = {k: after[1][k] - before[1][k] for k in after[1]}
    assert moved[CONV] == 2 * moved[FULL] > 0
    assert moved["sliding_attention"] == 0


# ------------------------------------------------- the defaults' case -----

def test_the_older_blocks_are_the_defaults_case():
    """The new field's default is what the older blocks are: no taps,
    every layer an attention. Their parameter trees hold an ``attn`` a
    layer and no ``conv``, and their traced losses carry neither of the
    convolution's names."""
    from horovod_tpu import models
    from horovod_tpu.jax import introspect

    assert models.BlockSpec().conv_taps == 0
    assert models.BlockSpec().layer_types == ()
    for name in ("gpt2m-s1024-c1", "olmoe-s4096-c1", "glm47f-s8192-ep8-c1",
                 "trinity-s8192-ep8-c1"):
        cell = cells.load(name, tiny=True)
        model = cell.builder.build(cell.config, cell.traffic)
        params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        layers = {k: v for k, v in params["params"].items()
                  if k.startswith("layer_")}
        assert layers and all(
            "attn" in layer and "conv" not in layer
            for layer in layers.values()), name
        tokens = jnp.zeros((1, cell.traffic["seq_len"] + 1), jnp.int32)
        traced = str(jax.make_jaxpr(
            lambda p, s: model.loss(p, s, tokens)[0])(params, state))
        assert introspect.SAVED_ATTN_OUT in traced, name
        for conv in (introspect.SAVED_CONV_IN, introspect.SAVED_CONV_OUT,
                     "pad["):
            assert conv not in traced, (name, conv)


def test_the_layer_pattern_has_to_fit_the_model():
    from horovod_tpu import models

    def init(block, **cfg):
        model = models.Transformer(models.TransformerConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=8,
            max_seq_len=8, block=block, **cfg))
        return jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    tree = init(models.BlockSpec(layer_types=(CONV, FULL), conv_taps=3))
    assert sorted(tree["params"]["layer_0"]) == ["conv", "ln1", "ln2", "mlp"]
    assert sorted(tree["params"]["layer_1"]) == ["attn", "ln1", "ln2", "mlp"]
    with pytest.raises(ValueError, match="conv_taps"):
        init(models.BlockSpec(layer_types=(CONV, FULL)))
    with pytest.raises(ValueError, match="names 1 layers"):
        init(models.BlockSpec(layer_types=(CONV,), conv_taps=3))
    with pytest.raises(ValueError, match="layer_types knows"):
        init(models.BlockSpec(layer_types=("convolution", FULL),
                              conv_taps=3))
    with pytest.raises(ValueError, match="exchanges none over seq_axis"):
        init(models.BlockSpec(layer_types=(CONV, FULL), conv_taps=3),
             attention="ring", seq_axis="seq")


def test_the_planner_counts_the_held_expert_leaves():
    import horovod_tpu as hvd

    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    plan = hvd.plan(params, batch=1, chips=1, **model.plan_kwargs)
    assert plan.workload.num_experts == 8
    # Four expert layers of three (8, 2048, 1792) float32 panels.
    assert plan.workload.expert_param_bytes == 4 * 3 * 8 * 2048 * 1792 * 4
    assert plan.workload.param_bytes == 507_820_160 * 4


def test_the_builder_refuses_what_it_has_no_one_answer_to():
    from benchmark.builders import lfm2_moe as builder

    cell = cells.load(CELL)
    builder.block_spec(cell.config)
    for key, value in (("model_type", "lfm2"), ("conv_bias", True),
                       ("norm_topk_prob", False), ("use_expert_bias", False),
                       ("tie_embedding", False),
                       ("first_k_dense_replace", 2), ("head_dim", 128)):
        with pytest.raises(ValueError, match=key):
            builder.block_spec(dict(cell.config, **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        builder.block_spec(dict(cell.config, first_layer=22))


# ---------------------------------------------------------- flops_lfm2 ----

def _published():
    with open(os.path.join(CONFIGS, "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_the_configuration_file_keeps_every_published_width():
    config = _published()
    assert {k: config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "experts_routed_over", "routed_scaling_factor", "rope_theta",
        "norm_eps", "conv_L_cache", "conv_bias", "norm_topk_prob",
        "use_expert_bias", "max_position_embeddings", "model_type")} == {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 7168,
        "moe_intermediate_size": 1792, "num_experts_per_tok": 4,
        "experts_routed_over": 32, "routed_scaling_factor": 1,
        "rope_theta": 1000000, "norm_eps": 1e-5, "conv_L_cache": 3,
        "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe"}
    assert config["head_dim"] == 2048 // 32
    # The published pattern whole; this chip's five layers are published
    # layers 1..5: conv, attention, conv, conv, conv.
    assert config["layer_types"] == (
        [CONV, CONV, FULL] + [CONV, CONV, CONV, FULL] * 4
        + [CONV, CONV, FULL, CONV, CONV])
    assert len(config["layer_types"]) == 24
    assert config["layer_types"].count(FULL) == 6
    assert config["first_layer"] == 1
    assert reference.layer_kinds(config) == [CONV, FULL, CONV, CONV, CONV]
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 16384)
    assert config["vocab_size"] * 4 == 65536
    assert sorted(config["reduced_from"]) == sorted(config["reduced"])
    assert sum("modeling_lfm2_moe.py" in text
               for text in config["assumed"].values()) >= 4
    for key in ("assumed", "departures", "deployment", "check"):
        assert config[key]
    assert "four chips" in config["deployment"]
    assert config["optimizer"]["learning_rate"] == 1e-5
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "s16384-conv-ep4-c1.json")) as f:
        mix = json.load(f)
    assert {k: mix[k] for k in (
        "seq_len", "per_chip_batch", "remat", "data", "require_axes",
        "warmup_steps", "trace_steps")} == {
        "seq_len": 16384, "per_chip_batch": 1, "remat": True,
        "data": {"kind": "markov_tokens", "successors": 4, "pool": 8},
        "require_axes": None, "warmup_steps": 3, "trace_steps": 6}
    held = mix["compiled_bytes"]["lfm2-8b-a1b"]["held_bytes_per_chip"]
    assert 0.25 * 16e9 < held < 15.0e9


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    """Every key of the public ``config.json`` (as the ``model-configs``
    catalog carries it, where the catalog is present) stands in the
    file under its own name with its own value, but the four keys of
    ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B"]
    config = _published()
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"])


def test_the_parameters_of_the_share_by_hand():
    """The program's own tree at the published widths (shapes only)."""
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa
    p = params["params"]
    conv = 2048 * 3 * 2048 + 2048 * 3 + 2048 * 2048
    assert conv == 16_783_360 == count(p["layer_0"]["conv"])
    attention = 2 * 2048 * 32 * 64 + 2 * 2048 * 8 * 64 + 2 * 64
    assert attention == 10_485_888 == count(p["layer_1"]["attn"])
    assert p["layer_1"]["attn"]["wkv"].shape == (2, 2048, 8, 64)
    expert = 3 * 2048 * 1792
    assert expert == 11_010_048
    dense = conv + 3 * 2048 * 7168 + 2 * 2048
    assert dense == 60_827_648 == count(p["layer_0"])
    conv_layer = conv + 8 * expert + 2048 * 32 + 2 * 2048
    assert conv_layer == 104_933_376 == count(p["layer_2"])
    assert count(p["layer_3"]) == count(p["layer_4"]) == conv_layer
    attn_layer = attention + 8 * expert + 2048 * 32 + 2 * 2048
    assert attn_layer == 98_635_904 == count(p["layer_1"])
    assert sorted(p["layer_1"]["moe"]) == ["router", "wg", "wi", "wo"]
    assert count(p["embed"]) == 16384 * 2048 == 33_554_432
    assert "lm_head" not in p and "pos" not in p     # tied; rotary
    assert count(params) == dense + 3 * conv_layer + attn_layer \
        + 33_554_432 + 2048 == 507_820_160
    assert 8.12e9 < 16 * count(params) < 8.13e9
    assert jax.tree.map(jnp.shape, state) == {
        "layer_%d" % i: {"moe": {"router_bias": (32,)}}
        for i in (1, 2, 3, 4)}


def test_the_step_of_the_share_by_hand():
    from benchmark.builders import lfm2_moe as builder

    config = _published()
    s, d, h, kv, hd = 16384, 2048, 32, 8, 64
    pairs = s * (s + 1) // 2
    assert flops.causal_pairs(s) == pairs == 134_225_920
    conv = 2 * s * d * 3 * d + 2 * s * d * d
    assert flops_lfm2.conv_mixer_forward_ops(s, d) == conv
    projections = 2 * s * d * (2 * h * hd + 2 * kv * hd)
    attention = projections + h * 4 * pairs * hd
    assert flops_lfm2.attention_forward_ops(
        s, hidden=d, n_head=h, n_kv=kv, head_dim=hd) == attention
    dense = 3 * 2 * s * d * 7168
    router = 2 * s * d * 32
    held = 3 * 2 * (s * 4 * 8 // 32) * d * 1792       # 16,384 rows of 65,536
    assert flops_glm.held_rows(s, 4, 8, 32) == 16384
    head = 2 * s * d * 16384
    model = builder.build(config, {"seq_len": s, "remat": True})
    ops = model.step_ops(1)
    assert ops == 3 * (4 * conv + attention + dense + 4 * (router + held)
                       + head)
    # 22.9 TFLOP: the four conv mixers 6.6, the attention layer 4.3 (its
    # pairs 3.3), the feed-forward side (dense + held experts) 8.7, the
    # head 3.3.
    assert 22.8e12 < ops < 23.0e12
    assert 3 * 4 * conv == pytest.approx(6.6e12, rel=5e-3)
    assert 3 * h * 4 * pairs * hd == pytest.approx(3.3e12, rel=5e-3)
    assert 3 * (dense + 4 * held) == pytest.approx(8.66e12, rel=5e-3)
    # What attention REQUIRES of the step: ONE attention layer's causal
    # pairs, two products forward and five backward, K/V panels 8 heads
    # wide (a conv layer has no pairs).
    work = model.attention_work(1)
    wide, narrow, row = h * s * hd * 2, kv * s * hd * 2, h * s * 4
    assert work["fwd"] == (2 * h * 2 * pairs * hd,
                           2 * wide + 2 * narrow + row)
    assert work["bwd"] == (5 * h * 2 * pairs * hd,
                           4 * wide + 4 * narrow + row)
    # The fossil ``tests/test_flash_tpu_compile.py`` reads, and no metric.
    assert model.kernels(1) == dict.fromkeys(("fwd", "dkv", "dq"), (1,))
    # The gates and taps of one conv layer: 11 widths of bf16 rows and
    # the taps' own gradient; the memory roof binds by far.
    gate_ops, gate_bytes = flops_lfm2.conv_gate_work(s, d, 3)
    assert gate_bytes == 11 * s * d * 2 + d * 3 * 4
    assert gate_ops == s * d * (7 + 6 + 2 + 5 + 6 + 2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, roof = flops.roofline_seconds(gate_ops, gate_bytes, peak)
    assert roof == "memory" and least == pytest.approx(0.901e-3, rel=1e-2)
    # ``moe.held_roofline`` reads these through the shared reader.
    sizes = builder.sizes_of(config)
    assert {k: sizes[k] for k in ("hidden", "expert_width", "k", "held",
                                  "routed")} == {
        "hidden": 2048, "expert_width": 1792, "k": 4, "held": 8,
        "routed": 32}
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 4
    # A quarter share: the sorted-row arrays are a prefix of half the pairs.
    from horovod_tpu.parallel.moe import prefix_rows

    assert prefix_rows(s, 4, 8, 32) == 32768 == s * 4 // 2


# -------------------------------------------------------------- scopes ----

STEP = "jit(hvd_bench_step)/"
FWD = STEP + "jvp(Transformer)/layer_2/"
BWD = STEP + "transpose(jvp(Transformer))/layer_2/"
REDONE = (STEP + "transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
          "rematted_computation/layer_2/")


@pytest.mark.parametrize("scope,phase,part", [
    (FWD + "conv/dot_general", "forward", "conv"),
    (FWD + "conv/hvd_conv_gate/mul", "forward", "conv"),
    (BWD + "conv/hvd_conv_gate/pad", "backward", "conv"),
    (REDONE + "conv/hvd_conv_gate/mul", "backward", "conv"),
    (BWD + "conv/dot_general", "backward", "conv"),
    (FWD + "ln1/mul", "forward", "norm"),
])
def test_phase_and_part_of_the_new_scopes(scope, phase, part):
    assert scope_view.classify(scope, "") == (phase, part)


def test_the_scope_constants_are_what_the_layers_set():
    from benchmark import conv_view
    from horovod_tpu.jax import introspect

    assert introspect.SCOPE_CONV_GATE == conv_view.GATE == "hvd_conv_gate"
    assert (introspect.SAVED_CONV_IN, introspect.SAVED_CONV_OUT) == (
        "hvd_conv_in", "hvd_conv_out")
    assert (conv_view.CONV, conv_view.MIXER, conv_view.ATTN) == (
        CONV, "conv", "attn")
    cell, model, params, state, tokens = _assembled("float32")
    grad = jax.grad(lambda p: model.loss(p, state, tokens)[0])
    text = jax.jit(grad).lower(params).as_text(debug_info=True)
    for name in ("layer_0/conv/hvd_conv_gate", "layer_2/conv/hvd_conv_gate",
                 "layer_1/attn/q_norm", "layer_1/attn/k_norm",
                 "layer_1/attn/rope", "layer_1/attn/hvd_flash/hvd_flash_fwd",
                 "layer_0/mlp", "layer_2/moe/hvd_moe_router"):
        assert name in text, name
    for name in ("layer_1/conv", "layer_0/attn", "layer_2/attn",
                 "hvd_moe_shared", "hvd_attn_gate", "layer_0/moe"):
        assert name not in text, name
    # The names of what a conv block keeps are traced, and lower to
    # nothing.
    traced = str(jax.make_jaxpr(grad)(params))
    for name in (introspect.SAVED_CONV_IN, introspect.SAVED_CONV_OUT):
        assert "name=%s]" % name in traced and name not in text, name


def _conv_step():
    """The recorded step as a model of conv and attention layers would
    name it: layer 1 (the attention one of this chip's five) keeps the
    three kernels; the feed-forward's two matmuls become layer 0's conv
    mixer, the forward one a gate fusion."""
    step = RECORDED_STEP.replace("layer_0/attn", "layer_1/attn").replace(
        "jvp(Transformer)/layer_0/mlp/dot_general",
        "jvp(Transformer)/layer_0/conv/hvd_conv_gate/mul", 1).replace(
        "transpose(jvp(Transformer))/layer_0/mlp/dot_general",
        "transpose(jvp(Transformer))/layer_0/conv/dot_general")
    assert step.count("hvd_conv_gate") == 1 and step.count("/conv/") == 2
    return step


def test_the_new_readers_on_the_recorded_trace(capsys):
    names = ("conv.mixer_ms", "conv.attn_ms", "conv.gate_ms",
             "conv.gate_roofline")
    ctx = _ctx(_conv_step())
    ctx.cell = cells.load(CELL)
    got = {name: reader(name)(ctx) for name in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    # The attention module: the three kernels, their glue, the transpose.
    assert got["conv.attn_ms"] == pytest.approx(sum(
        scope_view.part_ms(ctx, part)
        for part in ("attn", "flash_kernel", "flash_glue")))
    # The conv module is scope_view's part ``conv``; the gate is inside it.
    assert got["conv.mixer_ms"] == pytest.approx(
        scope_view.part_ms(ctx, "conv"))
    assert 0 < got["conv.gate_ms"] < got["conv.mixer_ms"]
    least = 4 * flops.roofline_seconds(
        *flops_lfm2.conv_gate_work(16384, 2048, 3), ctx.peak)[0]
    assert got["conv.gate_roofline"] == pytest.approx(
        100 * 1e3 * least / got["conv.gate_ms"])
    assert "conv gates and taps" in capsys.readouterr().err
    # A conv scope in an ATTENTION layer's number, an attention scope in
    # a conv layer's: neither is counted.
    crossed = _ctx(_conv_step().replace("layer_1/", "layer_9/").replace(
        "layer_0/", "layer_1/").replace("layer_9/", "layer_0/"))
    crossed.cell = cells.load(CELL)
    assert all(reader(name)(crossed) is None for name in names)
    # A step whose compiler left nothing under the gate's scope, a cell
    # without conv layers, a ctx a reader cannot use: nothing, and no
    # exception.
    fused = _ctx(_conv_step().replace("hvd_conv_gate/", ""))
    fused.cell = cells.load(CELL)
    assert reader("conv.mixer_ms")(fused) == pytest.approx(
        got["conv.mixer_ms"])
    assert reader("conv.gate_ms")(fused) is None
    assert reader("conv.gate_roofline")(fused) is None
    trinity = _ctx(_conv_step())
    trinity.cell = cells.load("trinity-s8192-ep8-c1")
    gpt2 = _ctx(_conv_step())
    gpt2.cell = cells.load("gpt2m-s1024-c1")
    broken = _ctx("HloModule jit_small_step")
    broken.cell = cells.load(CELL)
    broken.win0 = None
    for name in names:
        assert reader(name)(trinity) is None, name
        assert reader(name)(gpt2) is None, name
        assert reader(name)(broken) is None, name


def test_the_metrics_of_the_cell():
    """The cell reports the end-to-end pair, the shared per-layer metrics
    whose readers read it right, and its own; the shared expert's, the
    all-experts roofline and the other configurations' attention metrics
    are not its."""
    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"conv.mixer_ms", "conv.attn_ms", "moe.held_roofline",
            "moe.layer_ms", "moe.experts_ms", "moe.dispatch_ms",
            "kernel.flash_roofline", "kernel.flash_fwd_roofline",
            "kernel.flash_bwd_roofline",
            "kernel.flash_share_pct", "kernel.flash_glue_ms",
            "model.mfu_pct", "model.step_device_ms", "model.head_ms",
            "device.peak_hbm_gb", "device.idle_pct", "device.unscoped_pct",
            "launch.compile_s", "launch.cache_misses"} <= mine
    assert not mine & {"moe.shared_ms", "moe.experts_roofline",
                       "mla.attn_ms", "swa.attn_ms", "swa.full_ms",
                       "sync.collective_ms", "dsa.attn_ms", "dsa.sparse_ms",
                       "ssm.mixer_ms", "yoco.attn_ms"}
    conv = [m for m in cell.bench["per_layer"]
            if m["name"].startswith("conv.")]
    assert conv and {m["name"] for m in conv} <= mine
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "Convolution mixer"
               and os.path.exists(os.path.join(
                   ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
               for m in conv)
    # Eight cells or more (later PRs add theirs), one of them or more on
    # four chips; six configurations or more.
    assert len(cell.bench["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) >= 1
    assert len(cell.bench["configs"]) >= 6


def test_the_defects_own_rehearsal_pieces():
    """``lfm2_routing.spoiled_gated_taps``: each defect against the loop
    over positions with the same defect."""
    from benchmark import lfm2_routing
    from horovod_tpu.models import transformer

    m, s = 8, 12
    bcu = jax.random.normal(jax.random.PRNGKey(0), (1, s, 3 * m))
    w = jax.random.normal(jax.random.PRNGKey(1), (m, 3))
    b, c, u = (np.asarray(a, np.float64)[0]
               for a in jnp.split(bcu, 3, axis=-1))
    taps = np.asarray(w, np.float64)

    def by_hand(gated, shifts, with_c):
        out = np.zeros((s, m))
        for t in range(s):
            for j, shift in enumerate(shifts):
                if 0 <= t + shift < s:
                    out[t] += taps[:, j] * gated[t + shift]
        return c * out if with_c else out

    want = {"sound": by_hand(b * u, (-2, -1, 0), True),
            "no_c_gate": by_hand(b * u, (-2, -1, 0), False),
            "no_b_gate": by_hand(u, (-2, -1, 0), True),
            "acausal": by_hand(b * u, (0, 1, 2), True)}
    assert sorted(want)[:3] == sorted(lfm2_routing.CONV_DEFECTS)
    assert _rel(transformer._gated_taps(bcu, w)[0],
                jnp.asarray(want["sound"], jnp.float32)) < 1e-5
    for defect in lfm2_routing.CONV_DEFECTS:
        got = lfm2_routing.spoiled_gated_taps(defect)(bcu, w)[0]
        assert _rel(got, jnp.asarray(want[defect], jnp.float32)) < 1e-5
        assert _rel(got, jnp.asarray(want["sound"], jnp.float32)) > 1e-2


# ----------------------------------------------------------- rehearsal ----

@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_through_the_cpu_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3800000003", "--seconds", "1", "--trace", trace, "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert line["check"]["leaves"] == 30
    assert line["check"]["leaves_all_zero"] == 0
