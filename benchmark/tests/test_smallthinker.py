"""SmallThinker-21BA3B's share on the CPU at the builder's ``TINY``
widths (hidden 64, SEVEN query heads over one key/value head of 16;
window 32 at S=128; one full layer without positions and three window
layers with RoPE; every layer an expert layer that holds 4 of the 16
ReLU-gated experts of 32 it routes over, 3 a token, none shared;
vocabulary 512): the program against
``benchmark/reference/smallthinker.py`` on seeded weights, whole, at
free and at forced routing; the router's tap; ReGLU against a plain
expression; the gates against ``route``; the four shares against the
uncut layer; recomputation; causality; that ``router_tap`` 'ffn' is the
parent's program; the counting of ``flops_smallthinker.py`` by hand; the
new scope through its reader.

Tolerances. With the program computing in float32 the two are the same
mathematics in another order: logits to 1e-4 of their largest entry, the
loss to 1e-5, every gradient leaf to 1e-3 relative L2. That holds at
FREE routing too: no token of these seeds changes an expert (asserted).
"""

import dataclasses
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops, flops_afmoe, flops_smallthinker, traffic
from benchmark.layer_metrics import reader
from benchmark.reference import smallthinker as reference
from benchmark.tests.test_olmoe import _leaf_distances, _rel
from benchmark.tests.test_reference import _compare
from benchmark.tests.test_scope_view import RECORDED_STEP, _ctx

CELL = "smallthinker-s8192-ep4-c1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
SLIDING, FULL = "sliding_attention", "full_attention"


def _seen(params):
    """Every norm scale moved off its initial value, each entry its own
    way, so that a norm left out, or the OTHER norm tapped, shows."""
    leaves, treedef = jax.tree.flatten(params)
    return treedef.unflatten([
        p * (1 + 0.2 * jnp.cos(jnp.arange(p.shape[0]) + i))
        if p.ndim == 1 else p for i, p in enumerate(leaves)])


def _assembled(dtype, attention="flash", **config):
    cell = cells.load(CELL, tiny=True)
    cell.config.update(compute_dtype=dtype, attention=attention, **config)
    asm = cells.assemble(cell, jax.devices()[:1])
    key = jax.random.PRNGKey(11)
    params, state = jax.jit(asm.model.init)(key)
    assert state == {}
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **asm.model.pool_kwargs)
    return cell, asm.model, _seen(params), pool[0]


def _random_assignments(key, config, tokens):
    """Per layer, k distinct experts a token, nothing to do with any
    router."""
    noise = jax.random.uniform(key, (
        config["num_hidden_layers"], tokens, config["experts_routed_over"]))
    picks = jnp.argsort(noise, -1)[
        ..., :config["moe_num_active_primary_experts"]]
    return list(picks.astype(jnp.int32))


# ------------------------------------------------ program = reference -----

@pytest.mark.parametrize("routing,attention", [
    ("free", "flash"), ("forced", "flash"), ("free", "dense")])
def test_float32_program_is_the_reference(routing, attention):
    from horovod_tpu.parallel import moe

    cell, model, params, tokens = _assembled("float32", attention)
    config = cell.config
    assert reference.layer_kinds(config) == [FULL] + [SLIDING] * 3
    assert reference.rope_flags(config) == [False, True, True, True]
    t = tokens.shape[0] * (tokens.shape[1] - 1)
    assignments = None
    if routing == "forced":
        assignments = _random_assignments(jax.random.PRNGKey(5), config, t)

    want, aux = jax.jit(lambda p, x: reference.forward(
        config, p, {}, x, assignments))(params, tokens[:, :-1])
    got, sown = jax.jit(lambda p, x: model.module.apply(
        {"params": p["params"]}, x, assignments, mutable=["moe"]))(
            params, tokens[:, :-1])
    stats = moe.sown_stats(sown)
    # The same experts on both sides.
    assert (np.sort(np.asarray(stats["experts"]), -1)
            == np.sort(np.asarray(aux["chosen"]), -1)).all()
    assert (np.asarray(stats["tokens_per_expert"])
            == np.asarray(aux["tokens_per_expert"])).all()
    assert float(jnp.max(jnp.abs(got - want))) \
        < 1e-4 * float(jnp.max(jnp.abs(want)))
    counts = np.asarray(stats["tokens_per_expert"])
    assert counts.shape == (4, 16)
    assert (counts.sum(-1) == t * 3).all()
    assert (np.asarray(stats["rows_held"]) == counts[:, :4].sum(-1)).all()
    assert (np.asarray(stats["rows_held"]) > 0).all()

    def both(loss):
        return jax.jit(jax.value_and_grad(
            lambda p: loss(p, tokens, assignments)[0]))(params)

    (loss, grads), (ref_loss, ref_grads) = both(model.loss_and_stats), both(
        lambda p, x, a: reference.loss(config, p, {}, x, a))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    distances = _leaf_distances(grads, ref_grads)
    # embed, lm_head, ln_f; 3 attention + 2 norm leaves and router + 3
    # held a layer.
    assert len(distances) == 3 + 4 * (3 + 2 + 4)
    assert max(distances.values()) < 1e-3, distances
    assert all(float(jnp.linalg.norm(g)) > 0
               for g in jax.tree.leaves(ref_grads))


def test_bf16_program_at_forced_routing_is_inside_gpt2s_bounds():
    cell, model, params, tokens = _assembled("bfloat16")
    config = cell.config
    with open(os.path.join(CONFIGS, "gpt2-medium.json")) as f:
        bounds = json.load(f)["check"]
    chosen = list(jax.jit(lambda p, x: reference.forward(
        config, p, {}, x)[1]["chosen"])(params, tokens[:, :-1]))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_and_stats(p, tokens, chosen)[0]))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(config, p, {}, tokens, chosen)[0]))(params)
    assert abs(float(loss) - float(ref_loss)) \
        < bounds["loss_rtol"] * float(ref_loss)
    distances = _leaf_distances(grads, ref_grads)
    assert max(distances.values()) < bounds["grad_rel_l2"], distances
    assert max(distances.values()) > 1e-3, distances


def test_the_check_of_the_cell_in_float32():
    """``run.py``'s own comparison (``check.sgd_step_gradients`` against
    the reference, free routing)."""
    got = _compare(CELL, "float32", 1)
    assert got["loss_rel"] < 1e-5 and got["grad_rel_l2_max"] < 1e-3, got
    assert got["leaves"] == 39 and got["leaves_all_zero"] == 0, got


# ------------------------------------------------------ the router's tap --

def test_the_routers_gradient_is_that_of_the_tap():
    """The router reads ``ln1``'s output: its gradient, and ``ln1``'s,
    are the reference's at ``router_tap`` 'mixer' and are NOT what the
    same weights give at 'ffn' (the norm after the attention), program
    and reference alike; a wrong tap leaves the loss plausible."""
    cell, model, params, tokens = _assembled("float32")
    _, other, _, _ = _assembled("float32", router_tap="ffn")
    assert model.module.cfg.block.router_tap == "mixer"
    assert other.module.cfg.block.router_tap == "ffn"

    def grads(loss):
        return jax.jit(jax.value_and_grad(lambda p: loss(p, tokens)[0]))(
            params)

    (loss, mine), (loss_ffn, ffn) = grads(model.loss_and_stats), grads(
        other.loss_and_stats)
    ref_loss, ref = grads(lambda p, x: reference.loss(
        dict(cell.config, router_tap="mixer"), p, {}, x))
    ref_ffn_loss, ref_ffn = grads(lambda p, x: reference.loss(
        dict(cell.config, router_tap="ffn"), p, {}, x))
    assert max(_leaf_distances(mine, ref).values()) < 1e-3
    assert max(_leaf_distances(ffn, ref_ffn).values()) < 1e-3
    assert abs(float(loss_ffn) - float(ref_ffn_loss)) < 1e-5 * float(loss)
    # Plausible: the loss moves by under a hundredth...
    assert abs(float(loss_ffn) - float(loss)) < 1e-2 * float(loss)
    # ... and the gradients are another function's.
    apart = _leaf_distances(ffn, mine)
    for layer in range(4):
        name = "['params']['layer_%d']" % layer
        assert apart[name + "['moe']['router']"] > 0.05, apart
        # With the tap at the mixer the routing's gradient reaches the
        # block's input through ln1 and none of it through ln2.
        assert apart[name + "['ln2']['scale']"] > 0.05, apart


def test_the_layer_reads_the_array_its_spec_names():
    """The block always hands the layer its mixer's input; ``router_tap``
    alone says whether the router reads it: at 'ffn' it moves nothing,
    at 'mixer' the choice of experts follows it."""
    from horovod_tpu.parallel.moe import MoeMlp

    cell = cells.load(CELL, tiny=True)
    cfg = cell.builder.module_of(cell.config, cell.traffic).cfg
    x, a, b = (jax.random.normal(jax.random.PRNGKey(i), (1, 32, 64))
               for i in range(3))
    for tap, moved in (("ffn", False), ("mixer", True)):
        layer = MoeMlp(dataclasses.replace(cfg, block=dataclasses.replace(
            cfg.block, router_tap=tap)))
        params = layer.init(jax.random.PRNGKey(3), x, None, a)
        chosen = [layer.apply(params, x, None, other, mutable=["moe"])[1]
                  ["moe"]["experts"][0] for other in (a, b)]
        assert bool(jnp.any(chosen[0] != chosen[1])) == moved, tap


def test_a_changed_token_moves_no_earlier_logit():
    """Early routing reads the block's input at the token's OWN
    position: causality holds through the full layer, the windows and
    the routers."""
    cell, model, params, tokens = _assembled("float32")
    inputs = tokens[:1, :-1]
    at = 70
    other = inputs.at[0, at].set((inputs[0, at] + 1) % 512)
    run = jax.jit(lambda x: model.module.apply(
        {"params": params["params"]}, x, mutable=["moe"])[0])
    a, b = run(inputs), run(other)
    assert float(jnp.max(jnp.abs(a[0, :at] - b[0, :at]))) == 0.0
    assert float(jnp.max(jnp.abs(a[0, at:] - b[0, at:]))) > 1e-3


# ------------------------------------------------------------- ReGLU ------

def _plain_reglu(x, wg, wi, wo):
    return (jnp.maximum(x @ wg, 0) * (x @ wi)) @ wo


def test_reglu_in_the_dense_feed_forward():
    from horovod_tpu import models
    from horovod_tpu.models.transformer import Mlp

    cfg = models.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=24,
        max_seq_len=8, dtype=jnp.float32,
        block=models.BlockSpec(ffn="reglu"))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16))
    layer = Mlp(cfg)
    from flax.core import meta

    p = meta.unbox(layer.init(jax.random.PRNGKey(1), x))["params"]
    assert sorted(p) == ["wg", "wi", "wo"]
    p = jax.tree.map(lambda a: a * 20, p)      # gates on both sides of 0
    got, vjp = jax.vjp(lambda p, x: layer.apply({"params": p}, x), p, x)
    want, ref_vjp = jax.vjp(lambda p, x: _plain_reglu(
        x, p["wg"], p["wi"], p["wo"]), p, x)
    assert _rel(got, want) < 1e-6
    zeros = float(jnp.mean(jnp.maximum(x @ p["wg"], 0) == 0))
    assert 0.3 < zeros < 0.7        # a ReLU gate leaves exact zeros
    g = jax.random.normal(jax.random.PRNGKey(2), got.shape)
    assert max(_leaf_distances(vjp(g), ref_vjp(g)).values()) < 1e-5
    # SwiGLU is still what it was, and is not this.
    silu = Mlp(dataclasses.replace(cfg, block=models.BlockSpec(ffn="swiglu")))
    assert _rel(silu.apply({"params": p}, x), want) > 0.1


@pytest.mark.parametrize("rows", ["prefix", "whole"])
def test_reglu_in_the_grouped_experts(rows):
    """``grouped_ffn`` with ``ffn='reglu'`` against a loop over experts,
    forward and every gradient; and through ``_held_rows``, whose
    backward rule remakes the rows, in the prefix's branch and in the
    whole-length one (the routers send every pair to the held
    experts)."""
    from horovod_tpu.parallel import moe

    t, m, f, e, held, k = 1024, 16, 24, 8, 2, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    tokens = jax.random.normal(keys[0], (t, m))
    wg, wi = (jax.random.normal(key, (held, m, f)) * 0.3 for key in keys[1:3])
    wo = jax.random.normal(keys[3], (held, f, m)) * 0.3
    if rows == "whole":     # every pair falls to the two held experts
        experts = jnp.tile(jnp.arange(held, dtype=jnp.int32), (t, 1))
    else:                   # a quarter of them, as at the balanced load
        experts = jnp.stack([jnp.arange(t) % e, (jnp.arange(t) + 3) % e],
                            -1).astype(jnp.int32)
    gates = jax.nn.softmax(jax.random.normal(keys[4], (t, k)), -1)
    counts = jnp.sum(jax.nn.one_hot(experts.reshape(-1), e, dtype=jnp.int32),
                     0)
    sizes, live = counts[:held], jnp.sum(counts[:held])
    order, inverse = moe.sorted_by_expert(experts, 0, e)
    c = moe.prefix_rows(t, k, held, e)
    assert c < t * k and (int(live) > c) == (rows == "whole")

    def program(tokens, gates, wi, wo, wg):
        return moe._held_rows(c, k, tokens, order, inverse, gates, sizes,
                              live, wi, wo, wg, "reglu")

    def plain(tokens, gates, wi, wo, wg):
        out = jnp.zeros_like(tokens)
        for j in range(k):
            for x in range(held):
                mine = (experts[:, j] == x)[:, None]
                out = out + jnp.where(mine, gates[:, j:j + 1] * _plain_reglu(
                    tokens, wg[x], wi[x], wo[x]), 0)
        return out

    args = (tokens, gates, wi, wo, wg)
    got, vjp = jax.vjp(program, *args)
    want, ref_vjp = jax.vjp(plain, *args)
    assert _rel(got, want) < 1e-5
    g = jax.random.normal(keys[5], got.shape)
    for mine, theirs in zip(vjp(g), ref_vjp(g)):
        assert _rel(mine, theirs) < 1e-5
    # SiLU's gate is another function.
    silu = moe._held_rows(c, k, tokens, order, inverse, gates, sizes, live,
                          wi, wo, wg, "swiglu")
    assert _rel(silu, want) > 0.05


# ----------------------------------------------------------- the gates ----

def test_softmax_over_the_chosen_is_the_routers_norm_topk():
    """The reference's gates (``exp(r_e) / sum over the chosen``) are
    the program's ``route(scoring='softmax', norm_topk=True)``: the
    softmax over all experts renormalised over the chosen is the same
    number; the same experts, and the same gradient."""
    from horovod_tpu.parallel import moe

    config = {"moe_num_active_primary_experts": 3}
    n = jax.random.normal(jax.random.PRNGKey(0), (96, 64))
    router = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.3

    def mine(router):
        _, gates, experts = moe.route(n @ router, 3, scoring="softmax",
                                      norm_topk=True)
        full = jnp.sum(jax.nn.one_hot(experts, 16) * gates[..., None], 1)
        return full, experts

    def theirs(router):
        return reference.gates_over_all_experts(n, router, config)

    (got, experts), (want, chosen) = mine(router), theirs(router)
    assert (np.sort(np.asarray(experts), -1)
            == np.sort(np.asarray(chosen), -1)).all()
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6
    np.testing.assert_allclose(np.asarray(want.sum(-1)), 1.0, atol=1e-6)
    assert (np.asarray((want > 0).sum(-1)) == 3).all()
    weight = jax.random.normal(jax.random.PRNGKey(2), got.shape)
    grads = [jax.grad(lambda r, f=f: jnp.sum(f(r)[0] * weight))(router)
             for f in (mine, theirs)]
    assert _rel(*grads) < 1e-5
    # Without the renormalisation the gates are another number.
    _, plain, _ = moe.route(n @ router, 3, scoring="softmax")
    assert float(jnp.mean(plain.sum(-1))) < 0.9


# ------------------------------------------------------------ the shares --

def _expert_layer(cfg):
    from horovod_tpu.parallel.moe import MoeMlp

    return MoeMlp(cfg, None)


def test_the_four_shares_are_the_whole_layer():
    """What ties the share to the model: chips 0..3 each hold four of
    the 16 experts (``first_expert_held`` 0, 4, 8, 12); their parts of
    the routed sum add up to the uncut reference's layer, each pair
    computed exactly once, the router reading ANOTHER array than the
    experts."""
    from flax.core import meta

    cell = cells.load(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    config = cell.config
    cfg = cell.builder.module_of(config, cell.traffic).cfg
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 96, 64))
    mixer_input = jax.random.normal(jax.random.PRNGKey(9), (1, 96, 64))
    whole = _expert_layer(dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, experts_held=0)))
    p = meta.unbox(jax.jit(whole.init)(
        jax.random.PRNGKey(7), x, None, mixer_input))["params"]
    assert p["wi"].shape == (16, 64, 32) and p["router"].shape == (64, 16)
    want = reference.whole_layer(x[0], mixer_input[0], p, config)
    gates, _ = reference.gates_over_all_experts(mixer_input[0], p["router"],
                                                config)
    total, rows = jnp.zeros_like(x[0]), 0
    for chip in range(4):
        first = 4 * chip
        layer = _expert_layer(dataclasses.replace(
            cfg, block=dataclasses.replace(
                cfg.block, experts_held=4, first_expert_held=first)))
        mine = dict(p, **{w: p[w][first:first + 4]
                          for w in ("wi", "wg", "wo")})
        out, sown = jax.jit(lambda q, layer=layer: layer.apply(
            {"params": q}, x, None, mixer_input, mutable=["moe"]))(mine)
        assert int(sown["moe"]["tokens_per_expert"][0].sum()) == 96 * 3
        rows += int(sown["moe"]["rows_held"][0])
        total = total + out[0]
        ref = reference._experts(
            x[0], gates, mine, dict(config, first_expert_held=first))
        assert _rel(out[0], ref) < 1e-5
    assert rows == 96 * 3               # each pair computed exactly once
    assert _rel(total, want) < 1e-5
    out = jax.jit(lambda q: whole.apply(
        {"params": q}, x, None, mixer_input, mutable=["moe"])[0])(p)
    assert _rel(out[0], want) < 1e-5
    # Routed by the experts' own rows the layer is another function.
    assert _rel(reference.whole_layer(x[0], x[0], p, config), want) > 0.1


def test_recomputation_changes_no_gradient():
    cell, model, params, tokens = _assembled("float32")
    plain = cells.load(CELL, tiny=True)
    plain.config["compute_dtype"] = "float32"
    plain.traffic["remat"] = False
    assert cell.traffic["remat"] is True
    other = plain.builder.build(plain.config, plain.traffic)
    assert model.module.cfg.remat and not other.module.cfg.remat

    def run(m):
        return jax.jit(jax.value_and_grad(m.loss, has_aux=True))(
            params, {}, tokens)

    ((loss, _), grads), ((loss2, _), grads2) = run(model), run(other)
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    assert max(_leaf_distances(grads, grads2).values()) < 1e-5


# ------------------------------------- router_tap 'ffn' is the parent's ---

def _traced(name):
    """The jaxpr of the tiny cell's loss and gradient, as text with the
    addresses out."""
    cell = cells.load(name, tiny=True)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(
        lambda k: traffic.make_pool(
            k, dict(cell.traffic["data"], pool=1), global_batch=1,
            config=cell.config, **model.pool_kwargs), jax.random.PRNGKey(0))
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         pool)
    text = str(jax.make_jaxpr(jax.value_and_grad(model.loss, has_aux=True))(
        params, state, batch))
    return re.sub(r"0x[0-9a-f]+", "0x", text)


with open(os.path.join(HERE, "data", "router_tap_ffn_jaxprs.json")) as _f:
    PARENT_JAXPRS = json.load(_f)


@pytest.mark.parametrize("name", sorted(PARENT_JAXPRS["cells"]))
def test_router_tap_ffn_traces_the_parents_program(name):
    """With ``router_tap`` 'ffn' (every older configuration) the traced
    program is the parent's, equation for equation: the jaxpr of the
    tiny cell's loss and gradient, recorded from the parent commit's
    tree (``data/router_tap_ffn_jaxprs.json``: its length and sha256)."""
    cell = cells.load(name, tiny=True)
    assert cell.builder.block_spec(cell.config).router_tap == "ffn"
    text = _traced(name)
    want = PARENT_JAXPRS["cells"][name]
    assert len(text) == want["chars"]
    assert hashlib.sha256(text.encode()).hexdigest() == want["sha256"]


def test_the_mixer_tap_is_another_program():
    text = _traced(CELL)
    assert text.count("hvd_moe_preroute") == 0      # names are not in a jaxpr
    cell = cells.load(CELL, tiny=True)
    cell.config["router_tap"] = "ffn"
    other = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(other.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 129), jnp.int32)
    ffn = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(jax.value_and_grad(
        other.loss, has_aux=True))(params, state, tokens)))
    assert ffn != text


# ------------------------------------------------------ the builder's part -

def test_the_builder_refuses_what_it_has_no_one_answer_to():
    cell = cells.load(CELL, tiny=True)
    for key, other in (("moe_primary_router_apply_softmax", False),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", True),
                       ("hidden_act", "silu"),
                       ("first_k_dense_replace", 1),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            cell.builder.block_spec(dict(cell.config, **{key: other}))
    with pytest.raises(ValueError, match="layer_types"):
        cell.builder.block_spec(dict(cell.config,
                                     layer_types=[SLIDING] * 52))
    with pytest.raises(ValueError, match="rope_layout"):
        cell.builder.block_spec(dict(cell.config,
                                     rope_layout=[0, 1, 0, 1] * 13))
    spec = cell.builder.block_spec(cell.config)
    assert (spec.ffn, spec.router_tap, spec.router, spec.norm_topk,
            spec.rope_layers, spec.shared_experts, spec.first_dense_layers,
            spec.qk_norm_per_head, spec.attn_gate, spec.post_norms) == (
        "reglu", "mixer", "softmax", True, (SLIDING,), 0, 0, False, False,
        False)


def test_the_defaults_are_the_older_blocks():
    from horovod_tpu import models

    spec = models.BlockSpec()
    assert (spec.router_tap, spec.ffn) == ("ffn", "gelu")
    from horovod_tpu.parallel.moe import GATE_ACTIVATIONS

    assert sorted(GATE_ACTIVATIONS) == ["reglu", "swiglu"]
    for name in ("olmoe-s4096-c1", "glm47f-s8192-ep8-c1",
                 "trinity-s8192-ep8-c1", "lfm2-s16384-ep4-c1",
                 "keye-s8192-dsa-ep8-c1"):
        cell = cells.load(name, tiny=True)
        block = cell.builder.block_spec(cell.config)
        assert (block.router_tap, block.ffn) == ("ffn", "swiglu"), name


def test_the_planner_counts_the_held_expert_leaves():
    cell = cells.load(CELL, tiny=True)
    asm = cells.assemble(cell, jax.devices()[:1])
    assert asm.model.plan_kwargs["num_experts"] == 4
    assert asm.plan.mesh_axes == {"data": 1}


def test_the_builder_starts_the_embedding_at_the_configurations_scale():
    """``embed_init_scale`` moves the input embedding and nothing else."""
    cell = cells.load(CELL, tiny=True)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.jit(model.init)(jax.random.PRNGKey(5))
    assert cell.config["embed_init_scale"] == 2.0 and state == {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if leaf.ndim == 1:
            assert bool((leaf == 1).all()), name
        else:
            want = 2.0 if name == "['params']['embed']" else 0.02
            assert abs(float(leaf.std()) / want - 1) < 0.15, name


# -------------------------------------------------- the file and the counts

def _published():
    with open(os.path.join(CONFIGS, "smallthinker-21b-a3b.json")) as f:
        return json.load(f)


def test_the_configuration_file_keeps_every_published_width():
    config = _published()
    assert {k: config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window_size", "moe_ffn_hidden_size",
        "moe_num_active_primary_experts", "experts_routed_over",
        "rope_theta", "rms_norm_eps", "max_position_embeddings",
        "tie_word_embeddings", "norm_topk_prob",
        "moe_primary_router_apply_softmax")} == {
        "hidden_size": 2560, "num_attention_heads": 28,
        "num_key_value_heads": 4, "head_dim": 128,
        "sliding_window_size": 4096, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "experts_routed_over": 64,
        "rope_theta": 1500000, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 16384, "tie_word_embeddings": False,
        "norm_topk_prob": True, "moe_primary_router_apply_softmax": True}
    # Both published layouts whole: a full layer without positions, then
    # three window layers with them, thirteen times.
    assert config["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert config["rope_layout"] == [0, 1, 1, 1] * 13
    assert config["layer_types"] == ([FULL] + [SLIDING] * 3) * 13
    assert config["first_layer"] == 0
    assert reference.layer_kinds(config) == [FULL, SLIDING, SLIDING, SLIDING]
    assert config["router_tap"] == "mixer"
    assert config["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"], config["first_k_dense_replace"]) == (
        4, 16, 37984, 0)
    assert config["vocab_size"] * 4 == 151936
    assert sorted(config["reduced_from"]) == sorted(config["reduced"])
    assert "656,529,920" in config["reduced_from"]["num_hidden_layers"]
    for key in ("(a) block", "(b) router_tap", "(c) routing",
                "(d) attention", "(e) experts", "embed_init_scale",
                "optimizer"):
        assert config["assumed"][key], key
    for key in ("assumed", "departures", "deployment", "check"):
        assert config[key]
    assert "thirteen stages" in config["deployment"]
    assert config["optimizer"]["learning_rate"] == 1e-5
    assert config["check"]["via"] == "sgd_step"
    assert config["check"]["loss_rtol"] == 2e-4
    # Between the chip's largest sound reading and its smallest defect's.
    assert 0.0727 * 2 < config["check"]["grad_rel_l2"] < 0.3118 / 2
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "s8192-pre-ep4-c1.json")) as f:
        mix = json.load(f)
    assert {k: mix[k] for k in (
        "seq_len", "per_chip_batch", "remat", "data", "require_axes",
        "warmup_steps", "trace_steps")} == {
        "seq_len": 8192, "per_chip_batch": 1, "remat": True,
        "data": {"kind": "markov_tokens", "successors": 4, "pool": 8},
        "require_axes": None, "warmup_steps": 3, "trace_steps": 6}
    held = mix["compiled_bytes"]["smallthinker-21b-a3b"][
        "held_bytes_per_chip"]
    assert 0.25 * 16e9 < held < 14.5e9


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    """Every key of the public ``config.json`` (as the ``model-configs``
    catalog carries it, where the catalog is present) stands in the file
    under its own name with its own value, but the three keys of
    ``reduced``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "SmallThinker-21BA3B-Instruct"]
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
    attention = 2 * 2560 * 28 * 128 + 2 * 2560 * 4 * 128
    assert attention == 20_971_520 == count(p["layer_3"]["attn"])
    assert p["layer_0"]["attn"]["wq"].shape == (2560, 28, 128)
    assert p["layer_0"]["attn"]["wkv"].shape == (2, 2560, 4, 128)
    assert sorted(p["layer_0"]["attn"]) == ["wkv", "wo", "wq"]
    expert = 3 * 2560 * 768
    assert expert == 5_898_240
    assert count(p["layer_0"]["moe"]) == 16 * expert + 2560 * 64
    assert sorted(p["layer_0"]) == ["attn", "ln1", "ln2", "moe"]
    layer = attention + 16 * expert + 2560 * 64 + 2 * 2560
    assert layer == 115_512_320 == count(p["layer_2"])
    ends = 2 * 37984 * 2560
    assert ends == 194_478_080 == count(p["embed"]) + count(p["lm_head"])
    assert count(params) == 4 * layer + ends + 2560 == 656_529_920
    assert 10.50e9 < 16 * count(params) < 10.51e9
    assert state == {}


def test_the_step_of_the_share_by_hand():
    from benchmark.builders import smallthinker as builder

    config = _published()
    s, d, h, kv, hd, w = 8192, 2560, 28, 4, 128, 4096
    full_pairs = s * (s + 1) // 2
    kept = w * s - w * (w - 1) // 2
    assert flops_afmoe.window_pairs(s, None) == full_pairs == 33_558_528
    assert flops_afmoe.window_pairs(s, w) == kept == 25_167_872
    projections = 2 * s * (2 * d * h * hd + 2 * d * kv * hd)
    assert projections == 2 * s * 20_971_520
    assert 343.5e9 < projections < 343.7e9
    assert flops_smallthinker.projection_forward_ops(
        s, hidden=d, n_head=h, n_kv=kv, head_dim=hd) == projections
    # Attention: two products forward, five backward, over the pairs the
    # mask keeps: 3.5 times the forward's 28 x 512 x pairs.
    full_fwd, window_fwd = h * 4 * hd * full_pairs, h * 4 * hd * kept
    assert 480.9e9 < full_fwd < 481.1e9 and 360.7e9 < window_fwd < 360.9e9
    for kind, fwd in ((FULL, full_fwd), (SLIDING, window_fwd)):
        work = flops_smallthinker.layer_attention_work(
            1, s, kind, n_head=h, n_kv=kv, head_dim=hd, window=w)
        assert work["fwd"][0] == fwd
        assert work["bwd"][0] * 2 == 5 * fwd
        assert work == flops_afmoe.layer_attention_work(
            1, s, kind, n_head=h, n_kv=kv, head_dim=hd, window=w)
    rows = s * 6 * 16 // 64
    assert rows == 12_288
    experts = 2 * rows * 3 * d * 768
    assert 144.9e9 < experts < 145.1e9
    router = 2 * s * d * 64
    assert 2.6e9 < router < 2.8e9
    head = 2 * s * d * 37984
    assert 1592e9 < head < 1594e9
    want = (3 * (4 * (projections + experts + router) + head)
            + 7 * (full_fwd + 3 * window_fwd) // 2)
    sizes = builder.sizes_of(config)
    assert sizes == dict(hidden=d, n_head=h, n_kv=kv, head_dim=hd, window=w,
                         expert_width=768, k=6, held=16, routed=64)
    got = flops_smallthinker.smallthinker_step_ops(
        1, s, vocab=37984, kinds=reference.layer_kinds(config), **sizes)
    assert got == want
    assert 16.1e12 < got < 16.2e12
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    assert model.step_ops(1) == want and model.step_ops(2) == 2 * want
    total = model.attention_work(1)
    assert total["fwd"][0] == full_fwd + 3 * window_fwd
    # The shares the issue states: the head about 30%, the kernels 34%.
    assert 0.29 < 3 * head / want < 0.31
    assert 0.33 < 3.5 * (full_fwd + 3 * window_fwd) / want < 0.35


# ------------------------------------------------ the scope and its reader -

def test_the_scope_constants_are_what_the_layers_set():
    from benchmark import moe_view
    from horovod_tpu.jax import introspect
    from horovod_tpu.parallel.moe import _M_LAYERS

    assert introspect.SCOPE_MOE_PREROUTE == "hvd_moe_preroute"
    cell, model, params, tokens = _assembled("float32")
    before = {tap: _M_LAYERS.labels(tap=tap).get()
              for tap in ("mixer", "ffn")}
    text = jax.jit(jax.grad(
        lambda p: model.loss(p, {}, tokens)[0])).lower(params).as_text(
            debug_info=True)
    # Counted at trace time: four layers, every one tapped at the mixer.
    assert _M_LAYERS.labels(tap="mixer").get() - before["mixer"] >= 4
    assert _M_LAYERS.labels(tap="ffn").get() == before["ffn"]
    for layer in range(4):
        inside = "layer_%d/moe/hvd_moe_preroute/" % layer
        # The router and the sort are INSIDE the new scope, which is
        # inside the module's: every ``moe.*`` reader still finds them.
        assert inside + moe_view.ROUTER in text, layer
        assert inside + moe_view.DISPATCH in text, layer
        # The expert half is outside it.
        assert "hvd_moe_preroute/hvd_moe_rows" not in text
        assert "layer_%d/moe/hvd_moe_rows" % layer in text
    # RoPE in the sliding layers (1, 2, 3), none in the full one (0).
    assert "layer_1/attn/rope" in text and "layer_3/attn/rope" in text
    assert "layer_0/attn/rope" not in text
    assert "hvd_moe_shared" not in text and "/mlp/" not in text
    # An 'ffn' tap sets no such scope.
    _, other, _, _ = _assembled("float32", router_tap="ffn")
    assert "hvd_moe_preroute" not in jax.jit(jax.grad(
        lambda p: other.loss(p, {}, tokens)[0])).lower(params).as_text(
            debug_info=True)


def _preroute_step():
    """The recorded step as an early-routed expert layer would name it:
    the forward matmul is the router's, under the new scope."""
    step = RECORDED_STEP.replace(
        'jvp(Transformer))/layer_0/mlp/dot_general',
        'jvp(Transformer))/layer_0/moe/hvd_moe_rows/hvd_moe_experts/'
        'hvd_moe_gmm/pallas_call'
    ).replace(
        "jvp(Transformer)/layer_0/mlp/dot_general",
        "jvp(Transformer)/layer_0/moe/hvd_moe_preroute/hvd_moe_router/"
        "dot_general")
    assert step.count("hvd_moe_preroute") == 1
    assert step.count("hvd_moe_experts") == 1
    return step


def test_the_new_reader_on_the_recorded_trace():
    ctx = _ctx(_preroute_step())
    ctx.cell = cells.load(CELL)
    got = reader("moe.preroute_ms")(ctx)
    assert got is not None and got > 0
    # The router is inside the new scope: the older readers count it as
    # they did, and the layer is the two halves.
    assert reader("moe.dispatch_ms")(ctx) == pytest.approx(got)
    assert reader("moe.layer_ms")(ctx) == pytest.approx(
        got + reader("moe.experts_ms")(ctx))
    assert reader("moe.held_roofline")(ctx) > 0
    # A program without the scope (the parent's, any older cell's), a
    # ctx a reader cannot use: nothing, and no exception.
    older = _ctx(RECORDED_STEP.replace(
        "layer_0/mlp/dot_general", "layer_0/moe/hvd_moe_router/dot_general"))
    older.cell = ctx.cell
    assert reader("moe.dispatch_ms")(older) > 0
    assert reader("moe.preroute_ms")(older) is None
    plain = _ctx(RECORDED_STEP)
    plain.cell = ctx.cell
    assert reader("moe.preroute_ms")(plain) is None
    broken = _ctx("HloModule jit_small_step")
    broken.win0 = None
    assert reader("moe.preroute_ms")(broken) is None


def test_the_swa_readers_read_this_configuration(capsys):
    """``benchmark/swa_view.py`` reads the cell unedited: its kinds from
    ``layer_types`` / ``first_layer`` / ``num_hidden_layers``, its sizes
    from the builder's ``sizes_of``; one full layer and three window
    layers of 4096 at 28 over 4 heads."""
    step = RECORDED_STEP.replace(
        "layer_0/attn/hvd_flash/hvd_flash_dkv",
        "layer_2/attn/hvd_flash/hvd_flash_dkv"
    ).replace("layer_0/attn/hvd_flash/hvd_flash_dq",
              "layer_2/attn/hvd_flash/hvd_flash_dq")
    ctx = _ctx(step)
    ctx.cell = cells.load(CELL)
    names = ("swa.attn_ms", "swa.window_ms", "swa.full_ms",
             "swa.window_roofline", "swa.full_roofline")
    got = {name: reader(name)(ctx) for name in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    sizes = dict(n_head=28, n_kv=4, head_dim=128, window=4096)
    for kind, layers, took in ((SLIDING, 3, "swa.window_ms"),
                               (FULL, 1, "swa.full_ms")):
        work = flops_smallthinker.layer_attention_work(1, 8192, kind, **sizes)
        least = layers * sum(flops.roofline_seconds(*work[d], ctx.peak)[0]
                             for d in ("fwd", "bwd"))
        roofline = took.replace("_ms", "_roofline")
        assert got[roofline] == pytest.approx(100 * 1e3 * least / got[took])
    assert "flash kernels of sliding_attention layers" \
        in capsys.readouterr().err


def test_the_metrics_of_the_cell():
    """The cell reports the end-to-end pair, the shared per-layer
    metrics whose readers read it right, and its own one."""
    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"moe.preroute_ms", "moe.layer_ms", "moe.experts_ms",
            "moe.dispatch_ms", "moe.held_roofline", "swa.attn_ms",
            "swa.window_ms", "swa.full_ms", "swa.window_roofline",
            "swa.full_roofline", "kernel.flash_roofline",
            "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
            "kernel.flash_dkv_roofline", "kernel.flash_dq_roofline",
            "kernel.flash_share_pct", "kernel.flash_glue_ms",
            "model.mfu_pct", "model.step_device_ms", "model.head_ms",
            "model.fwd_ms", "model.bwd_ms", "model.update_ms",
            "device.peak_hbm_gb", "device.idle_pct", "device.unscoped_pct",
            "launch.compile_s", "launch.cache_misses"} <= mine
    assert not mine & {"moe.shared_ms", "moe.experts_roofline",
                       "mla.attn_ms", "conv.mixer_ms", "dsa.attn_ms",
                       "ssm.mixer_ms", "yoco.attn_ms", "loop.stack_ms",
                       "sync.collective_ms"}
    (own,) = [m for m in cell.bench["per_layer"]
              if m["name"] == "moe.preroute_ms"]
    assert own == {"name": "moe.preroute_ms", "unit": "ms", "better": "lower",
                   "source": "device_trace", "layer": "Experts",
                   "moves": "tokens_per_s", "workloads": [CELL]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", "moe.preroute_ms.py"))
    # Appended, last of their lists: twelve cells or more, one on four
    # chips; a quarter of twelve, rounded down, is three such slots.
    assert len(cell.bench["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) == 1
    assert len(cell.bench["configs"]) >= 10
    every = [w["name"] for w in cell.bench["workloads"]]
    assert every.index(CELL) == 11


def test_the_defects_own_rehearsal_pieces():
    """``smallthinker_routing._regrouped``: the permutation that makes
    head h read key/value head ``h % H_kv`` of its own number, and its
    inverse, at 28 heads over 4."""
    from benchmark import smallthinker_routing

    heads, n_kv = 28, 4
    wq = jnp.arange(heads, dtype=jnp.float32)[None, :, None] * jnp.ones(
        (2, heads, 3))
    tree = {"params": {"embed": jnp.zeros(1), "layer_0": {"attn": {
        "wq": wq, "wkv": jnp.zeros(1), "wo": wq[0][:, :, None]}}}}
    there = smallthinker_routing._regrouped(tree, n_kv)
    carried = np.asarray(there["params"]["layer_0"]["attn"]["wq"][0, :, 0])
    # The program's head h reads key/value head h // 7; it now carries
    # the weights of head ``carried[h]``, whose own number mod 4 is that.
    assert (carried % n_kv == np.arange(heads) // 7).all()
    assert sorted(carried) == list(range(heads))
    back = smallthinker_routing._regrouped(there, n_kv, inverse=True)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()),
                                     back, tree))
    assert set(smallthinker_routing.DEFECTS) == {
        "reference_fp8", "router_reads_ln2", "silu_gate", "rope_on_full",
        "window_half", "kv_head_mod", "no_norm", "top8"}


# ----------------------------------------------------------- rehearsal ----

@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_through_the_cpu_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    # The untraced window takes as many steps as fit its seconds, and
    # at the tiny size WHICH batches it ends on decides whether the loss
    # counts as falling (seed 3200000004 passed at 15, 24, 40, 62 and 96
    # steps): a rehearsal tests control flow, so it may try two more
    # lengths before it fails.
    for seconds in ("1", "1.4", "0.6"):
        run = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL,
             "--seed", "3200000004", "--seconds", seconds, "--trace", trace,
             "--rehearse-cpu"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
        if run.returncode == 0 or trace == "1":
            break
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert line["check"]["leaves"] == 39
    assert line["check"]["leaves_all_zero"] == 0
