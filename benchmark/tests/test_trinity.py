"""Trinity-Mini's share on the CPU at the builder's ``TINY`` widths
(hidden 64, 4 query heads over 2 key/value heads of 32, so q is twice
the hidden size wide as published; window 32 at S=128; a dense sliding
layer of 96, then a sliding and a full expert layer that hold 2 of the
16 experts of 32 they route over, 2 a token, one shared expert;
vocabulary 512): the program against ``benchmark/reference/afmoe.py``
on seeded weights and a NONZERO correction bias, block by block and
whole; the bias's update; recomputation; the eight shares against the
uncut layer; the counting of ``flops_afmoe.py`` by hand; the new scopes
through the scope view and their readers.

Tolerances. With the program computing in float32 the two are the same
mathematics in another order: logits to 1e-4 of their largest entry, the
loss to 1e-5, every gradient leaf to 1e-3 relative L2. That holds at
FREE routing too: no token of these seeds changes an expert (asserted).
"""

import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops, flops_afmoe, flops_glm, scope_view, traffic
from benchmark import trace_reduce as tr
from benchmark.layer_metrics import reader
from benchmark.reference import afmoe as reference
from benchmark.tests.test_olmoe import _leaf_distances, _rel
from benchmark.tests.test_reference import _compare
from benchmark.tests.test_scope_view import RECORDED_STEP, _ctx

CELL = "trinity-s8192-ep8-c1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
SLIDING, FULL = "sliding_attention", "full_attention"


def _biased(state, scale=0.02):
    """A correction bias that is not zero: large enough to change the
    choice of many tokens, small enough that the held experts still get
    rows."""
    leaves, treedef = jax.tree.flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(17), len(leaves))
    return treedef.unflatten([
        scale * jax.random.normal(k, b.shape, b.dtype)
        for k, b in zip(keys, leaves)])


def _seen(params):
    """Every norm scale moved off its initial value, each entry its own
    way, so that a norm left out or applied to the wrong thing shows."""
    leaves, treedef = jax.tree.flatten(params)
    return treedef.unflatten([
        p * (1 + 0.2 * jnp.cos(jnp.arange(p.shape[0]) + i))
        if p.ndim == 1 else p for i, p in enumerate(leaves)])


def _assembled(dtype, attention="flash"):
    cell = cells.load(CELL, tiny=True)
    cell.config.update(compute_dtype=dtype, attention=attention)
    asm = cells.assemble(cell, jax.devices()[:1])
    key = jax.random.PRNGKey(11)
    params, state = jax.jit(asm.model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **asm.model.pool_kwargs)
    return cell, asm.model, _seen(params), _biased(state), pool[0]


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
    assert reference.layer_kinds(config) == [SLIDING, SLIDING, FULL]
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
    assert counts.shape == (2, 16)
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
    # embed, lm_head, ln_f; 6 attention + 4 norm leaves a layer; 3 dense;
    # router + 3 held + 3 shared in each expert layer.
    assert len(distances) == 3 + 3 * 10 + 3 + 2 * 7
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
    assert got["leaves"] == 50 and got["leaves_all_zero"] == 0, got


def _tiny_cfg(**changes):
    cell = cells.load(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    cfg = cell.builder.module_of(cell.config, cell.traffic).cfg
    return cell.config, dataclasses.replace(cfg, **changes)


def _x(key, s=96, m=64):
    return jax.random.normal(jax.random.PRNGKey(key), (1, s, m))


@pytest.mark.parametrize("attention", ["dense", "flash"])
@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_the_attention_block(kind, attention):
    """One layer of each kind through the dense path and through the
    flash kernels: grouped heads of their own width, the norm per head,
    RoPE in the sliding kind only, the window, the gate."""
    from flax.core import meta
    from horovod_tpu.models.transformer import SelfAttention

    config, cfg = _tiny_cfg(attention=attention)
    sliding = kind == SLIDING
    layer = SelfAttention(cfg, 32 if sliding else None, sliding)
    x = _x(0)
    params = _seen(meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(1), x)))
    assert jax.tree.map(jnp.shape, params["params"]) == {
        "wq": (64, 4, 32), "wkv": (2, 64, 2, 32), "wgate": (64, 4, 32),
        "wo": (4, 32, 64), "q_norm": {"scale": (32,)},
        "k_norm": {"scale": (32,)}}
    got = jax.jit(layer.apply)(params, x)
    want = reference._attention(x, params["params"], config, kind)
    assert _rel(got, want) < 1e-5
    # Each mechanism is seen: another kind, another window, no gate,
    # the other head mapping all give something else.
    other = reference._attention(x, params["params"], config,
                                 FULL if sliding else SLIDING)
    assert _rel(other, want) > 1e-2
    if sliding:
        off = reference._attention(
            x, params["params"], dict(config, sliding_window=31), kind)
        assert _rel(off, want) > 1e-4
    # Query head h reads key/value head h // 2: with the two key/value
    # heads' V swapped, heads (0, 1) and (2, 3) trade what they read.
    swapped = dict(params["params"],
                   wkv=params["params"]["wkv"][:, :, ::-1])
    trade = jnp.array([2, 3, 0, 1])
    regrouped = dict(swapped, wq=params["params"]["wq"][:, trade],
                     wgate=params["params"]["wgate"][:, trade],
                     wo=params["params"]["wo"][trade])
    assert _rel(jax.jit(layer.apply)({"params": regrouped}, x), want) < 1e-5
    assert _rel(jax.jit(layer.apply)({"params": swapped}, x), want) > 1e-2


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_the_dense_block_has_four_norms(kind):
    """Layer 0's kind of block: a dense SwiGLU of ``intermediate_size``
    under the same ``Block``, two norms on the branches' outputs."""
    from flax.core import meta
    from horovod_tpu.models.transformer import Block

    config, cfg = _tiny_cfg()
    block = Block(cfg, cfg.block.dense_ff, kind)
    x = _x(2)
    params = _seen(meta.unbox(jax.jit(block.init)(jax.random.PRNGKey(3), x)))
    assert sorted(params["params"]) == [
        "attn", "ln1", "ln2", "mlp", "post_attn_norm", "post_mlp_norm"]
    assert params["params"]["mlp"]["wi"].shape == (64, 96)
    got = jax.jit(block.apply)(params, x)
    want, _, _ = reference._block(x, params["params"], None, None,
                                  config=config, kind=kind)
    assert _rel(got, want) < 1e-5
    # The two output norms are seen: without them the block is another.
    bare = dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, post_norms=False))
    assert _rel(jax.jit(Block(bare, cfg.block.dense_ff, kind).apply)(
        params, x), want) > 1e-2


def _expert_layer(cfg):
    """The expert layer as ``models.transformer.Block`` makes it."""
    from horovod_tpu.models.transformer import Mlp
    from horovod_tpu.parallel.moe import MoeMlp

    return MoeMlp(cfg, Mlp(cfg, cfg.block.shared_experts * cfg.d_ff,
                           parent=None))


def test_the_expert_block_chooses_by_score_plus_bias_and_gates_by_score():
    from flax.core import meta

    config, cfg = _tiny_cfg()
    layer = _expert_layer(cfg)
    x = _x(4)
    variables = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(5), x))
    params = variables["params"]
    assert params["wi"].shape == (2, 64, 32)       # the two HELD
    assert params["router"].shape == (64, 16)      # scores all 16
    # A bias that hands every token to experts 1 (held) and 9 (absent).
    bias = jnp.zeros(16).at[1].set(5.0).at[9].set(4.0)
    out, sown = jax.jit(lambda b: layer.apply(
        {"params": params, "moe_state": {"router_bias": b}}, x,
        mutable=["moe"]))(bias)
    assert (np.sort(np.asarray(sown["moe"]["experts"][0]), -1)
            == [1, 9]).all()
    # By hand: the two sigmoids WITHOUT the bias, renormalised, times
    # route_scale; only expert 1's term is computed here.
    y = x[0]
    s = jax.nn.sigmoid(y @ params["router"])
    g1 = 2.826 * s[:, 1] / (s[:, 1] + s[:, 9] + 1e-20)
    want = (g1[:, None] * reference._swiglu(
        y, params["wg"][1], params["wi"][1], params["wo"][1])
        + reference._shared(y, params))
    assert _rel(out[0], want) < 1e-5
    ref, chosen, _ = reference._experts(y, params, bias, config, None)
    assert _rel(out[0], ref) < 1e-5
    assert (np.sort(np.asarray(chosen), -1) == [1, 9]).all()


def test_the_eight_shares_and_one_shared_expert_are_the_whole_layer():
    """What ties the share to the model: chips 0..7 each hold two of the
    16 experts; their routed parts, plus the shared expert counted
    ONCE, add up to the uncut reference's layer."""
    from flax.core import meta

    config, cfg = _tiny_cfg()
    x = _x(6)
    y = x[0]
    whole = _expert_layer(dataclasses.replace(cfg, block=dataclasses.replace(
        cfg.block, experts_held=0)))
    p = meta.unbox(jax.jit(whole.init)(jax.random.PRNGKey(7), x))["params"]
    assert p["wi"].shape == (16, 64, 32)
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(8), (16,))
    state = {"router_bias": bias}
    want = reference.whole_layer(y, p, bias, config)
    shared = reference._shared(y, p)
    total, rows = jnp.zeros_like(y), 0
    for chip in range(8):
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
        total = total + (out[0] - shared)
        ref, _, _ = reference._experts(
            y, mine, bias, dict(config, first_expert_held=first), None)
        assert _rel(out[0], ref) < 1e-5
    assert rows == 96 * 2               # each pair computed exactly once
    assert _rel(total + shared, want) < 1e-5
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
            got - old, config["load_balance_coeff"] * np.sign(c.mean() - c),
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


# ------------------------------------------------- the defaults' case -----

def _tree_of(block, **cfg):
    from horovod_tpu import models

    config = models.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=8,
        max_seq_len=8, block=block, **cfg)
    from flax.core import meta

    tree = meta.unbox(jax.eval_shape(lambda: models.Transformer(config).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    return jax.tree.map(lambda a: a.shape, tree["params"]), config


def test_gpt2s_olmoes_and_glms_blocks_are_the_defaults_case():
    """Every new field's default is what the three older blocks are:
    their parameter trees are what they were (``wqkv``, two norms a
    block, no gate, no head norm of 128), and ``flash`` still agrees
    with ``dense`` for them."""
    from horovod_tpu import models

    spec = models.BlockSpec()
    assert (spec.head_dim, spec.n_kv_heads, spec.layer_types,
            spec.sliding_window, spec.rope_layers, spec.qk_norm_per_head,
            spec.attn_gate, spec.post_norms, spec.embed_scale) == (
        0, 0, (), 0, None, False, False, False, 1.0)
    gpt2, _ = _tree_of(models.BlockSpec())
    assert sorted(gpt2) == ["embed", "layer_0", "layer_1", "ln_f", "pos"]
    assert gpt2["layer_0"] == {
        "attn": {"wqkv": (3, 16, 2, 8), "wo": (2, 8, 16)},
        "ln1": {"scale": (16,), "bias": (16,)},
        "ln2": {"scale": (16,), "bias": (16,)},
        "mlp": {"wi": (16, 8), "wo": (8, 16)}}
    olmoe, _ = _tree_of(models.BlockSpec(
        norm="rmsnorm", ffn="swiglu", positions="rope", qk_norm=True,
        tied_head=False, num_experts=4, experts_per_token=2))
    assert olmoe["layer_1"]["attn"] == {
        "wqkv": (3, 16, 2, 8), "wo": (2, 8, 16),
        "q_norm": {"scale": (16,)}, "k_norm": {"scale": (16,)}}
    assert sorted(olmoe["layer_1"]) == ["attn", "ln1", "ln2", "moe"]
    glm = cells.load("glm47f-s8192-ep8-c1", tiny=True)
    tree, _ = jax.eval_shape(
        glm.builder.build(glm.config, glm.traffic).init,
        jax.random.PRNGKey(0))
    assert sorted(tree["params"]["layer_1"]) == ["attn", "ln1", "ln2", "moe"]
    assert sorted(tree["params"]["layer_1"]["attn"]) == [
        "kv_a", "kv_a_norm", "kv_b", "q_a", "q_a_norm", "q_b", "wo"]
    # The same outputs by both attention paths, for a block with every
    # new field set and for the default one.
    tokens = jnp.asarray(
        np.random.RandomState(4).randint(0, 64, (2, 8)), jnp.int32)
    for block in (models.BlockSpec(), models.BlockSpec(
            norm="rmsnorm", positions="rope", head_dim=16, n_kv_heads=1,
            layer_types=(SLIDING, FULL), sliding_window=3,
            rope_layers=(SLIDING,), qk_norm_per_head=True, attn_gate=True,
            post_norms=True, embed_scale=4.0)):
        _, cfg = _tree_of(block, dtype=jnp.float32)
        dense = models.Transformer(cfg)
        params = dense.init(jax.random.PRNGKey(0), tokens)
        flash = models.Transformer(dataclasses.replace(cfg,
                                                       attention="flash"))
        assert _rel(flash.apply(params, tokens),
                    dense.apply(params, tokens)) < 1e-4


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
@pytest.mark.parametrize("what", ["window", "grouped"])
def test_ring_and_ulysses_refuse_a_window_and_grouped_heads(attention, what):
    from horovod_tpu import models

    block = models.BlockSpec(
        layer_types=(SLIDING,), sliding_window=4) if what == "window" \
        else models.BlockSpec(n_kv_heads=1)
    model = models.Transformer(models.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=8,
        max_seq_len=8, attention=attention, seq_axis="seq", block=block))
    with pytest.raises(ValueError, match="no sliding window and no grouped"):
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))


def test_the_layer_pattern_has_to_fit_the_model():
    from horovod_tpu import models

    def init(block):
        model = models.Transformer(models.TransformerConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=8,
            max_seq_len=8, block=block))
        return jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    with pytest.raises(ValueError, match="names 1 layers"):
        init(models.BlockSpec(layer_types=(FULL,)))
    with pytest.raises(ValueError, match="sliding_window"):
        init(models.BlockSpec(layer_types=(SLIDING, FULL)))
    with pytest.raises(ValueError, match="Unknown attention layer type"):
        init(models.BlockSpec(layer_types=("local", FULL)))
    with pytest.raises(ValueError, match="latent attention has no sliding"):
        init(models.BlockSpec(
            attention_kind="latent", layer_types=(SLIDING, FULL),
            sliding_window=4, q_lora_rank=4, kv_lora_rank=4,
            qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=8))


def test_the_planner_counts_the_held_expert_leaves():
    import horovod_tpu as hvd

    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    plan = hvd.plan(params, batch=1, chips=1, **model.plan_kwargs)
    assert plan.workload.num_experts == 16
    # Four expert layers of three (16, 2048, 1024) float32 panels.
    assert plan.workload.expert_param_bytes == 4 * 3 * 16 * 2048 * 1024 * 4
    assert plan.workload.param_bytes == 705_473_792 * 4


def test_the_builder_refuses_what_it_has_no_one_answer_to():
    from benchmark.builders import afmoe as builder

    cell = cells.load(CELL)
    builder.block_spec(cell.config)
    for key, value in (("score_func", "softmax"), ("n_group", 8),
                       ("route_norm", False), ("mup_enabled", False),
                       ("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True),
                       ("first_k_dense_replace", 2)):
        with pytest.raises(ValueError, match=key):
            builder.block_spec(dict(cell.config, **{key: value}))
    with pytest.raises(ValueError, match="layer_types"):
        builder.block_spec(dict(cell.config, first_layer=30))


# --------------------------------------------------------- flops_afmoe ----

def _published():
    with open(os.path.join(CONFIGS, "trinity-mini.json")) as f:
        return json.load(f)


def test_the_configuration_file_keeps_every_published_width():
    config = _published()
    assert {k: config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "sliding_window", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok",
        "experts_routed_over", "route_scale", "num_shared_experts",
        "rope_theta", "rms_norm_eps", "load_balance_coeff",
        "global_attn_every_n_layers", "max_position_embeddings")} == {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 2048,
        "intermediate_size": 6144, "moe_intermediate_size": 1024,
        "num_experts_per_tok": 8, "experts_routed_over": 128,
        "route_scale": 2.826, "num_shared_experts": 1, "rope_theta": 10000,
        "rms_norm_eps": 1e-5, "load_balance_coeff": 0.001,
        "global_attn_every_n_layers": 4, "max_position_embeddings": 131072}
    # The published pattern whole: three sliding layers, then a full one,
    # eight times; this chip's five layers are published layers 1..5.
    assert config["layer_types"] == ([SLIDING] * 3 + [FULL]) * 8
    assert config["first_layer"] == 1
    assert reference.layer_kinds(config) == [
        SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (
        5, 1, 16, 25024)
    assert config["vocab_size"] * 8 == 200192
    assert sorted(config["reduced_from"]) == sorted(config["reduced"])
    for key in ("a", "b", "c", "d", "e"):
        assert any(name.startswith("(%s)" % key)
                   and "modeling_afmoe.py" in text
                   for name, text in config["assumed"].items()), key
    for key in ("assumed", "departures", "deployment", "check"):
        assert config[key]
    assert config["optimizer"]["learning_rate"] == 1e-5
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "s8192-swa-ep8-c1.json")) as f:
        mix = json.load(f)
    assert {k: mix[k] for k in (
        "seq_len", "per_chip_batch", "remat", "data", "require_axes",
        "warmup_steps", "trace_steps")} == {
        "seq_len": 8192, "per_chip_batch": 1, "remat": True,
        "data": {"kind": "markov_tokens", "successors": 4, "pool": 8},
        "require_axes": None, "warmup_steps": 3, "trace_steps": 6}


def test_the_builder_starts_the_output_norms_at_the_configurations_scale():
    """``post_norm_scale`` moves the two norms on the branches' outputs
    and nothing else: every matrix keeps the program's normal(0.02), the
    other norms their ones, the routers' bias its zeros."""
    cell = cells.load(CELL, tiny=True)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.jit(model.init)(jax.random.PRNGKey(5))
    scale = cell.config["post_norm_scale"]
    assert 0 < scale < 1
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = jax.tree_util.keystr(path)
        if "post_" in name:
            assert bool((leaf == jnp.float32(scale)).all()), name
        elif leaf.ndim == 1:
            assert bool((leaf == 1).all()), name
        else:
            assert abs(float(leaf.std()) / 0.02 - 1) < 0.15, name
    assert not any(bool(b.any()) for b in jax.tree.leaves(state))


def test_the_parameters_of_the_share_by_hand():
    """The program's own tree at the published widths (shapes only)."""
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa
    p = params["params"]
    attention = 3 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128 + 2 * 128
    assert attention == 27_263_232 == count(p["layer_3"]["attn"])
    assert p["layer_3"]["attn"]["wkv"].shape == (2, 2048, 4, 128)
    expert = 3 * 2048 * 1024
    assert expert == 6_291_456
    layer = attention + 16 * expert + expert + 2048 * 128 + 4 * 2048
    assert layer == 134_488_320 == count(p["layer_3"])
    dense = attention + 3 * 2048 * 6144 + 4 * 2048
    assert dense == 65_020_160 == count(p["layer_0"])
    ends = 2 * 25024 * 2048
    assert ends == 102_498_304 == count(p["embed"]) + count(p["lm_head"])
    assert count(params) == dense + 4 * layer + ends + 2048 == 705_473_792
    assert 11.28e9 < 16 * count(params) < 11.30e9
    assert jax.tree.map(jnp.shape, state) == {
        "layer_%d" % i: {"moe": {"router_bias": (128,)}}
        for i in (1, 2, 3, 4)}


def test_the_step_of_the_share_by_hand():
    from benchmark.builders import afmoe as builder

    config = _published()
    s, d, h, kv, hd, w = 8192, 2048, 32, 4, 128, 2048
    full_pairs = s * (s + 1) // 2
    kept = w * s - w * (w - 1) // 2
    assert flops_afmoe.window_pairs(s, None) == full_pairs == 33_558_528
    assert flops_afmoe.window_pairs(s, w) == kept == 14_681_088
    # Brute force at a small size, the first rows shorter than the window.
    assert flops_afmoe.window_pairs(10, 3) == sum(
        min(i + 1, 3) for i in range(10))
    assert flops_afmoe.window_pairs(10, 64) == 55
    projections = 2 * s * d * (3 * h * hd + 2 * kv * hd)
    assert flops_afmoe.attention_forward_ops(
        s, hidden=d, n_head=h, n_kv=kv, head_dim=hd, window=w) \
        == projections + h * 4 * kept * hd
    dense = 3 * 2 * s * d * 6144
    router = 2 * s * d * 128
    shared = 3 * 2 * s * d * 1024
    held = 3 * 2 * (s * 8 * 16 // 128) * d * 1024      # 8192 rows of 65,536
    assert flops_glm.held_rows(s, 8, 16, 128) == 8192
    head = 2 * s * d * 25024
    ops = builder.build(config, {"seq_len": s, "remat": True}).step_ops(1)
    attention = h * 4 * hd * (4 * kept + full_pairs)
    assert ops == 3 * (5 * projections + attention + dense
                       + 4 * (router + shared + held) + head)
    # 18.1 TFLOP: attention 4.5 (a full layer 1.65, the four sliding 2.9).
    assert 18.0e12 < ops < 18.2e12
    assert 3 * h * 4 * hd * full_pairs == pytest.approx(1.65e12, rel=5e-3)
    assert 3 * h * 4 * hd * 4 * kept == pytest.approx(2.89e12, rel=5e-3)
    # What attention REQUIRES of the step: four sliding layers' pairs and
    # one full layer's, two products forward and five backward, K/V
    # panels 4 heads wide.
    work = builder.build(config, {"seq_len": s, "remat": True}
                         ).attention_work(1)
    one = flops_afmoe.layer_attention_work(1, s, SLIDING, **{
        key: builder.sizes_of(config)[key]
        for key in ("n_head", "n_kv", "head_dim", "window")})
    whole = flops.attention_work(full_pairs, s, n_head=h, n_kv=kv, d=hd,
                                 d_v=hd)
    assert one["fwd"][0] == 2 * h * 2 * kept * hd
    assert one["bwd"][0] == 5 * h * 2 * kept * hd
    wide, narrow, row = h * s * hd * 2, kv * s * hd * 2, h * s * 4
    assert one["fwd"][1] == 2 * wide + 2 * narrow + row == whole["fwd"][1]
    assert one["bwd"][1] == 4 * wide + 4 * narrow + row
    assert work["fwd"][0] == attention
    assert work == flops.add_work(4 * [one] + [whole])
    # Both kinds are compute-bound, so the sum's roof is the roofs' sum.
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    for name in one:
        assert flops.roofline_seconds(*one[name], peak)[1] == "compute"
        assert flops.roofline_seconds(*work[name], peak)[0] \
            == pytest.approx(4 * flops.roofline_seconds(*one[name], peak)[0]
                             + flops.roofline_seconds(*whole[name], peak)[0])
    # ``moe.held_roofline`` reads these through the shared reader.
    sizes = builder.sizes_of(config)
    assert {k: sizes[k] for k in ("hidden", "expert_width", "k", "held",
                                  "routed")} == {
        "hidden": 2048, "expert_width": 1024, "k": 8, "held": 16,
        "routed": 128}
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] == 4


# -------------------------------------------------------------- scopes ----

STEP = "jit(hvd_bench_step)/"
FWD = STEP + "jvp(Transformer)/layer_2/"
BWD = STEP + "transpose(jvp(Transformer))/layer_2/"
REDONE = (STEP + "transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
          "rematted_computation/layer_2/")


@pytest.mark.parametrize("scope,phase,part", [
    (FWD + "attn/hvd_attn_gate/mul", "forward", "attn"),
    (BWD + "attn/hvd_attn_gate/logistic", "backward", "attn"),
    (REDONE + "attn/hvd_attn_gate/mul", "backward", "attn"),
    (FWD + "attn/q_norm/mul", "forward", "norm"),
    (FWD + "post_attn_norm/mul", "forward", "norm"),
    (BWD + "post_mlp_norm/reduce_sum", "backward", "norm"),
    (REDONE + "post_mlp_norm/mul", "backward", "norm"),
    (FWD + "attn/bsm,mhd->bshd/dot_general", "forward", "attn"),
])
def test_phase_and_part_of_the_new_scopes(scope, phase, part):
    assert scope_view.classify(scope, "") == (phase, part)


def test_the_scope_constants_are_what_the_layers_set():
    from benchmark import swa_view
    from horovod_tpu.jax import introspect
    from horovod_tpu.models.transformer import _M_ATTN_LAYERS

    assert introspect.SCOPE_ATTN_GATE == "hvd_attn_gate"
    assert (swa_view.SLIDING, swa_view.FULL) == (SLIDING, FULL)
    cell, model, params, state, tokens = _assembled("float32")
    text = jax.jit(jax.grad(
        lambda p: model.loss(p, state, tokens)[0])).lower(params).as_text(
            debug_info=True)
    for name in ("attn/hvd_attn_gate", "attn/q_norm", "attn/k_norm",
                 "attn/hvd_flash/hvd_flash_fwd", "post_attn_norm",
                 "post_mlp_norm", "moe/hvd_moe_shared/shared"):
        assert "layer_1/" + name in text, name
    # RoPE in the sliding layers (0, 1), none in the full one (2).
    assert "layer_1/attn/rope" in text and "layer_0/attn/rope" in text
    assert "layer_2/attn/rope" not in text
    assert "layer_0/mlp" in text and "layer_0/moe" not in text
    # Counted at trace time: two sliding layers and a full one.
    before = {k: _M_ATTN_LAYERS.labels(kind=k).get()
              for k in (SLIDING, FULL)}
    jax.make_jaxpr(lambda p: model.loss_and_stats(p, state, tokens)[0])(
        params)
    # (a block under recomputation is traced once more for its backward)
    assert _M_ATTN_LAYERS.labels(kind=SLIDING).get() - before[SLIDING] \
        == 2 * (_M_ATTN_LAYERS.labels(kind=FULL).get() - before[FULL]) > 0


def _swa_step():
    """The recorded step as layers of a gated model would name it: layer
    0 (sliding) holds the forward kernel and the gate, layer 2 (the full
    one of this chip's five) the two backward kernels."""
    step = RECORDED_STEP.replace(
        "layer_0/attn/transpose", "layer_0/attn/hvd_attn_gate/mul"
    ).replace("layer_0/attn/hvd_flash/hvd_flash_dkv",
              "layer_2/attn/hvd_flash/hvd_flash_dkv"
    ).replace("layer_0/attn/hvd_flash/hvd_flash_dq",
              "layer_2/attn/hvd_flash/hvd_flash_dq")
    assert step.count("hvd_attn_gate") == 1 and step.count("layer_2/") == 2
    return step


def test_the_new_readers_on_the_recorded_trace(capsys):
    names = ("swa.attn_ms", "swa.window_ms", "swa.full_ms",
             "swa.window_roofline", "swa.full_roofline")
    ctx = _ctx(_swa_step())
    ctx.cell = cells.load(CELL)
    got = {name: reader(name)(ctx) for name in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    table = scope_view.table(ctx)
    # The attention module: the gate, the three kernels and their glue.
    assert got["swa.attn_ms"] == pytest.approx(sum(
        scope_view.part_ms(ctx, part)
        for part in ("attn", "flash_kernel", "flash_glue")))
    # The kernels by the layer of their scope: the forward in the
    # sliding layer, dK/dV and dQ in the full one.
    took = {k: 1e3 * seconds / ctx.n_steps for k, (seconds, _)
            in tr.kernel_seconds(ctx.win0.ops).items()}
    assert got["swa.window_ms"] == pytest.approx(took["fwd"])
    assert got["swa.full_ms"] == pytest.approx(took["dkv"] + took["dq"])
    # Against what the configuration's layers of each kind REQUIRE,
    # forward AND backward, whatever calls the trace holds: four sliding
    # layers, one full.
    sizes = dict(n_head=32, n_kv=4, head_dim=128, window=2048)
    sliding = flops_afmoe.layer_attention_work(1, 8192, SLIDING, **sizes)
    whole = flops_afmoe.layer_attention_work(1, 8192, FULL, **sizes)
    assert got["swa.window_roofline"] == pytest.approx(
        100 * 1e3 * 4 * sum(flops.roofline_seconds(*sliding[d], ctx.peak)[0]
                            for d in ("fwd", "bwd")) / got["swa.window_ms"])
    assert got["swa.full_roofline"] == pytest.approx(
        100 * 1e3 * sum(flops.roofline_seconds(*whole[d], ctx.peak)[0]
                        for d in ("fwd", "bwd")) / got["swa.full_ms"])
    # One backward kernel in the place of two, of equal time: the same.
    fused = _ctx(_swa_step().replace("hvd_flash_dkv", "hvd_flash_bwd"
                                     ).replace("hvd_flash_dq", "hvd_flash_bwd"),
                 names={"jvp__.1": "hvd_flash_fwd.1",
                        "transpose_jvp___.2": "hvd_flash_bwd.2",
                        "transpose_jvp___.3": "hvd_flash_bwd.3"})
    fused.cell = ctx.cell
    assert {name: reader(name)(fused) for name in names} \
        == {name: pytest.approx(v) for name, v in got.items()}
    assert "flash kernels of sliding_attention layers" \
        in capsys.readouterr().err
    # A step with no attention module at all, a cell without
    # ``layer_types``, a ctx a reader cannot use: nothing, and no exception.
    plain = _ctx(RECORDED_STEP.replace("/attn/", "/other/"))
    plain.cell = cells.load(CELL)
    glm = _ctx(_swa_step())
    glm.cell = cells.load("glm47f-s8192-ep8-c1")
    broken = _ctx("HloModule jit_small_step")
    broken.win0 = None
    for name in names:
        assert reader(name)(plain) is None, name
        assert reader(name)(glm) is None, name
        assert reader(name)(broken) is None, name


def test_the_metrics_of_the_cell():
    """The cell reports the end-to-end pair, the shared per-layer
    metrics whose readers read it right, and its own five; GLM's
    latent-attention metrics and OLMoE's all-experts roofline are not
    its."""
    cell = cells.load(CELL)
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"swa.attn_ms", "swa.window_ms", "swa.full_ms",
            "swa.window_roofline", "swa.full_roofline", "moe.held_roofline", "moe.shared_ms", "kernel.flash_roofline",
            "kernel.flash_fwd_roofline", "model.mfu_pct",
            "device.unscoped_pct", "launch.compile_s"} <= mine
    assert not mine & {"mla.attn_ms", "mla.latent_ms",
                       "moe.experts_roofline", "sync.collective_ms"}
    swa = [m for m in cell.bench["per_layer"] if m["name"].startswith("swa.")]
    assert len(swa) == 5      # the gate fuses away: no ``swa.gate_ms``
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "Attention pattern" for m in swa)


def test_the_defects_own_rehearsal_pieces():
    """``trinity_routing._regrouped``: the permutation that makes head h
    read key/value head ``h % H_kv`` of its own number, and its
    inverse."""
    from benchmark import trinity_routing

    heads, n_kv = 32, 4
    wq = jnp.arange(heads, dtype=jnp.float32)[None, :, None] * jnp.ones(
        (2, heads, 3))
    tree = {"params": {"embed": jnp.zeros(1), "layer_0": {"attn": {
        "wq": wq, "wgate": wq, "wo": wq[0][:, :, None]}}}}
    there = trinity_routing._regrouped(tree, n_kv)
    carried = np.asarray(there["params"]["layer_0"]["attn"]["wq"][0, :, 0])
    # The program's head h reads key/value head h // 8; it now carries
    # the weights of head ``carried[h]``, whose own number mod 4 is that.
    assert (carried % n_kv == np.arange(heads) // (heads // n_kv)).all()
    assert sorted(carried) == list(range(heads))
    back = trinity_routing._regrouped(there, n_kv, inverse=True)
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()),
                                     back, tree))


# ----------------------------------------------------------- rehearsal ----

@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_through_the_cpu_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3200000003", "--seconds", "1", "--trace", trace, "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert line["check"]["leaves"] == 50
    assert line["check"]["leaves_all_zero"] == 0
