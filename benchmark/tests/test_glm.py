"""GLM-4.7-Flash's share on the CPU at the builder's ``TINY`` widths
(hidden 64, 4 heads of 12 + 4 = 16, latents 24 and 16, a dense layer of
96, then 2 expert layers that hold 2 of the 16 experts of 32 they route
over, 2 a token, one shared expert, vocabulary 512, S=128): the program
against ``benchmark/reference/glm4_moe_lite.py`` on seeded weights and
a NONZERO correction bias, block by block and whole; the bias's update;
recomputation; the eight shares against the uncut layer; the counting of
``flops_glm.py`` by hand; the new scopes through the scope view and
their readers.

Tolerances. With the program computing in float32 the two are the same
mathematics in another order: logits to 1e-4 of their largest entry, the
loss to 1e-5, every gradient leaf to 1e-3 relative L2. That holds at
FREE routing too: no token of these seeds changes an expert (asserted).
In bf16 the comparison is made at FORCED routing, inside
``gpt2-medium.json``'s bounds for the chip.
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
from benchmark import flops, flops_glm, scope_view, traffic
from benchmark.layer_metrics import reader
from benchmark.reference import glm4_moe_lite as reference
from benchmark.tests.test_olmoe import _leaf_distances, _rel
from benchmark.tests.test_reference import _compare
from benchmark.tests.test_scope_view import RECORDED_STEP, _ctx

CELL = "glm47f-s8192-ep8-c1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")


def _biased(state, scale=0.02):
    """A correction bias that is not zero: large enough to change the
    choice of many tokens (scores differ by ~0.01 at these weights),
    small enough that the held experts still get rows."""
    leaves, treedef = jax.tree.flatten(state)
    keys = jax.random.split(jax.random.PRNGKey(17), len(leaves))
    return treedef.unflatten([
        scale * jax.random.normal(k, b.shape, b.dtype)
        for k, b in zip(keys, leaves)])


def _assembled(dtype):
    cell = cells.load(CELL, tiny=True)
    cell.config["compute_dtype"] = dtype
    asm = cells.assemble(cell, jax.devices()[:1])
    key = jax.random.PRNGKey(11)
    params, state = jax.jit(asm.model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **asm.model.pool_kwargs)
    return cell, asm.model, params, _biased(state), pool[0]


def _random_assignments(key, config, tokens):
    """Per layer (None for the dense one), k distinct experts a token,
    nothing to do with any router."""
    n = config["num_hidden_layers"]
    noise = jax.random.uniform(
        key, (n, tokens, config["experts_routed_over"]))
    picks = jnp.argsort(noise, -1)[..., :config["num_experts_per_tok"]]
    return [None if i < config["first_k_dense_replace"]
            else picks[i].astype(jnp.int32) for i in range(n)]


# ------------------------------------------------ program = reference -----

@pytest.mark.parametrize("routing", ["free", "forced"])
def test_float32_program_is_the_reference(routing):
    from horovod_tpu.parallel import moe

    cell, model, params, state, tokens = _assembled("float32")
    config = cell.config
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
    # Two expert layers; nothing dropped: the counts over ALL 16 experts
    # are all T x k pairs, of which the held two got their part.
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
    # embed, lm_head, ln_f; 7 attention + 2 norm leaves a layer; 3 dense;
    # router + 3 held + 3 shared in each expert layer.
    assert len(distances) == 3 + 3 * 9 + 3 + 2 * 7
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
    chosen = [None] * config["first_k_dense_replace"] + list(chosen)
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
    assert got["leaves"] == 47 and got["leaves_all_zero"] == 0, got


def _tiny_cfg():
    cell = cells.load(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    return cell.config, cell.builder.module_of(cell.config,
                                               cell.traffic).cfg


def _x(key, s=96, m=64):
    return jax.random.normal(jax.random.PRNGKey(key), (1, s, m))


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_the_latent_attention_block(attention):
    from flax.core import meta
    from horovod_tpu.models.transformer import LatentAttention

    config, cfg = _tiny_cfg()
    layer = LatentAttention(dataclasses.replace(cfg, attention=attention))
    x = _x(0)
    params = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    assert {k: jax.tree.map(jnp.shape, v)
            for k, v in params["params"].items()} == {
        "q_a": (64, 24), "q_a_norm": {"scale": (24,)}, "q_b": (24, 4, 16),
        "kv_a": (64, 16 + 4), "kv_a_norm": {"scale": (16,)},
        "kv_b": (16, 4, 12 + 16), "wo": (4, 16, 64)}
    # Norm scales away from one, so that they are seen.
    for name in ("q_a_norm", "kv_a_norm"):
        scale = params["params"][name]["scale"]
        params["params"][name]["scale"] = scale + 0.1 * jnp.arange(
            scale.shape[0]) / scale.shape[0]
    got = jax.jit(layer.apply)(params, x)
    want = reference._attention(x, params["params"], config)
    assert _rel(got, want) < 1e-5
    # k_pe is ONE rotary key part for all heads: with the plain parts of
    # q and k silenced and v = 1, the heads' scores are all the same
    # function of q's rotary part, which differs by head; with q's made
    # equal across heads too, every head computes the same thing.
    p = jax.tree.map(lambda a: a, params["params"])
    p["q_b"] = p["q_b"].at[..., :12].set(0.0)
    p["q_b"] = jnp.broadcast_to(p["q_b"][:, :1], p["q_b"].shape)
    p["kv_b"] = p["kv_b"].at[..., 12:].set(
        jnp.broadcast_to(p["kv_b"][:, :1, 12:], (16, 4, 16)))
    per_head = jax.jit(lambda pp: layer.apply(
        {"params": dict(pp, wo=jnp.eye(64).reshape(4, 16, 64))}, x))(
            p).reshape(1, 96, 4, 16)
    for h in range(1, 4):
        np.testing.assert_allclose(per_head[:, :, h], per_head[:, :, 0],
                                   atol=1e-6)


def test_the_dense_block():
    """Layer 0: a dense SwiGLU of ``intermediate_size`` under the same
    ``Block``, the reference's whole block."""
    from flax.core import meta
    from horovod_tpu.models.transformer import Block

    config, cfg = _tiny_cfg()
    block = Block(cfg, cfg.block.dense_ff)
    x = _x(2)
    params = meta.unbox(jax.jit(block.init)(jax.random.PRNGKey(3), x))
    assert sorted(params["params"]) == ["attn", "ln1", "ln2", "mlp"]
    assert params["params"]["mlp"]["wi"].shape == (64, 96)
    got = jax.jit(block.apply)(params, x)
    want, _, _ = reference._block(x, params["params"], None, None,
                                  config=config)
    assert _rel(got, want) < 1e-5


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
    assert variables["moe_state"]["router_bias"].shape == (16,)
    assert not variables["moe_state"]["router_bias"].any()
    params = variables["params"]
    assert params["wi"].shape == (2, 64, 32)       # the two HELD
    assert params["router"].shape == (64, 16)      # scores all 16
    assert params["shared"]["wi"].shape == (64, 32)
    # A bias that hands every token to experts 1 (held) and 9 (absent),
    # whatever their scores.
    bias = jnp.zeros(16).at[1].set(5.0).at[9].set(4.0)
    out, sown = jax.jit(lambda b: layer.apply(
        {"params": params, "moe_state": {"router_bias": b}}, x,
        mutable=["moe"]))(bias)
    experts = np.asarray(sown["moe"]["experts"][0])
    assert (np.sort(experts, -1) == [1, 9]).all()
    assert int(sown["moe"]["rows_held"][0]) == 96
    assert sown["moe"]["tokens_per_expert"][0].tolist() == [
        96 if e in (1, 9) else 0 for e in range(16)]
    # By hand: the gates are the two sigmoids WITHOUT the bias,
    # renormalised, times 1.8; only expert 1's term is computed here.
    y = x[0]
    s = jax.nn.sigmoid(y @ params["router"])
    g1 = 1.8 * s[:, 1] / (s[:, 1] + s[:, 9] + 1e-20)
    want = (g1[:, None] * reference._swiglu(
        y, params["wg"][1], params["wi"][1], params["wo"][1])
        + reference._swiglu(y, params["shared"]["wg"],
                            params["shared"]["wi"], params["shared"]["wo"]))
    assert _rel(out[0], want) < 1e-5
    ref, chosen, counts = reference._experts(y, params, bias, config, None)
    assert _rel(out[0], ref) < 1e-5
    assert (np.sort(np.asarray(chosen), -1) == [1, 9]).all()
    # No held expert chosen at all: every row is a dead row and the
    # layer is the shared expert alone.
    away = jnp.zeros(16).at[8].set(5.0).at[9].set(4.0)
    out, sown = jax.jit(lambda b: layer.apply(
        {"params": params, "moe_state": {"router_bias": b}}, x,
        mutable=["moe"]))(away)
    assert int(sown["moe"]["rows_held"][0]) == 0
    assert _rel(out[0], reference._swiglu(
        y, params["shared"]["wg"], params["shared"]["wi"],
        params["shared"]["wo"])) < 1e-5
    # The bias takes no gradient; the router does, through the gates.
    g_bias, g_router = jax.jit(jax.grad(
        lambda b, r: jnp.sum(layer.apply(
            {"params": dict(params, router=r),
             "moe_state": {"router_bias": b}}, x, mutable=["moe"])[0] ** 2),
        argnums=(0, 1)))(bias, params["router"])
    assert not np.asarray(g_bias).any() and np.asarray(g_router).any()
    assert np.isfinite(np.asarray(g_router)).all()


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
    full = meta.unbox(jax.jit(whole.init)(jax.random.PRNGKey(7), x))
    p = full["params"]
    assert p["wi"].shape == (16, 64, 32)
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(8), (16,))
    state = {"router_bias": bias}
    want = reference.whole_layer(y, p["router"], bias, p["wg"], p["wi"],
                                 p["wo"], p["shared"], config)
    shared = reference._swiglu(y, p["shared"]["wg"], p["shared"]["wi"],
                               p["shared"]["wo"])
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
        # Every chip's router makes the same choice over all 16.
        assert int(sown["moe"]["tokens_per_expert"][0].sum()) == 96 * 2
        rows += int(sown["moe"]["rows_held"][0])
        total = total + (out[0] - shared)
        # and this chip's share is the reference's share
        ref, _, _ = reference._experts(
            y, mine, bias, dict(config, first_expert_held=first), None)
        assert _rel(out[0], ref) < 1e-5
    assert rows == 96 * 2               # each pair computed exactly once
    assert _rel(total + shared, want) < 1e-5
    # The uncut program layer is the same thing.
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
        # T x k = 508 pairs over 16 experts: the mean is no whole number,
        # so every expert moves, by exactly the rate, toward the mean.
        rate = config["router_bias_update_rate"]
        np.testing.assert_allclose(
            got - old, rate * np.sign(c.mean() - c), atol=1e-7)
        assert (np.abs(got - old) > 0.5 * rate).all()


def test_two_replicas_carry_the_bias_of_the_whole_batch():
    """Two chips, one sequence each: the rule is over the step's whole
    batch, so each replica's new bias is the one chip's on both
    sequences, not the rule on its own sequence's counts."""
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.parallel.mesh import shard_map_compat

    cell, model, params, state, tokens = _assembled("float32")
    whole = jax.jit(lambda p, s, x: model.loss(p, s, x)[1])(
        params, state, tokens)

    def replica(params, state, tokens):
        new = model.loss(params, state, tokens)[1]
        return jax.tree.map(lambda b: b[None], new)

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    both = jax.jit(shard_map_compat(
        replica, mesh=mesh, in_specs=(P(), P(), P("data")),
        out_specs=P("data"), check_vma=False))(params, state, tokens)
    alone = [jax.jit(lambda p, s, x: model.loss(p, s, x)[1])(
        params, state, tokens[i:i + 1]) for i in range(2)]
    differs = False
    for name in sorted(whole):
        want = np.asarray(whole[name]["moe"]["router_bias"])
        got = np.asarray(both[name]["moe"]["router_bias"])
        assert got.shape == (2,) + want.shape
        for i in range(2):
            np.testing.assert_allclose(got[i], want, rtol=0, atol=1e-7)
            differs |= bool(np.any(np.abs(np.asarray(
                alone[i][name]["moe"]["router_bias"]) - want) > 1e-7))
    assert differs      # or the sum over the replicas decided nothing


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


def test_olmoes_and_gpt2s_layers_are_the_case_of_all_experts_held():
    """The defaults: every expert held, the softmax router, no shared
    expert, plain heads. Their parameter trees do not change."""
    from horovod_tpu import models

    spec = models.BlockSpec()
    assert (spec.attention_kind, spec.router, spec.experts_held,
            spec.first_expert_held, spec.shared_experts, spec.norm_topk,
            spec.routed_scale, spec.first_dense_layers) == (
        "heads", "softmax", 0, 0, 0, False, 1.0, 0)
    cfg = models.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=2, d_ff=8,
        max_seq_len=8, block=models.BlockSpec(
            ffn="swiglu", num_experts=4, experts_per_token=2))
    tree = jax.eval_shape(lambda: models.Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert sorted(tree) == ["params"]            # no state of any kind
    assert sorted(tree["params"]["layer_1"]["moe"]) == [
        "router", "wg", "wi", "wo"]
    assert tree["params"]["layer_1"]["moe"]["wi"].value.shape == (4, 16, 8)


def test_the_planner_counts_the_held_expert_leaves():
    import horovod_tpu as hvd

    cell, model, params, _, _ = _assembled("float32")
    plan = hvd.plan(jax.eval_shape(lambda: params), batch=1, chips=1,
                    **model.plan_kwargs)
    assert plan.workload.num_experts == 2
    # Two expert layers of three (2, 64, 32) float32 panels.
    assert plan.workload.expert_param_bytes == 2 * 3 * 2 * 64 * 32 * 4
    assert plan.workload.param_bytes == sum(
        a.size * 4 for a in jax.tree.leaves(params))


def test_the_builder_refuses_what_it_has_no_one_answer_to():
    from benchmark.builders import glm4_moe_lite as builder

    cell = cells.load(CELL)
    builder.block_spec(cell.config)
    for key, value in (("topk_method", "greedy"), ("n_group", 8),
                       ("norm_topk_prob", False),
                       ("num_nextn_predict_layers", 1),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            builder.block_spec(dict(cell.config, **{key: value}))


# ----------------------------------------------------------- flops_glm ----

def _published():
    with open(os.path.join(CONFIGS, "glm-4.7-flash.json")) as f:
        return json.load(f)


def test_the_configuration_file_keeps_every_published_width():
    config = _published()
    assert {k: config[k] for k in (
        "hidden_size", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "experts_routed_over", "routed_scaling_factor", "n_shared_experts",
        "first_k_dense_replace", "rope_theta")} == {
        "hidden_size": 2048, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "num_attention_heads": 20, "intermediate_size": 10240,
        "moe_intermediate_size": 1536, "num_experts_per_tok": 4,
        "experts_routed_over": 64, "routed_scaling_factor": 1.8,
        "n_shared_experts": 1, "first_k_dense_replace": 1,
        "rope_theta": 1000000}
    assert config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
        5, 8, 19360, 0)
    assert sorted(config["reduced_from"]) == sorted(config["reduced"])
    for key in ("assumed", "departures", "deployment", "check"):
        assert config[key]
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "s8192-ep8-c1.json")) as f:
        mix = json.load(f)
    assert {k: mix[k] for k in (
        "seq_len", "per_chip_batch", "remat", "data", "require_axes",
        "warmup_steps", "trace_steps")} == {
        "seq_len": 8192, "per_chip_batch": 1, "remat": True,
        "data": {"kind": "markov_tokens", "successors": 4, "pool": 8},
        "require_axes": None, "warmup_steps": 3, "trace_steps": 6}


@pytest.mark.parametrize("std", [1.0, 0.02])
def test_the_builder_draws_the_embedding_at_the_configurations_width(std):
    """``embedding_std`` moves the input embedding and nothing else:
    every other matrix keeps the program's normal(0.02), the norms
    their ones, the routers' bias its zeros."""
    cell = cells.load(CELL, tiny=True)
    cell.config["embedding_std"] = std
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.jit(model.init)(jax.random.PRNGKey(5))
    leaves = {jax.tree_util.keystr(path): leaf for path, leaf
              in jax.tree_util.tree_leaves_with_path(params)}
    embed = leaves.pop("['params']['embed']")
    assert abs(float(embed.std()) / std - 1) < 0.05
    assert abs(float(embed.mean())) < 0.02 * std
    for name, leaf in leaves.items():
        if leaf.ndim == 1:
            assert bool((leaf == 1).all()), name
        else:
            assert abs(float(leaf.std()) / 0.02 - 1) < 0.15, name
    assert not any(bool(b.any()) for b in jax.tree.leaves(state))


def test_the_configuration_says_what_keeps_the_routing_at_rest():
    """The two values the cell's steadiness rests on are in the file,
    each with its reason under ``assumed``."""
    config = _published()
    assert config["embedding_std"] == 1.0
    assert config["optimizer"] == {
        "name": "adamw", "b1": 0.9, "b2": 0.95, "weight_decay": 0.1,
        "learning_rate": 1e-5, "warmup_steps": 20}
    assert "1e-5" in config["assumed"]["optimizer"]
    assert "1.0" in config["assumed"]["embedding_std"]
    assert any("normal(1.0)" in line for line in config["departures"])


def test_the_parameters_of_the_share_by_hand():
    """The program's own tree at the published widths (shapes only)."""
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))  # noqa
    p = params["params"]
    attention = (2048 * 768 + 768 * 20 * 256 + 2048 * (512 + 64)
                 + 512 * 20 * (192 + 256) + 20 * 256 * 2048 + 768 + 512)
    assert attention == 21_759_232 == count(p["layer_3"]["attn"])
    expert = 3 * 2048 * 1536
    assert expert == 9_437_184
    layer = attention + 8 * expert + expert + 2048 * 64 + 2 * 2048
    assert layer == 106_829_056 == count(p["layer_3"])
    dense = attention + 3 * 2048 * 10240 + 2 * 2048
    assert dense == 84_677_888 == count(p["layer_0"])
    ends = 2 * 19360 * 2048
    assert ends == 79_298_560 == count(p["embed"]) + count(p["lm_head"])
    assert count(params) == dense + 4 * layer + ends + 2048 == 591_294_720
    assert 9.4e9 < 16 * count(params) < 9.5e9
    assert jax.tree.map(jnp.shape, state) == {
        "layer_%d" % i: {"moe": {"router_bias": (64,)}} for i in (1, 2, 3, 4)}


def test_the_step_of_the_share_by_hand():
    from benchmark.builders import glm4_moe_lite as builder

    config = _published()
    s, d, h = 8192, 2048, 20
    pairs = s * (s + 1) // 2
    projections = 2 * s * (d * 768 + 768 * h * 256 + d * 576
                           + 512 * h * 448 + h * 256 * d)
    attention = h * 2 * pairs * (256 + 256)
    assert flops_glm.latent_attention_forward_ops(
        s, hidden=d, n_head=h, q_rank=768, kv_rank=512, nope=192, rope=64,
        v_dim=256) == projections + attention
    dense = 3 * 2 * s * d * 10240
    router = 2 * s * d * 64
    shared = 3 * 2 * s * d * 1536
    held = 3 * 2 * (s * 4 * 8 // 64) * d * 1536       # 4096 rows, not 32,768
    assert flops_glm.held_rows(s, 4, 8, 64) == 4096
    assert flops_glm.expert_layer_forward_ops(
        s, hidden=d, expert_width=1536, k=4, held=8, routed=64,
        shared=1) == router + shared + held
    head = 2 * s * d * 19360
    ops = flops_glm.glm_step_ops(
        1, s, vocab=config["vocab_size"],
        n_layer=config["num_hidden_layers"], **builder.sizes_of(config))
    assert ops == 3 * (5 * (projections + attention) + dense
                       + 4 * (router + shared + held) + head)
    # 23.5 TFLOP: attention 44%, the head 8%, the held experts 4%.
    assert 23.4e12 < ops < 23.6e12
    assert 0.43 < 3 * 5 * attention / ops < 0.45
    assert 0.08 < 3 * head / ops < 0.09
    assert 0.035 < 3 * 4 * held / ops < 0.045
    # What attention REQUIRES of the step: ONE forward a layer, recomputed
    # or not (two products over the causal pairs), and a backward of five.
    model = builder.build(config, {"seq_len": s, "remat": True})
    work = model.attention_work(1)
    assert work["fwd"][0] == 5 * attention
    assert work["bwd"][0] == 5 * h * 2 * pairs * 5 * 256
    once = builder.build(config, {"seq_len": s, "remat": False})
    assert once.attention_work(1) == work
    # The fossil ``tests/test_flash_tpu_compile.py`` holds: read by no
    # metric, the declaration PR 31 made stale.
    assert model.kernels(1) == {"fwd": (10,), "dkv": (5,), "dq": (5,)}
    assert once.kernels(1)["fwd"] == (5,)


def test_held_expert_matmul_work_by_hand():
    ops, nbytes = flops_glm.held_expert_matmul_work(
        8192, hidden=2048, expert_width=1536, k=4, held=8, routed=64)
    assert ops == 9 * 2 * 4096 * 2048 * 1536
    panels = 3 * 8 * 2048 * 1536 * 4           # float32, 302 MB
    rows = 4096 * 2048 * 2                     # bf16, 16.8 MB
    assert nbytes == 3 * panels + 5 * rows
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, roof = flops.roofline_seconds(ops, nbytes, peak)
    # 1.18 ms of matmul against 1.21 ms of weight and row traffic.
    assert roof == "memory" and 1.19e-3 < least < 1.23e-3
    assert ops / peak["bf16_flops"] == pytest.approx(1.177e-3, rel=1e-2)


# -------------------------------------------------------------- scopes ----

STEP = "jit(hvd_bench_step)/"
FWD = STEP + "jvp(Transformer)/layer_2/"
BWD = STEP + "transpose(jvp(Transformer))/layer_2/"
# Under ``nn.remat(Block)`` (copied from the step compiled for a v5e): the
# recomputed forward of a block, and its backward.
REDONE = (STEP + "transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
          "rematted_computation/layer_2/",
          STEP + "transpose(jvp(Transformer))/jvp(Transformer)/checkpoint/"
          "layer_2/")


@pytest.mark.parametrize("scope,phase,part", [
    (FWD + "attn/hvd_mla_latent/dot_general", "forward", "attn"),
    (FWD + "attn/hvd_mla_latent/bsr,rhd->bshd/dot_general", "forward",
     "attn"),
    (FWD + "attn/hvd_mla_latent/q_a_norm/mul", "forward", "norm"),
    (BWD + "attn/hvd_mla_latent/kv_a_norm/reduce_sum", "backward", "norm"),
    (BWD + "attn/hvd_mla_latent/concatenate", "backward", "attn"),
    (FWD + "attn/rope/mul", "forward", "attn"),
    (FWD + "moe/hvd_moe_shared/shared/dot_general", "forward", "mlp"),
    (BWD + "moe/hvd_moe_shared/shared/dot_general", "backward", "mlp"),
    (FWD + "moe/hvd_moe_experts/ragged_dot_general", "forward", "mlp"),
    (FWD.replace("layer_2", "layer_0") + "mlp/dot_general", "forward",
     "mlp"),
    (REDONE[0] + "attn/hvd_mla_latent/dot_general", "backward", "attn"),
    (REDONE[0] + "moe/hvd_moe_shared/shared/dot_general", "backward",
     "mlp"),
    (REDONE[1] + "attn/hvd_flash/reduce_sum", "backward", "flash_glue"),
    (REDONE[1] + "ln1/mul", "backward", "norm"),
])
def test_phase_and_part_of_the_new_scopes(scope, phase, part):
    assert scope_view.classify(scope, "") == (phase, part)


def test_the_scope_constants_are_what_the_layers_set():
    from benchmark import mla_view
    from horovod_tpu.jax import introspect

    assert (introspect.SCOPE_MLA_LATENT, introspect.SCOPE_MOE_SHARED) == (
        "hvd_mla_latent", "hvd_moe_shared") == (
        mla_view.LATENT, reader("moe.shared_ms").__globals__["SHARED"])
    cell, model, params, state, tokens = _assembled("float32")
    text = jax.jit(jax.grad(
        lambda p: model.loss(p, state, tokens)[0])).lower(params).as_text(
            debug_info=True)
    for name in ("attn/hvd_mla_latent", "attn/hvd_mla_latent/q_a_norm",
                 "attn/hvd_mla_latent/kv_a_norm", "attn/rope",
                 "attn/hvd_flash/hvd_flash_fwd", "moe/hvd_moe_shared/shared",
                 "moe/hvd_moe_router", "moe/hvd_moe_experts"):
        assert "layer_1/" + name in text, name
    assert "layer_0/mlp" in text and "layer_0/moe" not in text
    assert "layer_0/attn/hvd_mla_latent" in text
    # Counted at trace time, a layer: 2 held of 16 routed.
    from horovod_tpu.parallel.moe import _M_EXPERTS

    held = _M_EXPERTS.labels(kind="held").get()
    routed = _M_EXPERTS.labels(kind="routed").get()
    jax.make_jaxpr(lambda p: model.loss(p, state, tokens)[0])(params)
    assert _M_EXPERTS.labels(kind="held").get() - held == 2 * 2
    assert _M_EXPERTS.labels(kind="routed").get() - routed == 2 * 16


def _glm_step():
    """The recorded step with its attention's transpose named as the
    latent projections name themselves and its feed-forward as the
    shared expert and the held experts do."""
    step = RECORDED_STEP.replace(
        "layer_0/attn/transpose", "layer_0/attn/hvd_mla_latent/dot_general"
    ).replace(
        "jvp(Transformer)/layer_0/mlp/dot_general\"}\n  %convert",
        "jvp(Transformer)/layer_0/moe/hvd_moe_shared/shared/dot_general\"}\n"
        "  %convert").replace(
        "transpose(jvp(Transformer))/layer_0/mlp/dot_general",
        "transpose(jvp(Transformer))/layer_0/moe/hvd_moe_experts/"
        "ragged_dot_general")
    assert step.count("hvd_mla_latent") == 1 and step.count("/moe/") == 2
    return step


def test_the_new_readers_on_the_recorded_trace(capsys):
    names = ("mla.attn_ms", "mla.latent_ms", "moe.shared_ms",
             "moe.held_roofline")
    ctx = _ctx(_glm_step())
    ctx.cell = cells.load(CELL)
    got = {name: reader(name)(ctx) for name in names}
    assert all(v is not None and v > 0 for v in got.values()), got
    # The attention module: its latent part, the three kernels and their
    # glue; the scope view files the same events under these parts.
    assert got["mla.latent_ms"] < got["mla.attn_ms"]
    assert got["mla.attn_ms"] == pytest.approx(sum(
        scope_view.part_ms(ctx, part)
        for part in ("attn", "flash_kernel", "flash_glue")))
    assert got["mla.latent_ms"] == pytest.approx(
        scope_view.part_ms(ctx, "attn"))
    assert got["moe.shared_ms"] + reader("moe.experts_ms")(ctx) \
        == pytest.approx(reader("moe.layer_ms")(ctx))
    ops, nbytes = flops_glm.held_expert_matmul_work(
        8192, hidden=2048, expert_width=1536, k=4, held=8, routed=64)
    least, _ = flops.roofline_seconds(ops, nbytes, ctx.peak)
    assert got["moe.held_roofline"] == pytest.approx(
        100 * 1e3 * 4 * least / reader("moe.experts_ms")(ctx))
    assert "held expert matmuls:" in capsys.readouterr().err
    # A GPT-2 step and an OLMoE step have none of the scopes: nothing,
    # and no exception; nor from a ctx a reader cannot use.
    plain = _ctx(RECORDED_STEP)
    plain.cell = cells.load("gpt2m-s4096-c1")
    olmoe = _ctx(RECORDED_STEP.replace(
        "layer_0/mlp/dot_general", "layer_0/moe/hvd_moe_experts/ragged_dot"))
    olmoe.cell = cells.load("olmoe-s4096-c1")
    assert reader("moe.experts_ms")(olmoe) > 0
    broken = _ctx("HloModule jit_small_step")
    broken.win0 = None
    for name in names:
        assert reader(name)(plain) is None, name
        assert reader(name)(olmoe) is None, name
        assert reader(name)(broken) is None, name


# ----------------------------------------------------------- rehearsal ----

@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_through_the_cpu_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000003", "--seconds", "1", "--trace", trace, "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert line["check"]["leaves"] == 47
    assert line["check"]["leaves_all_zero"] == 0
