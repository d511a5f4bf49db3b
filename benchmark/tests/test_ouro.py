"""Ouro-2.6B on the CPU at tiny widths: the looped program against its
plain float32 reference (each pass's logits, the exit distribution, the
loss and every gradient leaf); a shared weight's gradient as the SUM
over an untied stack of copies; the exit distribution's sum and the
loss with the gates shut; recomputation; causality in every pass; the
configuration file, the parameter count and ``flops_ouro.py`` by hand;
the new scopes and their readers; the probe's defects; the cell through
the CPU rehearsal. What is held is what the cell REQUIRES (work, scopes,
``>=`` counts), never today's number of kernel calls or of recomputed
passes."""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops, flops_ouro, loop_view, scope_view
from benchmark import traffic
from benchmark.layer_metrics import reader
from benchmark.reference import ouro as reference
from benchmark.tests.test_olmoe import _leaf_distances, _rel
from benchmark.tests.test_scope_view import RECORDED_STEP, _ctx

CELL = "ouro-s4096-ut4-c1"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PASSES, BLOCKS = 4, 3     # the tiny model's
LOOP_METRICS = ["loop.stack_ms", "loop.recompute_ms", "loop.readout_ms",
                "loop.exit_ms", "loop.readout_roofline"]


def _seen(params):
    """The weights with every leaf moved off its initial value (a scale
    of one and a gate bias of zero hide a wrong use of themselves)."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    return treedef.unflatten([
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype)
        for p, k in zip(leaves, keys)])


def _cell(dtype="float32", attention="flash", remat=True, **config):
    cell = cells.load(CELL, tiny=True)
    cell.config.update(compute_dtype=dtype, attention=attention, **config)
    cell.traffic["remat"] = remat
    return cell


@functools.cache
def _assembled(dtype="float32", attention="flash", remat=True):
    cell = _cell(dtype, attention, remat)
    model = cell.builder.build(cell.config, cell.traffic)
    key = jax.random.PRNGKey(11)
    params, state = jax.jit(model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **model.pool_kwargs)
    return cell, model, _seen(params), state, pool[0]


def _both_sides(cell, model, params, state, tokens):
    (loss, _), grads = jax.jit(jax.value_and_grad(
        model.loss, has_aux=True))(params, state, tokens)
    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = jax.jit(jax.value_and_grad(
            model.reference_loss, has_aux=True))(params, state, tokens)
    return float(loss), grads, float(want), want_grads


# ------------------------------------------------ program = reference -----

@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_float32_program_is_the_reference(attention):
    loss, grads, want, want_grads = _both_sides(
        *_assembled("float32", attention))
    assert loss == pytest.approx(want, rel=2e-6)
    distances = _leaf_distances(grads, want_grads)
    # Three blocks of nine leaves, the final norm, both ends, the gate.
    assert len(distances) == 9 * BLOCKS + 4
    assert max(distances.values()) < 2e-4, max(distances, key=distances.get)
    assert np.median(list(distances.values())) < 1e-5


def test_bf16_program_is_inside_gpt2s_bounds():
    loss, grads, want, want_grads = _both_sides(*_assembled("bfloat16"))
    assert abs(loss - want) / want < 2e-4
    distances = _leaf_distances(grads, want_grads)
    assert np.median(list(distances.values())) < 0.05
    assert max(distances.values()) < 0.3


@pytest.mark.parametrize("norm_in_loop", [True, False])
def test_each_passes_logits_and_the_exit_distribution(norm_in_loop):
    """What the loss is made of, pass by pass: the program's normed
    states read out through its head against the reference's logits,
    and the exit distribution from the library's gate against the
    reference's plain product; under both readings of where the final
    norm stands."""
    from horovod_tpu.models import transformer

    cell, model, params, state, tokens = _assembled("float32", "flash",
                                                    False)
    config = dict(cell.config, norm_in_loop=norm_in_loop)
    module = cell.builder.module_of(config, cell.traffic)
    inputs = tokens[:, :-1]
    with jax.default_matmul_precision("highest"):
        hidden = module.apply(params, inputs)
        want_states = reference.hidden_states(config, params, inputs)
        assert hidden.shape == (PASSES, 2, 128, 64) == want_states.shape
        head = params["params"]["lm_head"]
        for t in range(PASSES):
            got = transformer._logits(hidden[t], head)
            want = reference.logits(params, want_states[t])
            assert got.dtype == jnp.float32 and got.shape == (2, 128, 512)
            assert _rel(got, want) < 1e-5, t
        gate = params["params"]["exit_gate"]
        score = jnp.sum(hidden * gate[:-1], -1) + gate[-1]
        p = jnp.exp(transformer._exit_log_p(score))
        want_p = reference.exit_distribution(params, want_states)
    assert _rel(p, want_p) < 1e-5
    np.testing.assert_allclose(np.asarray(jnp.sum(p, 0)), 1.0, atol=1e-6)
    # The two readings are two models: the second pass differs.
    if not norm_in_loop:
        normed = reference.hidden_states(cell.config, params, inputs)
        assert _rel(want_states[0], normed[0]) < 1e-6
        assert _rel(want_states[1], normed[1]) > 1e-2


def test_the_loss_and_its_statistics_against_the_references_terms():
    cell, model, params, state, tokens = _assembled("float32", "flash",
                                                    False)
    loss, stats = jax.jit(model.loss)(params, state, tokens)
    with jax.default_matmul_precision("highest"):
        want, p, losses = reference.terms(cell.config, params, tokens)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert set(stats) == set(state) == {"exit_share", "entropy",
                                        "cross_entropy"}
    np.testing.assert_allclose(stats["exit_share"], p.mean((1, 2)),
                               rtol=1e-5)
    np.testing.assert_allclose(stats["cross_entropy"], losses.mean((1, 2)),
                               rtol=1e-5)
    entropy = -jnp.sum(p * jnp.log(p), 0).mean()
    assert float(stats["entropy"]) == pytest.approx(float(entropy), rel=1e-5)
    assert float(stats["exit_share"].sum()) == pytest.approx(1.0, abs=1e-6)
    # By hand from the terms: expected loss less beta times the entropy.
    by_hand = jnp.mean(jnp.sum(p * losses, 0)) \
        - cell.config["exit_entropy_beta"] * entropy
    assert float(want) == pytest.approx(float(by_hand), rel=1e-6)


def _untied_loss(config, copies, rest, tokens):
    """The reference's loss over an UNTIED stack: ``copies[t][i]`` is
    pass t's own copy of block i (the same arithmetic as
    ``reference.terms``, the blocks looked up by pass)."""
    p = rest["params"]
    block = functools.partial(reference._block, config=config)
    eps = config["rms_norm_eps"]
    x, states = p["embed"][tokens[:, :-1]], []
    for t in range(config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            x = block(x, copies[t]["layer_%d" % i])
        x = reference._rms_norm(x, p["stack"]["ln_f"]["scale"], eps)
        states.append(x)
    states = jnp.stack(states)
    losses = jnp.stack([reference.readout_losses(rest, h, tokens[:, 1:])
                        for h in states])
    q = reference.exit_distribution(rest, states)
    return jnp.mean(jnp.sum(q * losses, 0) + config["exit_entropy_beta"]
                    * jnp.sum(q * jnp.log(q), 0))


@pytest.mark.parametrize("remat", [False, True])
def test_a_shared_weights_gradient_is_the_sum_over_an_untied_stack(remat):
    """The gradient the PROGRAM hands the optimizer for a block's weight
    is the sum of the gradients of ``T x L`` untied blocks holding
    copies of it, one a pass: no mechanism sums it, and none drops a
    pass."""
    cell, model, params, state, tokens = _assembled("float32", "flash",
                                                    remat)
    grads = jax.jit(jax.grad(lambda p: model.loss(p, state, tokens)[0]))(
        params)
    layers = {k: v for k, v in params["params"]["stack"].items()
              if k.startswith("layer_")}
    copies = [layers] * PASSES
    with jax.default_matmul_precision("highest"):
        untied = jax.jit(jax.grad(functools.partial(
            _untied_loss, cell.config), argnums=0))(copies, params, tokens)
    summed = jax.tree.map(lambda *g: sum(g), *untied)
    distances = _leaf_distances(
        {k: grads["params"]["stack"][k] for k in sorted(layers)},
        {k: summed[k] for k in sorted(layers)})
    assert len(distances) == 9 * BLOCKS
    assert max(distances.values()) < 2e-4, max(distances, key=distances.get)
    # Every pass has a part in it: no copy's gradient vanishes, and no
    # single pass's is the whole.
    wi = [u["layer_1"]["mlp"]["wi"] for u in untied]
    assert all(float(jnp.linalg.norm(g)) > 0 for g in wi)
    assert all(_rel(g, summed["layer_1"]["mlp"]["wi"]) > 0.1 for g in wi)


def test_with_the_gates_shut_the_loss_is_the_last_passes_cross_entropy():
    cell, model, params, state, tokens = _assembled("float32", "flash",
                                                    False)
    gate = params["params"]["exit_gate"]
    shut = jax.tree.map(lambda a: a, params)
    shut["params"]["exit_gate"] = (0.0 * gate).at[-1].set(-1e4)
    loss, stats = jax.jit(model.loss)(shut, state, tokens)
    np.testing.assert_allclose(stats["exit_share"], [0, 0, 0, 1], atol=1e-7)
    assert float(loss) == pytest.approx(float(stats["cross_entropy"][-1]),
                                        rel=1e-6)
    assert float(stats["entropy"]) == pytest.approx(0.0, abs=1e-6)
    grads = jax.jit(jax.grad(lambda p: model.loss(p, state, tokens)[0]))(shut)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    # Wide open: everything exits after the first pass.
    wide = jax.tree.map(lambda a: a, params)
    wide["params"]["exit_gate"] = (0.0 * gate).at[-1].set(1e4)
    loss, stats = jax.jit(model.loss)(wide, state, tokens)
    np.testing.assert_allclose(stats["exit_share"], [1, 0, 0, 0], atol=1e-7)
    assert float(loss) == pytest.approx(float(stats["cross_entropy"][0]),
                                        rel=1e-6)


def test_recomputation_changes_no_gradient():
    losses, grads = {}, {}
    for remat in (False, True):
        cell, model, params, state, tokens = _assembled("float32", "flash",
                                                        remat)
        (losses[remat], _), grads[remat] = jax.jit(jax.value_and_grad(
            model.loss, has_aux=True))(params, state, tokens)
    assert float(losses[True]) == pytest.approx(float(losses[False]),
                                                rel=1e-6)
    distances = _leaf_distances(grads[True], grads[False])
    assert max(distances.values()) < 1e-5, max(distances, key=distances.get)


def test_no_position_sees_a_later_token_in_any_pass():
    """One token changed at position 70: in no pass does a state before
    it move, on either side."""
    cell, model, params, state, tokens = _assembled("float32", "flash",
                                                    False)
    inputs = tokens[:1, :-1]
    changed = inputs.at[0, 70].set((inputs[0, 70] + 1) % 512)
    with jax.default_matmul_precision("highest"):
        sides = {
            "program": [model.module.apply(params, t)
                        for t in (inputs, changed)],
            "reference": [reference.hidden_states(cell.config, params, t)
                          for t in (inputs, changed)]}
    for name, (a, b) in sides.items():
        moved = jnp.abs(a - b).max(-1)[:, 0]         # (passes, positions)
        assert float(moved[:, :70].max()) == 0.0, name
        assert float(moved[:, 70:].max(-1).min()) > 1e-3, name


# ------------------------------------------ the configuration, by hand ----

def test_the_builder_refuses_what_it_has_no_one_answer_to():
    cell = cells.load(CELL)
    for key, other in (("tie_word_embeddings", True), ("hidden_act", "gelu"),
                       ("use_sliding_window", True),
                       ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            cell.builder.block_spec(dict(cell.config, **{key: other}))
    with pytest.raises(ValueError, match="grouped"):
        cell.builder.block_spec(dict(cell.config, num_key_value_heads=4))


def test_the_configuration_file_keeps_every_published_width():
    config = cells.load(CELL).config
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    assert config["layer_types"] == ["full_attention"] * 48
    # The ONE cut: depth, a sixth of the stack; not under the floor.
    assert config["reduced"] == ["num_hidden_layers"]
    assert 4 <= config["num_hidden_layers"] == 8 == 48 // 6
    assert set(config["reduced_from"]) == set(config["reduced"])
    for key in ("assumed", "departures", "deployment", "check", "source"):
        assert config[key], key
    assert config["norm_in_loop"] is True
    assert config["exit_entropy_beta"] == 0.1
    assert "six pipeline stages of eight blocks" in config["deployment"]
    check = config["check"]
    assert check["via"] == "sgd_step" and check["loss_rtol"] == 2e-4
    entry = [c for c in cells.load(CELL).bench["configs"]
             if c["name"] == "ouro-2.6b"][0]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_the_file_holds_the_catalogs_numbers_but_the_reduced():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guides here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Ouro-2.6B"]
    config = cells.load(CELL).config
    assert config["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if config.get(k) != v]
    assert differs == ["num_hidden_layers"]


def test_the_parameters_by_hand():
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    block = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert block == 51_388_416
    ends = 2 * 49_152 * 2048
    assert ends == 201_326_592
    assert count == 8 * block + ends + 2048 + 2049 == 612_438_017
    assert 16 * count == pytest.approx(9.80e9, rel=1e-3)
    assert 48 * block + ends + 2048 + 2049 == 2_667_974_657
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    stack = params["params"]["stack"]
    assert sorted(stack) == ["layer_%d" % i for i in range(8)] + ["ln_f"]
    assert params["params"]["exit_gate"].shape == (2049,)     # ONE leaf
    assert {k: v.shape for k, v in state.items()} == {
        "exit_share": (4,), "entropy": (), "cross_entropy": (4,)}
    # The planner prices block APPLICATIONS.
    assert model.plan_kwargs["n_layers"] == 32


def test_the_step_by_hand():
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    s, m, v = 4096, 2048, 49152
    projections = 2 * s * 51_380_224
    assert projections == flops_ouro.block_projection_ops(
        s, hidden=m, n_head=16, head_dim=128, width=5632)
    assert projections == pytest.approx(420.9e9, rel=1e-3)
    pairs = s * (s + 1) // 2
    assert pairs == 8_390_656
    attention = 16 * 2 * 2 * pairs * 128
    assert attention == flops_ouro.block_attention_ops(
        s, n_head=16, head_dim=128) == pytest.approx(68.7e9, rel=1e-3)
    one = 3 * projections + 7 * attention // 2
    assert one == pytest.approx(1503e9, rel=1e-3)
    readouts = 4 * 3 * 2 * s * m * v
    assert readouts == flops_ouro.readout_ops(1, s, vocab=v, hidden=m,
                                              passes=4)
    assert readouts == pytest.approx(9.9e12, rel=1e-2)
    gate = 4 * 3 * 2 * s * m
    assert model.step_ops(1) == 32 * one + readouts + gate
    assert model.step_ops(1) == pytest.approx(58.0e12, rel=1e-3)
    assert model.step_ops(2) == 2 * model.step_ops(1)
    # The readouts' share here and at the published depth.
    assert readouts / model.step_ops(1) == pytest.approx(0.17, abs=0.005)
    whole = 48 * 4 * one + readouts + gate
    assert readouts / whole == pytest.approx(0.033, abs=0.001)
    # One pass of an untied model of the same blocks is a quarter of the
    # blocks' work and of the head's.
    once = flops_ouro.ouro_step_ops(
        1, s, vocab=v, hidden=m, n_head=16, head_dim=128, width=5632,
        n_layer=8, passes=1)
    assert 4 * once == model.step_ops(1)


def test_what_the_steps_attention_requires():
    cell = cells.load(CELL)
    model = cell.builder.build(cell.config, cell.traffic)
    work = model.attention_work(1)
    one = flops.attention_work(8_390_656, 4096, n_head=16, n_kv=16, d=128,
                               d_v=128)
    # Once a block APPLICATION: 8 blocks x 4 passes.
    assert work["fwd"] == tuple(32 * x for x in one["fwd"])
    assert work["bwd"] == tuple(32 * x for x in one["bwd"])
    assert work["fwd"][0] == 32 * 68_736_253_952
    assert work["bwd"][0] * 2 == work["fwd"][0] * 5


# ------------------------------------------------- scopes and readers -----

def _lowered(remat=True):
    cell, model, params, state, tokens = _assembled("float32", "flash", remat)
    grad = jax.grad(lambda p: model.loss(p, state, tokens)[0])
    return jax.jit(grad).lower(params).as_text(debug_info=True)


def test_the_scope_constants_are_what_the_program_sets():
    from horovod_tpu.jax import introspect
    from horovod_tpu.utils import metrics

    assert introspect.SCOPE_LOOP_PASS == "hvd_loop_pass"
    assert introspect.SCOPE_LOOP_READOUT == "hvd_loop_readout"
    assert introspect.SCOPE_LOOP_EXIT == "hvd_loop_exit"
    assert loop_view.READOUT.search("jvp(hvd_loop_readout)")
    assert loop_view.EXIT.search("transpose(jvp(hvd_loop_exit))")
    before = metrics.value("hvd_loop_passes_total") or 0
    text = _lowered()
    # Blocks x passes applied, counted at trace time.
    assert (metrics.value("hvd_loop_passes_total") or 0) - before \
        >= BLOCKS * PASSES
    for t in range(PASSES):
        scope = "stack/hvd_loop_pass_%d/" % t
        assert scope in text, scope
        assert loop_view.PASS.search("hvd_loop_pass_%d" % t)
    assert "hvd_loop_pass_%d" % PASSES not in text
    # The projection itself stays under ``logits`` inside the readout.
    assert "hvd_loop_readout)/logits/" in text
    assert "(hvd_loop_exit)/" in text
    for name in ("layer_0/attn/hvd_flash/hvd_flash_fwd", "layer_2/mlp",
                 "ln_f", "post_attn_norm", "post_mlp_norm", "embed", "rope"):
        assert name in text, name
    # Every block application runs the forward kernel at least once and
    # the readouts' matmul at least once a pass and direction.
    assert text.count("hvd_flash_fwd") >= BLOCKS * PASSES
    assert not loop_view.PASS.search("hvd_loop_pass")
    assert not loop_view.PASS.search("layer_0")


def test_recomputation_keeps_a_count_of_its_blocks_a_pass():
    from horovod_tpu.utils import metrics

    def kept():
        return sum(metrics.value("hvd_remat_blocks_total", keeps=k) or 0
                   for k in ("flash+products", "products", "flash",
                             "pass_input"))

    before = kept()
    cell = _cell("float32", "dense", True)
    model = cell.builder.build(cell.config, cell.traffic)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jnp.zeros((1, 129), jnp.int32)
    jax.make_jaxpr(lambda p: model.loss(p, state, tokens)[0])(params)
    # Counted once a pass: at least the blocks x passes applied.
    assert kept() - before >= BLOCKS * PASSES


def _loop_step():
    """The recorded step as a looped model would name it: the attention
    layer stands in pass 1 of the stack; the feed-forward's forward
    matmul becomes a readout's projection, its backward matmul pass 0's
    feed-forward made AGAIN under the outer checkpoint; the loss's
    reduction becomes the exit gate's."""
    step = RECORDED_STEP.replace(
        "layer_0/attn", "stack/hvd_loop_pass_1/layer_0/attn").replace(
        "jvp(Transformer)/layer_0/mlp/dot_general",
        "jvp(hvd_loop_readout)/logits/dot_general", 1).replace(
        "transpose(jvp(Transformer))/layer_0/mlp/dot_general",
        "transpose(jvp(Transformer))/stack/hvd_loop_pass_0/checkpoint/"
        "rematted_computation/layer_0/mlp/dot_general").replace(
        "jvp()/reduce_sum", "jvp(hvd_loop_exit)/reduce_sum")
    assert step.count("hvd_loop_pass_") >= 5
    assert step.count("hvd_loop_readout") == 1 == step.count("hvd_loop_exit")
    return step


def test_the_new_readers_on_the_recorded_trace(capsys):
    ctx = _ctx(_loop_step())
    ctx.cell = cells.load(CELL)
    got = {name: reader(name)(ctx) for name in LOOP_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    # The recomputed part is a part of the stack's time.
    assert 0 < got["loop.recompute_ms"] < got["loop.stack_ms"]
    # The stack: the attention module's parts and the recomputed matmul.
    assert got["loop.stack_ms"] == pytest.approx(
        sum(scope_view.part_ms(ctx, part)
            for part in ("attn", "flash_kernel", "flash_glue"))
        + got["loop.recompute_ms"])
    least = flops_ouro.readout_ops(1, 4096, vocab=49152, hidden=2048,
                                   passes=4) / ctx.peak["bf16_flops"]
    assert least == pytest.approx(50.2e-3, rel=2e-3)
    assert got["loop.readout_roofline"] == pytest.approx(
        100 * 1e3 * least / got["loop.readout_ms"])
    assert "looped readouts" in capsys.readouterr().err
    # The readout's projection is still the ``head`` to the older
    # readers: ``logits`` stands inside the readout's scope.
    assert scope_view.classify(
        "jit(step)/jvp(hvd_loop_readout)/logits/dot_general", "") == (
        "forward", "head")
    assert scope_view.classify(
        "jit(step)/transpose(jvp(hvd_loop_exit))/mul", "") == (
        "backward", "head")
    assert scope_view.classify(
        "jit(step)/transpose(jvp(Transformer))/stack/hvd_loop_pass_2/"
        "checkpoint/rematted_computation/layer_1/mlp/dot_general", "") == (
        "backward", "mlp")
    # A step without the scopes (the parent's program), a configuration
    # that loops nothing, a ctx a reader cannot use: nothing, and no
    # exception.
    bare = _ctx(RECORDED_STEP)
    bare.cell = cells.load(CELL)
    gpt2 = _ctx(_loop_step())
    gpt2.cell = cells.load("gpt2m-s1024-c1")
    trinity = _ctx(_loop_step())
    trinity.cell = cells.load("trinity-s8192-ep8-c1")
    broken = _ctx("HloModule jit_small_step")
    broken.cell = cells.load(CELL)
    broken.win0 = None
    for name in LOOP_METRICS:
        assert reader(name)(bare) is None, name
        assert reader(name)(gpt2) is None, name
        assert reader(name)(trinity) is None, name
        assert reader(name)(broken) is None, name


@pytest.mark.parametrize("path,parts", [
    (["jvp(Transformer)", "stack", "hvd_loop_pass_0", "layer_3", "mlp",
      "dot_general"], ("stack",)),
    # A bare ``checkpoint`` marks the first forward and the backward
    # too: only ``rematted_computation`` is a forward made again.
    (["transpose(jvp(Transformer))", "stack", "hvd_loop_pass_3",
      "checkpoint", "layer_3", "mlp", "dot_general"], ("stack",)),
    (["transpose(jvp(Transformer))", "stack", "hvd_loop_pass_1",
      "checkpoint", "rematted_computation", "ln_f", "mul"],
     ("stack", "recompute")),
    (["transpose(jvp(hvd_loop_readout))", "logits", "dot_general"],
     ("readout",)),
    (["jvp(hvd_loop_readout)", "reduce_max"], ("readout",)),
    (["jvp(hvd_loop_exit)", "exp"], ("exit",)),
    (["jvp()", "hvd_loop_exit", "exp"], ("exit",)),
    (["jvp(Transformer)", "stack", "layer_3", "mlp"], ()),
    (["jvp(Transformer)", "embed", "gather"], ()),
    (["hvd_update", "mul"], ()),
])
def test_which_part_a_scope_counts_towards(path, parts):
    assert loop_view.part_of(path) == parts


def test_the_metrics_of_the_cell():
    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert set(LOOP_METRICS) | {
        "kernel.flash_roofline", "kernel.flash_fwd_roofline",
        "kernel.flash_bwd_roofline", "kernel.flash_share_pct",
        "kernel.flash_glue_ms", "model.mfu_pct", "model.step_device_ms",
        "model.fwd_ms", "model.bwd_ms", "model.update_ms", "model.head_ms",
        "device.peak_hbm_gb", "device.idle_pct", "device.unscoped_pct",
        "launch.compile_s", "launch.cache_misses"} <= mine
    assert not mine & {"moe.layer_ms", "moe.held_roofline", "mla.attn_ms",
                       "swa.attn_ms", "conv.mixer_ms", "dsa.attn_ms",
                       "ssm.scan_ms", "sync.collective_ms"}
    new = [m for m in cell.bench["per_layer"]
           if m["name"].startswith("loop.")]
    assert [m["name"] for m in new] == LOOP_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "Looped stack"
               and os.path.exists(os.path.join(
                   ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
               for m in new)
    roofline = [m for m in new if m["name"].endswith("_roofline")]
    assert [m["unit"] for m in roofline] == ["%"]
    # One cell on one chip, one configuration, the benchmark's one
    # four-chip cell still there.
    assert len(cell.bench["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) >= 1
    assert len(cell.bench["configs"]) >= 9
    assert [w["name"] for w in cell.bench["workloads"]
            if w["config"] == "ouro-2.6b"] == [CELL]
    traffic_file = cell.traffic
    assert traffic_file["seq_len"] == 4096
    assert traffic_file["per_chip_batch"] == 1 and traffic_file["remat"]
    assert traffic_file["data"] == {"kind": "markov_tokens",
                                    "successors": 4, "pool": 8}
    held = traffic_file["compiled_bytes"]["ouro-2.6b"]["held_bytes_per_chip"]
    assert 9.8e9 < held < 16e9


def test_the_probes_defects_are_defects():
    from benchmark import ouro_probe as probe
    from horovod_tpu.models import transformer

    assert len(probe.DEFECTS) == 6
    read = lambda ok: {"ok": ok}  # noqa: E731
    seed = dict({name: read(False) for name in probe.DEFECTS},
                sound=read(True))
    assert probe.not_as_it_has_to_be(seed) == []
    assert sorted(probe.not_as_it_has_to_be(
        dict(seed, sound=read(False), last_gated=read(True)))) == [
        "last_gated", "sound"]
    score = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 8))
    sound = jnp.exp(transformer._exit_log_p(score))
    spoiled = jnp.exp(probe._last_gated(score))
    np.testing.assert_allclose(np.asarray(sound.sum(0)), 1.0, atol=1e-6)
    # The first three passes are the sound ones; the last passes its own
    # gate too, and the distribution sums to less than one.
    assert _rel(spoiled[:3], sound[:3]) < 1e-6
    np.testing.assert_allclose(
        np.asarray(spoiled[3]),
        np.asarray(sound[3] * jax.nn.sigmoid(score[3])), rtol=1e-5)
    assert float(spoiled.sum(0).max()) < 1.0
    with probe.replaced(transformer, "_exit_log_p", probe._last_gated):
        assert transformer._exit_log_p is probe._last_gated
    assert transformer._exit_log_p is not probe._last_gated
    # The reference's hook reads the normed state; the defect the raw.
    assert reference._gate_input(1.0, 2.0) == 1.0
    # The defects through the builder: fields of the program's config.
    cell = _cell()
    three = cell.builder.build(cell.config, cell.traffic, passes=3)
    assert three.module.cfg.passes == 3
    loose = cell.builder.build(cell.config, cell.traffic, loop_norm=False)
    assert loose.module.cfg.loop_norm is False
    assert cell.builder.build(cell.config,
                              cell.traffic).module.cfg.loop_norm is True


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "ouro.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert not [line for line in imports if "horovod_tpu" in line
                or "flax" in line or "benchmark" in line], imports


# ----------------------------------------------------------- rehearsal ----

@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_through_the_cpu_rehearsal(trace, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4900000003", "--seconds", "1", "--trace", trace, "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert line["check"]["leaves"] == 9 * BLOCKS + 4
    assert line["check"]["leaves_all_zero"] == 0
    assert line["check"]["loss_rel"] < 2e-4
