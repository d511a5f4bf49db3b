"""OLMoE on the CPU at the builder's ``TINY`` widths (hidden 64, 4 heads
of 16, 8 experts of 32, 2 a token, vocabulary 512, S=128, 2 layers): the
program against ``benchmark/reference/olmoe.py`` on seeded weights, the
pieces against closed forms, the counting of ``flops_moe.py`` by hand,
and the expert layer's scopes through the scope view and their readers.

Tolerances. With the program computing in float32 the two are the same
mathematics in another order (a sort and grouped matmuls against every
token through every expert): logits to 1e-4 of their largest entry, the
loss to 1e-5, every gradient leaf to 1e-3 relative L2 (measured 2e-7 to
6e-6). That holds at FREE routing too: in float32 the two routers see
the same numbers to rounding and no token of these seeds changes an
expert (asserted, so a seed that did would say so instead of failing the
bound). In bf16 the comparison is made at FORCED routing (both sides
given the same experts), inside ``gpt2-medium.json``'s bounds for the
chip (loss 2e-4, gradient leaf 5e-2): the choice of 2 among 8 is
discrete, and a token whose 2nd and 3rd probabilities are closer than
bf16 rounding takes another expert, which is no precision defect.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cell as cells
from benchmark import flops, flops_moe, scope_view, traffic
from benchmark.layer_metrics import reader
from benchmark.reference import olmoe as reference
from benchmark.tests.test_reference import _compare
from benchmark.tests.test_scope_view import RECORDED_STEP, _ctx

CELL = "olmoe-s4096-c1"
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _assembled(dtype, n_layers=None):
    cell = cells.load(CELL, tiny=True)
    cell.config["compute_dtype"] = dtype
    if n_layers:
        cell.config["num_hidden_layers"] = n_layers
    asm = cells.assemble(cell, jax.devices()[:1])
    key = jax.random.PRNGKey(11)
    params, _ = jax.jit(asm.model.init)(key)
    pool = traffic.make_pool(
        key, dict(cell.traffic["data"], pool=1), global_batch=2,
        config=cell.config, **asm.model.pool_kwargs)
    return cell, asm.model, params, pool[0]


def _rel(got, want):
    return float(jnp.linalg.norm((got - want).astype(jnp.float32))
                 / jnp.linalg.norm(want.astype(jnp.float32)))


def _leaf_distances(got, want):
    return {jax.tree_util.keystr(path): _rel(g, w) for (path, g), w in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))}


def _random_assignments(key, n_layers, tokens, experts, k):
    """Per layer, k distinct experts a token, nothing to do with any
    router: what forcing must be able to impose."""
    noise = jax.random.uniform(key, (n_layers, tokens, experts))
    return list(jnp.argsort(noise, -1)[..., :k].astype(jnp.int32))


@pytest.mark.parametrize("routing", ["free", "forced"])
def test_float32_program_is_the_reference(routing):
    cell, model, params, tokens = _assembled("float32")
    config = cell.config
    t = tokens.shape[0] * (tokens.shape[1] - 1)
    assignments = None
    if routing == "forced":
        assignments = _random_assignments(
            jax.random.PRNGKey(5), config["num_hidden_layers"], t,
            config["num_experts"], config["num_experts_per_tok"])

    want, aux = jax.jit(lambda p, x: reference.forward(
        config, p, x, assignments))(params, tokens[:, :-1])
    got, sown = jax.jit(lambda p, x: model.module.apply(
        p, x, assignments, mutable=["moe"]))(params, tokens[:, :-1])
    from horovod_tpu.parallel import moe

    stats = moe.sown_stats(sown)
    # The same experts on both sides: free routing flips none in float32.
    assert (np.sort(np.asarray(stats["experts"]), -1)
            == np.sort(np.asarray(aux["chosen"]), -1)).all()
    if routing == "forced":
        assert (np.asarray(stats["experts"])
                == np.asarray(jnp.stack(assignments))).all()
    assert float(jnp.max(jnp.abs(got - want))) \
        < 1e-4 * float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(stats["load_balance"], aux["load_balance"],
                               rtol=1e-5)
    np.testing.assert_allclose(stats["z_loss"], aux["z_loss"], rtol=1e-5)
    # Nothing dropped: every layer's counts are all T x k assignments.
    assert (np.asarray(stats["tokens_per_expert"]).sum(-1)
            == t * config["num_experts_per_tok"]).all()

    def both(loss):
        return jax.jit(jax.value_and_grad(
            lambda p: loss(p, tokens, assignments)[0]))(params)

    (loss, grads), (ref_loss, ref_grads) = both(model.loss_and_stats), both(
        lambda p, x, a: reference.loss(config, p, {}, x, a))
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    distances = _leaf_distances(grads, ref_grads)
    assert len(distances) == 3 + 2 * 10     # every leaf, the routers' too
    assert max(distances.values()) < 1e-3, distances
    # The router's gradient through BOTH auxiliary losses: without the
    # cross entropy it is still there, and still the reference's.
    aux_only = dict(config, router_aux_loss_coef=1.0, router_z_loss_coef=1.0)

    def aux_loss(fwd):
        def f(p):
            a = fwd(p)
            return jnp.mean(a["load_balance"]) + jnp.mean(a["z_loss"])
        return jax.jit(jax.grad(f))(params)

    g_sys = aux_loss(lambda p: moe.sown_stats(model.module.apply(
        p, tokens[:, :-1], assignments, mutable=["moe"])[1]))
    g_ref = aux_loss(lambda p: reference.forward(
        aux_only, p, tokens[:, :-1], assignments)[1])
    router = "['params']['layer_1']['moe']['router']"
    d = _leaf_distances(g_sys, g_ref)
    assert d[router] < 1e-3 and float(jnp.linalg.norm(
        g_ref["params"]["layer_1"]["moe"]["router"])) > 0, d


def test_bf16_program_at_forced_routing_is_inside_gpt2s_bounds():
    cell, model, params, tokens = _assembled("bfloat16")
    config = cell.config
    with open(os.path.join(CONFIGS, "gpt2-medium.json")) as f:
        bounds = json.load(f)["check"]
    # The reference's own choice, forced on the program.
    chosen = list(jax.jit(lambda p, x: reference.forward(
        config, p, x)[1]["chosen"])(params, tokens[:, :-1]))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_and_stats(p, tokens, chosen)[0]))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(config, p, {}, tokens, chosen)[0]))(params)
    assert abs(float(loss) - float(ref_loss)) \
        < bounds["loss_rtol"] * float(ref_loss)
    distances = _leaf_distances(grads, ref_grads)
    assert max(distances.values()) < bounds["grad_rel_l2"], distances
    # and bf16 is visible: the comparison is not blind to precision
    assert max(distances.values()) > 1e-3, distances


def test_the_check_of_the_cell_in_float32():
    """``run.py``'s own comparison (``check.sgd_step_gradients`` against
    the reference, free routing) at the tiny sizes."""
    got = _compare(CELL, "float32", 1)
    assert got["loss_rel"] < 1e-5 and got["grad_rel_l2_max"] < 1e-3, got
    assert got["leaves"] == 23 and got["leaves_all_zero"] == 0, got


def _moe_layer(spec, d_model=16, d_ff=24, dtype=jnp.float32):
    from horovod_tpu import models
    from horovod_tpu.parallel.moe import MoeMlp

    cfg = models.TransformerConfig(d_model=d_model, d_ff=d_ff, dtype=dtype,
                                   block=spec)
    return MoeMlp(cfg)


def test_no_token_is_dropped_whatever_the_imbalance():
    """A router that sends EVERY token to experts 0 and 1: they take T
    rows each, the other six none, and the output is the reference's."""
    from flax.core import meta
    from horovod_tpu import models
    from horovod_tpu.parallel import moe

    spec = models.BlockSpec(ffn="swiglu", num_experts=8, experts_per_token=2)
    layer = _moe_layer(spec)
    t, m = 96, 16
    x = 1.0 + jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (1, t, m)))
    params = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(1), x))
    apply = jax.jit(lambda p: layer.apply(p, x, mutable=["moe"]))
    router = jnp.full((m, 8), -1.0).at[:, 0].set(1.0).at[:, 1].set(0.9)
    params["params"]["router"] = router
    out, sown = apply(params)
    stats = moe.sown_stats({"moe": {"layer_0": sown["moe"]}})
    counts = np.asarray(stats["tokens_per_expert"][0])
    assert counts.tolist() == [t, t, 0, 0, 0, 0, 0, 0]
    assert counts.sum() == t * 2
    config = {"num_experts": 8, "num_experts_per_tok": 2}
    want, load_balance, _, _ = reference._experts(
        x[0], params["params"], config, None)
    assert _rel(out[0], want) < 1e-5
    # f = (1/2, 1/2, 0...): E * sum f_e P_e = 4 (P_0 + P_1), over 1.
    assert float(stats["load_balance"][0]) == pytest.approx(
        float(load_balance), rel=1e-6)
    assert float(load_balance) > 2.0
    # An empty group in the middle, and a single token alone.
    lone = jnp.full((m, 8), -1.0).at[:, 7].set(1.0).at[0, 3].set(50.0)
    params["params"]["router"] = lone
    out, sown = apply(params)
    want, _, _, _ = reference._experts(x[0], params["params"], config, None)
    assert _rel(out[0], want) < 1e-5
    assert int(sown["moe"]["tokens_per_expert"][0].sum()) == t * 2


def test_one_gelu_expert_a_token_is_the_old_moe_ffn():
    """GPT-2's block with experts (k=1, GELU) against the Switch-style
    ``moe_ffn`` with a capacity nothing overflows."""
    from flax.core import meta
    from horovod_tpu import models
    from horovod_tpu.parallel import moe

    layer = _moe_layer(models.BlockSpec(num_experts=4))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 16))
    params = meta.unbox(jax.jit(layer.init)(jax.random.PRNGKey(3), x))
    p = params["params"]
    assert sorted(p) == ["router", "wi", "wo"]      # the old tree
    got = jax.jit(layer.apply)(params, x)
    want = moe.moe_ffn(x.reshape(40, 16), p["router"], p["wi"], p["wo"],
                       capacity=40)
    assert _rel(got.reshape(40, 16), want) < 1e-5


def test_rope_is_a_rotation_by_position_times_frequency():
    from horovod_tpu.models.transformer import rope

    b, s, h, d = 1, 9, 2, 8
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, h, d))
    theta = 10000.0
    # Closed form: pair i of a head is the complex number
    # x[i] + 1j * x[i + d/2], turned by position * theta^(-2i/d).
    z = np.asarray(x[..., :d // 2]) + 1j * np.asarray(x[..., d // 2:])
    angle = (np.arange(s)[:, None] * theta ** (-np.arange(d // 2)
                                               / (d // 2)))[:, None, :]
    turned = z * np.exp(1j * angle)
    want = np.concatenate([turned.real, turned.imag], -1)
    np.testing.assert_allclose(rope(x, 0, theta), want, atol=1e-5)
    np.testing.assert_allclose(reference._rope(x, theta), want, atol=1e-5)
    # A shard that starts at position 5 continues the sequence.
    np.testing.assert_allclose(rope(x[:, 5:], 5, theta), want[:, 5:],
                               atol=1e-5)
    # q . k depends on the distance alone.
    q, k = x[:, :1], x[:, 1:2]
    near = jnp.sum(rope(jnp.concatenate([q, k], 1), 0, theta)[:, 0]
                   * rope(jnp.concatenate([q, k], 1), 0, theta)[:, 1])
    far = jnp.sum(rope(jnp.concatenate([q, k], 1), 7, theta)[:, 0]
                  * rope(jnp.concatenate([q, k], 1), 7, theta)[:, 1])
    assert float(near) == pytest.approx(float(far), rel=1e-4)


def test_qk_norm_spans_all_heads():
    """q = x (identity projection): RMSNorm_q(q) is x over its root mean
    square across the WHOLE width, times the scale, before the heads
    are told apart."""
    from flax.core import meta
    from horovod_tpu import models
    from horovod_tpu.models.transformer import SelfAttention

    spec = models.BlockSpec(norm="rmsnorm", norm_eps=1e-5, qk_norm=True)
    cfg = models.TransformerConfig(d_model=8, n_heads=2, dtype=jnp.float32,
                                   block=spec)
    attn = SelfAttention(cfg)
    x = jnp.asarray([[[3.0, 4.0, 0, 0, 0, 0, 0, 0],
                      [1.0, 1, 1, 1, 1, 1, 1, 1]]])
    params = meta.unbox(jax.jit(attn.init)(jax.random.PRNGKey(0), x))
    eye = jnp.eye(8).reshape(8, 2, 4)
    params["params"]["wqkv"] = jnp.stack([eye, eye, eye])
    scale = jnp.arange(1.0, 9.0)
    params["params"]["q_norm"]["scale"] = scale
    _, seen = attn.apply(params, x, capture_intermediates=True)
    q = seen["intermediates"]["q_norm"]["__call__"][0]
    rms = np.sqrt(25.0 / 8 + 1e-5)
    np.testing.assert_allclose(q[0, 0], np.asarray(x[0, 0]) / rms * scale,
                               rtol=1e-6)
    np.testing.assert_allclose(q[0, 1], scale / np.sqrt(1 + 1e-5), rtol=1e-6)
    assert reference._rms_norm(x, scale, 1e-5)[0, 0, 1] == pytest.approx(
        4.0 / rms * 2.0, rel=1e-6)


def test_the_default_block_is_gpt2s_tree():
    from horovod_tpu import models

    cfg = models.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                   n_layers=1, d_ff=32, max_seq_len=8)
    assert cfg.block == models.GPT2_BLOCK == models.BlockSpec()
    shapes = jax.eval_shape(lambda: models.Transformer(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    names = {jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(shapes)}
    assert names == {
        "['params']['embed'].value", "['params']['pos'].value",
        "['params']['layer_0']['attn']['wqkv'].value",
        "['params']['layer_0']['attn']['wo'].value",
        "['params']['layer_0']['ln1']['scale']",
        "['params']['layer_0']['ln1']['bias']",
        "['params']['layer_0']['ln2']['scale']",
        "['params']['layer_0']['ln2']['bias']",
        "['params']['layer_0']['mlp']['wi'].value",
        "['params']['layer_0']['mlp']['wo'].value",
        "['params']['ln_f']['scale']", "['params']['ln_f']['bias']"}
    # A gated dense feed-forward is the same block without experts.
    gated = dataclasses.replace(cfg, block=models.BlockSpec(ffn="swiglu"))
    tree = jax.eval_shape(lambda: models.Transformer(gated).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert sorted(tree["params"]["layer_0"]["mlp"]) == ["wg", "wi", "wo"]


def test_the_planner_counts_the_expert_leaves():
    import horovod_tpu as hvd

    cell, model, params, _ = _assembled("float32")
    plan = hvd.plan(jax.eval_shape(lambda: params), batch=1, chips=1,
                    **model.plan_kwargs)
    assert plan.workload.num_experts == 8
    # Two layers of three (8, 64, 32) float32 panels; the router, the
    # (4, 16, 64) attention output and the norms are not expert weights.
    assert plan.workload.expert_param_bytes == 2 * 3 * 8 * 64 * 32 * 4
    total = sum(a.size * 4 for a in jax.tree.leaves(params))
    assert plan.workload.param_bytes == total


# ----------------------------------------------------------- flops_moe ----

def _published():
    with open(os.path.join(CONFIGS, "olmoe-1b-7b.json")) as f:
        return json.load(f)


def test_one_olmoe_layer_by_hand():
    s, d, h, hd, e, k, f = 4096, 2048, 16, 128, 64, 8, 1024
    projections = 4 * 2 * s * d * d
    attention = h * 2 * (2 * (s * (s + 1) // 2) * hd)
    router = 2 * s * d * e
    experts = 3 * 2 * (s * k) * d * f          # the 8 a token uses
    assert flops_moe.olmoe_layer_forward_ops(
        s, hidden=d, n_head=h, head_dim=hd, n_experts=e, k=k,
        expert_width=f) == projections + attention + router + experts
    assert experts == 412316860416 and router == 1073741824


def test_olmoe_step_at_the_published_widths():
    from benchmark.builders import olmoe

    config = _published()
    ops = flops_moe.olmoe_step_ops(
        1, 4096, vocab=config["vocab_size"],
        n_layer=config["num_hidden_layers"], **olmoe.sizes_of(config))
    head = 2 * 4096 * 2048 * 50304
    # 3 x (one layer + the head): 4.4 TFLOP, 2.5 of them the head's.
    assert ops == 3 * (flops_moe.olmoe_layer_forward_ops(
        4096, **olmoe.sizes_of(config)) + head)
    assert 4.3e12 < ops < 4.5e12 and 2.5e12 < 3 * head < 2.6e12
    # All 64 experts for every token would be 8 times the experts' part.
    dense = flops_moe.olmoe_step_ops(
        1, 4096, vocab=config["vocab_size"], n_layer=1,
        **dict(olmoe.sizes_of(config), k=64))
    assert dense - ops == 3 * 7 * flops_moe.expert_forward_ops(
        4096, 2048, 1024, 8)


def test_expert_matmul_work_by_hand():
    ops, nbytes = flops_moe.expert_matmul_work(
        4096, hidden=2048, expert_width=1024, n_experts=64, k=8)
    assert ops == 9 * 2 * 32768 * 2048 * 1024       # 1.24 TFLOP
    panels = 3 * 64 * 2048 * 1024 * 4               # float32, 1.6 GB
    rows = 32768 * 2048 * 2                         # bf16, 134 MB
    assert nbytes == 3 * panels + 5 * rows
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, roof = flops.roofline_seconds(ops, nbytes, peak)
    # 6.28 ms of matmul against 6.72 ms of weight and row traffic.
    assert roof == "memory" and 6.5e-3 < least < 6.9e-3
    assert ops / peak["bf16_flops"] == pytest.approx(6.28e-3, rel=1e-2)


# -------------------------------------------------------------- scopes ----

STEP = "jit(hvd_bench_step)/"
FWD = STEP + "jvp(Transformer)/layer_0/"
BWD = STEP + "transpose(jvp(Transformer))/layer_0/"


@pytest.mark.parametrize("scope,phase,part", [
    (FWD + "moe/hvd_moe_router/dot_general", "forward", "mlp"),
    (FWD + "moe/hvd_moe_router/top_k", "forward", "mlp"),
    (FWD + "moe/hvd_moe_dispatch/sort", "forward", "mlp"),
    (BWD + "moe/hvd_moe_dispatch/gather", "backward", "mlp"),
    (FWD + "moe/hvd_moe_experts/ragged_dot_general", "forward", "mlp"),
    (BWD + "moe/hvd_moe_experts/ragged_dot_general", "backward", "mlp"),
    (BWD + "moe/hvd_moe_combine/tk,tkm->tm/dot_general", "backward", "mlp"),
    (FWD + "attn/rope/mul", "forward", "attn"),
    (BWD + "attn/rope/concatenate", "backward", "attn"),
    (FWD + "attn/q_norm/reduce_sum", "forward", "norm"),
    (BWD + "attn/k_norm/mul", "backward", "norm"),
])
def test_phase_and_part_of_the_new_scopes(scope, phase, part):
    assert scope_view.classify(scope, "") == (phase, part)


def test_the_compilers_ragged_dot_kernels_are_not_taken_for_flash():
    """libtpu lowers ``ragged_dot`` to Mosaic calls of its own (7
    operands, and 1 for the metadata; copied from the step compiled for
    a v5e). ``trace_reduce.flash_kernel`` tells flash attention's calls
    by their NAME (``hvd_flash_*``), so these are none of them, and the
    scope view files them under the expert layer."""
    from benchmark import trace_reduce as tr

    ragged = ('%ragged-dot-none.7 = bf16[32768,1024]{1,0:T(8,128)(2,1)} '
              'custom-call(%get-tuple-element.16, %copy-done.9, '
              '%copy-done.10, %copy-done.11, %get-tuple-element.16, '
              '/*index=5*/%x.1, %copy.3), '
              'custom_call_target="tpu_custom_call", '
              'operand_layout_constraints={s32[1]{0}, s32[65]{0}}')
    metadata = ('%ragged-dot-metadata.1 = (s32[65]{0:T(128)}, '
                's32[127]{0:T(128)}, s32[127]{0:T(128)}, s32[1]{0:T(128)}) '
                'custom-call(%gs.1), custom_call_target="tpu_custom_call"')
    for text in (ragged, metadata):
        assert tr.is_mosaic_call(text) and tr.flash_kernel(text) == ""
        assert scope_view.classify(
            FWD + "moe/hvd_moe_experts/ragged_dot_general", text) == (
                "forward", "mlp")


def test_the_scope_constants_are_what_the_layer_sets():
    from horovod_tpu.jax import introspect

    assert (introspect.SCOPE_MOE_ROUTER, introspect.SCOPE_MOE_DISPATCH,
            introspect.SCOPE_MOE_EXPERTS, introspect.SCOPE_MOE_COMBINE,
            introspect.SCOPE_ROPE) == (
        "hvd_moe_router", "hvd_moe_dispatch", "hvd_moe_experts",
        "hvd_moe_combine", "rope")
    from benchmark import moe_view

    assert (moe_view.ROUTER, moe_view.DISPATCH, moe_view.EXPERTS,
            moe_view.COMBINE) == (
        introspect.SCOPE_MOE_ROUTER, introspect.SCOPE_MOE_DISPATCH,
        introspect.SCOPE_MOE_EXPERTS, introspect.SCOPE_MOE_COMBINE)
    cell, model, params, tokens = _assembled("float32", n_layers=1)
    text = jax.jit(jax.grad(
        lambda p: model.loss(p, {}, tokens)[0])).lower(params).as_text(
            debug_info=True)
    for name in ("moe/hvd_moe_router", "moe/hvd_moe_dispatch",
                 "moe/hvd_moe_experts", "moe/hvd_moe_combine", "attn/rope",
                 "attn/q_norm", "attn/k_norm"):
        assert "jvp(Transformer)/layer_0/" + name in text, name
        assert "transpose(jvp(Transformer))/layer_0/" + name in text, name


def test_the_moe_readers_on_the_recorded_trace(capsys):
    """The recorded step with its feed-forward named as the expert layer
    names itself: forward matmul under ``hvd_moe_experts``, its
    backward split between the experts and the dispatch."""
    step = RECORDED_STEP.replace(
        "jvp(Transformer)/layer_0/mlp/dot_general\"}\n  %convert",
        "jvp(Transformer)/layer_0/moe/hvd_moe_experts/ragged_dot\"}\n"
        "  %convert").replace(
        "transpose(jvp(Transformer))/layer_0/mlp/dot_general",
        "transpose(jvp(Transformer))/layer_0/moe/hvd_moe_dispatch/gather")
    assert step.count("/moe/") == 2
    ctx = _ctx(step)
    cell = cells.load(CELL)
    ctx.cell = cell
    got = {name: reader(name)(ctx) for name in (
        "moe.layer_ms", "moe.experts_ms", "moe.dispatch_ms",
        "moe.experts_roofline")}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["moe.layer_ms"] == pytest.approx(
        got["moe.experts_ms"] + got["moe.dispatch_ms"])
    # The same events the scope view files under part ``mlp``.
    assert got["moe.layer_ms"] == pytest.approx(
        scope_view.part_ms(ctx, "mlp"))
    ops, nbytes = flops_moe.expert_matmul_work(
        4096, hidden=2048, expert_width=1024, n_experts=64, k=8)
    least, _ = flops.roofline_seconds(ops, nbytes, ctx.peak)
    assert got["moe.experts_roofline"] == pytest.approx(
        100 * 1e3 * least / got["moe.experts_ms"])
    assert "expert matmuls:" in capsys.readouterr().err
    # A program without the layer, or a ctx a reader cannot use: nothing,
    # and no exception.
    plain = _ctx(RECORDED_STEP)
    plain.cell = cell
    broken = _ctx("HloModule jit_small_step")
    broken.win0 = None
    for name in got:
        assert reader(name)(plain) is None
        assert reader(name)(broken) is None
