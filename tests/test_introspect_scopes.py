"""Program scopes in the compiled step (jax/introspect.py): the names the
framework gives where flax gives none, the join from instruction to
scope with its inheritance rule, and that a scope changes no arithmetic.
"""

import contextlib
import re

import numpy as np
import optax

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu.jax as hvd_jax
from horovod_tpu.jax import introspect
from horovod_tpu.jax.optimizer import allreduce_transformation
from horovod_tpu.models import Transformer, TransformerConfig
from horovod_tpu.parallel.mesh import shard_map_compat

N_LAYERS = 2
CFG = TransformerConfig(vocab_size=256, d_model=32, n_heads=2,
                        n_layers=N_LAYERS, d_ff=64, max_seq_len=16,
                        dtype=jnp.float32, attention="flash")


def _step_and_args():
    """The tiny transformer's data-parallel AdamW step over two virtual
    devices, with concrete arguments."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    model = Transformer(CFG)
    tx = hvd_jax.DistributedOptimizer(optax.adamw(1e-2))

    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply(p, tokens[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens[:, 1:]).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss[None]

    sharded = jax.jit(shard_map_compat(
        step, mesh=mesh, in_specs=(P(), P(), P("data")),
        out_specs=(P(), P(), P("data"))))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 256),
        NamedSharding(mesh, P("data")))
    params = jax.tree.map(
        lambda x: getattr(x, "value", x),
        model.init(jax.random.PRNGKey(0), tokens[:1, :-1]),
        is_leaf=lambda x: hasattr(x, "value"))
    return sharded, tx, (params, tx.init(params), tokens)


def _equations(jaxpr, outer=""):
    """(equation, name stack) through the sub-jaxprs; a jitted helper's
    own equations carry a stack relative to their caller's."""
    for eqn in jaxpr.eqns:
        stack = outer + "/" + str(eqn.source_info.name_stack)
        yield eqn, stack
        for v in eqn.params.values():
            for cand in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, stack)


def test_compiled_step_names_sync_update_head_and_kernels():
    sharded, _, args = _step_and_args()
    text = sharded.lower(*args).compile().as_text()
    scopes = introspect.instruction_scopes(text)

    # XLA may combine the leaves' psums; whatever it leaves sits
    # directly under hvd_sync.
    collectives = re.findall(r"^\s*%(\S+) = .* all-reduce(?:-start)?\(",
                             text, re.M)
    assert collectives
    assert all("/shard_map/hvd_sync/psum" in scopes[c]
               for c in collectives), [scopes[c] for c in collectives]

    # Every equation of the inner optimizer is traced under hvd_update
    # (a compiled fusion shows its root's name only, so count in the
    # jaxpr): as many there as adamw's update has alone.
    params, (_, adam_state), _ = args
    alone = jax.make_jaxpr(optax.adamw(1e-2).update)(
        params, adam_state, params)
    in_step = [e for e, stack in _equations(
        jax.make_jaxpr(sharded)(*args).jaxpr)
        if introspect.SCOPE_UPDATE in stack]
    assert len(in_step) == len(list(_equations(alone.jaxpr))) > 100
    top_level = {s.rpartition("/")[2] for s in scopes.values()
                 if re.fullmatch(r"jit\(\w+\)/shard_map/[a-z_]+", s)}
    assert top_level == {"add"}      # optax.apply_updates

    def layers_with(fragment):
        return {re.search(r"/layer_(\d+)/", s).group(1)
                for s in scopes.values() if fragment in s}

    flash = "/attn/%s/" % introspect.SCOPE_FLASH
    # The static backward is ONE named call under ``hvd_flash``.
    for kernel, direction in ((introspect.KERNEL_FLASH_FWD, "/jvp("),
                              (introspect.KERNEL_FLASH_BWD, "/transpose(")):
        assert len(layers_with(flash + kernel + "/")) == N_LAYERS
        assert all(direction in s for s in scopes.values()
                   if flash + kernel + "/" in s)
    for scope in (introspect.SCOPE_EMBED, introspect.SCOPE_LOGITS):
        assert any("/jvp(Transformer)/%s/" % scope in s
                   for s in scopes.values())
        assert any("/transpose(jvp(Transformer))/%s/" % scope in s
                   for s in scopes.values())


HAND_WRITTEN = """\
HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[8]}

%fused_add (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %inside = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(step)/never/seen"}
}

%body (arg: (s32[], f32[4096,8], f32[8])) -> (s32[], f32[4096,8], f32[8]) {
  %arg = (s32[], f32[4096,8]{1,0:T(8,128)(2,1)}, f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %big = f32[4096,8]{1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %dus.1 = f32[4096,8]{1,0:T(8,128)(2,1)} dynamic-update-slice(%big, %big, %i, %i)
  %small = f32[8]{0} get-tuple-element(%arg), index=2
  ROOT %out = (s32[], f32[4096,8]{1,0:T(8,128)(2,1)}, f32[8]{0}) tuple(%i, %dus.1, %small)
}

%cond (arg.1: (s32[], f32[4096,8], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[4096,8]{1,0:T(8,128)(2,1)}, f32[8]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %lt = pred[] compare(%i.1, %i.1), direction=LT
}

ENTRY %main (w: f32[8], x: f32[4096,8]) -> f32[8] {
  %w = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %x = f32[4096,8]{1,0} parameter(1), metadata={op_name="batch"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(%w)
  %zero = s32[] constant(0)
  %grad = f32[4096,8]{1,0:T(8,128)(2,1)} fusion(%x), kind=kLoop, calls=%fused_add, metadata={op_name="jit(step)/transpose(jvp())/mul" source_file="loss.py"}
  %norm = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_add, metadata={op_name="jit(step)/jvp(Transformer)/ln_f/reduce_sum"}
  %bitcast.1 = f32[4096,8]{1,0:T(8,128)(2,1)} bitcast(%grad)
  %tuple.1 = (s32[], f32[4096,8]{1,0:T(8,128)(2,1)}, f32[8]{0}) tuple(%zero, %bitcast.1, %norm)
  %while.1 = (s32[], f32[4096,8]{1,0:T(8,128)(2,1)}, f32[8]{0}) while(%tuple.1), condition=%cond, body=%body
  %gte.1 = f32[4096,8]{1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=1
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %lonely = f32[] constant(1)
  ROOT %update = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_add, metadata={op_name="jit(step)/hvd_update/add"}
}
"""


def test_instructions_without_metadata_inherit_a_scope():
    scopes = introspect.instruction_scopes(HAND_WRITTEN)
    loss_bwd = "jit(step)/transpose(jvp())/mul"
    update = "jit(step)/hvd_update/add"
    # An argument's name is no scope: the prefetch of a weight takes its
    # first user's, and the -done its -start's.
    assert scopes["copy-start.1"] == update
    assert scopes["copy-done.1"] == update
    # Through bitcast and tuple to the largest array the loop carries,
    # not the index and not the small LayerNorm row; then into the body
    # and the condition, and out through get-tuple-element.
    assert scopes["bitcast.1"] == scopes["tuple.1"] == loss_bwd
    assert scopes["while.1"] == scopes["gte.1"] == loss_bwd
    assert scopes["dus.1"] == scopes["out"] == scopes["lt"] == loss_bwd
    # Own metadata wins; a fusion's inside is no instruction of the step;
    # what nothing reaches has the empty scope.
    assert scopes["norm"] == "jit(step)/jvp(Transformer)/ln_f/reduce_sum"
    assert "inside" not in scopes
    assert scopes["lonely"] == ""
    assert introspect.instruction_scopes("") == {}


CONDITIONAL = """\
HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[8,8]}

%prefix (arg: (f32[64,8], f32[512,8], f32[512,8], f32[8,8])) -> f32[8,8] {
  %arg = (f32[64,8]{1,0}, f32[512,8]{1,0}, f32[512,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  %tokens = f32[64,8]{1,0} get-tuple-element(%arg), index=0
  %rows = f32[64,8]{1,0} fusion(%tokens, %tokens), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/moe/cond/branch_1_fun/hvd_moe_dispatch/gather"}
  %panel = f32[512,8]{1,0} get-tuple-element(%arg), index=1, metadata={op_name="jit(step)/moe/cond/branch_1_fun/hvd_moe_experts/optimization_barrier"}
  %bare = f32[512,8]{1,0} get-tuple-element(%arg), index=2
  %turned = f32[512,8]{0,1} copy(%panel)
  %matmul = f32[64,8]{1,0} custom-call(%rows, %turned), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %unnamed = f32[64,8]{1,0} custom-call(%rows, %bare), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %sum = f32[8,8]{1,0} fusion(%matmul, %unnamed), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/moe/cond/branch_1_fun/hvd_moe_combine/reduce_sum"}
}

%whole (arg.1: (f32[64,8], f32[512,8], f32[512,8], f32[8,8])) -> f32[8,8] {
  %arg.1 = (f32[64,8]{1,0}, f32[512,8]{1,0}, f32[512,8]{1,0}, f32[8,8]{1,0}) parameter(0)
  ROOT %bare.1 = f32[8,8]{1,0} get-tuple-element(%arg.1), index=3
}

%fused (p0: f32[64,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[64,8]{1,0} parameter(0)
  ROOT %p1 = f32[8,8]{1,0} parameter(1)
}

ENTRY %main (w: f32[512,8], x: f32[64,8], b: f32[8,8]) -> f32[8,8] {
  %w = f32[512,8]{1,0} parameter(0), metadata={op_name="params['w']"}
  %x = f32[64,8]{1,0} parameter(1), metadata={op_name="batch"}
  %b = f32[8,8]{1,0} parameter(2), metadata={op_name="params['b']"}
  %fits = pred[] constant(true)
  %gathered = f32[64,8]{1,0} fusion(%x, %b), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/moe/hvd_moe_dispatch/gather"}
  %cast = f32[512,8]{1,0} fusion(%w, %b), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/moe/hvd_moe_experts/convert_element_type"}
  %operands = (f32[64,8]{1,0}, f32[512,8]{1,0}, f32[512,8]{1,0}, f32[8,8]{1,0}) tuple(%gathered, %cast, %cast, %b)
  ROOT %choice = f32[8,8]{1,0} conditional(%fits, %operands, %operands), branch_computations={%whole, %prefix}, metadata={op_name="jit(step)/moe/cond"}
}
"""


def test_a_branch_parameter_takes_the_conditionals_scope():
    """What a ``conditional`` hands a branch comes in under the
    ``conditional``'s OWN name, whatever named it outside: a grouped
    matmul, whose own name is bare, whose panel is its largest operand
    and comes straight from the branch's argument is filed under
    ``.../cond`` and no part of the layer. So the program names the
    panel INSIDE the branch (``parallel/moe.py`` ``_rows_branch``: a
    barrier under the experts' scope, which the compiler turns into the
    named ``get-tuple-element``), and the matmul, with the compiler's
    re-laid copy of the panel, reads that."""
    scopes = introspect.instruction_scopes(CONDITIONAL)
    barrier = ("jit(step)/moe/cond/branch_1_fun/hvd_moe_experts/"
               "optimization_barrier")
    assert scopes["panel"] == scopes["turned"] == scopes["matmul"] == barrier
    assert scopes["arg"] == scopes["bare"] == scopes["unnamed"] \
        == scopes["tokens"] == "jit(step)/moe/cond"
    assert scopes["rows"].endswith("hvd_moe_dispatch/gather")
    assert scopes["sum"].endswith("hvd_moe_combine/reduce_sum")
    assert scopes["bare.1"] == scopes["choice"] == "jit(step)/moe/cond"
    assert scopes["operands"].endswith("hvd_moe_experts/convert_element_type")


def test_scopes_change_no_arithmetic_and_no_state(monkeypatch):
    sharded, tx, args = _step_and_args()
    scoped = sharded(*args)
    assert "hvd_update" in sharded.lower(*args).as_text(debug_info=True)

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, _, _ = _step_and_args()
    assert "hvd_update" not in bare.lower(*args).as_text(debug_info=True)
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(bare(*args))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    plain = optax.chain(allreduce_transformation(), optax.adamw(1e-2))
    assert (jax.tree.structure(tx.init(args[0]))
            == jax.tree.structure(plain.init(args[0])))
    # Extra arguments still reach an inner transformation that takes them.
    seen = []
    inner = optax.GradientTransformationExtraArgs(
        lambda p: optax.EmptyState(),
        lambda u, s, p=None, *, scale: (seen.append(scale) or u, s))
    grads = {"w": jnp.ones(3)}
    wrapped = hvd_jax.DistributedOptimizer(inner)
    wrapped.update(grads, wrapped.init(grads), grads, scale=2.0)
    assert seen == [2.0]
