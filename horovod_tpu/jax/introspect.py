"""Jaxpr introspection: prove gradient sync runs through hvd's collectives.

Under plain ``pjit`` auto-sharding the DistributedOptimizer takes the
identity path (no bound axis name) and XLA inserts cross-replica
reductions on its own — numerically fine, but then none of the
framework's data plane (``ops.collective_ops``) is in the program, and a
"hvd trains multi-chip" claim would be vacuous. These helpers inspect
the traced jaxpr for the collective primitives the framework emits
(``lax.psum`` / ``psum_scatter`` / ``all_gather`` / ...), so a
regression to the identity path fails loudly instead of silently
delegating to XLA.

XLA auto-sharding reductions are inserted by the SPMD partitioner at
compile time and never appear in the jaxpr, so any collective primitive
found here was traced by framework (or user) code — exactly the
distinction the check needs.

Reference parity: the collectives being asserted are the repo's
equivalents of the reference's data-plane ops
(reference: horovod/common/ops/nccl_operations.cc:156-214 flat
allreduce, :233-440 hierarchical reduce-scatter/cross-allreduce/
all-gather).
"""

from __future__ import annotations

import re
from typing import Dict, List, Sequence

import jax

# Program scopes: the names under which the framework's own work shows
# in the ``op_name`` metadata of a compiled step, where flax's module
# scopes (``Transformer/layer_3/attn``) do not reach. ``jax.named_scope``
# acts at trace time only; docs/timeline.md lists who reads which.
SCOPE_SYNC = "hvd_sync"          # allreduce_gradients, in-graph branch
SCOPE_UPDATE = "hvd_update"      # the inner optimizer's update
SCOPE_FLASH = "hvd_flash"        # ops/pallas_attention.py, kernels + glue
SCOPE_EMBED = "embed"            # Transformer: lookup + positions
SCOPE_LOGITS = "logits"          # Transformer: output projection
SCOPE_ROPE = "rope"              # attention: rotary positions on q, k
# LatentAttention: both down-projections with their norms, both
# up-projections, and q, k, v put together (RoPE keeps ``rope``).
SCOPE_MLA_LATENT = "hvd_mla_latent"
# SelfAttention with ``BlockSpec.attn_gate``: the sigmoid of the gate
# projection times the attention output (the projection itself is the
# module's own).
SCOPE_ATTN_GATE = "hvd_attn_gate"
# ShortConv (a ``conv`` layer's mixer, flax scope ``conv``): the two gate
# multiplies and the causal taps between the in- and out-projections
# (the projections themselves are the module's own).
SCOPE_CONV_GATE = "hvd_conv_gate"
# SelfAttention with ``BlockSpec.index_topk`` (a learned key selection,
# DeepSeek sparse attention's indexer): under the first the indexer's
# three projections, its norm, RoPE and the (S, S) float32 scores; under
# the second each row's ``index_topk``-th largest score and the mask
# it makes (the packed planes are ops/pallas_attention.py's).
SCOPE_DSA_INDEX = "hvd_dsa_index"
SCOPE_DSA_SELECT = "hvd_dsa_select"
# Mamba (a ``mamba`` layer's mixer, flax scope ``mamba``): under the first
# the selective scan, its two kernels (ops/pallas_scan.py) and the pads
# and transposes round them; under the second the causal taps, their bias
# and the ``silu`` that make the scan's input. The four projections are
# the module's own.
SCOPE_SSM_SCAN = "hvd_ssm_scan"
SCOPE_SSM_CONV = "hvd_ssm_conv"
# MemoryUnit (a ``memory_unit`` layer's mixer, flax scope ``gmu``): the
# ``silu`` of the gate projection times the scan output another layer
# published (the two projections are the module's own).
SCOPE_GMU = "hvd_gmu"
# Differential attention (``BlockSpec.diff_attention``): the two maps'
# halves put side by side, lambda, the subtraction, the norm over each
# head's 2 D dims and the factor ``1 - lambda_init``.
SCOPE_DIFF_ATTN = "hvd_diff_attn"
# A looped stack (``TransformerConfig.passes`` > 1: ONE stack of blocks
# applied that many times a step): ``hvd_loop_pass_<t>`` round pass t of
# the stack with the norm that closes it (numbered: the loop is
# unrolled), ``hvd_loop_readout`` round each pass's output projection
# (itself still under ``logits``) and cross entropy, ``hvd_loop_exit``
# round the exit gate, the exit distribution and its entropy
# (models/transformer.py ``looped_loss``).
SCOPE_LOOP_PASS = "hvd_loop_pass"
SCOPE_LOOP_READOUT = "hvd_loop_readout"
SCOPE_LOOP_EXIT = "hvd_loop_exit"
# The expert layer (parallel/moe.py), inside the ``moe`` module's scope.
SCOPE_MOE_ROUTER = "hvd_moe_router"      # logits, softmax, top-k, aux losses
# The sorts (the gates ride one into row order); rows gathered from the
# (T, M) tokens; the visits of the layer's two sums; backward the sum
# of each token's rows (the kernel below).
SCOPE_MOE_DISPATCH = "hvd_moe_dispatch"
# The grouped matmuls and their activation, which the ROUTER's gate
# multiplies (its gradient is that fusion's reduction over F).
SCOPE_MOE_EXPERTS = "hvd_moe_experts"
# The plain sum of each token's live sorted rows (the kernel below);
# backward one gather from the (T, M) cotangent. No weighting here.
SCOPE_MOE_COMBINE = "hvd_moe_combine"
# Round the three above where the layer holds a share of the experts and
# chooses its row arrays' length on the device: the ``lax.cond`` and,
# once more, each branch as a whole
# (``moe/hvd_moe_rows/cond/branch_<i>_fun/hvd_moe_rows/hvd_moe_<part>/...``:
# inside the backward rule a transform's name wraps the inner one).
SCOPE_MOE_ROWS = "hvd_moe_rows"
# The shared expert's three matmuls, beside the routed sum.
SCOPE_MOE_SHARED = "hvd_moe_shared"
# Round the layer's ROUTING half (``hvd_moe_router`` and the sort of
# ``hvd_moe_dispatch`` stay inside it) where the router reads the
# block's normed input (``BlockSpec.router_tap`` 'mixer'): everything
# the layer does that depends on nothing its block's mixer makes.
SCOPE_MOE_PREROUTE = "hvd_moe_preroute"
# ``name=`` of the flash ``pallas_call``s (the Mosaic calls' op_name):
# the forward; the backward of a static mask in ONE pass; the two
# kernels that run it where the one pass's panels pass the VMEM cap.
KERNEL_FLASH_FWD = "hvd_flash_fwd"
KERNEL_FLASH_BWD = "hvd_flash_bwd"
KERNEL_FLASH_DKV = "hvd_flash_dkv"
KERNEL_FLASH_DQ = "hvd_flash_dq"
# The same kernels under a mask that is DATA (``select=``): a fourth
# operand in the forward (the plane packed along the keys), a seventh in
# the one-pass backward (the plane packed along the queries, the only
# one a backward reads) and in each of the two kernels that run it past
# the VMEM cap.
KERNEL_DSA_FWD = "hvd_dsa_fwd"
KERNEL_DSA_BWD = "hvd_dsa_bwd"
KERNEL_DSA_DKV = "hvd_dsa_dkv"
KERNEL_DSA_DQ = "hvd_dsa_dq"
# ``name=`` of the call that CHOOSES the keys (ops/pallas_selection.py;
# under ``hvd_dsa_select``): scores, each query's ``index_topk``-th
# largest and both bit planes, a block of queries a pass. FOUR operands.
KERNEL_DSA_CHOOSE = "hvd_dsa_choose"
# ``name=`` of the expert layer's sum over a token's sorted rows
# (ops/pallas_gather_sum.py; under ``hvd_moe_combine`` forward and
# ``hvd_moe_dispatch`` backward, where ``_sum_per_token`` stands).
KERNEL_MOE_GATHER_SUM = "hvd_moe_gather_sum"
# ``name=`` of the expert layer's grouped matmuls
# (ops/pallas_grouped_matmul.py; under ``hvd_moe_experts``, where
# ``grouped_ffn`` stands): a group's rows times its expert's panel,
# forward and (the panel read transposed) the input gradient; and the
# weight gradient's. FIVE operands each.
KERNEL_MOE_GROUPED = "hvd_moe_gmm"
KERNEL_MOE_GROUPED_DW = "hvd_moe_gmm_dw"
# ``name=`` of the selective scan's two calls (ops/pallas_scan.py; under
# ``hvd_ssm_scan``): forward SIX operands, backward EIGHT.
KERNEL_SSM_SCAN_FWD = "hvd_ssm_scan_fwd"
KERNEL_SSM_SCAN_BWD = "hvd_ssm_scan_bwd"
# ``checkpoint_name``s of what the forward kernel made, as the backward
# kernels read it: the (B, H, S, D) output and the (B, H, S) float32
# log-sum-exp; and of what it READ, its (B, H, S, D) / (B, H_kv, S, D)
# operands, which are the backward kernels' too. ``Transformer``'s
# ``remat`` keeps these, so a recomputed block neither runs the kernel
# again nor makes its operands again.
SAVED_FLASH_OUT = "hvd_flash_out"
SAVED_FLASH_LSE = "hvd_flash_lse"
SAVED_FLASH_Q = "hvd_flash_q"
SAVED_FLASH_K = "hvd_flash_k"
SAVED_FLASH_V = "hvd_flash_v"
# The learned selection as the kernels read it: two bit planes of the
# (S, S) mask, packed along the keys (forward, dQ) and along the
# queries (dK/dV), S x S / 8 bytes each. Kept, a recomputed block
# neither scores nor selects again.
SAVED_FLASH_SELECT = "hvd_flash_select"
# What else a recomputed block keeps (models/transformer.py
# ``_remat_block`` holds the rule and the bytes): the matmul products
# that the backward pass reads. In the attention module the latent
# down-projections' products, which a NORM reads (a norm's backward
# reads its input), the output gate's projection, and the output
# projection's product; in the dense feed-forward (a shared expert is
# one) the up and gate products and, where a norm reads it, the output.
SAVED_ATTN_PRENORM = "hvd_attn_prenorm"
SAVED_ATTN_GATE = "hvd_attn_gate_proj"
SAVED_ATTN_OUT = "hvd_attn_out"
# A block without a kernel (ShortConv): its in-projection's product,
# which the gates' and taps' backward reads, and the branch's output.
SAVED_CONV_IN = "hvd_conv_in"
SAVED_CONV_OUT = "hvd_conv_out"
# A ``mamba`` block: the in-projection's product ``[x, z]``, the scan's
# input ``x'`` (taps, bias and ``silu`` done), the ``[r, B, C]`` product,
# the state at each chunk's start (the scan's backward remakes a chunk
# from it), the scan's output y (which a publishing layer also hands to
# its readers: one array), and the branch's output.
SAVED_SSM_IN = "hvd_ssm_in"
SAVED_SSM_X = "hvd_ssm_x"
SAVED_SSM_PROJ = "hvd_ssm_proj"
SAVED_SSM_STATES = "hvd_ssm_states"
SAVED_SSM_Y = "hvd_ssm_y"
SAVED_SSM_OUT = "hvd_ssm_out"
# A ``memory_unit`` block: its gate projection's product and the
# branch's output.
SAVED_GMU_GATE = "hvd_gmu_gate_proj"
SAVED_GMU_OUT = "hvd_gmu_out"
SAVED_MLP_UP = "hvd_mlp_up"
SAVED_MLP_GATE = "hvd_mlp_gate"
SAVED_MLP_OUT = "hvd_mlp_out"
# The same for what an expert layer that chooses its row arrays' length
# returns (parallel/moe.py ``_held_rows``): its backward rule recomputes
# from its INPUTS, so a block needs the layer's forward again only where
# it reads the OUTPUT once more (a norm on it); kept, that run is dead
# code. Saved only where the backward pass reads it.
SAVED_MOE_OUT = "hvd_moe_out"

# Primitive names the framework's in-graph data plane lowers to.
# (lax.psum_scatter traces as the "reduce_scatter" primitive.)
COLLECTIVE_PRIMITIVES = (
    "psum", "reduce_scatter", "all_gather", "all_to_all",
    "pmin", "pmax", "ppermute",
)


def equations(jaxpr, skip=()):
    """Every equation of ``jaxpr`` and of the jaxprs inside it
    (shard_map / scan / cond / custom-vjp bodies), outer first; not
    what is inside an equation of a primitive named in ``skip`` (the
    body of a ``pallas_call``)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name in skip:
            continue
        for v in eqn.params.values():
            for cand in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(cand, "jaxpr", cand)
                if hasattr(inner, "eqns"):
                    yield from equations(inner, skip)


def collective_counts(fn, *args, **kwargs) -> Dict[str, int]:
    """Trace ``fn`` and count collective primitives in the full jaxpr
    (descending into shard_map / scan / cond / custom-vjp subjaxprs)."""
    counts: Dict[str, int] = {}
    for eqn in equations(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMITIVES:
            counts[name] = counts.get(name, 0) + 1
    return counts


def assert_in_graph_gradient_sync(
    fn, *args,
    required: Sequence[str] = ("psum",),
    **kwargs,
) -> Dict[str, int]:
    """Assert the traced ``fn`` contains every primitive in ``required``.

    Returns the full count dict so callers can log it. Raises
    ``AssertionError`` naming what is missing — the tripwire for the
    identity-path regression (jax/optimizer.py ``_axis_in_scope``
    returning False under plain pjit).
    """
    counts = collective_counts(fn, *args, **kwargs)
    missing = [p for p in required if counts.get(p, 0) == 0]
    if missing:
        raise AssertionError(
            "gradient sync is NOT going through the framework's "
            "collectives: traced program is missing %r (found: %r). "
            "This usually means the step is running under plain pjit "
            "auto-sharding instead of shard_map over the data axis."
            % (missing, counts))
    return counts


# Argument attributes XLA uses to mark a donated (aliased) input
# buffer in lowered StableHLO text; jax >= 0.4.31 may emit
# jax.buffer_donor for donations the compiler is free to use or drop.
_DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")
_ARG_RE = re.compile(r"%arg(\d+):")


def donated_input_indices(fn, donate_argnums, *args, **kwargs) -> List[int]:
    """Flattened input indices whose buffers survive lowering as donated.

    Lowers ``jit(fn, donate_argnums=...)`` and scans the StableHLO for
    the ``tf.aliasing_output`` / ``jax.buffer_donor`` argument
    attributes. Donation requested at the Python level can be silently
    dropped by lowering (dtype/layout mismatch with every output, or a
    platform that refuses aliasing) — XLA then materializes a fresh
    buffer per step and only prints a warning; this makes the drop
    checkable. Indices are over the *flattened* argument list (a pytree
    argument contributes one entry per leaf).

    The scan is segment-based, not one regex over the attribute dict:
    sharded args carry ``mhlo.sharding = "{...}"`` whose quoted braces
    would defeat any brace-balanced pattern. Each entry-function
    signature line is split at its ``%argN:`` markers and a donation
    attribute is credited to the argument whose segment contains it.
    """
    lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(
        *args, **kwargs)
    out = set()
    for line in lowered.as_text().splitlines():
        # Donation attrs only ever appear on func signatures; the
        # public @main is the jit entry point.
        if "func.func" not in line or "@main" not in line:
            continue
        marks = list(_ARG_RE.finditer(line))
        for i, m in enumerate(marks):
            end = marks[i + 1].start() if i + 1 < len(marks) else len(line)
            seg = line[m.end():end]
            if any(mk in seg for mk in _DONATION_MARKERS):
                out.add(int(m.group(1)))
    return sorted(out)


def assert_donation_survives_lowering(
    fn, donate_argnums, *args,
    min_donated: int = 1,
    **kwargs,
) -> List[int]:
    """Assert at least ``min_donated`` flattened inputs stay donated
    through lowering. Returns the donated indices for logging."""
    donated = donated_input_indices(fn, donate_argnums, *args, **kwargs)
    if len(donated) < min_donated:
        raise AssertionError(
            "buffer donation did NOT survive lowering: requested "
            "donate_argnums=%r but only %d flattened inputs carry an "
            "aliasing attribute (expected >= %d). XLA will materialize "
            "fresh gradient/optimizer buffers every step."
            % (donate_argnums, len(donated), min_donated))
    return donated


# ------------------------------------------------- compiled-step scopes ---

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
_OPERAND = re.compile(r"%([^\s,(){}]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRANSFORM = re.compile(r"[a-z_]+\(")     # jit(f)/..., pmap(f)/...
_SHAPE = re.compile(r"\b(pred|token|[a-z]+\d+[a-z0-9]*)\[([\d,]*)\]")
_BITS = re.compile(r"\d+")
# Attributes that name a computation whose instructions run (and show in
# a device trace) as instructions of their own. A fusion's ``calls`` and
# a reduction's ``to_apply`` do not.
_CALLED = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%([^\s,(){}]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_CALLED_BY_CALL = re.compile(r"\b(?:to_apply|calls)=%([^\s,(){}]+)")


def _type_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        bits = _BITS.search(dtype)
        size = max(int(bits.group()) // 8, 1) if bits else 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        total += size
    return total


def _operand_text(line: str, start: int) -> str:
    """The text between the opcode's parenthesis at ``start`` and its
    match (layouts such as ``T(8,128)`` nest inside)."""
    depth = 0
    for i in range(start, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i]
    return line[start + 1:]


def _parse_computations(hlo_text: str):
    """name -> [(instruction, opcode, result bytes, operands, op_name,
    called computations)] in the text's (scheduled) order; the entry
    computation's name; and each computation's ROOT instruction."""
    computations, roots, entry, current = {}, {}, None, None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group(1)
                computations[current] = []
                if line.startswith("ENTRY "):
                    entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        op = m and _OPCODE.search(line, m.end() - 1)
        if not op:
            continue
        rest = line[op.end() - 1:]
        operands = _OPERAND.findall(_operand_text(rest, 0))
        attributes = rest.split(", metadata={", 1)[0]
        called = [c.strip().lstrip("%")
                  for one, many in _CALLED.findall(attributes)
                  for c in (one or many).split(",")]
        if op.group(1) in ("call", "async-start"):
            called += _CALLED_BY_CALL.findall(attributes)
        # A parameter, and a copy of one, carries its argument's name
        # (``params['embed']``) where a scope would be (``jit(step)/...``).
        scope = _OP_NAME.search(rest)
        scope = scope.group(1) if scope else ""
        computations[current].append(
            (m.group(1), op.group(1), _type_bytes(line[m.end():op.start()]),
             operands, scope if _TRANSFORM.match(scope) else "", called))
        if "ROOT %" in line[:m.end()]:
            roots[current] = m.group(1)
    return computations, entry, roots


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` for the entry computation of a compiled
    step (``compiled.as_text()``) and every computation it calls as a
    loop body, a condition or a branch: the instructions a device trace
    has events for, each with the program scope it ran under
    (``jit(step)/jvp(Transformer)/layer_3/attn/...``).

    Most instructions the compiler makes itself carry no metadata
    (``copy-start``/``-done``, ``slice-start``, bitcasts, the ``while``
    loops it builds to re-tile a large array). They inherit one: a
    ``-done`` takes its ``-start``'s; a parameter of a called
    computation takes its caller's; anything else takes the scope of the
    producer of its largest operand (so a ``tuple``, a
    ``get-tuple-element``, a ``bitcast`` or a ``copy`` hands on what it
    carries, and a ``while`` lands on the largest array it loops over),
    then of its smaller operands; failing all that, its first user's.
    What still has none maps to ``""``.
    """
    computations, entry, roots = _parse_computations(hlo_text)
    if entry is None:
        return {}
    up: Dict[str, str] = {}      # own scope, or inherited from producers
    size: Dict[str, int] = {}
    order = []                   # (computation, caller), callers first
    visited = set()

    def forward(name, caller):
        visited.add(name)
        order.append((name, caller))
        for inst, opcode, nbytes, operands, own, called in \
                computations[name]:
            size[inst] = nbytes
            scope = own
            if not scope and opcode == "parameter":
                scope = up.get(caller, "")
            elif not scope and opcode.endswith("-done") and operands:
                scope = up.get(operands[0], "")
            if not scope:
                for operand in sorted(operands, key=lambda o: -size.get(o, 0)):
                    scope = up.get(operand, "")
                    if scope:
                        break
            up[inst] = scope
            for sub in called:
                if sub in computations and sub not in visited:
                    forward(sub, inst)

    forward(entry, None)
    scopes: Dict[str, str] = {}
    for name, caller in order:
        first_user: Dict[str, str] = {}
        instructions = computations[name]
        for inst, _, _, operands, _, _ in instructions:
            for operand in operands:
                first_user.setdefault(operand, inst)
        root = roots.get(name)
        for inst, *_ in reversed(instructions):
            scope = up[inst]
            if not scope and inst in first_user:
                scope = scopes.get(first_user[inst], "")
            if not scope and inst == root and caller is not None:
                scope = scopes.get(caller, "")
            scopes[inst] = scope
    return scopes
