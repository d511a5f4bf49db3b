"""np=2 torch worker: DistributedOptimizer grad-hook correctness.

Both ranks train one step on different data; the resulting parameters
must (a) be identical across ranks, (b) equal a single-process SGD step
on the mean gradient (the reference's core DistributedOptimizer
invariant).
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import torch  # noqa: E402

import horovod_tpu.torch as hvd  # noqa: E402


def main():
    hvd.init()
    r, n = hvd.rank(), hvd.size()
    torch.manual_seed(42)  # same init everywhere

    model = torch.nn.Linear(4, 2, bias=True)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    opt = hvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    hvd.broadcast_optimizer_state(opt, root_rank=0)

    # Per-rank batch, deterministic.
    g = torch.Generator().manual_seed(100 + r)
    x = torch.randn(8, 4, generator=g)
    loss = model(x).pow(2).mean()
    loss.backward()
    opt.step()

    # Reference computation: mean gradient across both ranks' batches.
    ref = torch.nn.Linear(4, 2, bias=True)
    torch.manual_seed(42)
    ref = torch.nn.Linear(4, 2, bias=True)
    grads = []
    for k in range(n):
        gk = torch.Generator().manual_seed(100 + k)
        xk = torch.randn(8, 4, generator=gk)
        ref.zero_grad()
        ref(xk).pow(2).mean().backward()
        grads.append([p.grad.clone() for p in ref.parameters()])
    mean_grads = [sum(gs) / n for gs in zip(*grads)]
    expect = [p.detach() - 0.1 * g for p, g in
              zip(ref.parameters(), mean_grads)]

    for p, e in zip(model.parameters(), expect):
        np.testing.assert_allclose(p.detach().numpy(), e.numpy(),
                                   rtol=1e-5, atol=1e-6)

    # Cross-rank identity check.
    gathered = hvd.allgather_object(
        [p.detach().numpy() for p in model.parameters()])
    for other in gathered:
        for a, b in zip(other, gathered[0]):
            np.testing.assert_array_equal(a, b)

    # SyncBatchNorm across ranks: stats must match the combined batch.
    sbn = hvd.SyncBatchNorm(3)
    sbn.train()
    gg = torch.Generator().manual_seed(7 + r)
    xb = torch.randn(4, 3, 5, generator=gg)
    out = sbn(xb)
    all_x = torch.cat([torch.randn(4, 3, 5,
                                   generator=torch.Generator().manual_seed(7 + k))
                       for k in range(n)], dim=0)
    bn = torch.nn.BatchNorm1d(3)
    bn.train()
    expect_all = bn(all_x)
    expect_mine = expect_all[r * 4:(r + 1) * 4]
    np.testing.assert_allclose(out.detach().numpy(),
                               expect_mine.detach().numpy(), atol=1e-5)

    # Sparse allreduce: embedding-style sparse grads survive both paths
    # (reference: test_torch.py sparse variants; mpi_ops.py:515-535).
    emb = torch.nn.Embedding(10, 4, sparse=True)
    with torch.no_grad():
        emb.weight.fill_(0.0)
    opt = torch.optim.SGD(emb.parameters(), lr=1.0)
    opt = hvd.DistributedOptimizer(
        opt, named_parameters=emb.named_parameters())
    # Each rank touches rows {r, 2}: row 2 is shared, rows 0/1 unique.
    idx = torch.tensor([r, 2])
    loss = emb(idx).sum()
    loss.backward()
    opt.step()
    # d(sum)/d(row) = 1 for touched rows; averaged over 2 ranks:
    # unique rows get 0.5, the shared row gets 1.0. SGD lr=1 subtracts.
    w = emb.weight.detach()
    np.testing.assert_allclose(w[2].numpy(), -1.0 * np.ones(4), atol=1e-6)
    for k in range(n):
        np.testing.assert_allclose(w[k].numpy(), -0.5 * np.ones(4),
                                   atol=1e-6)
    # sparse_as_dense path reduces identically.
    emb2 = torch.nn.Embedding(10, 4, sparse=True)
    with torch.no_grad():
        emb2.weight.fill_(0.0)
    opt2 = torch.optim.SGD(emb2.parameters(), lr=1.0)
    opt2 = hvd.DistributedOptimizer(
        opt2, named_parameters=emb2.named_parameters(),
        sparse_as_dense=True)
    emb2(torch.tensor([r, 2])).sum().backward()
    opt2.step()
    np.testing.assert_allclose(emb2.weight.detach().numpy(),
                               w.numpy(), atol=1e-6)

    # gradient_predivide_factor is scale-neutral: prescale 1/f and
    # postscale f must cancel around the average (reference:
    # optimizer.py:196-200).
    lin = torch.nn.Linear(3, 1, bias=False)
    with torch.no_grad():
        lin.weight.fill_(0.0)
    opt3 = hvd.DistributedOptimizer(
        torch.optim.SGD(lin.parameters(), lr=1.0),
        named_parameters=lin.named_parameters(),
        gradient_predivide_factor=4.0)
    xin = torch.full((1, 3), float(r + 1))
    lin(xin).sum().backward()
    opt3.step()
    # grad = x, averaged over ranks: (1+2)/2 = 1.5; lr=1 subtracts.
    np.testing.assert_allclose(lin.weight.detach().numpy(),
                               -1.5 * np.ones((1, 3)), atol=1e-6)

    # fp16 gradient compression: reduce in half precision, decompress
    # back (reference: torch/compression.py:20-74); small magnitudes
    # keep ~1e-3 fidelity.
    lin16 = torch.nn.Linear(3, 1, bias=False)
    with torch.no_grad():
        lin16.weight.fill_(0.0)
    optc = hvd.DistributedOptimizer(
        torch.optim.SGD(lin16.parameters(), lr=1.0),
        named_parameters=lin16.named_parameters(),
        compression=hvd.Compression.fp16)
    lin16(torch.full((1, 3), float(r + 1))).sum().backward()
    optc.step()
    np.testing.assert_allclose(lin16.weight.detach().numpy(),
                               -1.5 * np.ones((1, 3)), atol=1e-3)

    # Delta-Adasum optimizer (reference: optimizer.py:335-503): with
    # identical data on both ranks the adasum merge of two identical
    # deltas is that delta, so training matches single-process SGD.
    torch.manual_seed(99)
    ada = torch.nn.Linear(3, 1, bias=False)
    ref = torch.nn.Linear(3, 1, bias=False)
    with torch.no_grad():
        ref.weight.copy_(ada.weight)
    opt_ada = hvd.DistributedOptimizer(
        torch.optim.SGD(ada.parameters(), lr=0.1),
        named_parameters=ada.named_parameters(), op=hvd.Adasum)
    opt_ref = torch.optim.SGD(ref.parameters(), lr=0.1)
    xa = torch.tensor([[1.0, 2.0, 3.0], [0.5, -1.0, 2.0]])
    ya = torch.tensor([[1.0], [0.0]])
    for _ in range(3):
        opt_ada.zero_grad()
        torch.nn.functional.mse_loss(ada(xa), ya).backward()
        opt_ada.step()
        opt_ref.zero_grad()
        torch.nn.functional.mse_loss(ref(xa), ya).backward()
        opt_ref.step()
    np.testing.assert_allclose(ada.weight.detach().numpy(),
                               ref.weight.detach().numpy(), atol=1e-5)

    dtype_op_matrix(r, n)
    grouped_inplace(r, n)
    grouped_mixed_dtypes(r, n)
    collective_surfaces(r, n)
    async_handles(r, n)
    process_sets_through_binding(r, n)
    optimizer_state_broadcast(r, n)
    scale_factor_matrix(r, n)
    alltoall_edge_cases(r, n)
    backward_passes_accumulation(r, n)
    bf16_compression_and_uneven_reducescatter(r, n)
    join_through_binding(r, n)
    error_propagation(r, n)
    sync_bn_backward(r, n)

    hvd.shutdown()
    print("TORCH_OK rank=%d" % r)
    return 0


def scale_factor_matrix(r, n):
    """prescale/postscale across dtypes through the binding
    (reference: Request pre/postscale fields, common/message.h:50;
    test_torch.py prescale/postscale variants). Scaling happens in the
    reduction pipeline, so integer tensors keep integer semantics only
    when the factors keep values integral."""
    for dt, tol in ((torch.float32, 1e-6), (torch.float64, 1e-12),
                    (torch.bfloat16, 2e-2)):
        x = torch.full((5,), float(r + 1), dtype=dt)
        out = hvd.allreduce(x, name="sf.%s" % dt, op=hvd.Sum,
                            prescale_factor=0.5)
        expect = 0.5 * sum(range(1, n + 1))
        np.testing.assert_allclose(out.to(torch.float64).numpy(),
                                   np.full(5, expect), rtol=tol,
                                   atol=tol)
        out = hvd.allreduce(x, name="sf.post.%s" % dt, op=hvd.Sum,
                            postscale_factor=2.0)
        np.testing.assert_allclose(out.to(torch.float64).numpy(),
                                   np.full(5, 2.0 * sum(range(1, n + 1))),
                                   rtol=tol, atol=tol)
    # Combined pre+post on Average: (pre * mean) * post.
    out = hvd.allreduce(torch.full((3,), float(r + 1)),
                        name="sf.both", op=hvd.Average,
                        prescale_factor=4.0, postscale_factor=0.25)
    mean = sum(range(1, n + 1)) / n
    np.testing.assert_allclose(out.numpy(), np.full(3, mean), rtol=1e-6)


def alltoall_edge_cases(r, n):
    """Zero-length splits and 2-D payloads through the binding
    (reference: alltoallv semantics — a rank may send nothing to some
    peer; test_torch.py alltoall variants)."""
    if n != 2:
        return
    # Rank 0 sends everything to rank 1, nothing to itself; rank 1
    # sends one row to each.
    data = torch.arange(2, dtype=torch.float32).reshape(2, 1) + 10.0 * r
    splits = torch.tensor([0, 2] if r == 0 else [1, 1])
    out, rsplits = hvd.alltoall(data, splits=splits, name="a2a.zero")
    if r == 0:
        np.testing.assert_allclose(out.numpy().ravel(), [10.0])
        np.testing.assert_array_equal(np.asarray(rsplits), [0, 1])
    else:
        np.testing.assert_allclose(out.numpy().ravel(),
                                   [0.0, 1.0, 11.0])
        np.testing.assert_array_equal(np.asarray(rsplits), [2, 1])
    # 2-D payload with trailing feature dim keeps row structure.
    mat = torch.arange(8, dtype=torch.float32).reshape(4, 2) \
        + 100.0 * r
    out2, _ = hvd.alltoall(mat, name="a2a.2d")
    assert out2.shape == (4, 2)
    expect = np.concatenate([
        (np.arange(8).reshape(4, 2) + 100.0 * k)[r * 2:(r + 1) * 2]
        for k in range(n)])
    np.testing.assert_allclose(out2.numpy(), expect)


def backward_passes_accumulation(r, n):
    """backward_passes_per_step=2 through the torch optimizer: the
    first backward accumulates locally (no communication, no update);
    the second averages the accumulation across ranks and steps
    (reference: torch/optimizer.py:72-74 local aggregation)."""
    lin = torch.nn.Linear(3, 1, bias=False)
    with torch.no_grad():
        lin.weight.fill_(0.0)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(lin.parameters(), lr=1.0),
        named_parameters=lin.named_parameters(),
        backward_passes_per_step=2)
    # Torch usage pattern: k backwards accumulate into p.grad (no
    # zero_grad between), the hook fires the allreduce on the k-th
    # pass, then ONE step applies the result.
    lin(torch.full((1, 3), float(r + 1))).sum().backward()
    lin(torch.full((1, 3), float(r + 1))).sum().backward()
    opt.step()
    # Local sum 2(r+1), divided by passes -> (r+1), averaged over
    # ranks; lr=1 subtracts.
    mean = sum(range(1, n + 1)) / n
    np.testing.assert_allclose(lin.weight.detach().numpy(),
                               -mean * np.ones((1, 3)), atol=1e-6)
    opt.zero_grad()


def bf16_compression_and_uneven_reducescatter(r, n):
    """bf16 wire compression (the TPU-native narrow dtype) and the
    uneven-rows reducescatter shard math through the binding."""
    lin = torch.nn.Linear(3, 1, bias=False)
    with torch.no_grad():
        lin.weight.fill_(0.0)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(lin.parameters(), lr=1.0),
        named_parameters=lin.named_parameters(),
        compression=hvd.Compression.bf16)
    lin(torch.full((1, 3), float(r + 1))).sum().backward()
    opt.step()
    mean = sum(range(1, n + 1)) / n
    np.testing.assert_allclose(lin.weight.detach().numpy(),
                               -mean * np.ones((1, 3)), atol=2e-2)
    # Uneven reducescatter: 2n+1 rows over n ranks — rank 0 gets the
    # extra row (native core's shard math).
    full = torch.arange(2 * n + 1, dtype=torch.float32) * (r + 1)
    shard = hvd.reducescatter(full, op=hvd.Average, name="rs.uneven")
    total = sum(range(1, n + 1)) / n
    rows = 3 if r == 0 else 2
    offset = r * 2 + min(r, 1)
    expect = (np.arange(2 * n + 1) * total)[offset:offset + rows]
    np.testing.assert_allclose(shard.numpy(), expect, rtol=1e-6)


def async_handles(r, n):
    """Handle-based async API: poll + out-of-order synchronize +
    grouped async + in-place variants + reducescatter
    (reference: torch/mpi_ops_v2.cc PollHandle/WaitAndClear
    :566-575, mpi_ops.py:865-901)."""
    h1 = hvd.allreduce_async(torch.full((4,), float(r + 1)),
                             name="ah.1", op=hvd.Sum)
    h2 = hvd.allreduce_async(torch.full((2,), 2.0 * (r + 1)),
                             name="ah.2", op=hvd.Average)
    hg = hvd.grouped_allreduce_async(
        [torch.full((3,), float(r)), torch.full((1,), 10.0 * r)],
        name="ah.g", op=hvd.Sum)
    # Out-of-order synchronize is legal; poll never blocks.
    hvd.poll(h2)
    out2 = hvd.synchronize(h2)
    outs = hvd.synchronize(hg)
    out1 = hvd.synchronize(h1)
    total = float(sum(range(1, n + 1)))
    np.testing.assert_allclose(out1.numpy(), np.full(4, total))
    np.testing.assert_allclose(out2.numpy(), np.full(2, 2.0 * total / n))
    np.testing.assert_allclose(outs[0].numpy(),
                               np.full(3, float(sum(range(n)))))
    np.testing.assert_allclose(outs[1].numpy(),
                               np.full(1, 10.0 * sum(range(n))))
    # In-place async mutates the SAME storage.
    x = torch.full((3,), float(r + 1))
    h = hvd.allreduce_async_(x, name="ah.ip", op=hvd.Sum)
    out = hvd.synchronize(h)
    assert out is x
    np.testing.assert_allclose(x.numpy(), np.full(3, total))
    # Reducescatter: rank r owns shard r of the summed tensor.
    full = torch.arange(2 * n, dtype=torch.float32) * (r + 1)
    shard = hvd.reducescatter(full, op=hvd.Sum, name="ah.rs")
    expect = (np.arange(2 * n) * total)[r * 2:(r + 1) * 2]
    np.testing.assert_allclose(shard.numpy(), expect)


def optimizer_state_broadcast(r, n):
    """broadcast_optimizer_state must align stateful (momentum) and
    param-group hyperparameters across ranks (reference:
    torch/functions.py:29-266)."""
    torch.manual_seed(1000 + r)  # DIFFERENT init per rank on purpose
    model = torch.nn.Linear(3, 2)
    opt = torch.optim.SGD(model.parameters(), lr=0.05 * (r + 1),
                          momentum=0.9)
    # Build momentum state locally (diverged across ranks).
    model(torch.randn(4, 3)).sum().backward()
    opt.step()
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(opt, root_rank=0)
    assert opt.param_groups[0]["lr"] == 0.05  # rank 0's lr everywhere
    state_blobs = hvd.allgather_object(
        [v["momentum_buffer"].numpy().tolist()
         for v in opt.state.values()])
    assert state_blobs[0] == state_blobs[-1]
    params_blobs = hvd.allgather_object(
        [p.detach().numpy().tolist() for p in model.parameters()])
    assert params_blobs[0] == params_blobs[-1]


def dtype_op_matrix(r, n):
    """dtype x op allreduce matrix through the torch API
    (reference: test/parallel/test_torch.py:154+ test_horovod_allreduce
    and its dtype variants)."""
    base = np.arange(1, 7, dtype=np.float64).reshape(2, 3)
    float_dtypes = [torch.float32, torch.float64, torch.bfloat16,
                    torch.float16]
    int_dtypes = [torch.int32, torch.int64]
    scale = [float(k + 1) for k in range(n)]
    for dt in float_dtypes + int_dtypes:
        x = torch.tensor(base * (r + 1)).to(dt)
        cases = {
            hvd.Sum: base * sum(scale),
            hvd.Min: base * min(scale),
            hvd.Max: base * max(scale),
            hvd.Product: base ** n * np.prod(scale),
        }
        if dt in float_dtypes:
            cases[hvd.Average] = base * (sum(scale) / n)
        for op, expect in cases.items():
            out = hvd.allreduce(x, name="mx.%s.%s" % (dt, op), op=op)
            assert out.dtype == dt, (dt, out.dtype)
            tol = 2e-2 if dt in (torch.bfloat16, torch.float16) else 1e-6
            np.testing.assert_allclose(
                out.to(torch.float64).numpy(), expect, rtol=tol, atol=tol)


def grouped_inplace(r, n):
    """grouped_allreduce_ writes results back into the input tensors
    (reference: torch/mpi_ops.py grouped_allreduce_/async_)."""
    xs = [torch.full((3,), float(r + 1)), torch.full((2,), float(r * 2))]
    outs = hvd.grouped_allreduce_(xs, op=hvd.Sum, name="ginp")
    assert outs[0] is xs[0] and outs[1] is xs[1]  # same storage
    np.testing.assert_allclose(xs[0].numpy(), 3.0)   # 1 + 2
    np.testing.assert_allclose(xs[1].numpy(), 2.0)   # 0 + 2

    # Requires-grad leaves (nn.Parameter) must reduce in place too —
    # the reference's common case for parameter averaging.
    p = torch.nn.Parameter(torch.full((3,), float(r + 1)))
    (out,) = hvd.grouped_allreduce_([p], op=hvd.Average, name="ginp.p")
    assert out is p
    np.testing.assert_allclose(p.detach().numpy(), 1.5)
    q = torch.nn.Parameter(torch.full((2,), float(r)))
    hvd.allreduce_(q, op=hvd.Sum, name="ginp.q")
    np.testing.assert_allclose(q.detach().numpy(), 1.0)


def grouped_mixed_dtypes(r, n):
    """One explicit group mixing dtypes must reduce every member
    correctly (reference: grouped allreduce variants,
    torch/mpi_ops.py:300-513)."""
    xs = [torch.full((3,), float(r + 1), dtype=torch.float32),
          torch.full((2, 2), r + 1, dtype=torch.int64),
          torch.full((5,), float(r + 1), dtype=torch.bfloat16),
          torch.full((1,), float(r + 1), dtype=torch.float64)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="gmix")
    total = float(sum(range(1, n + 1)))
    for x, out in zip(xs, outs):
        assert out.dtype == x.dtype
        np.testing.assert_allclose(
            out.to(torch.float64).numpy(),
            np.full(x.shape, total), rtol=1e-2)


def collective_surfaces(r, n):
    """Ragged allgather, non-zero-root broadcast, explicit-splits
    alltoall through the torch API (reference: test_torch.py
    allgather/broadcast/alltoall variants)."""
    # Ragged dim 0: rank k contributes k+1 rows of value k.
    g = hvd.allgather(torch.full((r + 1, 2), float(r)), name="rag")
    expect = np.concatenate(
        [np.full((k + 1, 2), float(k)) for k in range(n)])
    np.testing.assert_allclose(g.numpy(), expect)
    # int64 allgather keeps dtype.
    gi = hvd.allgather(torch.arange(2, dtype=torch.int64) + r, name="ragi")
    assert gi.dtype == torch.int64 and gi.shape[0] == 2 * n

    # Broadcast from the LAST rank, float + int + 0-d scalar.
    for name, t in (("bf", torch.full((3,), float(r))),
                    ("bi", torch.tensor([r, r], dtype=torch.int32)),
                    ("bs", torch.tensor(float(r)))):
        out = hvd.broadcast(t, n - 1, name="bcast." + name)
        np.testing.assert_allclose(
            out.to(torch.float64).numpy(),
            np.full(t.shape, float(n - 1)))

    # Explicit uneven splits (np=2): rank0 sends 1 row to itself and 2
    # to rank1; rank1 sends 2 rows to rank0 and 1 to itself.
    if n == 2:
        data = torch.arange(3, dtype=torch.float32) + 10.0 * r
        splits = torch.tensor([1, 2] if r == 0 else [2, 1])
        out, rsplits = hvd.alltoall(data, splits=splits, name="a2av")
        if r == 0:
            np.testing.assert_allclose(out.numpy(), [0.0, 10.0, 11.0])
            np.testing.assert_allclose(np.asarray(rsplits), [1, 2])
        else:
            np.testing.assert_allclose(out.numpy(), [1.0, 2.0, 12.0])
            np.testing.assert_allclose(np.asarray(rsplits), [2, 1])


def process_sets_through_binding(r, n):
    """Collectives restricted to a process set via the torch surface
    (reference: test_torch.py process-set variants; registration is
    collective, so every rank registers every set)."""
    sets = [hvd.add_process_set(hvd.ProcessSet([k])) for k in range(n)]
    try:
        mine = sets[r]
        assert mine.included() and mine.rank() == 0 and mine.size() == 1
        out = hvd.allreduce(torch.full((4,), float(r + 1)),
                            name="ps.solo", op=hvd.Sum, process_set=mine)
        # Size-1 set: the reduction is the rank's own tensor.
        np.testing.assert_allclose(out.numpy(), np.full(4, float(r + 1)))
        g = hvd.allgather(torch.full((2, 1), float(r)), name="ps.g",
                          process_set=mine)
        assert g.shape == (2, 1)
        b = hvd.broadcast(torch.full((2,), float(r)), r, name="ps.b",
                          process_set=mine)
        np.testing.assert_allclose(b.numpy(), [float(r)] * 2)
    finally:
        for s in sets:
            hvd.remove_process_set(s)


def join_through_binding(r, n):
    """Uneven-data Join through the torch API (reference:
    torch/mpi_ops.py:888, controller.cc:262-317): the joined rank
    contributes zeros; join() returns the highest-indexed joined rank
    at the completion cycle (announcements fold in member-rank order,
    stable regardless of join timing)."""
    if r == 0:
        out = hvd.allreduce(torch.ones(3), name="join.ar", op=hvd.Sum)
        # Rank 1 already joined -> contributes zeros.
        np.testing.assert_allclose(out.numpy(), np.ones(3))
    last = hvd.join()
    assert last == 1, last


def error_propagation(r, n):
    """Cross-rank mismatches must raise through the framework API on
    EVERY rank, and the session must stay usable afterwards
    (reference: test_torch.py error cases -> coordinator ERROR
    response)."""
    with _expect_internal_error("shape"):
        hvd.allreduce(torch.ones(2 + r), name="err.shape", op=hvd.Sum)
    with _expect_internal_error("dtype"):
        t = torch.ones(4, dtype=torch.float32 if r == 0
                       else torch.float64)
        hvd.allreduce(t, name="err.dtype", op=hvd.Sum)
    # Duplicate name: second submission errors, the first completes.
    # The first completes once EVERY rank has submitted it, so one rank
    # (``holder``) submits last, behind a barrier the others reach after
    # their duplicate was refused: no rank's first submission can be
    # done before its second, however loaded the machine is (a rank
    # whose first had completed would have its second ACCEPTED and wait
    # for peers that refused theirs). Each rank is held once.
    for holder in (n - 1, 0):
        name = "err.dup.%d" % holder
        if r == holder:
            hvd.barrier()
        h1 = hvd.allreduce_async(torch.ones(4), name=name, op=hvd.Sum)
        if r != holder:
            with _expect_internal_error("duplicate"):
                h2 = hvd.allreduce_async(torch.ones(4), name=name,
                                         op=hvd.Sum)
                hvd.synchronize(h2)
            hvd.barrier()
        np.testing.assert_allclose(hvd.synchronize(h1).numpy(),
                                   np.full(4, float(n)))
    # Session still healthy.
    out = hvd.allreduce(torch.ones(2), name="err.after", op=hvd.Sum)
    np.testing.assert_allclose(out.numpy(), np.full(2, float(n)))


class _expect_internal_error:
    def __init__(self, what):
        self.what = what

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        assert exc_type is not None and issubclass(
            exc_type, hvd.HorovodInternalError), (
            "expected HorovodInternalError for %s mismatch, got %r"
            % (self.what, exc_type))
        return True  # swallow


def sync_bn_backward(r, n):
    """SyncBatchNorm BACKWARD at np=2 must match single-process BN on
    the concatenated batch (reference: torch/sync_batch_norm.py:110-163
    backward allreduces sum_dy / sum_dy_xmu)."""
    xs = [torch.randn(4, 3, 5,
                      generator=torch.Generator().manual_seed(70 + k))
          for k in range(n)]
    gs = [torch.randn(4, 3, 5,
                      generator=torch.Generator().manual_seed(170 + k))
          for k in range(n)]

    sbn = hvd.SyncBatchNorm(3)
    sbn.train()
    x_mine = xs[r].clone().requires_grad_(True)
    out = sbn(x_mine)
    out.backward(gs[r])

    bn = torch.nn.BatchNorm1d(3)
    bn.train()
    x_all = torch.cat(xs).requires_grad_(True)
    bn(x_all).backward(torch.cat(gs))
    expect_x_grad = x_all.grad[r * 4:(r + 1) * 4]
    np.testing.assert_allclose(x_mine.grad.numpy(),
                               expect_x_grad.numpy(), atol=1e-5)
    # Weight/bias grads stay LOCAL-batch sums (the optimizer averages
    # them later, as in the reference); summing across ranks must equal
    # BN's grads on the concatenated batch.
    wsum = hvd.allreduce(sbn.weight.grad, name="sbn.wg", op=hvd.Sum)
    bsum = hvd.allreduce(sbn.bias.grad, name="sbn.bg", op=hvd.Sum)
    np.testing.assert_allclose(wsum.numpy(), bn.weight.grad.numpy(),
                               atol=1e-4)
    np.testing.assert_allclose(bsum.numpy(), bn.bias.grad.numpy(),
                               atol=1e-5)


if __name__ == "__main__":
    sys.exit(main())
