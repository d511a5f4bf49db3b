"""Sharding-planner cost model units (parallel/costmodel.py).

ISSUE 13 tier-1 floor: monotone in bytes, DCN-penalty ordering,
memory-fit rejection, deterministic tie-break — all pure Python (the
cost model is jax-free by design), plus the planner-routing equality
the MULTICHIP dryrun relies on (planner-chosen mesh dicts == the
hand-built ones they replaced). The jit-heavy planner coverage
(np=2 bit-equality, the swept dryrun) lives in tests/test_planner.py.
"""

import pytest

from horovod_tpu.parallel import costmodel as cm


def _w(**kw):
    base = dict(param_bytes=4 << 20, batch=16, seq_len=32, d_model=64,
                n_layers=2)
    base.update(kw)
    return cm.Workload(**base)


def _choose(w, t, require=None):
    return cm.choose(cm.enumerate_candidates(w, t, require))


# --- scoring ----------------------------------------------------------------


def test_cost_monotone_in_param_bytes():
    t = cm.Topology(8, 8, 1)
    axes = {"data": 8, "model": 1, "seq": 1, "expert": 1, "pipe": 1}
    costs = [cm.score(axes, _w(param_bytes=b), t).seconds
             for b in (1 << 20, 4 << 20, 64 << 20, 1 << 30)]
    assert costs == sorted(costs)
    assert costs[0] < costs[-1]


def test_cost_monotone_in_activation_bytes():
    t = cm.Topology(8, 8, 1)
    axes = {"data": 1, "model": 8, "seq": 1, "expert": 1, "pipe": 1}
    costs = [cm.score(axes, _w(batch=b), t).seconds
             for b in (8, 32, 128)]
    assert costs == sorted(costs)
    assert costs[0] < costs[-1]


def test_dcn_penalty_ordering():
    """The same data-parallel payload costs strictly more on a
    2-slice topology than on a flat slice (the cross-slice leg rides
    the slow links), and lowering the DCN weight widens the gap."""
    axes = {"data": 8, "model": 1, "seq": 1, "expert": 1, "pipe": 1}
    w = _w(param_bytes=64 << 20)
    flat = cm.score(axes, w, cm.Topology(8, 8, 1)).seconds
    hier = cm.score(axes, w, cm.Topology(8, 4, 2)).seconds
    slow = cm.score(axes, w, cm.Topology(8, 4, 2, dcn_bw_gbps=1.0)).seconds
    assert flat < hier < slow
    # And the dcn bytes are attributed to the dcn fabric, not ici.
    c = cm.score(axes, w, cm.Topology(8, 4, 2))
    assert c.dcn_bytes > 0
    assert cm.score(axes, w, cm.Topology(8, 8, 1)).dcn_bytes == 0


def test_memory_fit_rejection_names_overflow():
    # 8 GB of params at 4x state replicated >> 6 GB bound: the pure-DP
    # candidate must be scored but infeasible, and the winner must
    # shard the params (model axis: 8 GB / 8 * 4 = 4 GB fits).
    w = _w(param_bytes=8 << 30, d_model=1024)
    t = cm.Topology(8, 8, 1, mem_per_chip_gb=6.0)
    chosen, losers = _choose(w, t)
    assert chosen.axes["model"] > 1
    dp = [c for c in losers if c.axes["data"] == 8]
    assert dp and not dp[0].feasible
    assert "memory" in dp[0].reason and "GB" in dp[0].reason


def test_no_feasible_layout_raises():
    w = _w(param_bytes=8 << 30, d_model=7)  # model axis illegal
    t = cm.Topology(8, 8, 1, mem_per_chip_gb=0.5)
    with pytest.raises(cm.PlanError, match="memory"):
        _choose(w, t)


def test_deterministic_tie_break_prefers_data():
    # Zero-comm workload: every candidate ties at 0; max data must win
    # and repeated runs must agree.
    w = cm.Workload(param_bytes=0, batch=8, seq_len=8, d_model=8,
                    n_layers=0)
    t = cm.Topology(8, 8, 1)
    first, _ = _choose(w, t)
    assert first.axes["data"] == 8
    for _ in range(3):
        again, _ = _choose(w, t)
        assert again.axes == first.axes


def test_grad_sync_spans_seq_axis():
    """Sequence parallelism must not dodge the gradient allreduce:
    same token-parallel degree => same grad payload, but seq adds the
    blocking K/V rotation on top, so pure-DP strictly wins."""
    w = _w()
    t = cm.Topology(8, 8, 1)
    dp = cm.score({"data": 8, "model": 1, "seq": 1, "expert": 1,
                   "pipe": 1}, w, t)
    sp = cm.score({"data": 1, "model": 1, "seq": 8, "expert": 1,
                   "pipe": 1}, w, t)
    assert sp.ici_bytes > dp.ici_bytes
    assert sp.seconds > dp.seconds
    chosen, _ = _choose(w, t)
    assert chosen.axes["data"] == 8


def test_expert_axis_cuts_expert_bytes():
    w = _w(param_bytes=512 << 20, seq_len=1, d_model=63,
           num_experts=4, expert_param_bytes=480 << 20)
    t = cm.Topology(8, 8, 1)
    chosen, _ = _choose(w, t)
    assert chosen.axes["expert"] == 4
    e1 = cm.score({"data": 8, "model": 1, "seq": 1, "expert": 1,
                   "pipe": 1}, w, t)
    assert chosen.cost.mem_bytes < e1.mem_bytes


# --- enumeration legality ---------------------------------------------------


def test_divisibility_constraints():
    w = cm.Workload(param_bytes=1 << 20, batch=6, seq_len=10,
                    d_model=12, n_layers=2)
    for c in cm.enumerate_candidates(w, cm.Topology(8, 8, 1)):
        assert w.batch % c.axes["data"] == 0
        assert c.axes["model"] == 1 or w.d_model % c.axes["model"] == 0
        assert c.axes["seq"] == 1 or w.seq_len % c.axes["seq"] == 0
        assert c.axes["expert"] == 1  # no experts declared
        assert c.axes["pipe"] == 1    # no stages declared


def test_multislice_data_absorbs_dcn():
    w = _w(batch=64)
    for c in cm.enumerate_candidates(w, cm.Topology(8, 4, 2)):
        assert c.axes["data"] % 2 == 0  # every candidate spans dcn


def test_require_axes_pins_exact_sizes():
    w = _w(batch=4)
    cands = cm.enumerate_candidates(w, cm.Topology(8, 8, 1),
                                    {"seq": 2, "model": 2})
    assert len(cands) == 1
    assert cands[0].axes == {"data": 2, "model": 2, "seq": 2,
                             "expert": 1, "pipe": 1}
    with pytest.raises(ValueError, match="unknown axes"):
        cm.enumerate_candidates(w, cm.Topology(8, 8, 1), {"bogus": 2})


# --- env-knob weights -------------------------------------------------------


def test_bandwidth_knobs_resolve_env(monkeypatch):
    monkeypatch.setenv("HVD_PLAN_ICI_BW_GBPS", "123.5")
    monkeypatch.setenv("HVD_PLAN_DCN_BW_GBPS", "2.5")
    monkeypatch.setenv("HVD_PLAN_MEM_PER_CHIP_GB", "3")
    monkeypatch.setenv("HVD_PLAN_GRAD_OVERLAP", "7")  # clamped
    assert cm.ici_bw_gbps() == 123.5
    assert cm.dcn_bw_gbps() == 2.5
    assert cm.mem_per_chip_gb() == 3.0
    assert cm.grad_overlap() == 1.0
    t = cm.Topology.make(8, dcn=2)
    assert (t.ici_bw_gbps, t.dcn_bw_gbps, t.mem_per_chip_gb) == \
        (123.5, 2.5, 3.0)
    monkeypatch.setenv("HVD_PLAN_ICI_BW_GBPS", "not-a-float")
    assert cm.ici_bw_gbps() == cm.DEFAULT_ICI_BW_GBPS


def test_tunable_schema_declares_plan_weights():
    from horovod_tpu.common.knobs import TUNABLE, tunable_snap

    for name, env in (("plan_ici_bw_gbps", "HVD_PLAN_ICI_BW_GBPS"),
                      ("plan_dcn_bw_gbps", "HVD_PLAN_DCN_BW_GBPS"),
                      ("plan_grad_overlap", "HVD_PLAN_GRAD_OVERLAP")):
        k = TUNABLE[name]
        assert k.env == env and k.apply_path == "env"
        assert not k.live_safe  # plan-time reads: offline search only
        assert tunable_snap(k, k.default) == k.default  # on the grid


# --- planner routing (pure mesh-dict checks; no compilation) ---------------


def test_flagship_routing_matches_legacy_composition():
    """The dryrun pins seq/model and the planner assigns the data
    split: the result must be the historical {data: n/4, seq: 2,
    model: 2} composition, byte-for-byte the same mesh dict."""
    from horovod_tpu.parallel import planner

    p = planner.plan(param_bytes=2 << 20, batch=4, seq_len=32,
                     d_model=64, n_layers=2, chips=8,
                     require_axes={"seq": 2, "model": 2})
    assert p.mesh_axes == {"data": 2, "seq": 2, "model": 2}
    assert p.sync == "psum"
    assert p.grad_axes == ("data", "seq")


def test_hierarchical_routing_matches_legacy_composition():
    from horovod_tpu.parallel import planner

    p = planner.plan(param_bytes=2 << 20, batch=4, seq_len=32,
                     d_model=64, n_layers=2, chips=8, dcn=2,
                     require_axes={"model": 2})
    assert p.mesh_axes == {"data_dcn": 2, "data_ici": 2, "model": 2}
    assert p.sync == "hierarchical"
    assert p.grad_axes == ("data_dcn", "data_ici")
    assert p.data_axes == ("data_dcn", "data_ici")


def test_report_names_chosen_and_rejected():
    from horovod_tpu.parallel import planner

    p = planner.plan(param_bytes=4 << 20, batch=16, seq_len=32,
                     d_model=64, n_layers=2, chips=8)
    assert p.mesh_axes == {"data": 8}
    report = p.report()
    assert "CHOSEN" in report
    assert report.count("rejected:") >= 1
    assert "per-axis rationale" in report
    assert "grad sync" in report
    rec = p.to_json()
    assert rec["mesh_axes"] == {"data": 8}
    assert rec["rejected"]
    # The one-line summary names a scored-and-rejected candidate too.
    assert "top-rejected=" in p.summary()


@pytest.mark.parametrize("surface", ["summary", "to_json", "attribute",
                                     "parameter"])
def test_plan_carries_no_bucket_size(surface):
    """The gradient sync has one shape, so a plan has nothing to record
    about it beyond ``sync`` (``bucket_bytes`` left every surface with
    the option it mirrored)."""
    from horovod_tpu.parallel import planner

    kwargs = dict(param_bytes=4 << 20, batch=16, seq_len=32, d_model=64,
                  n_layers=2, chips=8)
    p = planner.plan(**kwargs)
    if surface == "summary":
        assert "bucket" not in p.summary() and "bucket" not in p.report()
        assert "sync=psum" in p.summary()
    elif surface == "to_json":
        assert "bucket_bytes" not in p.to_json()
        assert p.to_json()["sync"] == "psum"
    elif surface == "attribute":
        assert not hasattr(p, "bucket_bytes")
    else:
        with pytest.raises(TypeError, match="bucket_bytes"):
            planner.plan(bucket_bytes=1 << 20, **kwargs)


def test_plan_scenarios_choose_distinct_meshes():
    """The MULTICHIP sweep's scenario table (pure Python, the same
    data the dryrun prints into its JSON tail): >= 4 distinct
    planner-chosen meshes across the workload shapes."""
    import __graft_entry__ as g
    from horovod_tpu.parallel import planner

    seen = set()
    for name, w, t in g._plan_scenarios(8):
        p = planner.plan(workload=w, topology=t)
        seen.add(tuple(sorted(p.mesh_axes.items())))
    assert len(seen) >= 4
