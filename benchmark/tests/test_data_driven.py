"""A configuration, a cell (with its traffic mix) and a per-layer metric
are each added as new files plus one entry in ``BENCHMARK.json``, with no
edit to a file that exists: shown on a temporary copy, through the CPU
rehearsal. Also holds ``BENCHMARK.json`` to what the files say."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_entry_has_its_files():
    bench = _bench()
    here = os.path.join(ROOT, "benchmark")
    for cfg in bench["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            data = json.load(f)
        assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
        assert data["reduced"] == cfg["reduced"]
        assert os.path.exists(os.path.join(
            here, "builders", data["builder"] + ".py"))
        assert os.path.exists(os.path.join(
            here, "reference", data["builder"] + ".py"))
    for w in bench["workloads"]:
        with open(os.path.join(here, "workloads",
                               w["traffic"] + ".json")) as f:
            assert json.load(f)["traffic"] == w["traffic"]
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            here, "layer_metrics", m["name"] + ".py")), m["name"]
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", cells)) <= cells
    on_disk = {f[:-3] for f in os.listdir(os.path.join(here, "layer_metrics"))
               if f.endswith(".py") and f != "__init__.py"}
    assert on_disk == {m["name"] for m in bench["per_layer"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "horovod_tpu"), tmp_path / "horovod_tpu")
    return tmp_path


def test_config_cell_and_metric_are_added_as_files(copy):
    here = copy / "benchmark"
    config = json.loads((here / "configs" / "gpt2-medium.json").read_text())
    config.update(name="dummy-gpt2", sample_unit="dummy_tokens")
    (here / "configs" / "dummy-gpt2.json").write_text(json.dumps(config))
    mix = json.loads((here / "workloads" / "s1024-c1.json").read_text())
    mix.update(traffic="dummy-mix", per_chip_batch=2, warmup_steps=1)
    (here / "workloads" / "dummy-mix.json").write_text(json.dumps(mix))
    (here / "layer_metrics" / "launch.warmup_s.py").write_text(
        'def read(ctx):\n    return ctx.timeline["warmup_s"]\n')
    (here / "layer_metrics" / "launch.nothing.py").write_text(
        "def read(ctx):\n    return None\n")

    bench = json.loads((copy / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "dummy-gpt2", "source": config["source"],
        "file": "benchmark/configs/dummy-gpt2.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "dummy-gpt2", "traffic": "dummy-mix",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("dummy-cell")
    for name in ("launch.warmup_s", "launch.nothing"):
        bench["per_layer"].append({
            "name": name, "unit": "s", "better": "lower",
            "source": "host_clock", "layer": "Launch and compile cache",
            "moves": "setup_s", "workloads": ["dummy-cell"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "dummy-cell",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse-cpu"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] >= 10 and not line["failed"]
    assert "metrics" not in line and line["rehearsal"] is True
    assert "dummy_tokens/s" in run.stderr   # the new file was read

    # The new readers are found by name; None leaves the metric out.
    probe = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "from types import SimpleNamespace\n"
        "from benchmark import cell as cells\n"
        "sys.argv = ['run.py']\n"
        "import importlib.util\n"
        "spec = importlib.util.spec_from_file_location('run', %r)\n"
        "run = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(run)\n"
        "cell = cells.load('dummy-cell')\n"
        "ctx = SimpleNamespace(timeline={'warmup_s': 0.5, 'compile_s': 2.0,"
        " 'init_s': 1.0})\n"
        "print(json.dumps(run.read_layer_metrics(cell, ctx)))\n"
        % (str(copy), str(here / "run.py")))
    out = subprocess.run([sys.executable, "-c", probe], cwd=copy, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "launch.compile_s": {"value": 2.0, "unit": "s"},
        "launch.init_s": {"value": 1.0, "unit": "s"},
        "launch.warmup_s": {"value": 0.5, "unit": "s"}}


def test_no_chip_means_no_result(copy):
    """Without a TPU (here: the CPU) and without the rehearsal flag the
    command exits non-zero and prints nothing on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"))
    run = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2m-s1024-c1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode != 0 and run.stdout.strip() == ""
    assert "needs 1 TPU chip" in run.stderr


def test_trace_view_on_the_recorded_trace(monkeypatch):
    """``trace_view.build`` and every reader that needs no collective,
    on the small recorded trace (its Mosaic calls under the names a
    program of today gives them: ``test_scope_view.named``) with a
    stand-in for the assembled cell."""
    sys.path.insert(0, ROOT)
    from benchmark import trace_reduce, trace_view
    from benchmark.layer_metrics import reader
    from benchmark.tests.test_scope_view import named

    load = trace_reduce.load

    def load_named(path):
        trace = load(path)
        return trace._replace(devices={
            chip: {line: [e._replace(name=named(e.name)) for e in events]
                   for line, events in lines.items()}
            for chip, lines in trace.devices.items()})

    monkeypatch.setattr(trace_reduce, "load", load_named)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    attention = {"fwd": (4.2e6, 1.3e5), "bwd": (10.5e6, 2.9e5)}
    asm = SimpleNamespace(
        model=SimpleNamespace(attention_work=lambda b: attention,
                              step_ops=lambda b: 1e9),
        per_chip_batch=1, global_batch=1, units_per_step=256, plan=None)
    xplane = os.path.join(ROOT, "benchmark", "tests", "data",
                          "small.xplane.pb")
    lines = []
    ctx = trace_view.build(
        cell=SimpleNamespace(chips=1), asm=asm, peak=peak, xplane=xplane,
        hlo_text="HloModule jit_small_step, is_scheduled=true\n",
        timeline={"compile_s": 1.0, "init_s": 2.0}, memory_peak=10 ** 10,
        baseline=None, untraced_step_s=0.001, log=lines.append)
    assert ctx.n_steps == 3 and ctx.busy_s < ctx.window_s
    assert len(ctx.breakdown["device_ops"]) == 10
    assert ctx.breakdown["device_ops"][0][0] == "fusion.1"
    assert len(ctx.breakdown["idle_gaps"]) == 5
    assert any("kernel flash dkv: 1 calls a step" in ln for ln in lines)
    assert any("attention bwd, required" in ln for ln in lines)
    got = {name: reader(name)(ctx) for name in (
        "device.idle_pct", "device.peak_hbm_gb", "model.step_device_ms",
        "model.mfu_pct", "kernel.flash_share_pct", "kernel.flash_roofline",
        "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
        "sync.collective_ms", "sync.exposed_ms", "dp.scaling_eff_pct",
        "images.model.mfu_pct")}
    assert got["device.idle_pct"] == pytest.approx(99.53, abs=0.01)
    assert got["device.peak_hbm_gb"] == 10.0
    assert got["model.step_device_ms"] == pytest.approx(0.0137, abs=1e-4)
    assert got["kernel.flash_share_pct"] == pytest.approx(40.2, abs=0.1)
    assert 0 < got["kernel.flash_fwd_roofline"] < 100
    assert 0 < got["kernel.flash_bwd_roofline"] \
        < got["kernel.flash_roofline"] < got["kernel.flash_fwd_roofline"]
    assert got["images.model.mfu_pct"] == got["model.mfu_pct"]
    assert got["sync.collective_ms"] is None
    assert got["sync.exposed_ms"] is None
    assert got["dp.scaling_eff_pct"] is None
