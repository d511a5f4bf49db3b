"""chip_smoke.py's contract off the chip, and the compile-cache helper.

The smoke itself only passes on a TPU (docs/benchmarks.md); what can be
pinned here is that it refuses to pass anywhere else, that its
supervisor stays off jax (a process that has touched jax holds the
chip), and where the persistent compile cache goes.
"""

import os
import subprocess
import sys

import jax
import pytest

from horovod_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_fails_without_a_tpu_and_names_the_platform():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=_REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "jax found platform cpu" in proc.stderr
    assert proc.stdout == ""  # a failing run prints no result


def test_smoke_parent_imports_no_jax():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "sys.exit('jax' in sys.modules or 'horovod_tpu' in sys.modules)"],
        cwd=_REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


def test_verdict_line_holds_exactly_ok_and_device():
    import json

    import chip_smoke

    line = chip_smoke.verdict_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_peak_is_the_benchmarks_own_table():
    import chip_smoke

    assert chip_smoke.chip_peak_flops("TPU v5 lite") == 197e12


def test_a_kind_the_table_lacks_is_refused_with_the_known_kinds():
    import chip_smoke

    with pytest.raises(KeyError) as e:
        chip_smoke.chip_peak_flops("TPU v9 imaginary")
    assert "TPU v9 imaginary" in str(e.value)
    assert "'TPU v5 lite'" in str(e.value)


def test_compile_cache_env_set_is_left_to_jax(monkeypatch, tmp_path):
    writes = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: writes.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert writes == []


def test_compile_cache_unset_is_one_fixed_path_in_the_checkout(monkeypatch):
    writes = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: writes.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert writes == [("jax_compilation_cache_dir", want)]
    # Exported, so spawned workers share it.
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
