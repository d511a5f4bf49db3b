"""Env-knob contract: every ``HOROVOD_*``/``HVD_*`` environment
variable *read* anywhere in the tree must be registered in
``common/knobs.py`` (or explicitly allowlisted here) and documented in
``docs/configuration.md``. PR 3 shipped `HVD_FAULT_*` knobs that lived
only in comm.cc — exactly the drift this checker exists to stop.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Set, Tuple

from tools.analysis import cpp, pyast
from tools.analysis.common import Finding, Project

KNOB_RE = re.compile(r"^(HOROVOD|HVD)_[A-Z0-9_]+$")

# Internal/dev-tooling variables that are not user-facing knobs: each
# entry must say why it is exempt from the registry + docs contract.
DEFAULT_ALLOWLIST: Dict[str, str] = {
    # Launcher <-> worker private handshake (hvdrun sets these; users
    # never do). The public surface is the hvdrun CLI.
    "HOROVOD_SLOT_KEY": "internal: per-slot identity token minted by the "
                        "elastic driver for worker registration",
    "HOROVOD_RENDEZVOUS_VERSION": "internal: elastic rendezvous epoch "
                                  "the driver stamps on each world",
    # Benchmark/CI harness tuning, not framework behavior.
    "HVD_CI_METRICS_BUDGET": "ci/run_tests.sh lane budget",
    "HVD_CI_FLIGHTREC_BUDGET": "ci/run_tests.sh lane budget",
    "HVD_CI_TIER1_BUDGET": "ci/run_tests.sh lane budget",
    "HVD_CI_TIER2_BUDGET": "ci/run_tests.sh lane budget",
    "HVD_CI_ANALYSIS_BUDGET": "ci/run_tests.sh lane budget",
    "HVD_CI_PLAN_BUDGET": "ci/run_tests.sh lane budget",
    "HVD_CI_FLEET_BUDGET": "ci/run_tests.sh lane budget",
    "HVD_CI_OPS_BUDGET": "ci/run_tests.sh lane budget",
    # Test-suite internals (set and read only by tests/).
    "HVD_FUZZ_SEED": "tests/fuzz_worker.py reproducibility seed",
    "HVD_WIRE_BENCH_SIZES": "tests/wire_bench_worker.py payload sweep "
                            "(set by the bench_wire.py harness)",
    "HVD_WIRE_BENCH_ITERS": "tests/wire_bench_worker.py timed "
                            "iterations per payload size",
    "HVD_WIRE_BENCH_WARMUP": "tests/wire_bench_worker.py warmup "
                             "iterations per payload size",
    "HVD_KERAS_SWEEP_TMP": "tests/keras_sweep_worker.py scratch dir",
    "HVD_TEST_CKPT_DIR": "tests/ckpt_worker.py scratch dir",
    "HVD_TL_DIR": "tests/timeline_worker.py scratch dir",
    "HVD_TPU_TEST_PLATFORM": "tests/conftest.py platform override",
}


def registered_knobs(project: Project) -> Tuple[Set[str], List[Finding]]:
    """Knob names declared in knobs.py — ``Knob("NAME", ...)`` first
    arguments plus the native targets of ALIASED entries — without
    importing the module (keeps the checker jax-free and side-effect
    free)."""
    findings: List[Finding] = []
    try:
        tree = pyast.parse(project.read(project.knobs_py), project.knobs_py)
    except (OSError, SyntaxError) as e:
        return set(), [Finding("knobs", project.knobs_py, 1, "unparseable",
                               "cannot parse knob registry: %s" % e)]
    names: Set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "Knob" and node.args):
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            findings.append(Finding(
                "knobs", project.knobs_py, node.lineno, "dynamic-knob-name",
                "Knob(...) with a non-literal name defeats static "
                "checking; use a string literal"))
            continue
        names.add(first.value)
        # ALIASED knobs name their native target in the detail string
        # ("X" or "X=value"); the target is registered by extension.
        if len(node.args) >= 3:
            status = node.args[1]
            detail = node.args[2]
            if isinstance(status, ast.Name) and status.id == "ALIASED" \
                    and isinstance(detail, ast.Constant) \
                    and isinstance(detail.value, str):
                names.add(detail.value.split("=", 1)[0])
    return names, findings


def referenced_knobs(project: Project) -> Dict[str, Tuple[str, int]]:
    """knob name -> (file, line) of one representative read."""
    refs: Dict[str, Tuple[str, int]] = {}

    def add(name: str, rel: str, line: int):
        if KNOB_RE.match(name):
            refs.setdefault(name, (rel, line))

    for rel in project.python_files():
        try:
            tree = project.parsed(rel)
        except (SyntaxError, UnicodeDecodeError, OSError):
            continue
        for name, line in pyast.env_reads(tree):
            add(name, rel, line)
    for rel in project.native_files():
        for name, line in cpp.env_reads(project.read(rel)):
            add(name, rel, line)
    return refs


def documented(name: str, doc_text: str) -> bool:
    """Boundary-anchored presence test: a bare substring match would
    let `HOROVOD_AUTOTUNE` ride on the documented `HOROVOD_AUTOTUNE_LOG`
    row and silently defeat the staleness guarantee."""
    return re.search(r"(?<![A-Za-z0-9_])" + re.escape(name)
                     + r"(?![A-Za-z0-9_])", doc_text) is not None


def check(project: Project) -> List[Finding]:
    registered, findings = registered_knobs(project)
    allowlist = (project.knob_allowlist if project.knob_allowlist is not None
                 else DEFAULT_ALLOWLIST)
    doc_text = project.read(project.config_doc) \
        if project.exists(project.config_doc) else ""
    for name, (rel, line) in sorted(referenced_knobs(project).items()):
        if name in allowlist:
            continue
        if name not in registered:
            findings.append(Finding(
                "knobs", rel, line, "unregistered:" + name,
                "env knob %s is read here but not registered in %s "
                "(register it, or allowlist it in tools/analysis/"
                "check_knobs.py with a justification)"
                % (name, project.knobs_py)))
        elif not documented(name, doc_text):
            findings.append(Finding(
                "knobs", rel, line, "undocumented:" + name,
                "env knob %s is read here but never mentioned in %s"
                % (name, project.config_doc)))
    return findings
