"""Shared plumbing for the contract checkers.

A checker is a function ``check(project) -> List[Finding]``. Findings
carry a stable fingerprint (checker + file + semantic key, no line
numbers) so the checked-in baseline survives unrelated edits; the
driver (``__main__.py``) diffs current findings against
``baseline.json`` and only *new* violations fail the run
(docs/static_analysis.md).
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence

# Directories never worth scanning (build output, caches, vendored docs
# assets). Matched against path components.
SKIP_DIRS = {"__pycache__", ".git", "build", "build-thread", "node_modules",
             ".pytest_cache"}


class Finding(NamedTuple):
    checker: str   # e.g. "knobs"
    path: str      # repo-relative path of the offending file
    line: int      # 1-based; advisory only (not part of the fingerprint)
    key: str       # semantic identity within (checker, path)
    message: str

    @property
    def fingerprint(self) -> str:
        return "%s::%s::%s" % (self.checker, self.path, self.key)

    def render(self) -> str:
        return "%s:%d: [%s] %s" % (self.path, self.line, self.checker,
                                   self.message)


class Project:
    """Paths of the contract surfaces, overridable so checker unit
    tests can point at a small fixture tree (tests/test_analysis.py)."""

    def __init__(self, root: str, *,
                 knobs_py: str = "horovod_tpu/common/knobs.py",
                 session_py: str = "horovod_tpu/core/session.py",
                 native_src: str = "horovod_tpu/core/src",
                 config_doc: str = "docs/configuration.md",
                 metrics_doc: str = "docs/metrics.md",
                 python_scan_dirs: Sequence[str] = (
                     "horovod_tpu", "bin", "ci", "tests", "tools"),
                 python_scan_files: Sequence[str] = (
                     "bench_scaling.py", "setup.py",
                     # Extensionless python launcher: _walk()'s .py
                     # filter misses it, and launch-time knobs are
                     # exactly what it would read.
                     "bin/hvdrun"),
                 except_scan_dirs: Sequence[str] = ("horovod_tpu",),
                 metric_scan_dirs: Sequence[str] = ("horovod_tpu",),
                 lock_scan_dirs: Sequence[str] = ("horovod_tpu",),
                 journal_scan_dirs: Sequence[str] = ("horovod_tpu",),
                 journal_allowed_files: Sequence[str] = (
                     "horovod_tpu/runner/journal.py",),
                 jax_allowed_files: Sequence[str] = (
                     "horovod_tpu/parallel/mesh.py",),
                 jax_scan_files: Sequence[str] = ("__graft_entry__.py",),
                 test_scan_dirs: Sequence[str] = ("tests",),
                 spmd_scan_dirs: Sequence[str] = ("horovod_tpu",
                                                  "examples"),
                 spmd_scan_files: Sequence[str] = (
                     "bench_scaling.py", "bench_wire.py",
                     "bench_serve.py", "__graft_entry__.py"),
                 tuner_py: str = "horovod_tpu/utils/online_tuner.py",
                 knob_allowlist: Optional[Dict[str, str]] = None):
        self.root = os.path.abspath(root)
        self.knobs_py = knobs_py
        self.session_py = session_py
        self.native_src = native_src
        self.config_doc = config_doc
        self.metrics_doc = metrics_doc
        self.python_scan_dirs = tuple(python_scan_dirs)
        self.python_scan_files = tuple(python_scan_files)
        self.except_scan_dirs = tuple(except_scan_dirs)
        self.metric_scan_dirs = tuple(metric_scan_dirs)
        self.lock_scan_dirs = tuple(lock_scan_dirs)
        self.journal_scan_dirs = tuple(journal_scan_dirs)
        self.journal_allowed_files = tuple(journal_allowed_files)
        self.jax_allowed_files = tuple(jax_allowed_files)
        self.jax_scan_files = tuple(jax_scan_files)
        self.test_scan_dirs = tuple(test_scan_dirs)
        self.spmd_scan_dirs = tuple(spmd_scan_dirs)
        self.spmd_scan_files = tuple(spmd_scan_files)
        self.tuner_py = tuner_py
        self.knob_allowlist = knob_allowlist
        self._ast_cache: Dict[str, object] = {}

    def abspath(self, rel: str) -> str:
        return os.path.join(self.root, rel)

    def read(self, rel: str) -> str:
        with open(self.abspath(rel), encoding="utf-8") as f:
            return f.read()

    def exists(self, rel: str) -> bool:
        return os.path.exists(self.abspath(rel))

    def parsed(self, rel: str):
        """Memoized ``ast.parse`` of a scanned file — three checkers
        walk the same ~24k-LoC Python surface; parsing it once per run
        instead of once per checker cuts most of the wall time.
        Raises OSError/SyntaxError/UnicodeDecodeError like ast.parse."""
        import ast

        if rel not in self._ast_cache:
            self._ast_cache[rel] = ast.parse(self.read(rel), rel)
        return self._ast_cache[rel]

    def _walk(self, dirs: Iterable[str], suffixes) -> List[str]:
        out = []
        for base in dirs:
            top = self.abspath(base)
            if not os.path.isdir(top):
                continue
            for dirpath, subdirs, files in os.walk(top):
                subdirs[:] = [d for d in subdirs if d not in SKIP_DIRS
                              and not d.startswith("build-")]
                for fn in sorted(files):
                    if fn.endswith(suffixes):
                        out.append(os.path.relpath(
                            os.path.join(dirpath, fn), self.root))
        return sorted(out)

    def python_files(self) -> List[str]:
        files = self._walk(self.python_scan_dirs, (".py",))
        for rel in self.python_scan_files:
            if self.exists(rel):
                files.append(rel)
        return sorted(set(files))

    def except_files(self) -> List[str]:
        return self._walk(self.except_scan_dirs, (".py",))

    def metric_files(self) -> List[str]:
        return self._walk(self.metric_scan_dirs, (".py",))

    def native_files(self) -> List[str]:
        return self._walk([self.native_src], (".cc", ".h"))

    def lock_files(self) -> List[str]:
        return self._walk(self.lock_scan_dirs, (".py",))

    def journal_files(self) -> List[str]:
        return [rel for rel in self._walk(self.journal_scan_dirs, (".py",))
                if rel not in self.journal_allowed_files]

    def jax_files(self) -> List[str]:
        files = self.python_files()
        for rel in self.jax_scan_files:
            if self.exists(rel):
                files.append(rel)
        return sorted({rel for rel in files
                       if rel not in self.jax_allowed_files})

    def test_files(self) -> List[str]:
        return [rel for rel in self._walk(self.test_scan_dirs, (".py",))
                if os.path.basename(rel).startswith("test_")]

    def spmd_files(self) -> List[str]:
        """The SPMD-checked surface: the library, the examples, and
        the bench/dryrun entry points (check_spmd.py). Library files
        overlap python_files(), so the shared ``parsed`` memoization
        means no second parse pass."""
        files = self._walk(self.spmd_scan_dirs, (".py",))
        for rel in self.spmd_scan_files:
            if self.exists(rel):
                files.append(rel)
        return sorted(set(files))


# --- baseline ---------------------------------------------------------------

def load_baseline(path: str) -> Dict[str, str]:
    """fingerprint -> justification. Missing file = empty baseline."""
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    entries = data.get("findings", {})
    if not isinstance(entries, dict):
        raise ValueError("baseline 'findings' must be a dict of "
                         "fingerprint -> justification")
    return dict(entries)

def save_baseline(path: str, findings: List[Finding],
                  old: Optional[Dict[str, str]] = None,
                  extra: Optional[Dict[str, str]] = None) -> None:
    """Write the current finding set, keeping justifications already
    recorded for fingerprints that persist. ``extra`` entries (e.g.
    out-of-scope checkers during a --checker-scoped update) are carried
    over verbatim."""
    old = old or {}
    entries = dict(extra or {})
    entries.update({
        f.fingerprint: old.get(
            f.fingerprint, "TODO: justify or fix (%s)" % f.message)
        for f in findings
    })
    payload = {
        "_comment": (
            "Accepted pre-existing findings of `python -m tools.analysis`. "
            "New violations (fingerprints not listed here) fail the run. "
            "Regenerate with --update-baseline, then replace every TODO "
            "justification or fix the finding (docs/static_analysis.md)."),
        "findings": dict(sorted(entries.items())),
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=False)
        f.write("\n")
