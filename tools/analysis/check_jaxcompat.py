"""jax-compat lint: drift-prone jax APIs stay behind the mesh shims.

``parallel/mesh.py``'s ``shard_map_compat`` / ``traced_axis_size``
wrappers own the spelling of shard_map and axis sizing (the
framework's ``check_vma`` default lives there). This checker pins the
discipline: direct use of those jax APIs anywhere outside
``parallel/mesh.py`` is a finding. Flagged patterns:

- ``from jax import shard_map`` / ``jax.shard_map`` — even inside a
  try/except import dance;
- ``from jax.experimental.shard_map import ...`` — gone from the
  installed jax;
- ``lax.axis_size`` / ``jax.lax.axis_size`` — use ``traced_axis_size``;
- ``psum(<literal 1>, axis)`` — bare psum-derived axis sizing; use
  ``traced_axis_size``.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Tuple

from tools.analysis.common import Finding, Project

_SHIM_HINT = ("use horovod_tpu.parallel.mesh.%s "
              "(docs/static_analysis.md#jax-compat)")


def _dotted(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return None if base is None else base + "." + node.attr
    return None


def _scan(tree: ast.Module) -> List[Tuple[str, str, int]]:
    """(key, message, line) per drift-prone use."""
    hits: List[Tuple[str, str, int]] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            names = {a.name for a in node.names}
            if mod == "jax" and "shard_map" in names:
                hits.append((
                    "import-shard_map",
                    "direct 'from jax import shard_map' — "
                    + _SHIM_HINT % "shard_map_compat",
                    node.lineno))
            if mod.startswith("jax.experimental.shard_map"):
                hits.append((
                    "import-experimental-shard_map",
                    "'jax.experimental.shard_map' is gone from the "
                    "installed jax — " + _SHIM_HINT % "shard_map_compat",
                    node.lineno))
        elif isinstance(node, ast.Attribute):
            dotted = _dotted(node)
            if dotted in ("jax.shard_map",):
                hits.append((
                    "attr-jax.shard_map",
                    "direct 'jax.shard_map' — "
                    + _SHIM_HINT % "shard_map_compat", node.lineno))
            elif dotted is not None and dotted.endswith("lax.axis_size"):
                hits.append((
                    "attr-lax.axis_size",
                    "direct 'lax.axis_size' — "
                    + _SHIM_HINT % "traced_axis_size", node.lineno))
        elif isinstance(node, ast.Call):
            f = node.func
            fname = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            if fname == "psum" and len(node.args) >= 2 \
                    and isinstance(node.args[0], ast.Constant) \
                    and node.args[0].value == 1:
                hits.append((
                    "psum-axis-sizing",
                    "bare 'psum(1, axis)' axis sizing — "
                    + _SHIM_HINT % "traced_axis_size", node.lineno))
    return hits


def check(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for rel in project.jax_files():
        try:
            tree = project.parsed(rel)
        except (OSError, SyntaxError, UnicodeDecodeError):
            continue
        per_key: dict = {}
        for key, message, line in _scan(tree):
            ordinal = per_key.get(key, 0)
            per_key[key] = ordinal + 1
            findings.append(Finding(
                "jaxcompat", rel, line,
                "%s:%d" % (key, ordinal), message))
    return findings
