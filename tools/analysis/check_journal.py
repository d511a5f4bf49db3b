"""Journal-discipline lint: no ad-hoc append-mode persistence.

The crash-safety story of PRs 5-8 (driver restart replay, serve-router
recovery) rests on ONE implementation of the append-only JSONL journal
discipline — fsync-after-append, newline/torn-tail guard before
appending, torn-tail-tolerant fold on read: ``runner/journal.py``
(``DriverJournal``: attach-truncate + fsync'd append + snapshot/event
replay).

Consumers route through it: the online tuner's decision log
(``utils/online_tuner.py``) appends exclusively through
``DriverJournal`` — its replay fold only READS the file — so it is
deliberately NOT a second primitive owner and stays inside this
checker's scope like everything else.

A second hand-rolled ``open(path, "a")`` + ``json.dumps`` persistence
path would re-import every bug that one already fixed (welded torn
tails, lost records after a mid-file garbage line, appends that never
reach disk). This checker flags every append-mode open — ``open``
with an ``a`` mode or ``os.open`` with ``O_APPEND`` — in
``horovod_tpu/`` outside the primitive's owner. Rare legitimate
non-journal appends carry ``# analysis: allow-append`` on (or one line
above) the ``open`` call, with a reason.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional

from tools.analysis.common import Finding, Project

ALLOW_TAG = "analysis: allow-append"


def _append_open(node: ast.Call) -> Optional[str]:
    """Return a short description when ``node`` opens a file in append
    mode; None otherwise."""
    f = node.func
    fname = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else None)
    if fname == "open" and not (isinstance(f, ast.Attribute)
                                and isinstance(f.value, ast.Name)
                                and f.value.id == "os"):
        mode = None
        if len(node.args) >= 2:
            mode = node.args[1]
        elif isinstance(f, ast.Attribute) and node.args:
            # Method-style opens take mode FIRST: Path(p).open("a").
            # For a bare open() the first positional is the filename,
            # never the mode — so this branch is attribute-calls only,
            # and only when the literal LOOKS like a mode string (a
            # lone positional to codecs.open-style wrappers is a
            # filename, which frequently contains an 'a').
            cand = node.args[0]
            if isinstance(cand, ast.Constant) \
                    and isinstance(cand.value, str) \
                    and re.fullmatch(r"[rwxab+tU]{1,4}", cand.value):
                mode = cand
        for kw in node.keywords:
            if kw.arg == "mode":
                mode = kw.value
        if isinstance(mode, ast.Constant) and isinstance(mode.value, str) \
                and "a" in mode.value:
            return "open(..., %r)" % mode.value
        return None
    if fname == "open" and isinstance(f, ast.Attribute) \
            and isinstance(f.value, ast.Name) and f.value.id == "os":
        flags = node.args[1] if len(node.args) >= 2 else None
        if flags is not None and any(
                isinstance(n, ast.Attribute) and n.attr == "O_APPEND"
                for n in ast.walk(flags)):
            return "os.open(..., O_APPEND)"
    return None


def _tagged(lines: List[str], lineno: int) -> bool:
    lo = max(0, lineno - 2)
    hi = min(len(lines), lineno + 1)
    return any(ALLOW_TAG in ln for ln in lines[lo:hi])


def check(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for rel in project.journal_files():
        try:
            tree = project.parsed(rel)
        except (OSError, SyntaxError, UnicodeDecodeError):
            continue
        lines = project.read(rel).splitlines()
        per_key: dict = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            what = _append_open(node)
            if what is None or _tagged(lines, node.lineno):
                continue
            ordinal = per_key.get(what, 0)
            per_key[what] = ordinal + 1
            findings.append(Finding(
                "journal", rel, node.lineno,
                "direct-append:%s:%d" % (what, ordinal),
                "%s — append-mode persistence outside the journal "
                "primitive; route through runner/journal.DriverJournal "
                "(fsync-after-append, torn-tail guard), or tag the "
                "line with "
                "'# %s' and a reason" % (what, ALLOW_TAG)))
    return findings
