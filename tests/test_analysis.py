"""Tier-1 tests of the cross-language contract checkers (ISSUE 4; the
second-generation locks/journal/jaxcompat/testtier checkers are
ISSUE 9).

Each checker runs against a small fixture tree: the known-good fixture
passes, every seeded violation fails, and the baseline suppresses
accepted findings. The final test pins the acceptance criterion that
the real tree is clean — `python -m tools.analysis` exits 0.

Pure AST/text analysis: no jax, no subprocesses — seconds, not minutes.
"""

import json
import os

import pytest

from tools.analysis import CHECKERS, cpp, run_all
from tools.analysis.__main__ import main as analysis_main
from tools.analysis.common import Finding, Project, load_baseline, \
    save_baseline

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --- fixture tree -----------------------------------------------------------

KNOBS_PY = '''
from typing import NamedTuple
HONORED = "honored"
ALIASED = "aliased"
class Knob(NamedTuple):
    name: str
    status: str
    detail: str
REGISTRY = {k.name: k for k in [
    Knob("HOROVOD_GOOD_KNOB", HONORED, "core/session.py"),
    Knob("HOROVOD_OLD_NAME", ALIASED, "HOROVOD_ALIAS_TARGET"),
]}
'''

SESSION_PY = '''
import ctypes

_M_CORE = {"responses": 1, "bytes_total": 2}


class CoreSession:
    def start(self, lib):
        lib.hvd_core_init.restype = ctypes.c_int
        lib.hvd_core_init.argtypes = [ctypes.c_int, ctypes.c_char_p]
        lib.hvd_core_counters.restype = None
        lib.hvd_core_counters.argtypes = [
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int]
        lib.hvd_core_init(1, b"addr")
        self._lib = lib

    def counters(self):
        buf = (ctypes.c_longlong * 2)()
        self._lib.hvd_core_counters(buf, 2)
        return {"responses": buf[0], "bytes_total": buf[1]}
'''

OPERATIONS_CC = '''
#include <cstdlib>

extern "C" {

int hvd_core_init(int rank, const char* addr) {
  (void)rank; (void)addr;
  if (getenv("HOROVOD_GOOD_KNOB")) return 1;
  return 0;
}

// Fills out[0..n): responses, bytes_total. Append-only layout.
void hvd_core_counters(long long* out, int n) {
  long long vals[2] = {1, 2};
  for (int i = 0; i < n && i < 2; ++i) out[i] = vals[i];
}

}  // extern "C"
'''

GOOD_MODULE = '''
import os

from fixture import metrics


def knob():
    return os.environ.get("HOROVOD_GOOD_KNOB", "0")


M = metrics.counter("hvd_good_total", "documented metric")


def careful(fn):
    try:
        return fn()
    except ValueError:
        return None
'''

CONFIG_DOC = "# knobs\n`HOROVOD_GOOD_KNOB` does things.\n"
METRICS_DOC = "# metrics\n| `hvd_good_total` | counts |\n"


def make_tree(root):
    files = {
        "horovod_tpu/__init__.py": "",
        "horovod_tpu/common/__init__.py": "",
        "horovod_tpu/common/knobs.py": KNOBS_PY,
        "horovod_tpu/core/__init__.py": "",
        "horovod_tpu/core/session.py": SESSION_PY,
        "horovod_tpu/core/src/operations.cc": OPERATIONS_CC,
        "horovod_tpu/good.py": GOOD_MODULE,
        "docs/configuration.md": CONFIG_DOC,
        "docs/metrics.md": METRICS_DOC,
    }
    for rel, content in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(content)
    return root


def project(root):
    return Project(str(root), python_scan_files=(), knob_allowlist={})


@pytest.fixture
def tree(tmp_path):
    return make_tree(str(tmp_path))


# --- known-good passes ------------------------------------------------------

def test_known_good_fixture_passes(tree):
    assert run_all(project(tree)) == []


def test_real_tree_is_clean():
    """Acceptance criterion: the shipped tree has no findings beyond
    the checked-in baseline (which is expected to stay empty or carry
    a justification per entry)."""
    rc = analysis_main(["--root", _REPO])
    assert rc == 0
    for fp, why in load_baseline(
            os.path.join(_REPO, "tools", "analysis",
                         "baseline.json")).items():
        assert why and "TODO" not in why, (
            "baseline entry %s lacks a justification" % fp)


# --- seeded violations fail -------------------------------------------------

def _seed(tree, rel, content):
    path = os.path.join(tree, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(content)


def _keys(findings, checker):
    return [f.key for f in findings if f.checker == checker]


def test_unregistered_knob_fails(tree):
    _seed(tree, "horovod_tpu/rogue.py",
          "import os\nV = os.environ.get('HOROVOD_ROGUE_KNOB')\n")
    assert "unregistered:HOROVOD_ROGUE_KNOB" in \
        _keys(run_all(project(tree)), "knobs")


def test_registered_but_undocumented_knob_fails(tree):
    _seed(tree, "horovod_tpu/common/knobs.py", KNOBS_PY.replace(
        'Knob("HOROVOD_GOOD_KNOB", HONORED, "core/session.py"),',
        'Knob("HOROVOD_GOOD_KNOB", HONORED, "core/session.py"),\n'
        '    Knob("HOROVOD_HIDDEN_KNOB", HONORED, "nowhere"),'))
    _seed(tree, "horovod_tpu/rogue.py",
          "import os\nV = os.environ['HOROVOD_HIDDEN_KNOB']\n")
    assert "undocumented:HOROVOD_HIDDEN_KNOB" in \
        _keys(run_all(project(tree)), "knobs")


def test_alias_target_counts_as_registered(tree):
    _seed(tree, "docs/configuration.md",
          CONFIG_DOC + "`HOROVOD_ALIAS_TARGET` too.\n")
    _seed(tree, "horovod_tpu/aliased.py",
          "import os\nV = os.environ.get('HOROVOD_ALIAS_TARGET')\n")
    assert _keys(run_all(project(tree)), "knobs") == []


def test_native_getenv_is_scanned(tree):
    _seed(tree, "horovod_tpu/core/src/operations.cc",
          OPERATIONS_CC.replace("HOROVOD_GOOD_KNOB",
                                "HVD_NATIVE_ONLY_KNOB"))
    assert "unregistered:HVD_NATIVE_ONLY_KNOB" in \
        _keys(run_all(project(tree)), "knobs")


def test_counter_slot_count_mismatch_fails(tree):
    _seed(tree, "horovod_tpu/core/src/operations.cc", OPERATIONS_CC
          .replace("long long vals[2] = {1, 2};",
                   "long long vals[3] = {1, 2, 3};")
          .replace("// Fills out[0..n): responses, bytes_total.",
                   "// Fills out[0..n): responses, bytes_total, extra."))
    keys = _keys(run_all(project(tree)), "counters")
    assert "slot-count-mismatch" in keys
    assert "slot-order-mismatch" in keys  # extra name vs python decode


def test_counter_order_mismatch_fails(tree):
    _seed(tree, "horovod_tpu/core/src/operations.cc", OPERATIONS_CC
          .replace("responses, bytes_total", "bytes_total, responses"))
    assert "slot-order-mismatch" in \
        _keys(run_all(project(tree)), "counters")


def test_counter_call_arg_mismatch_fails(tree):
    """The literal n passed to hvd_core_counters bounds the native
    fill; a stale value silently zeroes appended slots even when every
    other surface agrees."""
    _seed(tree, "horovod_tpu/core/session.py", SESSION_PY.replace(
        "self._lib.hvd_core_counters(buf, 2)",
        "self._lib.hvd_core_counters(buf, 1)"))
    assert "call-arg-count" in \
        _keys(run_all(project(tree)), "counters")


def test_counter_bridge_missing_key_fails(tree):
    _seed(tree, "horovod_tpu/core/session.py", SESSION_PY.replace(
        '_M_CORE = {"responses": 1, "bytes_total": 2}',
        '_M_CORE = {"responses": 1}'))
    assert "bridge-missing-keys" in \
        _keys(run_all(project(tree)), "counters")


def test_undeclared_ctypes_signature_fails(tree):
    _seed(tree, "horovod_tpu/raw_call.py",
          "def go(lib):\n    return lib.hvd_core_init(1, b'x')\n")
    keys = _keys(run_all(project(tree)), "ctypes")
    assert "undeclared-argtypes:hvd_core_init" in keys
    assert "undeclared-restype:hvd_core_init" in keys


def test_ctypes_argtype_mismatch_fails(tree):
    _seed(tree, "horovod_tpu/core/session.py", SESSION_PY.replace(
        "[ctypes.c_int, ctypes.c_char_p]", "[ctypes.c_int, ctypes.c_int]"))
    assert "argtypes-mismatch:hvd_core_init:1" in \
        _keys(run_all(project(tree)), "ctypes")


def test_ctypes_arity_mismatch_fails(tree):
    _seed(tree, "horovod_tpu/core/session.py", SESSION_PY.replace(
        "[ctypes.c_int, ctypes.c_char_p]", "[ctypes.c_int]"))
    assert "argtypes-arity:hvd_core_init" in \
        _keys(run_all(project(tree)), "ctypes")


def test_ctypes_unknown_symbol_fails(tree):
    _seed(tree, "horovod_tpu/raw_call.py",
          "def go(lib):\n    lib.hvd_core_vanished.restype = None\n"
          "    lib.hvd_core_vanished.argtypes = []\n"
          "    lib.hvd_core_vanished()\n")
    assert "unknown-symbol:hvd_core_vanished" in \
        _keys(run_all(project(tree)), "ctypes")


def test_undocumented_metric_fails(tree):
    _seed(tree, "horovod_tpu/extra_metric.py",
          "from fixture import metrics\n"
          "M = metrics.counter('hvd_rogue_total', 'oops')\n")
    assert "undocumented:hvd_rogue_total" in \
        _keys(run_all(project(tree)), "metrics")


def test_bare_except_fails(tree):
    _seed(tree, "horovod_tpu/sloppy.py",
          "def f(x):\n    try:\n        return x()\n"
          "    except:\n        pass\n")
    assert _keys(run_all(project(tree)), "excepts")


def test_blind_broad_except_fails_and_tag_suppresses(tree):
    _seed(tree, "horovod_tpu/sloppy.py",
          "def f(x):\n    try:\n        return x()\n"
          "    except Exception:\n        pass\n")
    assert _keys(run_all(project(tree)), "excepts")
    _seed(tree, "horovod_tpu/sloppy.py",
          "def f(x):\n    try:\n        return x()\n"
          "    except Exception:  # analysis: allow-broad-except\n"
          "        pass\n")
    assert _keys(run_all(project(tree)), "excepts") == []


def test_broad_except_that_handles_is_fine(tree):
    _seed(tree, "horovod_tpu/careful.py",
          "import logging\ndef f(x):\n    try:\n        return x()\n"
          "    except Exception as e:\n"
          "        logging.warning('fallback: %s', e)\n"
          "        return None\n")
    assert _keys(run_all(project(tree)), "excepts") == []


# --- baseline + CLI ---------------------------------------------------------

def test_cli_exit_codes_and_baseline_suppression(tree, tmp_path):
    _seed(tree, "horovod_tpu/rogue.py",
          "import os\nV = os.environ.get('HOROVOD_ROGUE_KNOB')\n")
    baseline = str(tmp_path / "baseline.json")
    # Fixture project defaults differ from main()'s Project(root), but
    # the rogue knob is visible to both; exit codes are the contract.
    assert analysis_main(["--root", tree, "--baseline", baseline]) == 1
    # Accept the finding into the baseline -> clean run.
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--update-baseline"]) == 0
    assert analysis_main(["--root", tree, "--baseline", baseline]) == 0
    # --no-baseline surfaces it again.
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--no-baseline"]) == 1


def test_scoped_update_baseline_preserves_other_checkers(tree, tmp_path):
    """--checker X --update-baseline must not delete other checkers'
    accepted entries (and their hand-written justifications)."""
    _seed(tree, "horovod_tpu/rogue.py",
          "import os\nV = os.environ.get('HOROVOD_ROGUE_KNOB')\n")
    _seed(tree, "horovod_tpu/sloppy.py",
          "def f(x):\n    try:\n        return x()\n"
          "    except Exception:\n        pass\n")
    baseline = str(tmp_path / "baseline.json")
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--update-baseline"]) == 0
    entries = load_baseline(baseline)
    excepts_fp = [fp for fp in entries if fp.startswith("excepts::")]
    assert excepts_fp and any(fp.startswith("knobs::") for fp in entries)
    # Scoped re-update of only the knobs checker:
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "knobs",
                          "--update-baseline"]) == 0
    after = load_baseline(baseline)
    assert set(excepts_fp) <= set(after), after
    assert analysis_main(["--root", tree, "--baseline", baseline]) == 0


def test_baseline_keeps_existing_justifications(tmp_path):
    path = str(tmp_path / "baseline.json")
    f1 = Finding("knobs", "a.py", 3, "unregistered:X", "msg")
    save_baseline(path, [f1])
    entries = load_baseline(path)
    assert "TODO" in entries[f1.fingerprint]
    entries[f1.fingerprint] = "accepted: legacy"
    with open(path, "w") as fh:
        json.dump({"findings": entries}, fh)
    f2 = Finding("metrics", "b.py", 9, "undocumented:hvd_x", "msg2")
    save_baseline(path, [f1, f2], load_baseline(path))
    fresh = load_baseline(path)
    assert fresh[f1.fingerprint] == "accepted: legacy"
    assert "TODO" in fresh[f2.fingerprint]


def test_doc_presence_is_boundary_anchored(tree):
    """`HOROVOD_GOOD_KNOB` must not be satisfiable by a documented
    `HOROVOD_GOOD_KNOB_LOG` row (substring ride-along defeats the
    staleness guarantee)."""
    _seed(tree, "docs/configuration.md",
          "# knobs\n`HOROVOD_GOOD_KNOB_LOG` only.\n")
    assert "undocumented:HOROVOD_GOOD_KNOB" in \
        _keys(run_all(project(tree)), "knobs")


def test_excepts_fingerprint_survives_line_shifts(tree):
    body = ("def f(x):\n    try:\n        return x()\n"
            "    except Exception:\n        pass\n")
    _seed(tree, "horovod_tpu/sloppy.py", body)
    before = _keys(run_all(project(tree)), "excepts")
    _seed(tree, "horovod_tpu/sloppy.py", "# shifted\n# down\n" + body)
    after = _keys(run_all(project(tree)), "excepts")
    assert before == after and len(before) == 1
    assert before[0].startswith("broad-except:f:")


def test_excepts_new_violation_does_not_steal_baselined_identity(tree):
    """Content-addressed keys: adding a distinct broad-except above an
    accepted one must produce a NEW fingerprint, not inherit the old
    (which would let the new swallow hide under the baseline entry)."""
    one = ("def f(x):\n    try:\n        return x()\n"
           "    except Exception:\n        pass\n")
    _seed(tree, "horovod_tpu/sloppy.py", one)
    [old_key] = _keys(run_all(project(tree)), "excepts")
    two = ("def f(x):\n"
           "    try:\n        x.prep()\n"
           "    except BaseException:\n        pass\n"
           "    try:\n        return x()\n"
           "    except Exception:\n        pass\n")
    _seed(tree, "horovod_tpu/sloppy.py", two)
    keys = _keys(run_all(project(tree)), "excepts")
    assert old_key in keys and len(keys) == 2


def test_extern_c_wrapper_call_is_not_a_prototype(tree):
    """A statement-position call of one export inside another must not
    register a bogus conflicting prototype (degrades the whole ctypes
    checker to 'unparseable')."""
    _seed(tree, "horovod_tpu/core/src/operations.cc", OPERATIONS_CC
          .replace("}  // extern \"C\"",
                   "int hvd_core_failed(void) { return 0; }\n"
                   "int hvd_core_healthy(void) {\n"
                   "  int x = hvd_core_failed();\n"
                   "  return hvd_core_failed() + x;\n"
                   "}\n"
                   "}  // extern \"C\""))
    findings = run_all(project(tree))
    assert _keys(findings, "ctypes") == [], findings


# --- parser unit coverage ---------------------------------------------------

def test_extern_c_parser_handles_callbacks_and_comments():
    protos = cpp.extern_c_prototypes('''
// extern "C" in a comment { should not confuse the parser
extern "C" {
void hvd_set_cb(void (*cb)(long long, int, const char*)); // decl
int hvd_go(double scale, const long long* shape, int ndim) { return 0; }
}
void hvd_not_exported(int x);
''')
    assert set(protos) == {"hvd_set_cb", "hvd_go"}
    assert protos["hvd_set_cb"].params[0].is_callback
    assert protos["hvd_go"].ret == "int"
    assert [p.ctype for p in protos["hvd_go"].params] == \
        ["double", "const long long*", "int"]
    assert cpp.expected_argtype(protos["hvd_go"].params[1]) == \
        "POINTER(c_longlong)"


def test_env_read_scanner_catches_helper_wrappers():
    hits = cpp.env_reads('''
double t = EnvDouble("HVD_T", 1.0);
long long k = EnvLL("HVD_K", 0);
const char* v = getenv("HVD_V");
// getenv("HVD_IN_COMMENT") must not count
''')
    assert [h[0] for h in hits] == ["HVD_T", "HVD_K", "HVD_V"]


def test_every_checker_ran_against_fixture(tree):
    """Guard against a checker silently dropping out of run_all."""
    assert set(CHECKERS) == {"knobs", "counters", "ctypes", "metrics",
                             "excepts", "locks", "journal", "jaxcompat",
                             "testtier", "spmd", "deadlock", "blocking"}


def test_build_refuses_any_sanitizer_preload(monkeypatch, tmp_path):
    """core/build.py must refuse to fork the compiler under ANY
    preloaded sanitizer runtime, not just libtsan (the docs promise
    the guard for the whole matrix)."""
    from horovod_tpu.core import build

    monkeypatch.setenv("HVD_CORE_SANITIZE", "address")
    monkeypatch.setenv("LD_PRELOAD",
                       "/usr/lib/x86_64-linux-gnu/libasan.so.6")
    # Point the build at a scratch dir with no library so the guard
    # path (not the cache path) is exercised.
    monkeypatch.setattr(build, "_build_dir", lambda: str(tmp_path / "b"))
    with pytest.raises(RuntimeError, match="libasan"):
        build.library_path(build_if_missing=True)


# ====================== second-generation checkers (ISSUE 9) ================
# locks / journal / jaxcompat / testtier: same fixture-tree discipline —
# known-good passes, each seeded violation fails, tags suppress, the
# real tree stays clean (test_real_tree_is_clean above already runs all
# checkers).

# --- locks: python ----------------------------------------------------------

LOCKED_CLASS_OK = '''
import threading


class Table:
    def __init__(self):
        self._lock = threading.Lock()
        self._rows = {}
        self.name = "t"  # never written under the lock: unguarded

    def put(self, k, v):
        with self._lock:
            self._rows[k] = v

    def get(self, k):
        with self._lock:
            return self._rows.get(k)

    def label(self):
        return self.name
'''


def test_locks_known_good_locked_class_passes(tree):
    _seed(tree, "horovod_tpu/table.py", LOCKED_CLASS_OK)
    assert _keys(run_all(project(tree)), "locks") == []


def test_locks_unguarded_read_fails(tree):
    _seed(tree, "horovod_tpu/table.py", LOCKED_CLASS_OK.replace(
        "        with self._lock:\n            return self._rows.get(k)",
        "        return self._rows.get(k)"))
    assert "unguarded:Table.get:_rows" in \
        _keys(run_all(project(tree)), "locks")


def test_locks_unguarded_mutator_call_fails(tree):
    """self._rows.pop(...) outside the lock is a WRITE of _rows."""
    _seed(tree, "horovod_tpu/table.py", LOCKED_CLASS_OK + '''
    def evict(self, k):
        self._rows.pop(k, None)
''')
    assert "unguarded:Table.evict:_rows" in \
        _keys(run_all(project(tree)), "locks")


def test_locks_holds_lock_tag_suppresses(tree):
    _seed(tree, "horovod_tpu/table.py", LOCKED_CLASS_OK + '''
    def get_locked(self, k):
        # analysis: holds-lock(_lock) -- callers hold self._lock
        return self._rows.get(k)
''')
    assert _keys(run_all(project(tree)), "locks") == []


def test_locks_init_writes_are_exempt(tree):
    """__init__ populates guarded attributes before the object escapes
    to other threads: LOCKED_CLASS_OK relies on it (already clean), and
    the exemption must not leak to other methods (covered above)."""
    _seed(tree, "horovod_tpu/table.py", LOCKED_CLASS_OK.replace(
        "        self._rows = {}",
        "        self._rows = {}\n        self._rows['seed'] = 1"))
    assert _keys(run_all(project(tree)), "locks") == []


def test_locks_closure_does_not_inherit_the_lock(tree):
    """A closure defined under `with self._lock:` outlives the scope
    (callbacks, thread targets) — its accesses are NOT lock-covered."""
    _seed(tree, "horovod_tpu/table.py", LOCKED_CLASS_OK + '''
    def deferred(self):
        with self._lock:
            def cb():
                return self._rows.copy()
        return cb
''')
    assert "unguarded:Table.deferred:_rows" in \
        _keys(run_all(project(tree)), "locks")


def test_locks_borrowed_lock_via_with_counts(tree):
    """An attribute used as `with self._mu:` is a lock even when the
    lock object is passed in (the metrics value classes share their
    family's RLock that way)."""
    _seed(tree, "horovod_tpu/borrowed.py", '''
class Child:
    def __init__(self, mu):
        self._mu = mu
        self._n = 0

    def inc(self):
        with self._mu:
            self._n += 1

    def peek(self):
        return self._n
''')
    assert "unguarded:Child.peek:_n" in \
        _keys(run_all(project(tree)), "locks")


# --- locks: C++ GUARDED_BY --------------------------------------------------

GUARDED_CC = '''
#include <mutex>

struct State {
  std::mutex mu_;
  int hits_ = 0;  // GUARDED_BY(mu_)
};

State st;

void Bump() {
  std::lock_guard<std::mutex> lk(st.mu_);
  st.hits_ += 1;
}
'''


def test_locks_guarded_by_locked_use_passes(tree):
    _seed(tree, "horovod_tpu/core/src/state.cc", GUARDED_CC)
    assert _keys(run_all(project(tree)), "locks") == []


def test_locks_guarded_by_unlocked_use_fails(tree):
    _seed(tree, "horovod_tpu/core/src/state.cc", GUARDED_CC + '''
int Peek() { return st.hits_; }
''')
    keys = _keys(run_all(project(tree)), "locks")
    assert "unguarded-native:hits_:0" in keys


def test_locks_guarded_by_holds_lock_comment_suppresses(tree):
    _seed(tree, "horovod_tpu/core/src/state.cc", GUARDED_CC + '''
int PeekLocked() {
  // analysis: holds-lock(mu_) -- callers hold mu_
  return st.hits_;
}
''')
    assert _keys(run_all(project(tree)), "locks") == []


def test_locks_guarded_by_lock_scope_ends_at_brace(tree):
    """The acquisition guards only until its enclosing brace closes."""
    _seed(tree, "horovod_tpu/core/src/state.cc", GUARDED_CC + '''
int Mixed() {
  {
    std::lock_guard<std::mutex> lk(st.mu_);
    st.hits_ += 1;
  }
  return st.hits_;  // outside the guard scope
}
''')
    keys = _keys(run_all(project(tree)), "locks")
    assert keys == ["unguarded-native:hits_:0"], keys


def test_guarded_by_parser_units():
    from tools.analysis.check_locks import guarded_fields, scan_cpp_uses

    text = '''
struct S {
  std::mutex mu_;
  std::map<int, int> table_;  // GUARDED_BY(mu_)
  int plain_;
  // GUARDED_BY(ghost_) in prose only: no declaration, no entry
};
void F(S& s) {
  std::unique_lock<std::mutex> lk(s.mu_);
  s.table_[1] = 2;
}
void G(S& s) { s.table_.clear(); }
'''
    fields = guarded_fields(text)
    assert set(fields) == {"table_"}
    assert fields["table_"][0] == "mu_"
    uses = scan_cpp_uses(text, fields)
    # The F use is guarded; only G's is reported.
    assert len(uses) == 1 and uses[0][0] == "table_"
    # Comment/string occurrences never count as uses.
    assert scan_cpp_uses('// table_ in a comment\n"table_ in a string"',
                         fields) == []


# --- journal ----------------------------------------------------------------

def test_journal_direct_append_fails(tree):
    _seed(tree, "horovod_tpu/sidecar.py", '''
import json


def persist(path, rec):
    with open(path, "a") as fh:
        fh.write(json.dumps(rec) + "\\n")
''')
    assert any(k.startswith("direct-append:open")
               for k in _keys(run_all(project(tree)), "journal"))


def test_journal_os_open_append_fails(tree):
    _seed(tree, "horovod_tpu/sidecar.py", '''
import os


def persist(path, line):
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND)
    os.write(fd, line)
    os.close(fd)
''')
    assert any(k.startswith("direct-append:os.open")
               for k in _keys(run_all(project(tree)), "journal"))


def test_journal_allowed_files_and_tag_are_exempt(tree):
    body = '''
def persist(path, line):
    with open(path, "a") as fh:  # analysis: allow-append -- test log
        fh.write(line)
'''
    _seed(tree, "horovod_tpu/sidecar.py", body)
    assert _keys(run_all(project(tree)), "journal") == []
    # The journal primitive itself may append (that is its job).
    _seed(tree, "horovod_tpu/runner/journal.py",
          "def attach(path):\n    return open(path, 'a')\n")
    assert _keys(run_all(project(tree)), "journal") == []


def test_journal_online_tuner_is_not_a_primitive_owner(tree):
    """The online tuner's decision log must go through
    runner/journal.DriverJournal — utils/online_tuner.py is a journal
    CONSUMER, not a second primitive owner, so a hand-rolled append-mode
    open seeded there is a finding like anywhere else (ISSUE 11: no
    further append-fsync implementation)."""
    _seed(tree, "horovod_tpu/utils/online_tuner.py", '''
import json


def journal_decision(path, rec):
    with open(path, "a") as fh:
        fh.write(json.dumps(rec) + "\\n")
''')
    assert any(k.startswith("direct-append:open")
               for k in _keys(run_all(project(tree)), "journal"))


# --- jaxcompat --------------------------------------------------------------

def test_jaxcompat_shard_map_import_fails(tree):
    _seed(tree, "horovod_tpu/rogue_sm.py", "from jax import shard_map\n")
    assert "import-shard_map:0" in \
        _keys(run_all(project(tree)), "jaxcompat")


def test_jaxcompat_try_except_import_dance_still_fails(tree):
    """The try/except dance is exactly what shard_map_compat exists to
    centralize — doing it inline is still a finding."""
    _seed(tree, "horovod_tpu/rogue_sm.py",
          "try:\n    from jax import shard_map\n"
          "except ImportError:\n"
          "    from jax.experimental.shard_map import shard_map\n")
    keys = _keys(run_all(project(tree)), "jaxcompat")
    assert "import-shard_map:0" in keys
    assert "import-experimental-shard_map:0" in keys


def test_jaxcompat_attribute_uses_fail(tree):
    _seed(tree, "horovod_tpu/rogue_sm.py", '''
import jax
from jax import lax


def f(fn, mesh, spec):
    sized = lax.axis_size("data")
    return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec), sized
''')
    keys = _keys(run_all(project(tree)), "jaxcompat")
    assert "attr-jax.shard_map:0" in keys
    assert "attr-lax.axis_size:0" in keys


def test_jaxcompat_bare_psum_axis_sizing_fails(tree):
    _seed(tree, "horovod_tpu/rogue_sm.py",
          "from jax import lax\n\n\ndef n(axis):\n"
          "    return lax.psum(1, axis)\n")
    assert "psum-axis-sizing:0" in \
        _keys(run_all(project(tree)), "jaxcompat")


def test_jaxcompat_mesh_shim_file_is_allowed(tree):
    _seed(tree, "horovod_tpu/parallel/mesh.py", '''
from jax import lax


def traced_axis_size(axis):
    return lax.axis_size(axis)


def shard_map_compat(f, **kw):
    from jax import shard_map as _sm
    return _sm(f, **kw)
''')
    assert _keys(run_all(project(tree)), "jaxcompat") == []


def test_jaxcompat_getattr_probe_is_not_a_finding(tree):
    _seed(tree, "horovod_tpu/probe.py",
          "import jax\n\nHAS_SM = hasattr(jax, 'shard_map')\n")
    assert _keys(run_all(project(tree)), "jaxcompat") == []


# --- testtier ---------------------------------------------------------------

TIER_OK_TEST = '''
import time

import pytest


@pytest.mark.tier2
@pytest.mark.slow
def test_heavy_fleet(launcher):
    launcher(8, timeout=600)
    time.sleep(6)


def test_light():
    time.sleep(0.1)
'''


def test_testtier_marked_and_light_tests_pass(tree):
    _seed(tree, "tests/test_fixture_tiers.py", TIER_OK_TEST)
    assert _keys(run_all(project(tree)), "testtier") == []


def test_testtier_sleep_budget_fails(tree):
    _seed(tree, "tests/test_fixture_tiers.py",
          "import time\n\n\ndef test_sleepy():\n"
          "    time.sleep(3)\n    time.sleep(3)\n")
    assert "needs-tier2-slow:test_sleepy" in \
        _keys(run_all(project(tree)), "testtier")


def test_testtier_timeout_budget_fails(tree):
    _seed(tree, "tests/test_fixture_tiers.py",
          "def test_budgeted(run):\n    run(timeout=420)\n")
    assert "needs-tier2-slow:test_budgeted" in \
        _keys(run_all(project(tree)), "testtier")


def test_testtier_fleet_evidence_fails(tree):
    _seed(tree, "tests/test_fixture_tiers.py",
          "def test_fleet(subprocess, sys):\n"
          "    subprocess.run([sys.executable, '-m', 'x', '-np', '8'])\n")
    assert "needs-tier2-slow:test_fleet" in \
        _keys(run_all(project(tree)), "testtier")


def test_testtier_half_marked_fails_and_pair_rule(tree):
    _seed(tree, "tests/test_fixture_tiers.py", TIER_OK_TEST.replace(
        "@pytest.mark.tier2\n@pytest.mark.slow\n", "@pytest.mark.tier2\n"))
    assert "needs-tier2-slow:test_heavy_fleet" in \
        _keys(run_all(project(tree)), "testtier")
    # slow without tier2 is inconsistent regardless of triggers.
    _seed(tree, "tests/test_fixture_tiers.py",
          "import pytest\n\n\n@pytest.mark.slow\ndef test_dangling():\n"
          "    pass\n")
    assert "slow-without-tier2:test_dangling" in \
        _keys(run_all(project(tree)), "testtier")


def test_testtier_module_pytestmark_honored(tree):
    _seed(tree, "tests/test_fixture_tiers.py",
          "import pytest\n\npytestmark = [pytest.mark.tier2, "
          "pytest.mark.slow]\n\n\ndef test_heavy(run):\n"
          "    run(timeout=999)\n")
    assert _keys(run_all(project(tree)), "testtier") == []


def test_testtier_tier1_ok_tag_suppresses(tree):
    _seed(tree, "tests/test_fixture_tiers.py",
          "def test_ceiling(run):\n"
          "    # analysis: tier1-ok(runs in seconds; big ceiling is "
          "flake insurance)\n"
          "    run(timeout=600)\n")
    assert _keys(run_all(project(tree)), "testtier") == []


def test_new_checker_findings_are_baselinable(tree, tmp_path):
    """The fingerprint/baseline machinery covers the new checkers the
    same way: accept, clean, resurface with --no-baseline."""
    _seed(tree, "horovod_tpu/rogue_sm.py", "from jax import shard_map\n")
    baseline = str(tmp_path / "baseline.json")
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "jaxcompat"]) == 1
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "jaxcompat",
                          "--update-baseline"]) == 0
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "jaxcompat"]) == 0
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "jaxcompat", "--no-baseline"]) == 1


def test_locks_guarded_by_skip_is_per_file(tree):
    """Review fix: the declaration-line skip must be per-file — an
    unguarded use in file B sharing a line NUMBER with file A's
    annotated declaration was silently suppressed."""
    _seed(tree, "horovod_tpu/core/src/state.h", '''#include <mutex>
struct State {
  std::mutex mu_;
  int hits_ = 0;  // GUARDED_BY(mu_)
};
extern State st;
''')
    # The unguarded use sits on line 4 — the same line number as the
    # annotated declaration in state.h.
    _seed(tree, "horovod_tpu/core/src/peek.cc", '''#include "state.h"
int Peek() {
  // line 3
  return st.hits_;
}
''')
    keys = _keys(run_all(project(tree)), "locks")
    assert "unguarded-native:hits_:0" in keys


def test_journal_pathlib_open_append_fails(tree):
    """Review fix: method-style opens take mode FIRST — Path(p).open("a")
    must be flagged; a lone filename positional that merely contains an
    'a' must not."""
    _seed(tree, "horovod_tpu/sidecar.py", '''
import pathlib


def persist(path, line):
    with pathlib.Path(path).open("a") as fh:
        fh.write(line)
''')
    assert any(k.startswith("direct-append:open")
               for k in _keys(run_all(project(tree)), "journal"))
    _seed(tree, "horovod_tpu/sidecar.py",
          "import codecs\n\n\ndef load():\n"
          "    return codecs.open('data.txt')\n")
    assert _keys(run_all(project(tree)), "journal") == []


def test_crashing_checker_dies_with_its_name(tree, monkeypatch):
    from tools import analysis as pkg

    def boom(project):
        raise ValueError("kaput")

    monkeypatch.setitem(pkg.CHECKERS, "locks", boom)
    with pytest.raises(RuntimeError, match="checker 'locks' crashed"):
        run_all(project(tree))


# ====================== spmd checker (ISSUE 14) ==============================
# Interprocedural SPMD-divergence & collective-deadlock lanes: fixture
# root-collective stubs below stand in for ops/eager.py; each seeded
# violation fails under --checker spmd, tags suppress, the machinery
# baselines, the real tree stays clean (test_real_tree_is_clean runs
# all twelve checkers).

SPMD_EAGER_STUB = '''
def allreduce(x, **kw):
    return x


def allreduce_async(x, **kw):
    return 0


def allgather(x, **kw):
    return x


def barrier():
    pass


def synchronize(handle):
    return handle
'''

SPMD_PKG_STUB = '''
from horovod_tpu.ops.eager import allreduce, allgather, barrier


def rank():
    return 0


def size():
    return 1
'''


def _seed_spmd_roots(tree):
    _seed(tree, "horovod_tpu/ops/__init__.py", "")
    _seed(tree, "horovod_tpu/ops/eager.py", SPMD_EAGER_STUB)
    # Overwrites the minimal fixture __init__ with a re-exporting one
    # so `import horovod_tpu as hvd; hvd.allreduce(...)` resolves.
    _seed(tree, "horovod_tpu/__init__.py", SPMD_PKG_STUB)


def test_spmd_known_good_fixture_passes(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "examples/clean.py", '''
import horovod_tpu as hvd


def main():
    out = hvd.allreduce(1)
    if hvd.rank() == 0:
        print(out)  # divergent print is fine: no collective inside
    return out
''')
    assert _keys(run_all(project(tree)), "spmd") == []


def test_spmd_tainted_branch_collective_fails(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "examples/gated.py", '''
import horovod_tpu as hvd


def main():
    if hvd.rank() == 0:
        hvd.allreduce(1)
''')
    keys = _keys(run_all(project(tree)), "spmd")
    assert any(k.startswith("divergent:main:") and ":branch:" in k
               for k in keys), keys


def test_spmd_transitive_helper_divergence_fails(tree):
    """The helper issues the collective; the caller's tainted branch
    is where the world desyncs — the call graph must connect them."""
    _seed_spmd_roots(tree)
    _seed(tree, "examples/helper.py", '''
import horovod_tpu as hvd


def sync_up(x):
    return hvd.allreduce(x)


def main():
    r = hvd.rank()
    if r == 0:
        return sync_up(1)
''')
    keys = _keys(run_all(project(tree)), "spmd")
    assert any(k.startswith("divergent:main:") for k in keys), keys
    # The helper itself is NOT a finding: it issues unconditionally.
    assert not any(k.startswith("divergent:sync_up:") for k in keys)


def test_spmd_early_exit_domination_fails(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "examples/early.py", '''
import horovod_tpu as hvd


def main():
    if hvd.rank() != 0:
        return
    hvd.barrier()
''')
    keys = _keys(run_all(project(tree)), "spmd")
    assert any(":early-exit:" in k for k in keys), keys


def test_spmd_tainted_while_and_loop_bound_fail(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "examples/loops.py", '''
import random
import time

import horovod_tpu as hvd


def timed(deadline):
    while time.monotonic() < deadline:
        hvd.allreduce(1)


def randomized():
    for _ in range(random.randint(1, 4)):
        hvd.barrier()
''')
    keys = _keys(run_all(project(tree)), "spmd")
    assert any(k.startswith("divergent:timed:") and ":loop:" in k
               for k in keys), keys
    assert any(k.startswith("divergent:randomized:") and ":loop:" in k
               for k in keys), keys


def test_spmd_while_else_runs_uniformly(tree):
    """A tainted while's ELSE clause runs on normal loop exit —
    every rank reaches it (same rule as for-else) — so a collective
    there is NOT dominated by the loop condition."""
    _seed_spmd_roots(tree)
    _seed(tree, "examples/while_else.py", '''
import time

import horovod_tpu as hvd


def drain(deadline):
    while time.monotonic() < deadline:
        pass
    else:
        hvd.barrier()
''')
    assert _keys(run_all(project(tree)), "spmd") == []


def test_spmd_per_rank_env_gate_fails(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "examples/envgate.py", '''
import os

import horovod_tpu as hvd


def main():
    if os.environ.get("HVD_FAULT_RANK") == "1":
        hvd.barrier()
''')
    keys = _keys(run_all(project(tree)), "spmd")
    assert any(k.startswith("divergent:main:") for k in keys), keys


def test_spmd_rank_uniform_tag_suppresses(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "examples/tagged.py", '''
import horovod_tpu as hvd


def main():
    # analysis: rank-uniform(every rank reads the same journal, so the
    # replayed decision — and this branch — agree across the world)
    if hvd.rank() >= 0:
        hvd.allreduce(1)
''')
    assert _keys(run_all(project(tree)), "spmd") == []


def test_spmd_callback_thread_collective_fails_and_tag(tree):
    _seed_spmd_roots(tree)
    body = '''
import threading

from horovod_tpu.ops import eager


class Svc:
    def _beat(self):
        eager.barrier()

    def start(self):
        t = threading.Thread(target=self._beat, daemon=True)
        t.start()
'''
    _seed(tree, "horovod_tpu/svc.py", body)
    keys = _keys(run_all(project(tree)), "spmd")
    assert "thread-collective:Svc._beat" in keys, keys
    # Async submission from a thread is fine — only BLOCKING waits
    # can deadlock the completing thread against itself.
    _seed(tree, "horovod_tpu/svc.py",
          body.replace("eager.barrier()", "eager.allreduce_async(1)"))
    assert _keys(run_all(project(tree)), "spmd") == []
    # thread-ok tag on the registration suppresses.
    _seed(tree, "horovod_tpu/svc.py", body.replace(
        "        t = threading.Thread(target=self._beat, daemon=True)",
        "        # analysis: thread-ok(joined before init; no world)\n"
        "        t = threading.Thread(target=self._beat, daemon=True)"))
    assert _keys(run_all(project(tree)), "spmd") == []


def test_spmd_put_callback_entry_fails(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "horovod_tpu/kv.py", '''
from horovod_tpu.ops import eager


def on_put(scope, key):
    eager.allgather(key)


def serve(server_cls):
    return server_cls(port=0, put_callback=on_put)
''')
    assert "thread-collective:on_put" in \
        _keys(run_all(project(tree)), "spmd")


def test_spmd_live_unsafe_knob_in_runtime_loop_fails(tree):
    _seed_spmd_roots(tree)
    _seed(tree, "horovod_tpu/common/knobs.py", KNOBS_PY + '''
from typing import Dict, Optional


class TunableKnob(NamedTuple):
    name: str
    lo: float
    hi: float
    step: float
    apply_path: str
    env: Optional[str]
    default: float
    live_safe: bool
    detail: str


TUNABLE: Dict[str, TunableKnob] = {t.name: t for t in [
    TunableKnob("cycle_time_ms", 1.0, 100.0, 0.5, "native",
                "HOROVOD_CYCLE_TIME", 1.0, True, "safe"),
    TunableKnob("grad_bucket_bytes", 0.0, 64.0, 1.0, "env",
                "HVD_GRAD_BUCKET_BYTES", 4.0, False, "trace-time"),
]}
''')
    _seed(tree, "horovod_tpu/utils/__init__.py", "")
    _seed(tree, "horovod_tpu/utils/online_tuner.py",
          'TRAINING_KNOBS = ("cycle_time_ms",)\n')
    assert _keys(run_all(project(tree)), "spmd") == []
    _seed(tree, "horovod_tpu/utils/online_tuner.py",
          'TRAINING_KNOBS = ("cycle_time_ms", "grad_bucket_bytes")\n')
    assert "live-unsafe:grad_bucket_bytes" in \
        _keys(run_all(project(tree)), "spmd")


def test_spmd_findings_are_baselinable(tree, tmp_path):
    _seed_spmd_roots(tree)
    _seed(tree, "examples/gated.py", '''
import horovod_tpu as hvd


def main():
    if hvd.rank() == 0:
        hvd.allreduce(1)
''')
    baseline = str(tmp_path / "baseline.json")
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "spmd"]) == 1
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "spmd", "--update-baseline"]) == 0
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "spmd"]) == 0
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "spmd", "--no-baseline"]) == 1


def test_json_format_output(tree, tmp_path, capsys):
    """--format json: machine-readable findings with fingerprints and
    baselined-ness; exit codes unchanged; text default untouched."""
    _seed_spmd_roots(tree)
    _seed(tree, "examples/gated.py", '''
import horovod_tpu as hvd


def main():
    if hvd.rank() == 0:
        hvd.allreduce(1)
''')
    baseline = str(tmp_path / "baseline.json")
    rc = analysis_main(["--root", tree, "--baseline", baseline,
                        "--checker", "spmd", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["ok"] is False and doc["new"] == 1
    [f] = doc["findings"]
    assert f["checker"] == "spmd"
    assert f["fingerprint"].startswith("spmd::examples/gated.py::")
    assert f["file"] == "examples/gated.py" and f["line"] > 0
    assert f["location"] == "%s:%d" % (f["file"], f["line"])
    assert f["baselined"] is False and f["justification"] is None
    # Baselined finding: ok flips, the justification rides along.
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "spmd", "--update-baseline"]) == 0
    capsys.readouterr()
    rc = analysis_main(["--root", tree, "--baseline", baseline,
                        "--checker", "spmd", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["ok"] is True and doc["new"] == 0
    [f] = doc["findings"]
    assert f["baselined"] is True and f["justification"]


def test_analysis_runtime_stays_in_seconds():
    """Deflake guard (ISSUE 14 ridealong; re-pinned for the twelve-
    checker run in ISSUE 19): the whole twelve-checker run over the
    REAL tree must stay interactive — the spmd call graph and the
    deadlock/blocking model ride the same per-run AST memoization as
    the other checkers (one parse per file per Project), so the full
    run is a few seconds of pure-Python AST work. 60 s is ~10x
    headroom for a loaded CI host; breaching it means a second parse
    pass or quadratic propagation crept in."""
    import time as _time

    t0 = _time.monotonic()
    rc = analysis_main(["--root", _REPO])
    elapsed = _time.monotonic() - t0
    assert rc == 0
    assert elapsed < 60.0, "analysis run took %.1fs" % elapsed


def test_spmd_shares_the_ast_memoization():
    """No second parse pass: after one run_all, every file the spmd
    surface shares with the python scan surface sits in the SAME
    Project parse cache (parsed() memoizes per rel path)."""
    from tools.analysis.common import Project as _P

    p = _P(_REPO)
    run_all(p)
    shared = set(p.python_files()) & set(p.spmd_files())
    assert shared, "surfaces unexpectedly disjoint"
    missing = [rel for rel in shared if rel not in p._ast_cache]
    assert not missing, missing[:5]


def test_spmd_collective_in_nested_header_under_taint_fails(tree):
    """Review fix: a collective inside a nested statement's HEADER
    expression (for-iter, while-test, with-item) under a tainted
    branch must be flagged — header expressions execute whenever
    control reaches the statement, so the outer taint dominates."""
    _seed_spmd_roots(tree)
    _seed(tree, "examples/header.py", '''
import horovod_tpu as hvd


def main(ys):
    if hvd.rank() == 0:
        for x in hvd.allgather(ys):
            print(x)
''')
    keys = _keys(run_all(project(tree)), "spmd")
    assert any(k.startswith("divergent:main:") for k in keys), keys


def test_json_format_update_baseline_emits_json(tree, tmp_path, capsys):
    """Review fix: --format json --update-baseline must keep the
    one-JSON-document-on-stdout contract, not fall through to text."""
    _seed_spmd_roots(tree)
    _seed(tree, "examples/gated.py", '''
import horovod_tpu as hvd


def main():
    if hvd.rank() == 0:
        hvd.allreduce(1)
''')
    baseline = str(tmp_path / "baseline.json")
    rc = analysis_main(["--root", tree, "--baseline", baseline,
                        "--checker", "spmd", "--update-baseline",
                        "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["ok"] is True and doc["updated"] == 1
    assert doc["baseline"] == baseline


def test_spmd_local_named_like_collective_is_not_flagged(tree):
    """Review fix: a local/parameter that merely SHARES a collective's
    name (barrier, join, broadcast...) must not resolve to a root —
    only names vouched for by an import or def may."""
    _seed_spmd_roots(tree)
    _seed(tree, "horovod_tpu/localnames.py", '''
from horovod_tpu import rank


def f(make_barrier):
    barrier = make_barrier()
    if rank() == 0:
        barrier()


def g(rows):
    join = rows.join
    if rank() == 0:
        return join(",")
''')
    assert _keys(run_all(project(tree)), "spmd") == []


def test_spmd_imported_class_state_method_still_resolves(tree):
    """Review fix: `from ...state import State; State.commit(...)`
    must reach the state-method root fallback instead of being
    misread as a submodule lookup that resolves to nothing."""
    _seed_spmd_roots(tree)
    _seed(tree, "horovod_tpu/elastic/__init__.py", "")
    _seed(tree, "horovod_tpu/elastic/state.py", '''
class State:
    @staticmethod
    def commit(s):
        pass
''')
    _seed(tree, "examples/clsmeth.py", '''
from horovod_tpu import rank
from horovod_tpu.elastic.state import State


def main(s):
    if rank() == 0:
        State.commit(s)
''')
    keys = _keys(run_all(project(tree)), "spmd")
    assert any(k.startswith("divergent:main:State.commit")
               for k in keys), keys


def test_spmd_bare_name_never_resolves_to_sibling_method(tree):
    """Review fix: a bare call inside a method must not resolve to a
    same-named sibling METHOD (Python bare names cannot see class
    attributes) — only nested defs, enclosing-function defs, and
    module-namespace names count."""
    _seed_spmd_roots(tree)
    _seed(tree, "horovod_tpu/driver.py", '''
from horovod_tpu import rank
from horovod_tpu.ops import eager


def helper_shutdown():
    pass


class Driver:
    def shutdown(self):
        eager.barrier()

    def run(self, shutdown=helper_shutdown):
        if rank() == 0:
            shutdown()
''')
    assert _keys(run_all(project(tree)), "spmd") == []


# ================ deadlock/blocking checkers (ISSUE 19) ======================
# Lock-order inversions and blocking-under-lock, Python and C++ lanes
# (tools/analysis/check_deadlock.py): each seeded violation fails,
# consistent nesting passes, tags suppress, the machinery baselines,
# and the SARIF emitter keeps the one-document contract.


def test_deadlock_two_lock_cycle_caught(tree):
    _seed(tree, "horovod_tpu/inverted.py", '''
import threading


class Pool:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def grow(self):
        with self._a:
            with self._b:
                pass

    def shrink(self):
        with self._b:
            with self._a:
                pass
''')
    findings = [f for f in run_all(project(tree))
                if f.checker == "deadlock"]
    assert len(findings) == 1, findings
    [f] = findings
    assert f.key.startswith("inversion:"), f.key
    # Both paths are printed: each direction's witness names its
    # function.
    assert "Pool.grow" in f.message and "Pool.shrink" in f.message


def test_deadlock_transitive_cycle_caught(tree):
    """The inversion hides behind a method call: grow nests a->b
    directly, shrink holds b and CALLS a helper that takes a."""
    _seed(tree, "horovod_tpu/transitive.py", '''
import threading


class Pool:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def _take_a(self):
        with self._a:
            pass

    def grow(self):
        with self._a:
            with self._b:
                pass

    def shrink(self):
        with self._b:
            self._take_a()
''')
    keys = _keys(run_all(project(tree)), "deadlock")
    assert any(k.startswith("inversion:") for k in keys), keys


def test_deadlock_consistent_nesting_passes(tree):
    _seed(tree, "horovod_tpu/nested_ok.py", '''
import threading


class Pool:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def grow(self):
        with self._a:
            with self._b:
                pass

    def shrink(self):
        with self._a:
            with self._b:
                pass
''')
    assert _keys(run_all(project(tree)), "deadlock") == []


def test_deadlock_declared_order_violation(tree):
    """lock-order(a before b) converts a lone b->a edge into a
    finding even without a full cycle."""
    _seed(tree, "horovod_tpu/ordered.py", '''
import threading


class Pool:
    def __init__(self):
        # analysis: lock-order(_a before _b)
        self._a = threading.Lock()
        self._b = threading.Lock()

    def backwards(self):
        with self._b:
            with self._a:
                pass
''')
    keys = _keys(run_all(project(tree)), "deadlock")
    assert any(k.startswith("order-violation:_a-before-_b") for k in keys), keys


def test_blocking_fsync_under_lock_caught(tree):
    _seed(tree, "horovod_tpu/fsyncy.py", '''
import os
import threading


class Table:
    def __init__(self, fh):
        self._lock = threading.Lock()
        self._fh = fh

    def write(self, rec):
        with self._lock:
            self._fh.write(rec)
            os.fsync(self._fh.fileno())
''')
    findings = [f for f in run_all(project(tree))
                if f.checker == "blocking"]
    assert len(findings) == 1, findings
    assert "os.fsync()" in findings[0].message
    assert "Table._lock" in findings[0].message


def test_blocking_transitive_reach_caught(tree):
    """The blocking op hides one call away: the locked method calls a
    helper whose body sleeps."""
    _seed(tree, "horovod_tpu/sleepy.py", '''
import threading
import time

_lock = threading.Lock()


def _backoff():
    time.sleep(1.0)


def update():
    with _lock:
        _backoff()
''')
    findings = [f for f in run_all(project(tree))
                if f.checker == "blocking"]
    assert len(findings) == 1, findings
    assert "time.sleep()" in findings[0].message
    assert "_backoff" in findings[0].message


def test_blocking_journal_append_under_lock_caught(tree):
    _seed(tree, "horovod_tpu/journaling.py", '''
import threading


class Router:
    def __init__(self, journal):
        self._lock = threading.Lock()
        self._journal = journal

    def admit(self, rec):
        with self._lock:
            self._journal.append(rec)
''')
    findings = [f for f in run_all(project(tree))
                if f.checker == "blocking"]
    assert len(findings) == 1, findings
    assert "journal append() (fsync)" in findings[0].message


def test_blocking_ok_tag_suppresses(tree):
    _seed(tree, "horovod_tpu/tagged.py", '''
import os
import threading


class Table:
    def __init__(self, fh):
        self._lock = threading.Lock()
        self._fh = fh

    def write(self, rec):
        with self._lock:
            self._fh.write(rec)
            # analysis: blocking-ok(this lock exists to serialize
            # exactly this durable write)
            os.fsync(self._fh.fileno())
''')
    assert _keys(run_all(project(tree)), "blocking") == []


def test_blocking_str_join_not_flagged(tree):
    """Precision pin: str.join under a lock is not a thread join."""
    _seed(tree, "horovod_tpu/strjoin.py", '''
import threading

_lock = threading.Lock()


def render(parts, sep):
    with _lock:
        return ", ".join(parts) + sep.join(parts)
''')
    assert _keys(run_all(project(tree)), "blocking") == []


def test_blocking_thread_join_under_lock_caught(tree):
    _seed(tree, "horovod_tpu/threadjoin.py", '''
import threading


class Owner:
    def __init__(self, worker):
        self._lock = threading.Lock()
        self._worker = worker

    def stop(self):
        with self._lock:
            self._worker.join(timeout=5)
''')
    findings = [f for f in run_all(project(tree))
                if f.checker == "blocking"]
    assert len(findings) == 1, findings
    assert ".join() (thread join)" in findings[0].message


def test_cpp_lock_order_inversion_caught(tree):
    _seed(tree, "horovod_tpu/core/src/inverted.cc", '''
#include <mutex>

struct State {
  std::mutex ps_mutex;
  std::mutex tl_mutex;
  int table;  // GUARDED_BY(ps_mutex)

  void Grow() {
    std::lock_guard<std::mutex> a(ps_mutex);
    std::lock_guard<std::mutex> b(tl_mutex);
    table = 1;
  }

  void Shrink() {
    std::lock_guard<std::mutex> b(tl_mutex);
    std::lock_guard<std::mutex> a(ps_mutex);
    table = 0;
  }
};
''')
    findings = [f for f in run_all(project(tree))
                if f.checker == "deadlock"]
    assert len(findings) == 1, findings
    [f] = findings
    assert f.key.startswith("inversion:"), f.key
    assert "Grow" in f.message and "Shrink" in f.message


def test_cpp_blocking_under_lock_caught_and_tag_suppresses(tree):
    _seed(tree, "horovod_tpu/core/src/blocky.cc", '''
#include <mutex>

struct Comm {
  std::mutex send_mutex;
  std::mutex init_mutex;
  int fd;

  void Flush(const void* p, long n) {
    std::lock_guard<std::mutex> lk(send_mutex);
    ::send(fd, p, n, 0);
  }

  void Bootstrap(const void* p, long n) {
    std::lock_guard<std::mutex> lk(init_mutex);
    // analysis: blocking-ok(init-time handshake; nothing else ever
    // takes init_mutex)
    ::send(fd, p, n, 0);
  }
};
''')
    findings = [f for f in run_all(project(tree))
                if f.checker == "blocking"]
    assert len(findings) == 1, findings
    assert "::send()" in findings[0].message
    assert "Flush" in findings[0].message


def test_deadlock_findings_are_baselinable(tree, tmp_path, capsys):
    """The new lanes ride the same baseline machinery as the rest:
    --update-baseline accepts a seeded inversion, the next run is
    clean, and the justification slot is present."""
    _seed(tree, "horovod_tpu/inverted.py", '''
import threading


class Pool:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def grow(self):
        with self._a:
            with self._b:
                pass

    def shrink(self):
        with self._b:
            with self._a:
                pass
''')
    baseline = str(tmp_path / "baseline.json")
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "deadlock"]) == 1
    capsys.readouterr()
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "deadlock",
                          "--update-baseline"]) == 0
    capsys.readouterr()
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "deadlock"]) == 0
    assert load_baseline(baseline)


def test_deadlock_shares_the_ast_memoization():
    """The deadlock/blocking model parses through the SAME per-run
    Project cache as every other checker — no second parse pass over
    the lock surface."""
    from tools.analysis.common import Project as _P

    p = _P(_REPO)
    run_all(p)
    missing = [rel for rel in p.lock_files() if rel not in p._ast_cache]
    assert not missing, missing[:5]


# --- SARIF output (ISSUE 19 satellite) ---------------------------------------

def test_sarif_format_schema_and_exit_codes(tree, capsys):
    """Pin the SARIF 2.1.0 shape CI and editors ingest: version,
    schema URI, one rule per checker that ran, one result per finding
    with ruleId/level/message/location/fingerprint — and the exit-code
    contract unchanged (1 with a new finding, 0 clean)."""
    _seed(tree, "horovod_tpu/fsyncy.py", '''
import os
import threading

_lock = threading.Lock()


def write(fh, rec):
    with _lock:
        fh.write(rec)
        os.fsync(fh.fileno())
''')
    rc = analysis_main(["--root", tree, "--checker", "blocking",
                        "--no-baseline", "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    assert doc["$schema"].endswith("sarif-2.1.0.json")
    [run] = doc["runs"]
    assert run["tool"]["driver"]["name"] == "tools.analysis"
    assert [r["id"] for r in run["tool"]["driver"]["rules"]] \
        == ["blocking"]
    [res] = run["results"]
    assert res["ruleId"] == "blocking"
    assert res["level"] == "error"
    assert "os.fsync()" in res["message"]["text"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "horovod_tpu/fsyncy.py"
    assert loc["region"]["startLine"] > 0
    assert res["partialFingerprints"]["fingerprint/v1"].startswith(
        "blocking::horovod_tpu/fsyncy.py::")


def test_sarif_clean_tree_is_empty_run(tree, capsys):
    rc = analysis_main(["--root", tree, "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    [run] = doc["runs"]
    assert run["results"] == []
    assert len(run["tool"]["driver"]["rules"]) == len(CHECKERS)


def test_sarif_baselined_finding_is_note_level(tree, tmp_path, capsys):
    _seed(tree, "horovod_tpu/fsyncy.py", '''
import os
import threading

_lock = threading.Lock()


def write(fh, rec):
    with _lock:
        fh.write(rec)
        os.fsync(fh.fileno())
''')
    baseline = str(tmp_path / "baseline.json")
    assert analysis_main(["--root", tree, "--baseline", baseline,
                          "--checker", "blocking",
                          "--update-baseline"]) == 0
    capsys.readouterr()
    rc = analysis_main(["--root", tree, "--baseline", baseline,
                        "--checker", "blocking", "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    [res] = doc["runs"][0]["results"]
    assert res["level"] == "note"
