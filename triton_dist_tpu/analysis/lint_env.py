"""Env-knob registry pass: every ``TDT_*`` knob is documented, and
integer knobs parse through ``obs.registry.env_int``.

An undocumented knob is configuration surface nobody can discover;
hand-rolled ``int(os.environ.get(...))`` parsing scatters the
validation (empty-string handling, minimums, error wording) that
``env_int`` centralizes. The pass scans the package (plus the
top-level entry scripts) for ``TDT_``-prefixed string constants and
flags (a) knobs that appear in no ``docs/*.md``, (b) ``int(...)``
applied — directly or through a local variable — to an env read of a
knob.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path

from triton_dist_tpu.analysis.findings import Finding

__all__ = ["collect_knobs", "documented_knobs", "run"]

_KNOB = re.compile(r"^TDT_[A-Z0-9_]+$")
_KNOB_IN_DOCS = re.compile(r"TDT_[A-Z0-9_]+")


def _env_read_knob(node):
    """Knob name when ``node`` reads a TDT_* env var:
    ``os.environ.get("TDT_X", ...)`` / ``os.getenv("TDT_X")`` /
    ``os.environ["TDT_X"]`` / ``env_int("TDT_X", ...)``-style helpers,
    optionally wrapped in ``.strip()``/``.lower()`` chains."""
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in ("strip", "lower"):
            return _env_read_knob(f.value)
        name = f.attr if isinstance(f, ast.Attribute) else \
            getattr(f, "id", None)
        if name in ("get", "getenv", "setdefault") and node.args:
            a = node.args[0]
            if isinstance(a, ast.Constant) and isinstance(a.value, str) \
                    and _KNOB.match(a.value):
                return a.value
    if isinstance(node, ast.Subscript):
        s = node.slice
        if isinstance(s, ast.Constant) and isinstance(s.value, str) \
                and _KNOB.match(s.value):
            return s.value
    return None


def _scope_walk(scope):
    """Descendants of ``scope`` excluding nested function subtrees
    (each function is its own taint scope)."""
    from collections import deque
    queue = deque(ast.iter_child_nodes(scope))
    while queue:   # breadth-first, like ast.walk: assignments at a
        node = queue.popleft()   # shallower level taint deeper reads
        yield node
        if not isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            queue.extend(ast.iter_child_nodes(node))


def collect_knobs(files):
    """(knob, file, line) for every TDT_* string constant, plus
    int-parse findings-to-be as (knob, file, line) in the second
    list."""
    mentions = []
    int_parses = []
    for py in files:
        try:
            tree = ast.parse(Path(py).read_text(), filename=str(py))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) \
                    and isinstance(node.value, str) \
                    and _KNOB.match(node.value):
                mentions.append((node.value, str(py), node.lineno))
        # One taint scope per function (module top level is a scope
        # too, with function bodies excluded): a name assigned from an
        # env read taints later int(name) calls in the SAME scope only.
        scopes = [n for n in ast.walk(tree)
                  if isinstance(n, (ast.FunctionDef,
                                    ast.AsyncFunctionDef))]
        scopes.append(tree)
        seen_parses = set()
        for fn in scopes:
            tainted = {}   # local name -> knob it was read from
            for node in _scope_walk(fn):
                if isinstance(node, ast.Assign):
                    knob = next(
                        (k for sub in ast.walk(node.value)
                         if (k := _env_read_knob(sub))), None)
                    if knob:
                        for tgt in node.targets:
                            if isinstance(tgt, ast.Name):
                                tainted[tgt.id] = knob
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Name) \
                        and node.func.id == "int" and node.args:
                    arg = node.args[0]
                    knob = next(
                        (k for sub in ast.walk(arg)
                         if (k := _env_read_knob(sub))), None)
                    if knob is None:
                        for sub in ast.walk(arg):
                            if isinstance(sub, ast.Name) \
                                    and sub.id in tainted:
                                knob = tainted[sub.id]
                                break
                    if knob and (knob, node.lineno) not in seen_parses:
                        seen_parses.add((knob, node.lineno))
                        int_parses.append((knob, str(py), node.lineno))
    return mentions, int_parses


def documented_knobs(docs_dir) -> set:
    knobs = set()
    for md in Path(docs_dir).glob("*.md"):
        knobs |= set(_KNOB_IN_DOCS.findall(md.read_text()))
    return knobs


def run(root=None, files=None, docs_dir=None) -> list:
    if root is None:
        import triton_dist_tpu
        root = Path(triton_dist_tpu.__file__).parent.parent
    root = Path(root)
    if files is None:
        files = sorted((root / "triton_dist_tpu").rglob("*.py"))
        if (root / "tpu_smoke.py").exists():
            files.append(root / "tpu_smoke.py")
    if docs_dir is None:
        docs_dir = root / "docs"
    if not Path(docs_dir).exists():
        return [Finding(
            code="lint.env_docs_missing", severity="warning",
            message=f"docs dir not found at {docs_dir} — env-knob "
                    f"documentation check skipped",
            pass_name="env-knobs")]
    documented = documented_knobs(docs_dir)
    mentions, int_parses = collect_knobs(files)
    findings = []
    reported = set()
    for knob, file, line in mentions:
        if knob in documented or knob in reported:
            continue
        reported.add(knob)
        findings.append(Finding(
            code="lint.env_undocumented",
            message=f"env knob {knob} is read here but documented in "
                    f"no docs/*.md",
            file=file, line=line, pass_name="env-knobs",
            fix_hint="add it to the knob table of the owning doc "
                     "(docs/observability.md 'Knobs', "
                     "docs/resilience.md, ...)"))
    for knob, file, line in int_parses:
        findings.append(Finding(
            code="lint.env_int_parse",
            message=f"hand-rolled int() parse of {knob} — integer "
                    f"knobs go through obs.registry.env_int "
                    f"(validated, shared error wording)",
            file=file, line=line, pass_name="env-knobs",
            fix_hint="from triton_dist_tpu.obs import env_int; "
                     f"env_int({knob!r}, default, minimum=...)"))
    return findings
