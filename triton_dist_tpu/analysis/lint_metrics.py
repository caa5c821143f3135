"""Metric-catalog drift pass: code and docs/observability.md agree.

The metric catalog is the contract dashboards and the report renderer
are built against; an emitted-but-undocumented metric is invisible
operational surface, and a documented-but-never-emitted one is a
dashboard reading zeros forever. This pass walks the package AST for
every ``counter``/``gauge``/``histogram`` emission (plus ``span``
calls, which record into ``<name>_ms``), normalizes f-string holes to
wildcards, and diffs both directions against the catalog table.

Dynamic names that contain no string constant at all (e.g. a name
computed in a variable) cannot be checked statically and are skipped —
keep metric names as literals or f-string templates at the emission
site so this pass can see them.
"""

from __future__ import annotations

import ast
import fnmatch
import re
from pathlib import Path

from triton_dist_tpu.analysis.findings import Finding

__all__ = ["collect_emissions", "catalog_patterns", "run"]

_EMIT_ATTRS = ("counter", "gauge", "histogram")
_PLACEHOLDER = re.compile(r"<[^<>]*>")
_BACKTICK = re.compile(r"`([^`]+)`")


def _templates(node) -> list:
    """Wildcard name templates of a metric-name argument expression.
    f-string holes become ``*``; an ``a if c else b`` of literals
    yields both; anything non-constant yields nothing (unverifiable)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.JoinedStr):
        parts = []
        for piece in node.values:
            if isinstance(piece, ast.Constant):
                parts.append(str(piece.value))
            else:
                parts.append("*")
        tpl = "".join(parts)
        return [tpl] if tpl.strip("*") else []
    if isinstance(node, ast.IfExp):
        return _templates(node.body) + _templates(node.orelse)
    return []


def collect_emissions(files) -> list:
    """(file, line, template) for every statically visible metric
    emission in ``files``."""
    out = []
    for py in files:
        try:
            tree = ast.parse(Path(py).read_text(), filename=str(py))
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.args):
                continue
            attr = node.func.attr
            recv = node.func.value
            recv_name = recv.id if isinstance(recv, ast.Name) else \
                recv.attr if isinstance(recv, ast.Attribute) else None
            if attr in _EMIT_ATTRS:
                suffix = ""
            elif attr == "span" and recv_name not in ("trace",
                                                      "_trace"):
                # obs.span times into <name>_ms; trace.span is
                # timeline-only (no histogram).
                suffix = "_ms"
            else:
                continue
            for tpl in _templates(node.args[0]):
                if "." in tpl:   # every metric name is dotted
                    out.append((str(py), node.lineno, tpl + suffix))
    return out


def catalog_patterns(md_path) -> list:
    """(line, [candidate patterns]) per metric the catalog table names.

    Each backticked token in a row's metric column is one name;
    ``<placeholder>`` segments become wildcards. Suffix/alternate
    tokens (``.plain``, ``_p99_ms``, ``<name>_slow``) expand against
    the row's preceding full name at every split point sharing the
    alternate's leading character — e.g. ``.plain`` after
    ``engine.decode_path.mega`` yields ``engine.decode_path.plain``
    among its candidates; a token matches when ANY candidate does."""
    text = Path(md_path).read_text()
    out = []
    in_catalog = False
    for lineno, line in enumerate(text.splitlines(), 1):
        if line.startswith("## "):
            in_catalog = line.strip() == "## Metric catalog"
            continue
        if not in_catalog or not line.startswith("|"):
            continue
        cells = line.split("|")
        if len(cells) < 3 or set(cells[1].strip()) <= {"-", " "} \
                or cells[1].strip() == "metric":
            continue
        prev = None
        for tok in _BACKTICK.findall(cells[1]):
            pat = _PLACEHOLDER.sub("*", tok.strip())
            if not pat:
                continue
            if pat[0] not in "._*" and "." in pat:
                prev = pat
                out.append((lineno, [pat]))
                continue
            cands = ["*" + pat.lstrip("*")]
            if prev and pat[0] in "._":
                cands += [prev[:i] + pat
                          for i in range(len(prev))
                          if prev[i] == pat[0]]
            out.append((lineno, cands))
    return out


def _matches(a: str, b: str) -> bool:
    """Do two wildcard templates plausibly name the same metric?"""
    return (a == b
            or fnmatch.fnmatchcase(a.replace("*", "X"), b)
            or fnmatch.fnmatchcase(b.replace("*", "X"), a))


def run(root=None, files=None, catalog=None) -> list:
    if root is None:
        import triton_dist_tpu
        root = Path(triton_dist_tpu.__file__).parent.parent
    root = Path(root)
    if files is None:
        files = sorted((root / "triton_dist_tpu").rglob("*.py"))
    if catalog is None:
        catalog = root / "docs" / "observability.md"
    if not Path(catalog).exists():
        return [Finding(
            code="lint.metric_catalog_missing", severity="warning",
            message=f"metric catalog not found at {catalog} — "
                    f"metric-drift check skipped",
            pass_name="metric-catalog")]
    emissions = collect_emissions(files)
    patterns = catalog_patterns(catalog)
    findings = []
    for file, line, tpl in emissions:
        if not any(_matches(tpl, pat)
                   for _, cands in patterns for pat in cands):
            findings.append(Finding(
                code="lint.metric_undocumented",
                message=f"metric {tpl!r} is emitted here but missing "
                        f"from the docs/observability.md catalog",
                file=file, line=line, pass_name="metric-catalog",
                fix_hint="add a catalog row (metric | type | meaning)"))
    for line, cands in patterns:
        if not any(_matches(tpl, pat)
                   for _, _, tpl in emissions for pat in cands):
            findings.append(Finding(
                code="lint.metric_dead",
                message=f"catalog names {cands[0]!r} but no code "
                        f"emits it",
                file=str(catalog), line=line,
                pass_name="metric-catalog",
                fix_hint="drop the stale row, or restore the emission "
                         "it documented"))
    return findings
