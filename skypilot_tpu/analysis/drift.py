"""Drift lints, unified on the skylint module walker: the invariants
that tie code to its catalogs (and the catalogs to the code) in BOTH
directions, so neither can rot alone.

- injection-drift: every `fault_injection.point(name)` call site is
  declared in `KNOWN_POINTS`, every declared point has a live call
  site, is exercised by at least one test, and documented in
  docs/resilience.md (the PR-6 lint, now AST-accurate: a point name
  in a comment or docstring no longer counts as a call site).
- metrics-drift: every `skytpu_*` metric registered through
  `counter(...)`/`gauge(...)`/`histogram(...)` has a catalog row in
  docs/observability.md, and every `skytpu_*` name the doc mentions
  is registered somewhere (stale rows are findings too).
- trace-discipline: every `tracing.span(...)` / `start_span(...)` /
  `record_span(...)` / `phase(...)` call site uses a LITERAL name
  declared in `tracing.KNOWN_SPANS`, every declared span name has a live call
  site, and the docs/observability.md span catalog matches the table
  in both directions — span names cannot silently drift out of the
  trace vocabulary `skytpu trace` and the flight recorder render.

Sub-checks that need the sibling `tests/` or `docs/` trees are
skipped when those trees are absent (fixture runs); the real tree has
both.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Tuple

from skypilot_tpu.analysis.core import (Checker, Finding, ProjectTree,
                                        dotted_of, register)

_FAULT_MODULE_SUFFIX = 'utils/fault_injection.py'
_KNOWN_POINTS = 'KNOWN_POINTS'
_METRIC_KINDS = ('counter', 'gauge', 'histogram')
_METRIC_PREFIX = 'skytpu_'
_DOC_METRIC_RE = re.compile(r'(skytpu_[A-Za-z0-9_]+)')


def collect_points(tree: ProjectTree) -> List[Tuple[str, str, int]]:
    """(point name, repo_rel, line) for every fault_injection.point()
    call — exported for the tests/test_preemption.py thin wrapper."""
    out = []
    for mod in tree.modules.values():
        imports = tree.import_map(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            is_point = False
            if isinstance(func, ast.Attribute) and \
                    func.attr == 'point':
                chain = dotted_of(func.value)
                if chain is not None:
                    head = chain.split('.')[0]
                    target = imports.resolve_module(head) or head
                    is_point = target.endswith('fault_injection')
            elif isinstance(func, ast.Name) and \
                    func.id in imports.symbols:
                prefix, sym = imports.symbols[func.id]
                is_point = (sym == 'point' and
                            prefix.endswith('fault_injection'))
            if is_point and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                out.append((node.args[0].value, mod.repo_rel,
                            node.lineno))
    return out


def known_points(tree: ProjectTree) -> Optional[Tuple[Optional[list],
                                                      str, int]]:
    """(names, repo_rel, line) of the KNOWN_POINTS declaration; names
    is None when the table exists but is not a pure literal (the
    checker turns that into a finding rather than silently skipping —
    a drift lint that can be refactored off is worse than none). The
    whole return is None only when the tree has no fault_injection
    module (fixture trees)."""
    for mod in tree.modules.values():
        if not mod.rel.endswith(_FAULT_MODULE_SUFFIX.split('/')[-1]):
            continue
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == _KNOWN_POINTS
                    for t in node.targets):
                try:
                    value = ast.literal_eval(node.value)
                except ValueError:
                    return (None, mod.repo_rel, node.lineno)
                return (list(value), mod.repo_rel, node.lineno)
    return None


@register
class InjectionDriftChecker(Checker):

    id = 'injection-drift'
    description = ('fault_injection.point() call sites ↔ KNOWN_POINTS '
                   '↔ tests ↔ docs/resilience.md stay in lockstep')

    def run(self, tree: ProjectTree) -> List[Finding]:
        declared = known_points(tree)
        if declared is None:
            return []
        known, known_path, known_line = declared
        if known is None:
            return [Finding(
                self.id, known_path, known_line,
                f'{_KNOWN_POINTS} is not a pure literal — the '
                f'injection-drift checker cannot evaluate it, so the '
                f'whole lint would silently disable; keep the table a '
                f'literal tuple of strings')]
        sites = collect_points(tree)
        findings: List[Finding] = []
        seen = set()
        for name, path, line in sites:
            seen.add(name)
            if name not in known:
                findings.append(Finding(
                    self.id, path, line,
                    f'undeclared injection point {name!r} — add it to '
                    f'fault_injection.{_KNOWN_POINTS}'))
        for name in known:
            if name not in seen:
                findings.append(Finding(
                    self.id, known_path, known_line,
                    f'{_KNOWN_POINTS} entry {name!r} has no call site '
                    f'— dead chaos seams mislead chaos-test authors'))
        tests_blob = tree.tests_blob()
        if tests_blob is not None:
            for name in known:
                if f"'{name}'" not in tests_blob and \
                        f'"{name}"' not in tests_blob:
                    findings.append(Finding(
                        self.id, known_path, known_line,
                        f'injection point {name!r} is never exercised '
                        f'by any test'))
        doc = tree.repo_text('docs/resilience.md')
        if doc is not None:
            for name in known:
                if f'`{name}`' not in doc:
                    findings.append(Finding(
                        self.id, 'docs/resilience.md', 1,
                        f'injection point {name!r} missing from '
                        f'docs/resilience.md'))
        return findings


def collect_metrics(tree: ProjectTree) -> Dict[str, Tuple[str, int]]:
    """name -> (repo_rel, line) for every skytpu_* registration —
    exported for the tests/test_observability.py thin wrapper."""
    out: Dict[str, Tuple[str, int]] = {}
    for mod in tree.modules.values():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else None)
            if name not in _METRIC_KINDS:
                continue
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str) and \
                    node.args[0].value.startswith(_METRIC_PREFIX):
                out.setdefault(node.args[0].value,
                               (mod.repo_rel, node.lineno))
    return out


_TRACING_MODULE = 'tracing'
_SPAN_FUNCS = ('span', 'start_span', 'record_span', 'phase')
_KNOWN_SPANS = 'KNOWN_SPANS'
_DOC_SPAN_SECTION = '### Span catalog'
_DOC_SPAN_ROW_RE = re.compile(r'^\|\s*`([a-z_]+(?:\.[a-z_]+)+)`')


def collect_span_sites(tree: ProjectTree
                       ) -> List[Tuple[Optional[str], str, int]]:
    """(span name, repo_rel, line) for every tracing.span/start_span/
    record_span/phase call; name is None when the first argument is not
    a string literal (a finding — a dynamic name defeats the closed
    vocabulary). Exported for thin test wrappers."""
    out: List[Tuple[Optional[str], str, int]] = []
    for mod in tree.modules.values():
        # In the tracer itself a bare call of one of its own span
        # functions is a site too (its compile listener records
        # `engine.compile`).
        own = mod.rel.endswith(f'{_TRACING_MODULE}.py')
        imports = tree.import_map(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            is_span = False
            if isinstance(func, ast.Attribute) and \
                    func.attr in _SPAN_FUNCS:
                chain = dotted_of(func.value)
                if chain is not None:
                    head = chain.split('.')[0]
                    target = imports.resolve_module(head) or head
                    is_span = target.endswith(_TRACING_MODULE)
            elif isinstance(func, ast.Name) and own:
                is_span = func.id in _SPAN_FUNCS
            elif isinstance(func, ast.Name) and \
                    func.id in imports.symbols:
                prefix, sym = imports.symbols[func.id]
                is_span = (sym in _SPAN_FUNCS and
                           prefix.endswith(_TRACING_MODULE))
            if not is_span:
                continue
            if node.args and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                out.append((node.args[0].value, mod.repo_rel,
                            node.lineno))
            else:
                out.append((None, mod.repo_rel, node.lineno))
    return out


def known_spans(tree: ProjectTree) -> Optional[Tuple[Optional[list],
                                                     str, int]]:
    """(names, repo_rel, line) of the KNOWN_SPANS declaration; names
    is None when the table is not a pure literal (a finding, same
    rationale as KNOWN_POINTS); the whole return is None only when
    the tree has no tracing module (fixture trees)."""
    for mod in tree.modules.values():
        if not mod.rel.endswith(f'{_TRACING_MODULE}.py'):
            continue
        for node in mod.tree.body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == _KNOWN_SPANS
                    for t in node.targets):
                try:
                    value = ast.literal_eval(node.value)
                except ValueError:
                    return (None, mod.repo_rel, node.lineno)
                return (list(value), mod.repo_rel, node.lineno)
    return None


@register
class TraceDisciplineChecker(Checker):

    id = 'trace-discipline'
    description = ('tracing span call sites ↔ tracing.KNOWN_SPANS ↔ '
                   'the docs/observability.md span catalog, both '
                   'directions')

    def run(self, tree: ProjectTree) -> List[Finding]:
        declared = known_spans(tree)
        if declared is None:
            return []
        known, known_path, known_line = declared
        if known is None:
            return [Finding(
                self.id, known_path, known_line,
                f'{_KNOWN_SPANS} is not a pure literal — the '
                f'trace-discipline checker cannot evaluate it, so the '
                f'whole lint would silently disable; keep the table a '
                f'literal tuple of strings')]
        findings: List[Finding] = []
        seen = set()
        for name, path, line in collect_span_sites(tree):
            if name is None:
                findings.append(Finding(
                    self.id, path, line,
                    'span name is not a string literal — dynamic span '
                    'names defeat the closed vocabulary (pass a '
                    f'{_KNOWN_SPANS} entry)'))
                continue
            seen.add(name)
            if name not in known:
                findings.append(Finding(
                    self.id, path, line,
                    f'unregistered span name {name!r} — add it to '
                    f'tracing.{_KNOWN_SPANS} and the '
                    f'docs/observability.md span catalog'))
        for name in known:
            if name not in seen:
                findings.append(Finding(
                    self.id, known_path, known_line,
                    f'{_KNOWN_SPANS} entry {name!r} has no call site '
                    f'— a dead vocabulary entry misleads trace '
                    f'readers'))
        doc = tree.repo_text('docs/observability.md')
        if doc is not None:
            in_section = False
            doc_names: Dict[str, int] = {}
            for lineno, line in enumerate(doc.splitlines(), 1):
                if line.startswith(_DOC_SPAN_SECTION):
                    in_section = True
                    continue
                if in_section and line.startswith('#'):
                    in_section = False
                if not in_section:
                    continue
                m = _DOC_SPAN_ROW_RE.match(line.strip())
                if m:
                    doc_names.setdefault(m.group(1), lineno)
            for name in known:
                if name not in doc_names:
                    findings.append(Finding(
                        self.id, 'docs/observability.md', 1,
                        f'span {name!r} missing from the '
                        f'docs/observability.md span catalog'))
            for name, lineno in sorted(doc_names.items()):
                if name not in known:
                    findings.append(Finding(
                        self.id, 'docs/observability.md', lineno,
                        f'span catalog names {name!r} but '
                        f'tracing.{_KNOWN_SPANS} does not declare it '
                        f'(stale row?)'))
        return findings


@register
class MetricsDriftChecker(Checker):

    id = 'metrics-drift'
    description = ('registered skytpu_* metrics ↔ the '
                   'docs/observability.md catalog, both directions')

    def run(self, tree: ProjectTree) -> List[Finding]:
        registered = collect_metrics(tree)
        doc = tree.repo_text('docs/observability.md')
        if doc is None:
            if registered:
                return [Finding(
                    self.id, 'docs/observability.md', 1,
                    f'{len(registered)} skytpu_* metrics registered '
                    f'but docs/observability.md is missing')]
            return []
        doc_lines: Dict[str, int] = {}
        for lineno, line in enumerate(doc.splitlines(), 1):
            for m in _DOC_METRIC_RE.finditer(line):
                doc_lines.setdefault(m.group(1), lineno)
        findings: List[Finding] = []
        for name, (path, line) in sorted(registered.items()):
            if name not in doc_lines:
                findings.append(Finding(
                    self.id, path, line,
                    f'metric {name!r} registered here but missing '
                    f'from docs/observability.md'))
        for name, lineno in sorted(doc_lines.items()):
            if name not in registered:
                findings.append(Finding(
                    self.id, 'docs/observability.md', lineno,
                    f'docs/observability.md names {name!r} but no '
                    f'code registers it (stale row?)'))
        return findings
