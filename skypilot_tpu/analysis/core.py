"""skylint core: the one-pass module loader, findings model, checker
registry, and runner behind `skytpu lint`.

Design (docs/static-analysis.md):

- `ProjectTree` parses every `*.py` under the package root exactly once
  (plus lazy text access to the sibling `docs/` and `tests/` trees for
  the drift checkers) — checkers share the ASTs, never re-read files.
- `Checker` subclasses register themselves; each `run(tree)` returns
  `Finding`s carrying repo-relative ``path:line`` + checker id +
  message, so output is greppable and clickable.
- Waivers (`analysis/waivers.toml`) suppress reviewed findings; an
  expired or unmatched waiver surfaces as a `waivers` finding so debt
  records cannot rot silently.
- Exit-code contract (pinned by tests/test_skylint.py): 0 clean,
  1 unwaived findings, 2 internal error (`LintError`).

Everything here is stdlib-only (`ast`, no jax import) so the linter
runs in milliseconds on any CPU, including inside CI collection.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple


class LintError(Exception):
    """Analyzer-internal failure (bad selection, unreadable waiver
    file): `skytpu lint` exits 2, distinct from findings (1)."""


@dataclasses.dataclass
class Finding:
    """One diagnostic: repo-relative path, 1-based line, checker id."""
    checker: str
    path: str
    line: int
    message: str
    waived: bool = False
    waiver_reason: Optional[str] = None

    def to_dict(self) -> dict:
        return {
            'checker': self.checker,
            'path': self.path,
            'line': self.line,
            'message': self.message,
            'waived': self.waived,
            'waiver_reason': self.waiver_reason,
        }

    def __str__(self) -> str:
        tag = ' (waived)' if self.waived else ''
        return f'{self.path}:{self.line}: [{self.checker}]{tag} ' \
               f'{self.message}'


class Module:
    """One parsed source file."""

    __slots__ = ('path', 'rel', 'repo_rel', 'dotted', 'source', 'tree',
                 'is_package')

    def __init__(self, path: str, rel: str, repo_rel: str,
                 dotted: str, source: str, tree: ast.AST) -> None:
        self.path = path          # absolute
        self.rel = rel            # relative to the package root
        self.repo_rel = repo_rel  # relative to the repo root (findings)
        self.dotted = dotted      # e.g. skypilot_tpu.models.inference
        self.source = source
        self.tree = tree
        self.is_package = rel.endswith('__init__.py')


class ProjectTree:
    """All modules under one package root, parsed once.

    `repo_root` (the package root's parent) anchors the cross-tree
    reads the drift checkers need: `docs/*.md` and `tests/*.py`.
    """

    def __init__(self, root: str) -> None:
        self.root = os.path.abspath(root)
        if not os.path.isdir(self.root):
            raise LintError(f'lint root is not a directory: {root}')
        self.repo_root = os.path.dirname(self.root)
        self.pkg_name = os.path.basename(self.root)
        self.modules: Dict[str, Module] = {}   # keyed by package-rel
        self._import_maps: Dict[str, 'ImportMap'] = {}
        self.parse_errors: List[Finding] = []
        for dirpath, dirnames, filenames in os.walk(self.root):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != '__pycache__')
            for fname in sorted(filenames):
                if not fname.endswith('.py'):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, self.root).replace(
                    os.sep, '/')
                repo_rel = f'{self.pkg_name}/{rel}'
                try:
                    with open(path, encoding='utf-8') as f:
                        source = f.read()
                    tree = ast.parse(source, filename=path)
                except (OSError, SyntaxError, ValueError) as e:
                    line = getattr(e, 'lineno', None) or 1
                    self.parse_errors.append(Finding(
                        'parse-error', repo_rel, line,
                        f'cannot parse module: {e}'))
                    continue
                parts = rel[:-3].split('/')       # strip .py
                if parts[-1] == '__init__':
                    parts = parts[:-1]
                dotted = '.'.join([self.pkg_name] + parts)
                self.modules[rel] = Module(path, rel, repo_rel, dotted,
                                           source, tree)

    def import_map(self, mod: Module) -> 'ImportMap':
        """Cached per-module ImportMap — checkers share one import
        walk per module, matching the parse-once design."""
        cached = self._import_maps.get(mod.rel)
        if cached is None:
            cached = ImportMap(mod)
            self._import_maps[mod.rel] = cached
        return cached

    def has_dir(self, rel_dir: str) -> bool:
        return os.path.isdir(os.path.join(self.root, rel_dir))

    # -- cross-tree text access (docs/, tests/) --

    def repo_text(self, repo_rel: str) -> Optional[str]:
        """Text of a repo-root-relative file, or None if absent."""
        path = os.path.join(self.repo_root, repo_rel)
        try:
            with open(path, encoding='utf-8') as f:
                return f.read()
        except OSError:
            return None

    def tests_blob(self) -> Optional[str]:
        """Concatenated `tests/*.py`, or None when no tests/ tree."""
        tests_dir = os.path.join(self.repo_root, 'tests')
        if not os.path.isdir(tests_dir):
            return None
        blob = []
        for fname in sorted(os.listdir(tests_dir)):
            if fname.endswith('.py'):
                try:
                    with open(os.path.join(tests_dir, fname),
                              encoding='utf-8') as f:
                        blob.append(f.read())
                except OSError:
                    continue
        return '\n'.join(blob)


class Checker:
    """Base: subclass, set `id`/`description`, implement `run`."""

    id = ''
    description = ''

    def run(self, tree: ProjectTree) -> List[Finding]:
        raise NotImplementedError


_REGISTRY: Dict[str, type] = {}


def register(cls: type) -> type:
    """Class decorator: add a Checker to the registry (import order =
    run order; `all_checker_ids` is the CLI's --select vocabulary)."""
    if not cls.id:
        raise ValueError(f'checker {cls.__name__} has no id')
    if cls.id in _REGISTRY:
        raise ValueError(f'duplicate checker id {cls.id!r}')
    _REGISTRY[cls.id] = cls
    return cls


def all_checker_ids() -> List[str]:
    _ensure_builtin_checkers()
    return list(_REGISTRY)


def _ensure_builtin_checkers() -> None:
    # Deferred so core.py imports standalone (fixture tests, docs).
    from skypilot_tpu.analysis import (  # noqa: F401  pylint: disable=unused-import,cyclic-import
        drift, hotpath, locks, sharding, wallclock)


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]
    selected: List[str]
    root: str
    duration_s: float

    @property
    def unwaived(self) -> List[Finding]:
        return [f for f in self.findings if not f.waived]

    @property
    def waived(self) -> List[Finding]:
        return [f for f in self.findings if f.waived]

    @property
    def ok(self) -> bool:
        return not self.unwaived

    def to_dict(self) -> dict:
        """The stable `skytpu lint --json` row (schema pinned by
        tests/test_skylint.py): one JSON object on one line, `ok` +
        `summary` up front."""
        by_checker: Dict[str, int] = {}
        for f in self.findings:
            if not f.waived:
                by_checker[f.checker] = by_checker.get(f.checker, 0) + 1
        return {
            'schema': 'skylint/1',
            'ok': self.ok,
            'root': self.root,
            'selected': self.selected,
            'summary': {
                'total': len(self.findings),
                'unwaived': len(self.unwaived),
                'waived': len(self.waived),
                'by_checker': dict(sorted(by_checker.items())),
                'duration_s': round(self.duration_s, 3),
            },
            'findings': [f.to_dict() for f in self.findings],
        }


def _default_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_lint(root: Optional[str] = None,
             select: Optional[Sequence[str]] = None,
             waiver_path: Optional[str] = None) -> LintResult:
    """Run checkers over the tree rooted at `root` (default: the
    installed skypilot_tpu package) and apply waivers.

    Raises LintError for operator mistakes (unknown --select id, bad
    root, malformed waiver file) — the CLI maps that to exit 2.
    """
    from skypilot_tpu.analysis import waivers as waivers_lib
    _ensure_builtin_checkers()
    started = time.monotonic()
    tree = ProjectTree(root or _default_root())
    if select:
        unknown = [s for s in select if s not in _REGISTRY]
        if unknown:
            raise LintError(
                f'unknown checker id(s) {unknown}; '
                f'known: {sorted(_REGISTRY)}')
        selected = [s for s in _REGISTRY if s in set(select)]
    else:
        selected = list(_REGISTRY)

    findings: List[Finding] = list(tree.parse_errors)
    for checker_id in selected:
        findings.extend(_REGISTRY[checker_id]().run(tree))

    if waiver_path is None:
        candidate = os.path.join(tree.root, 'analysis', 'waivers.toml')
        waiver_path = candidate if os.path.exists(candidate) else None
    waiver_findings: List[Finding] = []
    if waiver_path is not None:
        waiver_rel = os.path.relpath(
            os.path.abspath(waiver_path), tree.repo_root).replace(
                os.sep, '/')
        entries = waivers_lib.load_waivers(waiver_path)
        for entry in entries:
            if entry.checker not in selected:
                continue   # not evaluated this run: neither applied
                           # nor reported unused
            matched = 0
            if not entry.expired():
                for f in findings:
                    if not f.waived and entry.matches(f):
                        f.waived = True
                        f.waiver_reason = entry.reason
                        matched += 1
            if not matched:
                state = ('expired' if entry.expired() else 'unmatched')
                waiver_findings.append(Finding(
                    'waivers', waiver_rel, entry.line,
                    f'{state} waiver for [{entry.checker}] '
                    f'{entry.path}: remove it or refresh it '
                    f'(reason was: {entry.reason})'))
    findings.extend(waiver_findings)
    findings.sort(key=lambda f: (f.path, f.line, f.checker, f.message))
    return LintResult(findings, selected,
                      os.path.relpath(tree.root, tree.repo_root),
                      time.monotonic() - started)


# -- shared AST helpers (used by every checker) --


class ImportMap:
    """Per-module view of what names mean: `module_aliases` maps local
    names to dotted module paths (`jnp` -> `jax.numpy`), `symbols`
    maps names imported with `from X import y` to `(X, y)`."""

    def __init__(self, module: Module) -> None:
        self.module_aliases: Dict[str, str] = {}
        self.symbols: Dict[str, Tuple[str, str]] = {}
        # The package a relative import resolves against: for
        # pkg/a/b.py (dotted pkg.a.b) level 1 means pkg.a — drop the
        # module's own name first; for pkg/a/__init__.py the dotted
        # name pkg.a IS the package, so level 1 drops nothing.
        pkg_parts = module.dotted.split('.')
        if not module.is_package:
            pkg_parts = pkg_parts[:-1]
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    name = alias.asname or alias.name.split('.')[0]
                    target = (alias.name if alias.asname
                              else alias.name.split('.')[0])
                    self.module_aliases[name] = target
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    # Relative: resolve against this module's package.
                    base = (pkg_parts[:len(pkg_parts) - node.level + 1]
                            if node.level <= len(pkg_parts) + 1 else [])
                    prefix = '.'.join(base + (
                        [node.module] if node.module else []))
                else:
                    prefix = node.module or ''
                for alias in node.names:
                    if alias.name == '*':
                        continue
                    name = alias.asname or alias.name
                    self.symbols[name] = (prefix, alias.name)

    def resolve_module(self, name: str) -> Optional[str]:
        """Dotted module path a bare name refers to, if any — covers
        both `import x.y as name` and `from x import y` where y is a
        submodule."""
        if name in self.module_aliases:
            return self.module_aliases[name]
        if name in self.symbols:
            prefix, sym = self.symbols[name]
            return f'{prefix}.{sym}' if prefix else sym
        return None


def dotted_of(node: ast.AST) -> Optional[str]:
    """`a.b.c` attribute chain as a string, None for anything else."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return '.'.join(reversed(parts))
    return None


def resolves_to(imports: ImportMap, node: ast.AST,
                dotted_targets: Sequence[str]) -> bool:
    """True when an expression names one of `dotted_targets` (fully
    qualified, e.g. 'jax.numpy.asarray' or 'time.time') through this
    module's imports."""
    chain = dotted_of(node)
    if chain is None:
        return False
    head, _, rest = chain.partition('.')
    candidates = [chain]
    mod = imports.resolve_module(head)
    if mod is not None:
        candidates.append(f'{mod}.{rest}' if rest else mod)
    if head in imports.symbols:
        prefix, sym = imports.symbols[head]
        full = f'{prefix}.{sym}' if prefix else sym
        candidates.append(f'{full}.{rest}' if rest else full)
    return any(c in dotted_targets for c in candidates)
