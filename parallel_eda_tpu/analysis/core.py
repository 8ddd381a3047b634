"""graft-lint core: project model, rule registry, suppressions, runner.

Stdlib-only (``ast`` + ``re``) so the analyzer imports without jax —
it has to run in the CI lint job before any heavyweight dependency is
installed, and inside flow_doctor on a bare host.

A *rule* sees the whole :class:`Project` (every parsed module plus the
markdown docs) and returns :class:`Finding`s.  Findings carry a stable
``key`` (rule-specific, line-number free) so the committed baseline
survives unrelated edits.  Per-line opt-outs use

    # graftlint: ignore[rule-id]            (or ignore[*])

on the finding's line or on a comment-only line directly above it.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional

SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*ignore\[([^\]]*)\]")

#: repo-relative scan roots (files or directories)
DEFAULT_TARGETS = ("parallel_eda_tpu", "tools", "bench.py", "scale_bench.py",
                   "chip_smoke.py")
#: path fragments excluded from the scan
EXCLUDE_PARTS = ("__pycache__", "tests/", ".git/")
#: markdown docs a project rule may want (metric registry)
DEFAULT_DOCS = ("OBSERVABILITY.md",)


@dataclasses.dataclass
class Finding:
    rule: str
    path: str          # repo-relative, posix separators
    line: int
    message: str
    key: str           # stable identity for baseline matching (no line#)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class ModuleCtx:
    """One parsed python file."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree: Optional[ast.Module] = None
        self.parse_error: Optional[str] = None
        try:
            self.tree = ast.parse(source, filename=path)
        except SyntaxError as e:  # surfaced as a finding by the runner
            self.parse_error = f"{e.msg} (line {e.lineno})"
        self._sup: Dict[int, set] = {}
        for i, text in enumerate(self.lines, start=1):
            m = SUPPRESS_RE.search(text)
            if m:
                ids = {t.strip() for t in m.group(1).split(",") if t.strip()}
                self._sup[i] = ids

    def _line_is_comment_only(self, line: int) -> bool:
        if not (1 <= line <= len(self.lines)):
            return False
        return self.lines[line - 1].lstrip().startswith("#")

    def suppressions_at(self, line: int) -> set:
        """Suppression ids effective for a finding on ``line``: the line
        itself plus any contiguous run of comment-only lines above it."""
        ids = set(self._sup.get(line, ()))
        up = line - 1
        while self._line_is_comment_only(up):
            ids |= self._sup.get(up, set())
            up -= 1
        return ids

    def is_suppressed(self, rule: str, line: int) -> bool:
        ids = self.suppressions_at(line)
        return bool(ids) and (rule in ids or "*" in ids)


class Project:
    """All modules + docs a rule may inspect."""

    def __init__(self, modules: Dict[str, ModuleCtx],
                 docs: Optional[Dict[str, str]] = None,
                 root: Optional[str] = None):
        self.modules = modules
        self.docs = docs or {}
        self.root = root

    @classmethod
    def from_sources(cls, sources: Dict[str, str],
                     docs: Optional[Dict[str, str]] = None) -> "Project":
        """In-memory project for fixture tests: {relpath: source}."""
        return cls({p: ModuleCtx(p, s) for p, s in sources.items()},
                   docs=docs)

    @classmethod
    def from_tree(cls, root: str,
                  targets: Iterable[str] = DEFAULT_TARGETS,
                  docs: Iterable[str] = DEFAULT_DOCS) -> "Project":
        modules: Dict[str, ModuleCtx] = {}
        for tgt in targets:
            full = os.path.join(root, tgt)
            if os.path.isfile(full):
                paths = [full]
            elif os.path.isdir(full):
                paths = []
                for dirpath, dirnames, filenames in os.walk(full):
                    dirnames[:] = [d for d in sorted(dirnames)
                                   if d != "__pycache__"]
                    for fn in sorted(filenames):
                        if fn.endswith(".py"):
                            paths.append(os.path.join(dirpath, fn))
            else:
                continue
            for p in paths:
                rel = os.path.relpath(p, root).replace(os.sep, "/")
                if any(part in rel for part in EXCLUDE_PARTS):
                    continue
                with open(p, "r", encoding="utf-8") as f:
                    modules[rel] = ModuleCtx(rel, f.read())
        doc_map: Dict[str, str] = {}
        for d in docs:
            full = os.path.join(root, d)
            if os.path.isfile(full):
                with open(full, "r", encoding="utf-8") as f:
                    doc_map[d] = f.read()
        return cls(modules, docs=doc_map, root=root)


class Rule:
    """Base class; subclasses set ``id``/``doc`` and implement check()."""

    id: str = ""
    doc: str = ""

    def check(self, project: Project) -> List[Finding]:
        raise NotImplementedError


_REGISTRY: Dict[str, Rule] = {}


def register(cls):
    """Class decorator adding a rule to the global registry."""
    inst = cls()
    if inst.id in _REGISTRY:
        raise ValueError(f"duplicate rule id {inst.id!r}")
    _REGISTRY[inst.id] = inst
    return cls


def all_rules() -> Dict[str, Rule]:
    # import side-effect registration; local to avoid import cycles
    from parallel_eda_tpu.analysis import (  # noqa: F401
        rules_determinism, rules_io, rules_jax, rules_registry)
    return dict(_REGISTRY)


@dataclasses.dataclass
class LintResult:
    findings: List[Finding]            # live: not suppressed, not baselined
    suppressed: List[Finding]          # silenced by inline ignore[..]
    baselined: List[Finding]           # matched a baseline entry
    unused_baseline: List[dict]        # stale entries worth pruning
    baseline_errors: List[str]         # e.g. empty justification
    rules_run: List[str] = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings and not self.baseline_errors


def run_lint(project: Project, rules: Optional[Iterable[str]] = None,
             baseline: Optional[dict] = None) -> LintResult:
    registry = all_rules()
    selected = sorted(registry) if rules is None else list(rules)
    unknown = [r for r in selected if r not in registry]
    if unknown:
        raise KeyError(f"unknown rule id(s): {unknown}")

    raw: List[Finding] = []
    for path, mod in sorted(project.modules.items()):
        if mod.parse_error:
            raw.append(Finding("parse-error", path, 1, mod.parse_error,
                               key=f"parse:{path}"))
    for rid in selected:
        raw.extend(registry[rid].check(project))
    raw.sort(key=lambda f: (f.path, f.line, f.rule, f.key))

    live: List[Finding] = []
    suppressed: List[Finding] = []
    for f in raw:
        mod = project.modules.get(f.path)
        if mod is not None and mod.is_suppressed(f.rule, f.line):
            suppressed.append(f)
        else:
            live.append(f)

    baselined: List[Finding] = []
    unused: List[dict] = []
    berrs: List[str] = []
    if baseline:
        from parallel_eda_tpu.analysis.baseline import apply_baseline
        live, baselined, unused, berrs = apply_baseline(live, baseline)
    return LintResult(live, suppressed, baselined, unused, berrs,
                      rules_run=selected)
