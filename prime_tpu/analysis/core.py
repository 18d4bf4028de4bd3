"""prime-lint core: project scanning, findings, waivers.

The serve stack's correctness invariants — lock discipline, jit-boundary
purity, the obs catalog contract, the env-knob registry — were each hardened
by hand across PRs 2-6 (see docs/analysis.md for the rule-by-rule history).
This package turns those review checklists into machine-enforced checks:
dependency-free AST analysis (stdlib ``ast`` only — the suite must run in a
bare CI container before any wheel installs), one module per checker, a
checked-in waiver file (``analysis/baseline.toml``) whose every entry carries
a justification, and a CLI (``python -m prime_tpu.analysis --check``) CI runs
as its own job.

A checker is a function ``check(project: Project) -> list[Finding]``. The
:class:`Project` hands it parsed ASTs for every production module plus the
doc files the contract checkers cross-reference; it can be built from a repo
root or (in tests) from an in-memory ``{path: source}`` mapping.

Suppression, most-local first:
- ``# prime-lint: ignore[rule-name] <why>`` on the flagged line — for sites
  whose justification belongs next to the code;
- a ``[[waiver]]`` entry in ``baseline.toml`` keyed ``(rule, path, symbol)``
  — for accepted pre-existing violations; ``reason`` is mandatory, and a
  waiver matching nothing is itself reported (rule ``stale-waiver``) so the
  baseline can only shrink honestly.
"""

from __future__ import annotations

import ast
import fnmatch
import re
import tomllib
from dataclasses import dataclass, field
from pathlib import Path

# skipped entirely: test fixtures simulate product behavior (fake planes
# start threads and read env on purpose) and this package's own checker
# sources quote the very patterns they hunt for
EXCLUDE_DIRS = ("analysis", "testing")

_PRAGMA_RE = re.compile(r"#\s*prime-lint:\s*ignore\[([a-z0-9_,\- ]+)\]")


@dataclass(frozen=True)
class Finding:
    """One rule violation at one site.

    ``symbol`` is the stable waiver key (e.g. ``ClassName.attr``,
    ``fn:offender``, a metric/span/knob name) — line numbers drift with
    every edit, so waivers match on ``(rule, path, symbol)`` instead.
    """

    rule: str
    path: str  # repo-relative, forward slashes
    line: int
    symbol: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class SourceFile:
    path: str  # repo-relative
    source: str
    tree: ast.Module
    lines: list[str] = field(default_factory=list)

    def pragma_rules(self, line: int) -> set[str]:
        """Rules suppressed by a ``# prime-lint: ignore[...]`` pragma on the
        given 1-based line (or the line above, for long statements)."""
        out: set[str] = set()
        for candidate in (line, line - 1):
            if 1 <= candidate <= len(self.lines):
                m = _PRAGMA_RE.search(self.lines[candidate - 1])
                if m:
                    out.update(p.strip() for p in m.group(1).split(","))
        return out


class Project:
    """Everything the checkers read: parsed production modules + doc files."""

    def __init__(
        self,
        files: dict[str, str],
        docs: dict[str, str] | None = None,
        root: Path | None = None,
    ) -> None:
        self.root = root
        self.docs = docs or {}
        self.files: list[SourceFile] = []
        self.parse_errors: list[Finding] = []
        for path, source in sorted(files.items()):
            try:
                tree = ast.parse(source, filename=path)
            except SyntaxError as e:
                self.parse_errors.append(
                    Finding("parse-error", path, e.lineno or 1, path, str(e))
                )
                continue
            self.files.append(SourceFile(path, source, tree, source.splitlines()))

    @classmethod
    def from_root(cls, root: str | Path) -> "Project":
        root = Path(root)
        files: dict[str, str] = {}
        pkg = root / "prime_tpu"
        for path in sorted(pkg.rglob("*.py")):
            rel = path.relative_to(root).as_posix()
            parts = rel.split("/")
            if any(part in EXCLUDE_DIRS for part in parts[1:-1]):
                continue
            files[rel] = path.read_text(encoding="utf-8")
        docs: dict[str, str] = {}
        for doc in ("docs/observability.md", "docs/architecture.md"):
            p = root / doc
            if p.exists():
                docs[doc] = p.read_text(encoding="utf-8")
        return cls(files, docs, root=root)

    def doc(self, path: str) -> str | None:
        return self.docs.get(path)

    def pragma_rules(self, path: str, line: int) -> set[str]:
        """Rules an inline pragma suppresses at (path, line). Applied
        centrally by ``run_checks`` so every checker honors pragmas the
        same way. Unknown paths (doc files) have no pragmas."""
        if not hasattr(self, "_by_path"):
            self._by_path = {src.path: src for src in self.files}
        src = self._by_path.get(path)
        return src.pragma_rules(line) if src is not None else set()


@dataclass(frozen=True)
class Waiver:
    rule: str
    path: str  # fnmatch pattern (exact paths match themselves)
    symbol: str  # fnmatch pattern
    reason: str

    def matches(self, finding: Finding) -> bool:
        return (
            self.rule == finding.rule
            and fnmatch.fnmatchcase(finding.path, self.path)
            and fnmatch.fnmatchcase(finding.symbol, self.symbol)
        )


def load_baseline(path: str | Path) -> list[Waiver]:
    path = Path(path)
    data = tomllib.loads(path.read_text(encoding="utf-8"))
    waivers: list[Waiver] = []
    for i, entry in enumerate(data.get("waiver", [])):
        missing = [k for k in ("rule", "path", "symbol", "reason") if not entry.get(k)]
        if missing:
            raise ValueError(
                f"{path}: waiver #{i + 1} is missing required field(s) "
                f"{missing} — every waiver must name its rule/path/symbol "
                "and justify itself"
            )
        waivers.append(
            Waiver(entry["rule"], entry["path"], entry["symbol"], entry["reason"])
        )
    return waivers


def apply_baseline(
    findings: list[Finding], waivers: list[Waiver]
) -> tuple[list[Finding], list[Finding], list[Waiver]]:
    """Split findings into (active, waived); also return waivers that
    matched nothing — stale entries the caller reports for cleanup."""
    active: list[Finding] = []
    waived: list[Finding] = []
    used: set[int] = set()
    for finding in findings:
        hit = None
        for i, waiver in enumerate(waivers):
            if waiver.matches(finding):
                hit = i
                break
        if hit is None:
            active.append(finding)
        else:
            used.add(hit)
            waived.append(finding)
    stale = [w for i, w in enumerate(waivers) if i not in used]
    return active, waived, stale


# -- shared AST helpers -------------------------------------------------------


def attr_root(node: ast.AST) -> str | None:
    """The ``self``-attribute name a store/load expression roots at:
    ``self.x`` / ``self.x[k]`` / ``self.x.y.z`` all return ``"x"``;
    anything not rooted at ``self`` returns None."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        ):
            return node.attr
        node = node.value
    return None


def self_attr(node: ast.AST) -> str | None:
    """``self.x`` exactly (no deeper chain) -> ``"x"``, else None."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def call_name(node: ast.expr) -> str | None:
    """Dotted name of a call target: ``jax.jit`` -> ``"jax.jit"``,
    ``jit`` -> ``"jit"``, anything unresolvable -> None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def const_str(node: ast.expr | None) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None
