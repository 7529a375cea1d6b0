"""dolo-lint core: file walking, finding objects, suppressions, baseline, runner.

The framework is deliberately tiny: a checker sees every repo ``.py`` file once as a
parsed AST (`visit_file`) and may emit more findings from whole-repo state at the end
(`finalize`). Everything execution-free — scanned code is parsed, never imported (the
telemetry/config checkers import *declaration tables* from the package under
``tools.lint``'s own interpreter, which is the same contract the original
``scripts/check_telemetry_schema.py`` had).

Suppressions: append ``# dolint: disable=<rule>[,<rule>...]`` (or a bare
``# dolint: disable`` for all rules) to the finding's line. Findings that predate a rule
live in ``tools/lint/baseline.json`` instead (``--update-baseline`` rewrites it) so new
rules can land strict without a flag day: the suite fails only on NEW findings.
"""

from __future__ import annotations

import ast
import json
import os
import re
from collections import Counter
from dataclasses import dataclass, field

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "baseline.json")

# roots walked for .py files, relative to the repo root; tools/lint itself is excluded
# (its sources quote the violating patterns) and tests/ are excluded (fixtures plant them)
DEFAULT_ROOTS = (
    "dolomite_engine_tpu",
    "tools",
    "scripts",
    "chip_smoke.py",
    "__graft_entry__.py",
)
EXCLUDED_PREFIXES = ("tools/lint",)

_SUPPRESS_RE = re.compile(r"#\s*dolint:\s*disable(?:=(?P<rules>[\w\-, ]+))?")


@dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str  # repo-relative
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def baseline_key(self) -> str:
        # line numbers excluded on purpose: unrelated edits above a baselined finding
        # must not resurface it
        return f"{self.rule}::{self.path}::{self.message}"


@dataclass
class SourceFile:
    """A parsed repo file handed to checkers."""

    path: str  # absolute
    rel: str  # repo-relative (posix separators)
    source: str
    tree: ast.AST
    lines: list[str] = field(default_factory=list)

    @classmethod
    def load(cls, path: str, repo_root: str = REPO_ROOT) -> "SourceFile | None":
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError:
            return None
        rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
        return cls(path=path, rel=rel, source=source, tree=tree, lines=source.splitlines())

    def suppressed_rules(self, line: int) -> set[str] | None:
        """Rules suppressed on `line` (1-based); None means ALL rules are suppressed."""
        if not 1 <= line <= len(self.lines):
            return set()
        m = _SUPPRESS_RE.search(self.lines[line - 1])
        if m is None:
            return set()
        rules = m.group("rules")
        if rules is None:
            return None
        return {r.strip() for r in rules.split(",") if r.strip()}


class Checker:
    """Base class: override `visit_file` for per-file rules, `finalize` for repo-level
    ones. `rules` lists every rule id the checker can emit (drives --rule filtering and
    docs)."""

    name: str = "base"
    rules: tuple[str, ...] = ()

    def start(self, repo_root: str) -> None:  # pragma: no cover - trivial default
        pass

    def visit_file(self, f: SourceFile) -> list[Finding]:
        return []

    def finalize(self) -> list[Finding]:
        return []


def iter_python_files(repo_root: str = REPO_ROOT, roots: tuple[str, ...] = DEFAULT_ROOTS):
    for root in roots:
        top = os.path.join(repo_root, root)
        if os.path.isfile(top):
            yield top
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
            rel_dir = os.path.relpath(dirpath, repo_root).replace(os.sep, "/")
            if any(rel_dir.startswith(p) for p in EXCLUDED_PREFIXES):
                dirnames[:] = []
                continue
            for filename in sorted(filenames):
                if filename.endswith(".py"):
                    yield os.path.join(dirpath, filename)


def load_baseline(path: str = BASELINE_PATH) -> Counter:
    if not os.path.isfile(path):
        return Counter()
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return Counter({str(k): int(v) for k, v in data.get("findings", {}).items()})


def save_baseline(findings: list[Finding], path: str = BASELINE_PATH) -> None:
    counts = Counter(f.baseline_key() for f in findings)
    payload = {
        "_comment": (
            "dolo-lint baseline: pre-existing findings tolerated by `python -m tools.lint`. "
            "Regenerate with --update-baseline; drive this toward empty, never grow it."
        ),
        "findings": {k: counts[k] for k in sorted(counts)},
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2, sort_keys=False)
        f.write("\n")


@dataclass
class LintResult:
    findings: list[Finding]  # post-suppression, pre-baseline
    new_findings: list[Finding]  # not covered by the baseline
    stale_baseline: list[str]  # baseline keys with no matching finding anymore
    files_scanned: int


def run_checkers(
    checkers: list[Checker],
    repo_root: str = REPO_ROOT,
    roots: tuple[str, ...] = DEFAULT_ROOTS,
    rules: set[str] | None = None,
    baseline: Counter | None = None,
    files: list[str] | None = None,
) -> LintResult:
    """Run `checkers` over the repo (or an explicit `files` list, for tests).

    `rules` filters which rule ids may be reported; `baseline` (None = load committed
    file) absorbs known findings.
    """
    for checker in checkers:
        checker.start(repo_root)

    findings: list[Finding] = []
    paths = files if files is not None else list(iter_python_files(repo_root, roots))
    scanned = 0
    sources: list[SourceFile] = []
    for path in paths:
        f = SourceFile.load(path, repo_root)
        if f is None:
            findings.append(
                Finding("parse-error", os.path.relpath(path, repo_root), 1, "unparseable file")
            )
            continue
        scanned += 1
        sources.append(f)
        for checker in checkers:
            findings.extend(checker.visit_file(f))
    for checker in checkers:
        findings.extend(checker.finalize())

    by_rel = {f.rel: f for f in sources}

    def _kept(finding: Finding) -> bool:
        if rules is not None and finding.rule not in rules:
            return False
        src = by_rel.get(finding.path)
        if src is None:
            return True
        suppressed = src.suppressed_rules(finding.line)
        if suppressed is None:  # bare `# dolint: disable`
            return False
        return finding.rule not in suppressed

    findings = sorted(
        (f for f in findings if _kept(f)), key=lambda f: (f.path, f.line, f.rule, f.message)
    )

    baseline = load_baseline() if baseline is None else baseline
    remaining = Counter(baseline)
    new_findings = []
    for finding in findings:
        key = finding.baseline_key()
        if remaining.get(key, 0) > 0:
            remaining[key] -= 1
        else:
            new_findings.append(finding)
    stale = sorted(k for k, v in remaining.items() if v > 0)
    return LintResult(
        findings=findings, new_findings=new_findings, stale_baseline=stale, files_scanned=scanned
    )
