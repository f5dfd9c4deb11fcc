"""rapidslint rule framework.

Deliberately runtime-free: the engine parses source with :mod:`ast` and
never imports the modules it checks (importing would initialize jax — the
lint gate must run in ~seconds and must be able to lint a module that
would crash at import).  Rules come in two shapes:

* :class:`Rule` — per-file: ``check(SourceFile) -> findings``.
* :class:`ProjectRule` — whole-tree: ``check_project(files) -> findings``
  (cross-file consistency like the config-registry and metrics-key sync).

Suppression model (mirrors the reference's opt-in conf kill-switches —
every override is explicit and auditable):

* ``# rapidslint: disable=R2`` on the offending line (or
  ``disable=R2,R3``) suppresses that line only.
* ``# rapidslint: disable-file=R3`` anywhere in a file suppresses the
  rule for the whole file.
* The checked-in baseline (``tools/rapidslint_baseline.json``) accepts
  specific findings with a one-line justification each.  Baseline
  entries are fingerprinted by (rule, path, normalized line text) so
  they survive line-number drift but die with the code they excused.
"""

from __future__ import annotations

import ast
import json
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple


class Severity:
    ERROR = "error"
    WARNING = "warning"


class Finding:
    """One rule violation at a source location."""

    def __init__(self, rule_id: str, path: str, line: int, message: str,
                 severity: str = Severity.ERROR):
        self.rule_id = rule_id
        self.path = path  # repo-relative, '/'-separated
        self.line = line  # 1-based; 0 = whole-file/project finding
        self.message = message
        self.severity = severity
        self.line_text = ""  # filled by the engine from the source

    def fingerprint(self) -> Tuple[str, str, str]:
        """Line-drift-tolerant identity: the line's normalized text stands
        in for its number, so a finding keeps matching its baseline entry
        when unrelated edits move it — and stops matching the moment the
        excused code itself changes."""
        return (self.rule_id, self.path, _norm(self.line_text))

    def __repr__(self):
        return (f"{self.path}:{self.line}: {self.severity} "
                f"[{self.rule_id}] {self.message}")


def _norm(text: str) -> str:
    return re.sub(r"\s+", " ", text.strip())


_DISABLE_RE = re.compile(r"#\s*rapidslint:\s*disable=([A-Za-z0-9_,\-]+)")
_DISABLE_FILE_RE = re.compile(
    r"#\s*rapidslint:\s*disable-file=([A-Za-z0-9_,\-]+)")


class SourceFile:
    """A parsed source file plus its suppression comments."""

    def __init__(self, abs_path: str, rel_path: str, text: str):
        self.abs_path = abs_path
        self.path = rel_path.replace(os.sep, "/")
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=rel_path)
        self.line_disables: Dict[int, set] = {}
        self.file_disables: set = set()
        for i, ln in enumerate(self.lines, start=1):
            if "rapidslint" not in ln:
                continue
            m = _DISABLE_RE.search(ln)
            if m:
                self.line_disables.setdefault(i, set()).update(
                    m.group(1).split(","))
            m = _DISABLE_FILE_RE.search(ln)
            if m:
                self.file_disables.update(m.group(1).split(","))

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1]
        return ""

    def suppressed(self, rule_id: str, line: int) -> bool:
        if rule_id in self.file_disables:
            return True
        return rule_id in self.line_disables.get(line, set())


class Rule:
    """Per-file rule: subclass and implement :meth:`check`."""

    id = "R0"
    name = "unnamed"
    severity = Severity.ERROR
    description = ""

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, sf: SourceFile, node_or_line, message: str) -> Finding:
        line = getattr(node_or_line, "lineno", node_or_line)
        return Finding(self.id, sf.path, int(line), message, self.severity)


class ProjectRule(Rule):
    """Whole-tree rule: sees every file (and the repo root for docs)."""

    def check(self, sf: SourceFile) -> Iterator[Finding]:
        return iter(())

    def check_project(self, files: Sequence[SourceFile],
                      repo_root: str) -> Iterator[Finding]:
        raise NotImplementedError


class Baseline:
    """The checked-in accepted-findings file.

    JSON list of ``{"rule", "path", "line", "reason"}`` where ``line`` is
    the normalized source line text (see :meth:`Finding.fingerprint`).
    Each entry excuses exactly one matching finding; a second identical
    offense on another line needs its own entry.
    """

    def __init__(self, entries: Optional[List[dict]] = None):
        self.entries = list(entries or [])

    @classmethod
    def load(cls, path: str) -> "Baseline":
        if not os.path.exists(path):
            return cls()
        with open(path) as f:
            data = json.load(f)
        return cls(data.get("findings", []))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({
                "_comment": "rapidslint accepted findings; each entry "
                            "needs a one-line reason.  Regenerate with "
                            "tools/rapidslint.py --write-baseline (reasons "
                            "are preserved for surviving entries).",
                "findings": self.entries,
            }, f, indent=2)
            f.write("\n")

    def partition(self, findings: List[Finding]
                  ) -> Tuple[List[Finding], List[dict], List[dict]]:
        """-> (new findings, used entries, stale entries)."""
        pool: Dict[Tuple[str, str, str], List[dict]] = {}
        for e in self.entries:
            key = (e.get("rule", ""), e.get("path", ""),
                   _norm(e.get("line", "")))
            pool.setdefault(key, []).append(e)
        new: List[Finding] = []
        used: List[dict] = []
        for f in findings:
            hits = pool.get(f.fingerprint())
            if hits:
                used.append(hits.pop(0))
            else:
                new.append(f)
        stale = [e for bucket in pool.values() for e in bucket]
        return new, used, stale


#: Directories under the repo root whose .py files are linted.  tests/ is
#: deliberately excluded: R3's no-unbounded-wait invariant (and friends)
#: bind non-test code; tests may block/wait freely under the harness's
#: SIGALRM bound.
DEFAULT_LINT_DIRS = ("spark_rapids_tpu", "tools", "ci")
DEFAULT_LINT_FILES = ("__graft_entry__.py",)


def discover_files(repo_root: str,
                   extra_paths: Iterable[str] = ()) -> List[SourceFile]:
    paths: List[str] = []
    for d in DEFAULT_LINT_DIRS:
        base = os.path.join(repo_root, d)
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    paths.append(os.path.join(dirpath, fn))
    for fn in DEFAULT_LINT_FILES:
        p = os.path.join(repo_root, fn)
        if os.path.exists(p):
            paths.append(p)
    paths.extend(extra_paths)
    out: List[SourceFile] = []
    for p in sorted(set(paths)):
        rel = os.path.relpath(p, repo_root)
        with open(p, encoding="utf-8") as f:
            text = f.read()
        try:
            out.append(SourceFile(p, rel, text))
        except SyntaxError as e:
            sf = SourceFile.__new__(SourceFile)
            sf.abs_path, sf.path, sf.text = p, rel.replace(os.sep, "/"), text
            sf.lines = text.splitlines()
            sf.tree = None
            sf.line_disables, sf.file_disables = {}, set()
            f0 = Finding("syntax", sf.path, e.lineno or 0,
                         f"file does not parse: {e.msg}")
            sf._syntax_finding = f0  # surfaced by LintEngine.run
            out.append(sf)
    return out


class LintEngine:
    def __init__(self, rules: Sequence[Rule]):
        self.rules = list(rules)

    def run(self, files: Sequence[SourceFile],
            repo_root: str) -> List[Finding]:
        findings: List[Finding] = []
        for sf in files:
            syn = getattr(sf, "_syntax_finding", None)
            if syn is not None:
                findings.append(syn)
                continue
            for rule in self.rules:
                if isinstance(rule, ProjectRule):
                    continue
                for f in rule.check(sf):
                    if not sf.suppressed(f.rule_id, f.line):
                        f.line_text = sf.line_text(f.line)
                        findings.append(f)
        by_path = {sf.path: sf for sf in files}
        parsed = [sf for sf in files if sf.tree is not None]
        for rule in self.rules:
            if not isinstance(rule, ProjectRule):
                continue
            for f in rule.check_project(parsed, repo_root):
                sf = by_path.get(f.path)
                if sf is not None:
                    if sf.suppressed(f.rule_id, f.line):
                        continue
                    f.line_text = sf.line_text(f.line)
                findings.append(f)
        findings.sort(key=lambda f: (f.path, f.line, f.rule_id))
        return findings


# -- small AST helpers shared by the rules ------------------------------------


def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def walk_no_nested_functions(node: ast.AST) -> Iterator[ast.AST]:
    """Walk ``node``'s subtree but do not descend into nested function or
    lambda bodies (their control flow is separate)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None
