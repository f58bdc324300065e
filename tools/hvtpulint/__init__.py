"""hvtpulint — zero-dependency static analysis for the hvtpu tree.

Seven passes guard invariants that are otherwise only enforced at
runtime (see docs/static-analysis.md):

  wire-twin        C++ wire format (native/src) vs the Python twin
  rank-divergence  collectives issued under rank-dependent control flow
  thread-safety    guarded-by lock discipline in eager/controller.py
  knob-registry    HVTPU_* env knobs vs the generated docs/knobs.md
  metrics-catalog  registered metrics vs docs/observability.md
  sim-purity       no host time / ambient RNG in horovod_tpu/sim
  kv-discipline    raw coordination-client KV calls outside the
                   FencedKV/ResilientKV wrappers (core/retry.py)

Everything here is stdlib-only (ast + re); the C++ side is scanned
lexically, never compiled.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

SUPPRESSION_FILE = ".hvtpulint.suppress"

# Directories never scanned by the tree-walking passes.
SKIP_DIRS = {
    ".git", "__pycache__", "build", "dist", ".eggs", "node_modules",
    "lint_fixtures",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint finding.

    ``key`` is the stable suppression key: it must be whitespace-free
    and should survive unrelated edits (so suppressions key on
    pass/file/symbol rather than line numbers).
    """

    pass_name: str
    path: str  # repo-relative posix path ("-" for repo-level findings)
    line: int  # 1-based; 0 when the finding has no single line
    key: str
    message: str

    def format_text(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: [{self.pass_name}] {self.message} (key: {self.key})"

    def as_json(self) -> dict:
        return dataclasses.asdict(self)


class Project:
    """Root-anchored file access with a shared AST cache.

    Passes receive a Project rather than raw paths so the tier-1
    clean-tree run parses each Python file at most once across all
    passes.
    """

    def __init__(self, root: Path):
        self.root = Path(root).resolve()
        self._text: Dict[Path, Optional[str]] = {}
        self._ast: Dict[Path, Optional[ast.Module]] = {}
        self._errors: List[Finding] = []

    # -- file access -------------------------------------------------
    def rel(self, path: Path) -> str:
        try:
            return path.resolve().relative_to(self.root).as_posix()
        except ValueError:
            return path.as_posix()

    def read(self, path: Path) -> Optional[str]:
        path = Path(path)
        if not path.is_absolute():
            path = self.root / path
        if path not in self._text:
            try:
                self._text[path] = path.read_text(encoding="utf-8")
            except OSError:
                self._text[path] = None
        return self._text[path]

    def parse(self, path: Path) -> Optional[ast.Module]:
        path = Path(path)
        if not path.is_absolute():
            path = self.root / path
        if path not in self._ast:
            src = self.read(path)
            if src is None:
                self._ast[path] = None
            else:
                try:
                    self._ast[path] = ast.parse(src, filename=str(path))
                except SyntaxError as exc:
                    self._ast[path] = None
                    self._errors.append(Finding(
                        "parse", self.rel(path), exc.lineno or 0,
                        f"syntax-error:{path.name}",
                        f"could not parse: {exc.msg}"))
        return self._ast[path]

    def py_files(self, *rel_dirs: str) -> List[Path]:
        """All .py files under the given repo-relative dirs (sorted)."""
        out: List[Path] = []
        for rel in rel_dirs:
            base = self.root / rel
            if base.is_file() and base.suffix == ".py":
                out.append(base)
                continue
            if not base.is_dir():
                continue
            for p in sorted(base.rglob("*.py")):
                # Root-relative skip: a fixture tree rooted *inside* a
                # skipped dir (tests/lint_fixtures/<case>) still scans.
                try:
                    parts = p.relative_to(self.root).parts
                except ValueError:
                    parts = p.parts
                if any(part in SKIP_DIRS for part in parts):
                    continue
                out.append(p)
        return out

    def missing(self, pass_name: str, rel_path: str) -> Finding:
        """A required input file is gone — fail loudly instead of
        silently disabling the pass (guards against renames)."""
        return Finding(pass_name, rel_path, 0,
                       f"missing-file:{Path(rel_path).name}",
                       "required input file is missing or unreadable")

    @property
    def parse_errors(self) -> List[Finding]:
        return list(self._errors)


# ---------------------------------------------------------------------------
# Suppressions
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Suppression:
    pass_name: str
    key: str
    justification: str
    line: int
    used: bool = False


def load_suppressions(path: Path) -> Tuple[List[Suppression], List[Finding]]:
    """Parse the suppression file.

    Format (one entry per line)::

        <pass-name> <key> <justification -- mandatory free text>

    Blank lines and ``#`` comments are ignored.  An entry without a
    justification is itself a finding: silencing a check must leave a
    written reason behind.
    """
    entries: List[Suppression] = []
    findings: List[Finding] = []
    if not path.is_file():
        return entries, findings
    rel = path.name
    for lineno, raw in enumerate(path.read_text(encoding="utf-8").splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        parts = line.split(None, 2)
        if len(parts) < 3 or not parts[2].strip():
            findings.append(Finding(
                "suppressions", rel, lineno, f"malformed:{lineno}",
                "suppression entry needs '<pass> <key> <justification>' "
                "with a non-empty justification"))
            continue
        entries.append(Suppression(parts[0], parts[1], parts[2].strip(), lineno))
    return entries, findings


def apply_suppressions(findings: Iterable[Finding],
                       entries: List[Suppression],
                       suppress_rel: str) -> List[Finding]:
    """Filter suppressed findings; flag unused suppression entries."""
    kept: List[Finding] = []
    for f in findings:
        hit = None
        for s in entries:
            if s.pass_name == f.pass_name and s.key == f.key:
                hit = s
                break
        if hit is not None:
            hit.used = True
        else:
            kept.append(f)
    for s in entries:
        if not s.used:
            kept.append(Finding(
                "suppressions", suppress_rel, s.line,
                f"unused:{s.pass_name}:{s.key}",
                f"suppression '{s.pass_name} {s.key}' matched nothing — "
                "delete it or fix the key"))
    return kept


# ---------------------------------------------------------------------------
# Pass registry
# ---------------------------------------------------------------------------

def _registry() -> Dict[str, Callable[[Project], List[Finding]]]:
    # Imported lazily so `import tools.hvtpulint` stays cheap and the
    # passes can import this module for Finding/Project.
    from . import (knob_registry, kv_discipline, metrics_catalog,
                   rank_divergence, sim_purity, thread_safety, wire_twin)
    return {
        "wire-twin": wire_twin.run,
        "rank-divergence": rank_divergence.run,
        "thread-safety": thread_safety.run,
        "knob-registry": knob_registry.run,
        "metrics-catalog": metrics_catalog.run,
        "sim-purity": sim_purity.run,
        "kv-discipline": kv_discipline.run,
    }


def pass_names() -> List[str]:
    return list(_registry())


def run_passes(root: Path,
               only: Optional[Sequence[str]] = None,
               suppress_path: Optional[Path] = None) -> List[Finding]:
    """Run the selected passes and return unsuppressed findings."""
    project = Project(root)
    registry = _registry()
    names = list(only) if only else list(registry)
    unknown = [n for n in names if n not in registry]
    if unknown:
        raise ValueError(f"unknown pass(es): {', '.join(unknown)}; "
                         f"available: {', '.join(registry)}")
    findings: List[Finding] = []
    for name in names:
        findings.extend(registry[name](project))
    findings.extend(project.parse_errors)

    if suppress_path is None:
        suppress_path = project.root / SUPPRESSION_FILE
    entries, bad = load_suppressions(suppress_path)
    if only:
        # A partial run must not report entries for passes it skipped.
        entries = [s for s in entries if s.pass_name in names]
    findings = apply_suppressions(findings, entries, suppress_path.name)
    findings.extend(bad)
    findings.sort(key=lambda f: (f.path, f.line, f.pass_name, f.key))
    return findings
