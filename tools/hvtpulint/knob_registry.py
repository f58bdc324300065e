"""knob-registry pass: HVTPU_* env knobs vs the generated docs/knobs.md.

Extraction sources (all AST-based — error-message strings that merely
mention a knob name do not count):

  * env reads: ``os.environ.get("HVTPU_X")`` / ``os.getenv`` /
    ``environ["HVTPU_X"]`` / ``.pop`` / ``.setdefault``
  * the config helpers: ``_env*("X", default)`` in core/config.py
    expands to HVTPU_X (with the HOROVOD_X compatibility fallback)
  * env writes: launcher-side ``env["HVTPU_X"] = ...`` stores and
    dict-literal keys (worker environment construction)
  * the ``hvtpurun`` CLI binding: the ``flag_env`` map in
    runner/launch.py plus ``add_argument`` flags

The documentation side is the table in docs/knobs.md (regenerated via
``python -m tools.hvtpulint --write-knobs``; descriptions are
hand-written and preserved across regenerations).  Findings:

  * read in code, no table row        -> undocumented-knob
  * table row, never read or written  -> dead-knob
  * table row with a TODO description -> undescribed-knob
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from . import Finding, Project

PASS = "knob-registry"

KNOBS_MD = "docs/knobs.md"
LAUNCH_PY = "horovod_tpu/runner/launch.py"
SCAN_DIRS = ("horovod_tpu", "examples")
SCAN_FILES = ("setup.py",)

_ENV_HELPER_RE = re.compile(r"^_env(_\w+)?$")
_GET_LIKE = {"get", "getenv", "pop", "setdefault"}
_ROW_RE = re.compile(r"^\|\s*`(HVTPU_\w+)`\s*\|(.*)")
PLACEHOLDER = "TODO"


@dataclasses.dataclass
class Knob:
    reads: List[Tuple[str, int]] = dataclasses.field(default_factory=list)
    writes: List[Tuple[str, int]] = dataclasses.field(default_factory=list)
    defaults: List[str] = dataclasses.field(default_factory=list)
    cli_flag: str = ""


def _env_receiver(node: ast.expr) -> bool:
    """True when `node` plausibly denotes an environment mapping."""
    try:
        text = ast.unparse(node)
    except Exception:
        return False
    return text == "os" or text == "env" or text.endswith("environ")


def _knob_name(value: ast.expr) -> Optional[str]:
    if (isinstance(value, ast.Constant) and isinstance(value.value, str)
            and value.value.startswith("HVTPU_")
            and len(value.value) > len("HVTPU_")):
        return value.value
    return None


def _module_str_consts(tree: ast.Module) -> Dict[str, str]:
    """Module-level `NAME = "literal"` bindings (knob names are often
    hoisted into constants, e.g. runner/secret.py's ENV_KEY)."""
    out: Dict[str, str] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            out[node.targets[0].id] = node.value.value
    return out


class _Extractor(ast.NodeVisitor):
    def __init__(self, rel: str, knobs: Dict[str, Knob],
                 consts: Dict[str, str]):
        self.rel = rel
        self.knobs = knobs
        self.consts = consts

    def _knob(self, name: str) -> Knob:
        return self.knobs.setdefault(name, Knob())

    def _resolve(self, value: ast.expr) -> Optional[str]:
        name = _knob_name(value)
        if name is not None:
            return name
        if isinstance(value, ast.Name):
            lit = self.consts.get(value.id, "")
            if lit.startswith("HVTPU_") and len(lit) > len("HVTPU_"):
                return lit
        return None

    def visit_Call(self, node: ast.Call):
        f = node.func
        # os.environ.get("HVTPU_X") / env.pop("HVTPU_X") / os.getenv(...)
        if (isinstance(f, ast.Attribute) and f.attr in _GET_LIKE
                and node.args and _env_receiver(f.value)):
            name = self._resolve(node.args[0])
            if name:
                knob = self._knob(name)
                if f.attr == "setdefault":
                    knob.writes.append((self.rel, node.lineno))
                else:
                    knob.reads.append((self.rel, node.lineno))
                if f.attr in {"get", "getenv"} and len(node.args) > 1:
                    knob.defaults.append(ast.unparse(node.args[1]))
        # config.py helpers: _env("CYCLE_TIME", 1.0) -> HVTPU_CYCLE_TIME;
        # local helpers passing the full name (_env_float("HVTPU_X", d))
        # count as reads of that name verbatim
        if (isinstance(f, ast.Name) and _ENV_HELPER_RE.match(f.id)
                and node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            arg = node.args[0].value
            name = arg if arg.startswith("HVTPU_") else "HVTPU_" + arg
            if len(name) > len("HVTPU_"):
                knob = self._knob(name)
                knob.reads.append((self.rel, node.lineno))
                if len(node.args) > 1:
                    knob.defaults.append(ast.unparse(node.args[1]))
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        if _env_receiver(node.value):
            name = self._resolve(node.slice)
            if name:
                knob = self._knob(name)
                if isinstance(node.ctx, (ast.Store, ast.Del)):
                    knob.writes.append((self.rel, node.lineno))
                else:
                    knob.reads.append((self.rel, node.lineno))
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict):
        # Worker-env / flag_env dict literals keyed by knob name.
        for key in node.keys:
            if key is None:
                continue
            name = _knob_name(key)
            if name:
                self._knob(name).writes.append((self.rel, node.lineno))
        self.generic_visit(node)


def _cli_flags(project: Project, knobs: Dict[str, Knob]) -> None:
    """Attach hvtpurun flag spellings via launch.py's flag_env map."""
    tree = project.parse(LAUNCH_PY)
    if tree is None:
        return
    # argparse dest -> "--flag" spelling
    dest_to_flag: Dict[str, str] = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument" and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)
                and node.args[0].value.startswith("--")):
            flag = node.args[0].value
            dest = flag.lstrip("-").replace("-", "_")
            for kw in node.keywords:
                if kw.arg == "dest" and isinstance(kw.value, ast.Constant):
                    dest = kw.value.value
            dest_to_flag[dest] = flag
    # flag_env = {"HVTPU_X": args.attr, ...}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "flag_env"
                and isinstance(node.value, ast.Dict)):
            for key, val in zip(node.value.keys, node.value.values):
                name = _knob_name(key) if key is not None else None
                if not name:
                    continue
                # args.attr, or the getattr(args, "attr", None) spelling
                # launch.py uses for flags absent from older namespaces.
                dest = None
                if isinstance(val, ast.Attribute):
                    dest = val.attr
                elif (isinstance(val, ast.Call)
                        and isinstance(val.func, ast.Name)
                        and val.func.id == "getattr" and len(val.args) >= 2
                        and isinstance(val.args[1], ast.Constant)
                        and isinstance(val.args[1].value, str)):
                    dest = val.args[1].value
                if dest:
                    flag = dest_to_flag.get(dest)
                    if flag and name in knobs:
                        knobs[name].cli_flag = flag


def extract_knobs(project: Project) -> Dict[str, Knob]:
    knobs: Dict[str, Knob] = {}
    files = project.py_files(*SCAN_DIRS)
    for rel in SCAN_FILES:
        p = project.root / rel
        if p.is_file():
            files.append(p)
    for path in files:
        tree = project.parse(path)
        if tree is None:
            continue
        _Extractor(project.rel(path), knobs,
                   _module_str_consts(tree)).visit(tree)
    _cli_flags(project, knobs)
    return knobs


def parse_knobs_md(text: str) -> Dict[str, Tuple[int, str]]:
    """Documented knob -> (line, description column)."""
    out: Dict[str, Tuple[int, str]] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        m = _ROW_RE.match(line.strip())
        if not m:
            continue
        cols = [c.strip() for c in m.group(2).split("|")]
        desc = cols[-2] if len(cols) >= 2 else ""
        out[m.group(1)] = (lineno, desc)
    return out


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    knobs = extract_knobs(project)
    doc_text = project.read(KNOBS_MD)
    if doc_text is None:
        findings.append(project.missing(PASS, KNOBS_MD))
        return findings
    documented = parse_knobs_md(doc_text)

    for name, knob in sorted(knobs.items()):
        if knob.reads and name not in documented:
            rel, line = knob.reads[0]
            findings.append(Finding(
                PASS, rel, line, name,
                f"undocumented knob {name} — add a row to {KNOBS_MD} "
                "(python -m tools.hvtpulint --write-knobs)"))
    for name, (line, desc) in sorted(documented.items()):
        knob = knobs.get(name)
        if knob is None or (not knob.reads and not knob.writes):
            findings.append(Finding(
                PASS, KNOBS_MD, line, name,
                f"documented knob {name} is never read or written — "
                "dead doc row (or the knob's reader was deleted)"))
        elif not desc or PLACEHOLDER in desc:
            findings.append(Finding(
                PASS, KNOBS_MD, line, f"describe:{name}",
                f"knob {name} has a placeholder description — write "
                "one line of real semantics"))
    return findings


# ---------------------------------------------------------------------------
# docs/knobs.md generation (--write-knobs)
# ---------------------------------------------------------------------------

_HEADER = """\
# Environment knobs

<!-- The knob rows in this file are generated: run
     `python -m tools.hvtpulint --write-knobs` after adding or removing
     an HVTPU_* read.  Edit descriptions in place — regeneration
     preserves them.  The knob-registry lint pass fails on rows that
     drift from the code. -->

Every `HVTPU_*` environment variable the tree reads, with defaults and
the `hvtpurun` flag that sets it (where one exists).  Knobs read
through `core/config.py` also accept a `HOROVOD_*` spelling as a
compatibility fallback.  `HVTPU_SECRET_KEY` is intentionally **not**
forwarded via argv by the launcher — the HMAC key travels in a 0600
file named by `HVTPU_SECRET_FILE` (see runner/launch.py).

| Knob | Default | `hvtpurun` flag | Description |
|---|---|---|---|
"""


def _default_col(knob: Knob) -> str:
    uniq = sorted(set(knob.defaults))
    if not uniq:
        return "(unset)"
    return "`" + "`, `".join(uniq) + "`"


def generate_knobs_md(project: Project) -> str:
    knobs = extract_knobs(project)
    old = project.read(KNOBS_MD)
    existing = parse_knobs_md(old) if old else {}
    rows = []
    for name, knob in sorted(knobs.items()):
        if not knob.reads:
            # Write-only names (e.g. rank wiring the launcher computes)
            # are still documented: workers read them via config.
            pass
        desc = existing.get(name, (0, ""))[1] or PLACEHOLDER
        flag = f"`{knob.cli_flag}`" if knob.cli_flag else ""
        rows.append(f"| `{name}` | {_default_col(knob)} | {flag} | {desc} |")
    return _HEADER + "\n".join(rows) + "\n"


def write_knobs_md(project: Project) -> Path:
    out = project.root / KNOBS_MD
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(generate_knobs_md(project), encoding="utf-8")
    return out
