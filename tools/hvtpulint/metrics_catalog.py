"""metrics-catalog pass: registered metrics vs docs/observability.md.

Two sources, checked in both directions:

  * registered: literal first arguments of counter()/gauge()/
    histogram() calls under horovod_tpu/, plus op_counter() — the one
    dynamic registration, `hvtpu_{kind}_total`, expanded over the
    collective kinds (the kind_to_type map in eager/controller.py
    plus literal op_counter call sites)
  * cataloged: every `hvtpu_*` token in docs/observability.md

Findings: registered-but-uncataloged and cataloged-but-unregistered.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Set, Tuple

from . import Finding, Project

PASS = "metrics-catalog"

SCAN_DIRS = ("horovod_tpu",)
OBS_MD = "docs/observability.md"
CONTROLLER_PY = "horovod_tpu/eager/controller.py"

_REGISTER_FUNCS = {"counter", "gauge", "histogram"}
_METRIC_TOKEN_RE = re.compile(r"\bhvtpu_\w+\b")


def _func_name(func: ast.expr):
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _collective_kinds(project: Project) -> Set[str]:
    """Keys of the kind_to_type dict in eager/controller.py — the
    closed set of values op_counter() is called with dynamically."""
    kinds: Set[str] = set()
    tree = project.parse(CONTROLLER_PY)
    if tree is None:
        return kinds
    for node in ast.walk(tree):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "kind_to_type"
                and isinstance(node.value, ast.Dict)):
            for key in node.value.keys:
                if isinstance(key, ast.Constant) and isinstance(key.value, str):
                    kinds.add(key.value)
    return kinds


def registered_metrics(project: Project) -> Dict[str, Tuple[str, int]]:
    """Metric name -> (file, line) of one registration site."""
    out: Dict[str, Tuple[str, int]] = {}
    kinds = _collective_kinds(project)
    for path in project.py_files(*SCAN_DIRS):
        tree = project.parse(path)
        if tree is None:
            continue
        rel = project.rel(path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fname = _func_name(node.func)
            if fname in _REGISTER_FUNCS and node.args:
                arg = node.args[0]
                if (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)
                        and arg.value.startswith("hvtpu_")):
                    out.setdefault(arg.value, (rel, node.lineno))
            elif fname == "op_counter" and node.args:
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    out.setdefault(f"hvtpu_{arg.value}_total",
                                   (rel, node.lineno))
                else:
                    # dynamic kind: expands over the collective kinds
                    for kind in kinds:
                        out.setdefault(f"hvtpu_{kind}_total",
                                       (rel, node.lineno))
    return out


def cataloged_metrics(text: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        for m in _METRIC_TOKEN_RE.finditer(line):
            out.setdefault(m.group(0), lineno)
    return out


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    obs_text = project.read(OBS_MD)
    if obs_text is None:
        findings.append(project.missing(PASS, OBS_MD))
        return findings

    registered = registered_metrics(project)
    cataloged = cataloged_metrics(obs_text)

    for name, (rel, line) in sorted(registered.items()):
        if name not in cataloged:
            findings.append(Finding(
                PASS, rel, line, name,
                f"metric {name} is registered but missing from {OBS_MD}"))
    for name, line in sorted(cataloged.items()):
        if name not in registered:
            findings.append(Finding(
                PASS, OBS_MD, line, name,
                f"metric {name} is cataloged but never registered — "
                "stale doc or a renamed registration"))
    return findings
