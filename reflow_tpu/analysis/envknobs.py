"""Env-knob pass: every ``REFLOW_*`` read goes through the registry.

``reflow_tpu/utils/config.py`` is the single place a ``REFLOW_*``
environment variable may be read raw: it declares each knob (type,
default, one-line doc) and exposes typed accessors. Three rules keep
that true:

- **env-knob-direct** — ``os.environ.get("REFLOW_X")`` (or subscript)
  anywhere else. Direct reads fork the default value from the declared
  one and hide the knob from ``knob_table()`` / the docs.
- **env-knob-undeclared** — an accessor call (``env_flag("REFLOW_X")``
  …) naming a knob the registry does not declare. The accessors raise
  ``KeyError`` at runtime for these; the lint catches them before any
  code path runs.
- **env-knob-undocumented** — a declared knob whose name never appears
  in ``docs/guide.md``. The guide embeds ``knob_table()``'s rows, so a
  missing name means the table went stale.

Writes (``env["REFLOW_X"] = ...``, ``setdefault``) are exempt — the
process harness builds child-process environments and that is the point.
"""

from __future__ import annotations

import ast
import os
from typing import List

from reflow_tpu.analysis.core import Corpus, Finding, register_pass

RULES = {
    "env-knob-direct": "REFLOW_* must be read via utils/config.py "
                       "accessors",
    "env-knob-undeclared": "accessor call names a knob declare() never "
                           "registered",
    "env-knob-undocumented": "declared knob missing from docs/guide.md",
}

_ACCESSORS = ("env_flag", "env_int", "env_float", "env_str")


def _first_str(arg: ast.expr) -> str:
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return ""


@register_pass("envknobs", RULES)
def envknob_pass(corpus: Corpus) -> List[Finding]:
    findings: List[Finding] = []
    try:
        from reflow_tpu.utils.config import KNOBS
        declared = set(KNOBS)
    except Exception:  # registry import broken: other rules still run
        declared = None

    for sf in corpus.files.values():
        if sf.tree is None or sf.path.endswith("utils/config.py") \
                or sf.path.startswith("reflow_tpu/analysis/"):
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            attr = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            if attr == "get" and isinstance(f, ast.Attribute) and \
                    isinstance(f.value, ast.Attribute) and \
                    f.value.attr == "environ" and node.args:
                name = _first_str(node.args[0])
                if name.startswith("REFLOW_"):
                    findings.append(Finding(
                        "env-knob-direct", sf.path, node.lineno,
                        f"direct os.environ read of {name!r} — use "
                        f"the utils/config.py accessor so the default "
                        f"and doc stay single-sourced"))
            elif attr in _ACCESSORS and node.args:
                name = _first_str(node.args[0])
                if name.startswith("REFLOW_") and declared is not None \
                        and name not in declared:
                    findings.append(Finding(
                        "env-knob-undeclared", sf.path, node.lineno,
                        f"{attr}({name!r}) but the registry never "
                        f"declare()d it — add it to "
                        f"reflow_tpu/utils/config.py"))
        # environ["REFLOW_X"] subscript READS (loads only)
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load) and \
                    isinstance(node.value, ast.Attribute) and \
                    node.value.attr == "environ":
                name = _first_str(node.slice)
                if name.startswith("REFLOW_"):
                    findings.append(Finding(
                        "env-knob-direct", sf.path, node.lineno,
                        f"direct os.environ[{name!r}] read — use the "
                        f"utils/config.py accessor"))

    if declared:
        guide = os.path.join(corpus.root, "docs", "guide.md")
        try:
            guide_text = open(guide, encoding="utf-8").read()
        except OSError:
            guide_text = ""
        for name in sorted(declared):
            if name not in guide_text:
                findings.append(Finding(
                    "env-knob-undocumented",
                    "reflow_tpu/utils/config.py", 1,
                    f"knob {name} is declared but never mentioned in "
                    f"docs/guide.md — regenerate the knob table "
                    f"(knob_table())"))
    return findings
