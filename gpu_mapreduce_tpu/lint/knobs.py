"""knob-registry: every ``MRTPU_*`` knob routes through
``utils/env.py`` and has a row in ``doc/settings.md``.

``utils/env.py`` is the one place knob parsing is allowed to live: the
crash-proof warn-and-fall-back contract (a malformed observability
knob must degrade, never crash the run it was meant to observe) cannot
drift between sites when every read goes through ``env_knob`` /
``env_str`` / ``env_flag``.  A raw ``os.environ.get("MRTPU_...")``
bypasses that contract; an undocumented knob is invisible to operators;
a documented-but-removed knob sends them setting a variable nothing
reads.

Scope: the package plus the Project's ``extra`` modules (the scripts
scripts/mrlint.py names).  Only the reserved ``MRTPU_`` namespace is
enforced; legacy ``MR_*`` app knobs predate the registry and stay out
of it until renamed.

Rules:

* ``knob-bypass`` — a reserved-namespace knob read via raw
  ``os.environ``/``os.getenv`` outside utils/env.py;
* ``knob-undocumented`` — a knob read anywhere but absent from
  doc/settings.md;
* ``knob-stale`` — a knob documented in doc/settings.md but read
  nowhere in code.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Tuple

from .callgraph import env_reads, is_env_helper_call
from .driver import Finding, Project, register

_KNOB = re.compile(r"^MRTPU_[A-Z0-9_]+$")
_DOC_KNOB = re.compile(r"\bMRTPU_[A-Z0-9_]+\b")


def check(project: Project) -> List[Finding]:
    out: List[Finding] = []
    read_knobs: Dict[str, Tuple[str, int]] = {}

    for mod in project.all_modules(include_extra=True):
        in_registry = mod.relpath.endswith("utils/env.py")
        for knob, node in env_reads(mod.tree):
            if not _KNOB.match(knob):
                continue
            read_knobs.setdefault(knob, (mod.relpath, node.lineno))
            raw = not (isinstance(node, ast.Call)
                       and is_env_helper_call(node))
            if raw and not in_registry:
                out.append(Finding(
                    "knob-bypass", mod.relpath, node.lineno,
                    f"{knob} read via raw os.environ — route through "
                    f"utils/env.py (env_knob/env_str/env_flag) so the "
                    f"warn-and-fall-back contract can't drift"))

    doc = project.doc("settings.md") or ""
    doc_knobs = set(_DOC_KNOB.findall(doc))

    for knob, (rel, line) in sorted(read_knobs.items()):
        if knob not in doc_knobs:
            out.append(Finding(
                "knob-undocumented", rel, line,
                f"{knob} is read here but has no row in "
                f"doc/settings.md — operators can't discover it",
                symbol=knob))

    doc_lines = doc.splitlines()
    for knob in sorted(doc_knobs - set(read_knobs)):
        line = next((i for i, t in enumerate(doc_lines, 1) if knob in t),
                    1)
        out.append(Finding(
            "knob-stale", "doc/settings.md", line,
            f"{knob} is documented but read nowhere in code — setting "
            f"it does nothing", symbol=knob))
    return out


register(
    "knob-registry", check,
    "MRTPU_* knobs must route through utils/env.py and have a "
    "doc/settings.md row (and doc rows must match live knobs)",
    global_findings=("knob-undocumented", "knob-stale"))
