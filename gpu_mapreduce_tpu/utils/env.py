"""Crash-proof env-knob parsing — THE knob registry.

Observability knobs share one rule (doc/settings.md): a malformed value
must degrade with a stderr warning, never crash the run it was meant to
observe.  Every ``MRTPU_*`` knob reads through one of the
three helpers here so the warn-and-fall-back behavior cannot drift
between sites — ``env_knob`` for numerics, ``env_str`` for
paths/specs, ``env_flag`` for booleans.  mrlint's ``knob-registry``
rule fails CI on any raw ``os.environ`` read of a reserved-namespace
knob outside this module, and on any knob without a doc/settings.md
row (doc/lint.md).
"""

from __future__ import annotations

import os
import sys
from typing import Callable, Optional, TypeVar

T = TypeVar("T")

_TRUE = frozenset(("1", "true", "yes", "on"))
_FALSE = frozenset(("0", "false", "no", "off"))


def env_knob(name: str, cast: Callable[[str], T], default: T) -> T:
    """``cast(os.environ[name])``, or ``default`` (with one stderr
    line) when the variable is unset, empty, or malformed."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return cast(raw)
    except (TypeError, ValueError) as e:
        print(f"{name} ignored: {e!r}", file=sys.stderr)
        return default


def env_str(name: str, default: Optional[str] = "") -> Optional[str]:
    """The string knob read (paths, schedules, spec strings): the raw
    value, or ``default`` when unset or empty.  No parsing — callers
    own the value's grammar; they route here so the registry (and the
    knob-registry lint rule) sees every consumption site."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    return raw


def env_flag(name: str, default: bool) -> bool:
    """Boolean knob: 1/true/yes/on and 0/false/no/off (case-
    insensitive); unset, empty, or malformed values degrade to
    ``default`` — malformed with one stderr line, same contract as
    :func:`env_knob`."""
    def cast(raw: str) -> bool:
        v = raw.strip().lower()
        if v in _TRUE:
            return True
        if v in _FALSE:
            return False
        raise ValueError(f"not a boolean flag: {raw!r}")
    return env_knob(name, cast, default)
