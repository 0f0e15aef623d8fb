"""Shared BASELINE.json publishing — one implementation of the
read/merge/write pattern soak.py and weakscale.py each hand-rolled (backend-qualified keys so no harness clobbers
another's records)."""

import json
import os
from typing import Optional

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read_published(key: str, path: Optional[str] = None):
    """The current published.<key> record, or {} (same file layout
    owner as publish — harnesses merge partial runs through this)."""
    if path is None:
        path = os.path.join(_ROOT, "BASELINE.json")
    try:
        with open(path) as f:
            return json.load(f).get("published", {}).get(key, {})
    except (FileNotFoundError, ValueError):
        return {}


def publish(key: str, record, path: Optional[str] = None) -> None:
    """Merge ``record`` under published.<key> of the REPO's
    BASELINE.json (cwd-independent by default).

    A missing or corrupt baseline must not crash a harness at the very
    end of a long capture and lose the run (ADVICE r3) — but starting
    fresh over a CORRUPT file would silently destroy every previously
    published record (r4 review), so the unparsable file is moved aside
    to ``<path>.corrupt`` for repair first.  The write itself is
    tmp+rename so a crash mid-dump can no longer produce such a file."""
    if path is None:
        path = os.path.join(_ROOT, "BASELINE.json")
    try:
        with open(path) as f:
            base = json.load(f)
    except FileNotFoundError:
        base = {}
    except ValueError:
        os.replace(path, path + ".corrupt")   # preserve for repair
        base = {}
    base.setdefault("published", {})[key] = record
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(base, f, indent=2)
    os.replace(tmp, path)
