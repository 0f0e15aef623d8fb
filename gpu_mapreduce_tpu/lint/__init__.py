"""mrlint — domain-aware static analysis for this repo's recurring
review-fix classes.

Six checkers over a shared AST driver (``driver.py``) and best-effort
callgraph (``callgraph.py``):

* ``trace-purity`` — host effects inside jit/shard_map/pallas_call
  bodies (purity.py);
* ``lock-discipline`` — acquisition-order cycles + guarded/unguarded
  mutation splits (locks.py);
* ``cache-key`` — knob reads reachable from cached builders must key
  the cache (cachekey.py);
* ``knob-registry`` — MRTPU_* knobs route through utils/env.py
  and match doc/settings.md (knobs.py);
* ``metric-catalog`` — mrtpu_* metrics match doc/observability.md
  (metrics_doc.py);
* ``net-timeout`` — outbound network calls in serve/router/client code
  must carry an explicit timeout (nettimeout.py).

CLI: ``scripts/mrlint.py`` (which loads this package standalone so jax
stays cold).  Policy, rule catalog and pragma etiquette: doc/lint.md.

IMPORTANT: nothing in this package may import from the parent package —
the analyzer must run with no side effects in milliseconds.
"""

from .driver import (Finding, Project, RULES, RULE_DOC, load_baseline,
                     run, summary, write_baseline)

# importing the checker modules registers their rules
from . import (cachekey, knobs, locks, metrics_doc,  # noqa: F401,E402
               nettimeout, purity)

__all__ = ["Finding", "Project", "RULES", "RULE_DOC", "run", "summary",
           "load_baseline", "write_baseline"]
